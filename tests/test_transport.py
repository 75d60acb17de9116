"""Streaming transport tests: chunked codec round trips, wire framing
(torn delivery included), sharded rANS, rate control, and the asyncio
edge<->cloud session layer."""

import asyncio
import pathlib
import struct
import sys

import numpy as np
import pytest

from repro.core import CodecConfig, calibrate
from repro.core import cabac
from repro.transport import (CloudServer, CodecBank, EdgeClient, Frame,
                             FrameReader, FramingError, RateControlConfig,
                             RateController, ResultAssembler,
                             TensorAssembler, TransportError, encode_frame,
                             framing, pack_arrays, result_frames,
                             tensor_to_frames, unpack_arrays)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from golden_cases import build_sharded_payload  # noqa: E402


@pytest.fixture(scope="module")
def features():
    rng = np.random.default_rng(0)
    mu = np.linspace(0.0, 6.0, 16).astype(np.float32)
    return (mu[None, :] + rng.exponential(1.0, (512, 16))).astype(np.float32)


def _codec(features, granularity="tensor", n_levels=4, **kw):
    cfg = CodecConfig(n_levels=n_levels, clip_mode="minmax",
                      constrain_cmin_zero=False, granularity=granularity,
                      channel_axis=-1, channel_group_size=4, **kw)
    return calibrate(cfg, samples=features)


class TestChunkedStream:
    @pytest.mark.parametrize("granularity", ["tensor", "channel"])
    @pytest.mark.parametrize("mode", ["serial", "rans"])
    def test_bit_exact_with_one_shot(self, features, granularity, mode):
        codec = _codec(features, granularity)
        one = codec.decode(codec.encode(features, coder_mode=mode),
                           shape=features.shape)
        st = codec.decode_stream(
            codec.encode_stream(features, chunk_elems=777, coder_mode=mode))
        assert st.shape == features.shape
        np.testing.assert_array_equal(st, one)

    def test_bit_exact_ecsq(self, features):
        codec = calibrate(CodecConfig(n_levels=4, use_ecsq=True,
                                      clip_mode="minmax",
                                      constrain_cmin_zero=False),
                          samples=features)
        one = codec.decode(codec.encode(features), shape=features.shape)
        st = codec.decode_stream(codec.encode_stream(features,
                                                     chunk_elems=500))
        np.testing.assert_array_equal(st, one)

    def test_single_chunk_and_odd_sizes(self, features):
        codec = _codec(features)
        for chunk in (1, 13, features.size, 10 * features.size):
            st = codec.decode_stream(
                codec.encode_stream(features, chunk_elems=chunk))
            np.testing.assert_array_equal(
                st, codec.decode(codec.encode(features),
                                 shape=features.shape))

    def test_out_of_order_chunks(self, features):
        from repro.core import ChunkStreamDecoder
        codec = _codec(features)
        payloads = list(codec.encode_stream(features, chunk_elems=1000))
        dec = ChunkStreamDecoder(payloads[0])
        for p in reversed(payloads[1:]):
            dec.add_chunk(p)
        np.testing.assert_array_equal(
            dec.finish(), codec.decode(codec.encode(features),
                                       shape=features.shape))

    def test_incomplete_and_duplicate_chunks(self, features):
        from repro.core import ChunkStreamDecoder
        codec = _codec(features)
        payloads = list(codec.encode_stream(features, chunk_elems=1000))
        dec = ChunkStreamDecoder(payloads[0])
        dec.add_chunk(payloads[1])
        with pytest.raises(ValueError, match="incomplete"):
            dec.finish()
        with pytest.raises(ValueError, match="duplicate"):
            dec.add_chunk(payloads[1])


class TestFraming:
    def test_roundtrip_and_torn_delivery(self, features):
        codec = _codec(features)
        wire = b"".join(tensor_to_frames(codec, features, session=3,
                                         chunk_elems=900))
        ref = codec.decode(codec.encode(features), shape=features.shape)
        # byte-at-a-time delivery
        reader = FrameReader()
        asm = TensorAssembler()
        out = None
        for i in range(len(wire)):
            reader.feed(wire[i:i + 1])
            for frame in reader:
                assert frame.session == 3
                r = asm.feed(frame)
                if r is not None:
                    out = r
        assert out is not None and reader.pending_bytes == 0
        np.testing.assert_array_equal(out, ref)

    def test_interleaved_sessions(self, features):
        codec = _codec(features)
        a = list(tensor_to_frames(codec, features, session=1,
                                  chunk_elems=1500))
        b = list(tensor_to_frames(codec, 2.0 * features, session=2,
                                  chunk_elems=700))
        wire = bytearray()
        for i in range(max(len(a), len(b))):  # interleave frame-wise
            if i < len(a):
                wire += a[i]
            if i < len(b):
                wire += b[i]
        reader = FrameReader()
        reader.feed(bytes(wire))
        asms = {1: TensorAssembler(), 2: TensorAssembler()}
        outs = {}
        for frame in reader:
            r = asms[frame.session].feed(frame)
            if r is not None:
                outs[frame.session] = r
        np.testing.assert_array_equal(
            outs[1], codec.decode(codec.encode(features),
                                  shape=features.shape))
        np.testing.assert_array_equal(
            outs[2], codec.decode(codec.encode(2.0 * features),
                                  shape=features.shape))

    def test_crc_corruption_detected(self):
        frame = encode_frame(framing.FT_CHUNK, 0, 0, b"payload-bytes")
        corrupted = bytearray(frame)
        corrupted[-3] ^= 0xFF  # flip a payload byte
        reader = FrameReader()
        reader.feed(bytes(corrupted))
        with pytest.raises(FramingError, match="CRC"):
            list(reader)

    def test_bad_magic_detected(self):
        reader = FrameReader()
        reader.feed(b"\x00" * 32)
        with pytest.raises(FramingError, match="magic"):
            list(reader)

    def test_pack_unpack_arrays(self):
        arrays = [np.arange(6, dtype=np.float32).reshape(2, 3),
                  np.arange(4, dtype=np.int32),
                  np.zeros((2, 2, 2), np.uint8)]
        back = unpack_arrays(pack_arrays(arrays))
        assert len(back) == 3
        for a, b in zip(arrays, back):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)


class TestShardedRans:
    """Coder ids 2 and 3 are decode-only: their containers come from the
    test-side layout builder (``golden_cases.build_sharded_payload``)."""

    @pytest.mark.parametrize("coder_id", [2, 3])
    @pytest.mark.parametrize("n", [0, 1, 7, 4096, 100_001])
    def test_round_trip(self, n, coder_id):
        rng = np.random.default_rng(n)
        idx = rng.integers(0, 4, n).astype(np.int32)
        blob = build_sharded_payload(idx, 4, n_shards=3, coder_id=coder_id)
        assert blob[0] == coder_id
        np.testing.assert_array_equal(
            cabac.decode_indices(blob, n, 4), idx)


class TestRateControl:
    def test_tracks_budget(self, features):
        rng = np.random.default_rng(2)
        bank = CodecBank(CodecConfig(n_levels=8, clip_mode="minmax",
                                     constrain_cmin_zero=False), features)
        rc = RateController(RateControlConfig(target_bpe=2.0))
        bits = elems = 0
        for _ in range(30):
            x = (rng.exponential(1.0, (256, 16))
                 + np.linspace(0, 6, 16)[None, :]).astype(np.float32)
            n = rc.next_levels()
            blob = bank.get(n).encode(x)
            rc.on_tensor(n, len(blob), x.size, send_seconds=0.01)
            bits += 8 * len(blob)
            elems += x.size
        assert abs(bits / elems - 2.0) <= 0.2  # within 10% of budget

    def test_backpressure_steps_down(self, features):
        bank = CodecBank(CodecConfig(n_levels=8, clip_mode="minmax",
                                     constrain_cmin_zero=False), features)
        rc = RateController(RateControlConfig(target_bpe=3.0, queue_high=4))
        first = rc.next_levels()
        rc.on_tensor(first, 1000, 4000)
        rc.on_queue_depth(10)             # sustained pressure
        assert rc.next_levels() < first

    def test_bank_caches_and_validates(self, features):
        bank = CodecBank(CodecConfig(n_levels=8, clip_mode="minmax"),
                         features, ladder=(2, 4))
        assert bank.get(4) is bank.get(4)
        with pytest.raises(KeyError):
            bank.get(7)

    def test_seed_estimate_replaced_by_first_measurement(self):
        rc = RateController(RateControlConfig(target_bpe=1.0,
                                              ladder=(2, 4, 8)))
        rc.seed_estimate(4, 0.5)
        assert rc.estimate_bpe(4) == 0.5
        rc.on_tensor(4, coded_bytes=25000, n_elems=100000)  # 2.0 bpe
        # the estimate is dropped outright, not EWMA-blended
        assert rc.estimate_bpe(4) == 2.0
        rc.on_tensor(4, coded_bytes=12500, n_elems=100000)  # 1.0 bpe
        assert rc.estimate_bpe(4) == pytest.approx(0.4 * 1.0 + 0.6 * 2.0)
        # seeding never overrides an existing measurement
        rc.seed_estimate(4, 9.9)
        assert rc.estimate_bpe(4) != 9.9

    def test_prime_controller_orders_mixed_ladder(self, features):
        from repro.transport.rate_control import Rung
        ladder = (2, 4, Rung(4, "channel"), 8)
        bank = CodecBank(CodecConfig(n_levels=4, clip_mode="minmax",
                                     constrain_cmin_zero=False,
                                     channel_axis=-1), features,
                         ladder=ladder)
        rc = RateController(RateControlConfig(target_bpe=1.0,
                                              ladder=ladder))
        bank.prime_controller(rc)
        # every rung carries an in-graph estimate before any coding,
        # and the per-channel rung estimates below per-tensor at equal N
        # on these channel-biased features
        est = {r: rc.estimate_bpe(r) for r in rc.ladder}
        assert all(v > 0 for v in est.values())
        assert est[Rung(4, "channel")] < est[Rung(4)]

    def test_tile_rate_bits_sums_to_estimate(self, features):
        import jax.numpy as jnp
        codec = _codec(features, "channel")
        tr = np.asarray(codec.tile_rate_bits(jnp.asarray(features)))
        assert tr.shape == (codec.plan.n_cgroups, codec.plan.n_sblocks)
        est = float(codec.estimate_rate(jnp.asarray(features)))
        assert tr.sum() / features.size == pytest.approx(est, rel=1e-4)


class TestAsyncTransport:
    def test_concurrent_sessions_bit_exact(self, features):
        codec = _codec(features, granularity="channel", n_levels=8)

        def tail(t):
            return [np.asarray(t, np.float32).sum(axis=-1)]

        async def run():
            async with CloudServer(tail_fn=tail, echo_features=True) as srv:
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600) as client:
                    tensors = [features, 0.5 * features, 2.0 * features]
                    return await asyncio.gather(
                        *[client.submit(t) for t in tensors]), srv

        results, srv = asyncio.run(run())
        assert srv.sessions_served == 3
        for t, res in zip([features, 0.5 * features, 2.0 * features],
                          results):
            ref = codec.decode(codec.encode(t), shape=t.shape)
            recon = np.asarray(res.arrays[0])
            assert recon.shape == t.shape
            np.testing.assert_array_equal(recon, ref)
            np.testing.assert_allclose(res.arrays[1], ref.sum(axis=-1),
                                       rtol=1e-5)
            assert res.bits_per_elem > 0
            assert res.feedback is not None
            assert res.feedback.recv_bytes_per_s > 0

    def test_rate_controlled_client(self, features):
        async def run():
            async with CloudServer(echo_features=True) as srv:
                bank = CodecBank(CodecConfig(n_levels=8, clip_mode="minmax",
                                             constrain_cmin_zero=False),
                                 features)
                rc = RateController(RateControlConfig(target_bpe=2.0))
                async with EdgeClient("127.0.0.1", srv.port,
                                      codec_bank=bank, rate_controller=rc,
                                      chunk_elems=2048) as client:
                    for _ in range(5):   # sequential: lets the bucket adapt
                        res = await client.submit(features)
                        c = bank.get(res.n_levels)
                        ref = c.decode(c.encode(features),
                                       shape=features.shape)
                        np.testing.assert_array_equal(
                            np.asarray(res.arrays[0]), ref)
                return rc

        rc = asyncio.run(run())
        assert len(rc.history) == 5
        assert abs(rc.measured_bpe - 2.0) <= 0.4


class TestLargeResults:
    """RESULTs past the frame bound, with the bound cut to a few KiB so
    that a (512, 16) float32 echo needs several frames."""

    BOUND = 4096

    @pytest.fixture
    def small_frames(self, monkeypatch):
        monkeypatch.setattr(framing, "MAX_PAYLOAD", self.BOUND)

    @staticmethod
    def _submit(codec, x, timeout_s=20.0, **server_kw):
        async def run():
            async with CloudServer(echo_features=True, **server_kw) as srv:
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600) as client:
                    res = await asyncio.wait_for(client.submit(x),
                                                 timeout_s)
                frames = srv.metrics.get(
                    "repro_server_result_frames_total").value()
                return res, frames
        return asyncio.run(run())

    def test_result_past_the_bound_arrives_whole(self, features,
                                                 small_frames):
        codec = _codec(features)

        def tail(t):
            return [np.asarray(t, np.float32).sum(axis=-1)]

        res, frames = self._submit(codec, features, tail_fn=tail)
        ref = codec.decode(codec.encode(features), shape=features.shape)
        np.testing.assert_array_equal(np.asarray(res.arrays[0]), ref)
        np.testing.assert_allclose(res.arrays[1], ref.sum(axis=-1),
                                   rtol=1e-5)
        packed = len(pack_arrays([ref, ref.sum(axis=-1)]))
        assert packed > 8 * self.BOUND
        assert frames == -(-packed // self.BOUND)

    def test_result_under_the_bound_is_one_frame_as_before(self, features):
        payload = pack_arrays([features])
        assert result_frames(7, 3, payload) == [
            encode_frame(framing.FT_RESULT, 7, 3, payload)]
        codec = _codec(features)
        res, frames = self._submit(codec, features)
        assert frames == 1
        np.testing.assert_array_equal(
            np.asarray(res.arrays[0]),
            codec.decode(codec.encode(features), shape=features.shape))

    def test_frames_split_at_the_bound_and_join_in_order(self,
                                                         small_frames):
        payload = bytes(range(256)) * 33          # 8448 B: 3 frames
        wire = result_frames(5, 9, payload)
        reader = FrameReader()
        reader.feed(b"".join(wire))
        frames = list(reader)
        assert [f.ftype for f in frames] == [framing.FT_RESULT_PART] * 2 \
            + [framing.FT_RESULT]
        assert [f.seq for f in frames] == [9, 10, 11]
        assert [len(f.payload) for f in frames] == [4096, 4096, 256]
        asm = ResultAssembler()
        assert [asm.add(f) for f in frames[:2]] == [None, None]
        assert asm.add(frames[2]) == payload
        # exactly the bound is still one frame
        assert len(result_frames(5, 9, payload[:self.BOUND])) == 1

    def test_reassembly_is_bounded(self, monkeypatch):
        monkeypatch.setattr(framing, "MAX_RESULT", 1000)
        part = Frame(framing.FT_RESULT_PART, 1, 0, b"x" * 600)
        asm = ResultAssembler()
        assert asm.add(part) is None
        with pytest.raises(FramingError, match="exceeds"):
            asm.add(part)
        with pytest.raises(FramingError, match="too large"):
            result_frames(1, 0, b"x" * 1001)

    @pytest.mark.parametrize("where", ["pack", "drain"])
    def test_result_path_failure_raises_in_submit(self, features,
                                                  monkeypatch, where):
        import repro.transport.server as server_mod
        from repro.serving.batcher import DecodeBatcher

        def boom(*_a, **_k):
            raise RuntimeError(f"forced failure in {where}")

        if where == "pack":
            monkeypatch.setattr(server_mod, "pack_arrays", boom)
        else:
            monkeypatch.setattr(DecodeBatcher, "drain", boom)
        with pytest.raises(TransportError, match="forced failure"):
            self._submit(_codec(features), features, timeout_s=10.0)


class TestModelSplitHelpers:
    def test_head_plus_tail_equals_forward(self):
        import dataclasses

        import jax
        import jax.numpy as jnp

        from repro.configs import ARCHS, reduced
        from repro.models import (forward, forward_from_boundary,
                                  forward_head, init_params)

        cfg = dataclasses.replace(reduced(ARCHS["codeqwen1.5-7b"]),
                                  vocab_size=64, d_model=32, d_ff=64,
                                  num_heads=2, num_kv_heads=2, head_dim=16)
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = (np.arange(24, dtype=np.int32).reshape(2, 12)) % 64
        ref, _ = forward(cfg, params, jnp.asarray(toks),
                         codec_fn=lambda x: (x, 0.0))
        head = forward_head(cfg, params, jnp.asarray(toks))
        tail = forward_from_boundary(cfg, params, head)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(tail),
                                   rtol=1e-5, atol=1e-5)

"""Cross-session batching tick tests: byte-identical wire streams from
stacked encode ticks, one batched entropy drain across sessions, tick
triggers/latency bounds, failure isolation, and the shared worker-level
codec bank."""

import asyncio
import pathlib
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from repro.core import CodecConfig, calibrate
from repro.core.codec import ChunkStreamDecoder, HeaderCache, flush_decoders
from repro.serving import DecodeBatcher, TickConfig, encode_tick
from repro.serving import batcher as batcher_mod
from repro.transport import (DEFAULT_CHUNK_ELEMS, CloudServer, EdgeClient,
                             bank_cache_stats, clear_bank_cache,
                             encode_frame, shared_bank, tensor_to_frames)
from repro.transport.framing import FT_HEADER

from golden_cases import (CASES, _conv_input, _flat_input,  # noqa: E402
                          _v2_uniform_codec, _v3_tile_codec,
                          _v4_tile2d_codec)


def _ref_payloads(codec, x, cfg: TickConfig):
    return list(codec.encode_stream(x, chunk_elems=cfg.chunk_elems,
                                    coder_mode=cfg.coder_mode))


def _channel_codec(x, n_levels=4):
    return calibrate(CodecConfig(n_levels=n_levels, clip_mode="minmax",
                                 constrain_cmin_zero=False,
                                 granularity="channel", channel_axis=-1,
                                 channel_group_size=2),
                     samples=x)


def test_default_chunk_elems_matches_transport():
    # batcher duplicates the constant to keep serving free of the wire
    # layer; they must never drift apart
    assert batcher_mod.DEFAULT_CHUNK_ELEMS == DEFAULT_CHUNK_ELEMS


class TestEncodeTick:
    @pytest.mark.parametrize("n_sessions", [1, 2, 5])
    def test_per_tensor_matches_encode_stream(self, n_sessions):
        codec = _v2_uniform_codec(n_levels=8)
        cfg = TickConfig(chunk_elems=700, coder_mode="rans")
        xs = [_flat_input(n=3000, seed=100 + i) * 0.9
              for i in range(n_sessions)]
        payloads, stats = encode_tick([(codec, x) for x in xs], cfg)
        assert payloads == [_ref_payloads(codec, x, cfg) for x in xs]
        assert stats.entropy_calls == 1
        assert stats.fused_launches == 1   # flat concat: one launch, any K

    def test_per_tensor_mixed_shapes_one_launch(self):
        codec = _v2_uniform_codec()
        cfg = TickConfig(chunk_elems=1 << 12, coder_mode="rans")
        xs = [_flat_input(n=n) for n in (500, 3000, 1700)]
        payloads, stats = encode_tick([(codec, x) for x in xs], cfg)
        assert payloads == [_ref_payloads(codec, x, cfg) for x in xs]
        # per-tensor codecs concatenate flat: shapes mix in ONE launch
        assert stats.fused_launches == 1
        assert stats.stacked_sessions == 3

    def test_channel_granularity_stacks(self):
        x0 = _flat_input(n=1024).reshape(128, 8)
        codec = _channel_codec(x0)
        cfg = TickConfig(chunk_elems=300, coder_mode="rans")
        xs = [x0, 0.5 * x0, x0[::-1].copy()]
        payloads, stats = encode_tick([(codec, x) for x in xs], cfg)
        assert payloads == [_ref_payloads(codec, x, cfg) for x in xs]
        assert stats.fused_launches == 1
        assert stats.stacked_sessions == 3

    def test_tile1d_stackable_vs_ragged(self):
        # stackable: M = 2*32 divides the 32-element blocks
        x = _conv_input(shape=(1, 4, 8, 8))
        codec = _v3_tile_codec(x)
        cfg = TickConfig(chunk_elems=1 << 10, coder_mode="rans")
        payloads, stats = encode_tick([(codec, x), (codec, 2.0 * x)], cfg)
        assert payloads == [_ref_payloads(codec, t, cfg)
                            for t in (x, 2.0 * x)]
        assert stats.fused_launches == 1 and stats.stacked_sessions == 2
        # ragged (golden geometry, M=99 % 32 != 0): per-session launches,
        # but STILL one entropy call for the tick
        xr = _conv_input()
        codec_r = _v3_tile_codec(xr)
        payloads, stats = encode_tick([(codec_r, xr), (codec_r, 0.5 * xr)],
                                      cfg)
        assert payloads == [_ref_payloads(codec_r, t, cfg)
                            for t in (xr, 0.5 * xr)]
        assert stats.fused_launches == 2 and stats.stacked_sessions == 0
        assert stats.entropy_calls == 1

    @pytest.mark.parametrize("use_ecsq", [False, True])
    def test_tile2d_stackable(self, use_ecsq):
        # H = 8 divides bh = 4 -> stacked (K*H, W) grid
        x = _conv_input(shape=(1, 4, 8, 9))
        codec = _v4_tile2d_codec(x, use_ecsq=use_ecsq)
        cfg = TickConfig(chunk_elems=1 << 10, coder_mode="rans")
        xs = [x, 0.25 * x, 4.0 * x]
        payloads, stats = encode_tick([(codec, t) for t in xs], cfg)
        assert payloads == [_ref_payloads(codec, t, cfg) for t in xs]
        assert stats.fused_launches == 1 and stats.stacked_sessions == 3

    def test_golden_cases_byte_identical(self):
        # every re-encodable conformance case, two sessions each: the
        # batched path must write the exact v2/v3/v4 bytes of the
        # per-session encoder (ragged tile cases cover the fallback)
        for case in CASES:
            if case.decode_only or case.coder_mode != "rans":
                continue
            x = case.make_input()
            codec = case.make_codec(x)
            chunk = case.chunk_elems or DEFAULT_CHUNK_ELEMS
            cfg = TickConfig(chunk_elems=chunk, coder_mode="rans")
            payloads, stats = encode_tick([(codec, x), (codec, 0.5 * x)],
                                          cfg)
            ref = [_ref_payloads(codec, t, cfg) for t in (x, 0.5 * x)]
            assert payloads == ref, case.name
            assert stats.entropy_calls == 1, case.name

    def test_mixed_rungs_and_shapes_one_tick(self):
        flat = _flat_input(n=2048)
        conv = _conv_input(shape=(1, 4, 8, 9))
        items = [
            (_v2_uniform_codec(4), flat),
            (_v2_uniform_codec(8), 0.5 * flat),
            (_channel_codec(flat.reshape(256, 8)), flat.reshape(256, 8)),
            (_v4_tile2d_codec(conv), conv),
        ]
        cfg = TickConfig(chunk_elems=600, coder_mode="rans")
        payloads, stats = encode_tick(items, cfg)
        for (codec, x), got in zip(items, payloads):
            assert got == _ref_payloads(codec, x, cfg)
        assert stats.entropy_calls == 1     # mixed n_levels share the call
        assert stats.groups == 4

    def test_max_batch_splits_launches(self):
        codec = _channel_codec(_flat_input(n=1024).reshape(128, 8))
        cfg = TickConfig(chunk_elems=1 << 10, coder_mode="rans",
                         max_batch=2)
        xs = [_flat_input(n=1024, seed=i).reshape(128, 8)
              for i in range(5)]
        payloads, stats = encode_tick([(codec, x) for x in xs], cfg)
        assert payloads == [_ref_payloads(codec, x, cfg) for x in xs]
        # ceil(5/2) = 3 launches: two stacked pairs + one singleton
        assert stats.fused_launches == 3
        assert stats.stacked_sessions == 4
        assert stats.entropy_calls == 1


class TestDecodeBatcher:
    def _streams(self, specs, chunk_elems=500):
        """[(codec, x)] -> (decoders fed out-of-order, refs)."""
        decs, refs = [], []
        for codec, x in specs:
            payloads = list(codec.encode_stream(x, chunk_elems=chunk_elems,
                                                coder_mode="rans"))
            dec = ChunkStreamDecoder(payloads[0], chunk_batch=0)
            for p in reversed(payloads[1:]):    # out-of-order arrival
                dec.add_chunk(p)
            decs.append(dec)
            refs.append(codec.decode(codec.encode(x, coder_mode="rans"),
                                     shape=x.shape))
        return decs, refs

    def test_cross_session_flush_bit_exact(self):
        flat = _flat_input(n=2600)
        conv = _conv_input(shape=(1, 4, 8, 9))
        specs = [(_v2_uniform_codec(4), flat),
                 (_v2_uniform_codec(8), 0.7 * flat),
                 (_channel_codec(flat[:2048].reshape(256, 8)),
                  flat[:2048].reshape(256, 8)),
                 (_v4_tile2d_codec(conv, use_ecsq=True), conv)]
        decs, refs = self._streams(specs)
        batcher = DecodeBatcher()
        for d in decs:
            batcher.note(d)
        assert batcher.pending_sessions == len(decs)
        failures = batcher.drain()
        assert failures == []
        assert batcher.counters["entropy_calls"] == 1
        assert batcher.counters["sessions"] == len(decs)
        for d, (codec, x), ref in zip(decs, specs, refs):
            np.testing.assert_array_equal(d.finish(x.shape), ref)

    def test_corrupt_session_isolated(self):
        flat = _flat_input(n=2600)
        specs = [(_v2_uniform_codec(4), flat),
                 (_v2_uniform_codec(8), 0.7 * flat)]
        decs, refs = self._streams(specs)
        # a third session whose chunk blob is truncated garbage
        codec = _v2_uniform_codec(4)
        payloads = list(codec.encode_stream(flat, chunk_elems=500,
                                            coder_mode="rans"))
        bad = ChunkStreamDecoder(payloads[0], chunk_batch=0)
        bad.add_chunk(payloads[1][:5])
        n_chunks, n_elems, failures = flush_decoders(decs + [bad])
        assert [d for d, _ in failures] == [bad]
        for d, (codec, x), ref in zip(decs, specs, refs):
            np.testing.assert_array_equal(d.finish(x.shape), ref)

    def test_discard_leaves_others_intact(self):
        flat = _flat_input(n=2600)
        specs = [(_v2_uniform_codec(4), flat),
                 (_v2_uniform_codec(8), 0.7 * flat)]
        decs, refs = self._streams(specs)
        batcher = DecodeBatcher()
        for d in decs:
            batcher.note(d)
        batcher.discard(decs[0])
        assert batcher.pending_sessions == 1
        assert batcher.drain() == []
        np.testing.assert_array_equal(decs[1].finish(specs[1][1].shape),
                                      refs[1])


@pytest.fixture(scope="module")
def features():
    rng = np.random.default_rng(7)
    mu = np.linspace(0.0, 6.0, 16).astype(np.float32)
    return (mu[None, :] + rng.exponential(1.0, (512, 16))).astype(np.float32)


def _live_codec(features, n_levels=8):
    return calibrate(CodecConfig(n_levels=n_levels, clip_mode="minmax",
                                 constrain_cmin_zero=False,
                                 granularity="channel", channel_axis=-1,
                                 channel_group_size=4), samples=features)


class TestServerTick:
    def test_concurrent_sessions_tick_counters(self, features):
        codec = _live_codec(features)

        async def run():
            async with CloudServer(echo_features=True) as srv:
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600) as client:
                    tensors = [features, 0.5 * features, 2.0 * features]
                    res = await asyncio.gather(
                        *[client.submit(t) for t in tensors])
                    return res, srv.counters

        results, counters = asyncio.run(run())
        for t, res in zip([features, 0.5 * features, 2.0 * features],
                          results):
            ref = codec.decode(codec.encode(t), shape=t.shape)
            np.testing.assert_array_equal(np.asarray(res.arrays[0]), ref)
        assert counters["sessions_served"] == 3
        assert counters["ticks"] >= 1
        assert counters["entropy_calls"] >= 1
        assert counters["queue_depth"] == 0
        assert counters["bpe_avg"] > 0
        # same codec + shape -> same header bytes: parsed once, shared
        assert counters["header_cache"]["hits"] >= 2
        assert counters["header_cache"]["misses"] >= 1

    def test_max_chunks_trigger_beats_long_window(self, features):
        # max_wait_s is effectively infinite; completion must come from
        # the max_chunks drain trigger + ready-with-nothing-pending rule
        codec = _live_codec(features)
        tick = TickConfig(max_wait_s=60.0, max_chunks=1)

        async def run():
            async with CloudServer(echo_features=True, tick=tick) as srv:
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600) as client:
                    return await client.submit(features)

        t0 = time.perf_counter()
        res = asyncio.run(run())
        assert time.perf_counter() - t0 < 30.0
        ref = codec.decode(codec.encode(features), shape=features.shape)
        np.testing.assert_array_equal(np.asarray(res.arrays[0]), ref)

    def test_tick_window_latency_bound(self, features):
        # a lone session's END must not wait out more than ~max_wait_s
        # plus processing time; generous margin for CI schedulers
        codec = _live_codec(features)
        tick = TickConfig(max_wait_s=0.01, max_chunks=1 << 30)

        async def run():
            async with CloudServer(echo_features=True, tick=tick) as srv:
                async with EdgeClient("127.0.0.1", srv.port,
                                      codec=codec) as client:
                    t0 = time.perf_counter()
                    await client.submit(features)
                    return time.perf_counter() - t0

        assert asyncio.run(run()) < 10.0

    def test_disconnect_mid_tick_leaves_others_intact(self, features):
        codec = _live_codec(features)
        tick = TickConfig(max_wait_s=0.05, max_chunks=1 << 30)

        async def run():
            async with CloudServer(echo_features=True, tick=tick) as srv:
                # connection A: half a tensor stream, then vanish
                frames = list(tensor_to_frames(codec, features, session=0,
                                               chunk_elems=600))
                reader_a, writer_a = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                for fb in frames[:max(2, len(frames) // 2)]:
                    writer_a.write(fb)
                await writer_a.drain()
                await asyncio.sleep(0.01)   # let the server buffer them
                writer_a.close()
                await writer_a.wait_closed()
                # connection B: a full submit, concurrently mid-tick
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600) as client:
                    res = await client.submit(0.5 * features)
                await asyncio.sleep(0.2)    # tick drains, A forgotten
                return res, srv.counters

        res, counters = asyncio.run(run())
        ref = codec.decode(codec.encode(0.5 * features),
                           shape=features.shape)
        np.testing.assert_array_equal(np.asarray(res.arrays[0]), ref)
        assert counters["sessions_served"] == 1
        assert counters["queue_depth"] == 0     # A's decoder was purged
        assert counters["decode_errors"] == 0


class TestClientTick:
    def test_coalesced_submits_bit_exact(self, features):
        codec = _live_codec(features)
        tick = TickConfig(max_wait_s=0.01, max_batch=8)

        async def run():
            async with CloudServer(echo_features=True) as srv:
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600,
                                      tick=tick) as client:
                    tensors = [features, 0.5 * features, 2.0 * features]
                    res = await asyncio.gather(
                        *[client.submit(t) for t in tensors])
                    return res, dict(client.encode_counters)

        results, counters = asyncio.run(run())
        for t, res in zip([features, 0.5 * features, 2.0 * features],
                          results):
            ref = codec.decode(codec.encode(t), shape=t.shape)
            np.testing.assert_array_equal(np.asarray(res.arrays[0]), ref)
            assert res.coded_bytes > 0
        assert counters["sessions"] == 3
        assert counters["ticks"] >= 1
        assert counters["entropy_calls"] == counters["ticks"]


class TestSharedBank:
    def test_hit_miss_and_identity(self, features):
        clear_bank_cache()
        cfg = CodecConfig(n_levels=8, clip_mode="minmax",
                          constrain_cmin_zero=False)
        try:
            b1 = shared_bank(cfg, features.reshape(-1))
            b2 = shared_bank(cfg, features.reshape(-1))
            assert b1 is b2
            assert bank_cache_stats() == {"hits": 1, "misses": 1,
                                          "entries": 1}
            # different samples -> different bank
            b3 = shared_bank(cfg, 2.0 * features.reshape(-1))
            assert b3 is not b1
            assert bank_cache_stats()["entries"] == 2
        finally:
            clear_bank_cache()


class TestHeaderCache:
    def test_parse_once_per_distinct_header(self, features):
        codec = _live_codec(features)
        payloads = list(codec.encode_stream(features, chunk_elems=600))
        hdr = payloads[0]
        cache = HeaderCache(maxsize=4)
        # deferred decoders wired to one cache share the parsed header
        dec1 = ChunkStreamDecoder(hdr, chunk_batch=0, header_cache=cache)
        dec2 = ChunkStreamDecoder(hdr, chunk_batch=0, header_cache=cache)
        assert dec1.header is dec2.header
        assert cache.stats == {"hits": 1, "misses": 1, "entries": 1}
        # a different rung -> different header bytes -> fresh parse
        other = list(_live_codec(features, n_levels=4)
                     .encode_stream(features, chunk_elems=600))
        dec3 = ChunkStreamDecoder(other[0], chunk_batch=0,
                                  header_cache=cache)
        assert dec3.header is not dec1.header
        assert cache.stats == {"hits": 1, "misses": 2, "entries": 2}

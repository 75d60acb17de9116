"""Stream-conformance suite: frozen golden bitstreams for every wire
format the codec has shipped (v1 seed, v2 per-tensor/ECSQ/legacy-channel,
v3 1-D tile, v4 2-D tile; one-shot and chunked-stream forms).

Asserts *byte-exact* encode and *bit-exact* decode against the committed
vectors under ``tests/golden/``, so a refactor of the quantizer, entropy
stage, header layout or coded order cannot silently break compatibility
with streams already on the wire.  Regenerate (only for intentional
format changes) with ``python tests/regen_golden.py``; diffs in existing
``.stream.bin`` files are wire-compatibility breaks and need a new
header version instead.
"""

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from golden_cases import CASES, unpack_payloads  # noqa: E402
from repro.core.codec import (FLAG_CHANNEL, FLAG_ECSQ, FLAG_TILE,
                              FLAG_TILE2D, FLAG_V2,
                              parse_header)  # noqa: E402

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "golden"
_IDS = [c.name for c in CASES]


def _load(case):
    stream = (GOLDEN_DIR / f"{case.name}.stream.bin").read_bytes()
    x = np.load(GOLDEN_DIR / f"{case.name}.input.npy")
    decoded = np.load(GOLDEN_DIR / f"{case.name}.decoded.npy")
    return x, stream, decoded


@pytest.mark.parametrize("case", CASES, ids=_IDS)
class TestGoldenStreams:
    def test_input_deterministic(self, case):
        """The case's input construction still reproduces the committed
        tensor (separates "rng/input drifted" from "format broke")."""
        np.testing.assert_array_equal(case.make_input(), _load(case)[0])

    def test_encode_byte_exact(self, case):
        """Encoding the frozen input reproduces the frozen bytes --
        header layout, coded order and entropy payload all unchanged.
        Decode-only legacy formats freeze their layout through the
        manual builder the seed/PR-1 encoders used."""
        x, stream, _ = _load(case)
        assert case.encode(x) == stream, (
            f"{case.name}: encoder output differs from the committed "
            "golden stream -- this is a wire-format change")

    def test_decode_bit_exact(self, case):
        x, stream, decoded = _load(case)
        got = np.asarray(case.decode(stream, x), np.float32)
        assert got.dtype == decoded.dtype and got.shape == decoded.shape
        np.testing.assert_array_equal(got, decoded)


class TestGoldenCoverage:
    """The committed vectors actually span the formats they claim to."""

    def _flags(self, name):
        stream = (GOLDEN_DIR / f"{name}.stream.bin").read_bytes()
        return parse_header(stream).flags

    def test_v1_has_no_flags(self):
        assert self._flags("v1_seed_uniform") == 0

    def test_v2_flags(self):
        assert self._flags("v2_uniform_rans") == FLAG_V2
        assert self._flags("v2_ecsq") == FLAG_V2 | FLAG_ECSQ
        assert self._flags("v2_channel_legacy") == FLAG_V2 | FLAG_CHANNEL

    def test_v3_v4_flags(self):
        assert self._flags("v3_tile") == FLAG_V2 | FLAG_TILE
        assert self._flags("v4_tile2d") == FLAG_V2 | FLAG_TILE2D
        assert self._flags("v4_tile2d_ecsq") == FLAG_V2 | FLAG_TILE2D

    def test_v4_header_carries_2d_geometry(self):
        stream = (GOLDEN_DIR / "v4_tile2d.stream.bin").read_bytes()
        hdr = parse_header(stream)
        assert hdr.plan is not None and hdr.plan.is_2d
        assert hdr.plan.spatial_block_hw == (4, 3)
        assert hdr.plan.spatial_hw == (11, 9)
        ecsq = parse_header(
            (GOLDEN_DIR / "v4_tile2d_ecsq.stream.bin").read_bytes())
        assert ecsq.tile_levels is not None
        assert ecsq.tile_levels.shape == (ecsq.plan.n_tiles, 4)

    def test_streamed_chunks_align_to_tiles(self):
        """The committed streamed vectors chunk on tile-aligned element
        boundaries (the v3/v4 chunk-alignment rule): the stream-meta
        chunk size must be a whole multiple of the plan's tile run
        length in coded order."""
        from repro.core.codec import ChunkStreamDecoder
        for name in ("v3_tile_stream", "v4_tile2d_stream"):
            payloads = unpack_payloads(
                (GOLDEN_DIR / f"{name}.stream.bin").read_bytes())
            assert len(payloads) > 2, "streamed vector must be chunked"
            dec = ChunkStreamDecoder(payloads[0])
            plan = dec.header.plan
            assert plan is not None
            m = dec.header.n_elems // plan.n_channels
            sizes = plan.band_sizes(m)
            run = int(sizes[0]) if (sizes == sizes[0]).all() else m
            assert dec.chunk_elems % run == 0, (
                f"{name}: chunk size {dec.chunk_elems} splits the "
                f"{run}-element tile run")
            assert dec.n_chunks == -(-dec.header.n_elems
                                     // dec.chunk_elems)

    def test_coder_ids(self):
        """Payload coder-id bytes stay stable (1-byte id after header)."""
        serial = (GOLDEN_DIR / "v2_uniform_serial.stream.bin").read_bytes()
        hdr = parse_header(serial)
        assert serial[hdr.payload_off] == 0          # serial CABAC
        rans = (GOLDEN_DIR / "v2_uniform_rans.stream.bin").read_bytes()
        hdr = parse_header(rans)
        assert rans[hdr.payload_off] == 1            # vectorized rANS
        # decode-only sharded containers
        for name, coder in (("v2_rans_shard2", 2), ("v2_rans_proc3", 3)):
            stream = (GOLDEN_DIR / f"{name}.stream.bin").read_bytes()
            assert stream[parse_header(stream).payload_off] == coder


_ENCODABLE = [c for c in CASES if not c.decode_only]
_BACKENDS = ["jnp", "kernel_interpret"]


def _with_backend(codec, backend):
    import dataclasses
    codec.config = dataclasses.replace(codec.config, backend=backend)
    return codec


def _host_single_shard(codec, x):
    """The host reference for coder 4: coder-2 layout with exactly one
    shard (``rans.encode_planes``) over the same coded-order indices."""
    import jax.numpy as jnp

    from golden_cases import build_sharded_payload

    coded = np.asarray(codec.backend.coded_indices_device(
        jnp.asarray(x), codec.spec(), codec.bits_per_index()))
    return build_sharded_payload(coded, codec.config.n_levels, 1)


@pytest.mark.parametrize("backend", _BACKENDS)
class TestDeviceEntropyConformance:
    """Coder id 4 (device-resident interleaved rANS): the device stream
    must be byte-identical to the host coder-2 single-shard stream past
    the coder-id byte, and decode bit-exact to the committed golden
    reconstructions -- the fused encode path may emit wire bytes on
    device only because these hold on every shipped format."""

    @pytest.mark.parametrize("case", _ENCODABLE,
                             ids=[c.name for c in _ENCODABLE])
    def test_payload_byte_identity_vs_host_coder2(self, case, backend):
        import jax.numpy as jnp

        x = _load(case)[0]
        codec = _with_backend(case.make_codec(x), backend)
        host2 = _host_single_shard(codec, x)
        dev, hist = codec.backend.encode_fused(
            jnp.asarray(x), codec.spec(), codec.bits_per_index(),
            emit_wire=True)
        assert hist is None
        assert dev[0] == 4 and host2[0] == 2
        assert dev[1:] == host2[1:], (
            f"{case.name}: device rANS payload diverged from the host "
            "single-shard reference")

    @pytest.mark.parametrize("case", _ENCODABLE,
                             ids=[c.name for c in _ENCODABLE])
    def test_device_stream_decodes_to_golden(self, case, backend):
        """encode(device_entropy=True) decodes bit-exact to the same
        committed reconstruction the host stream decodes to."""
        from golden_cases import pack_payloads

        x, _, decoded = _load(case)
        codec = _with_backend(case.make_codec(x), backend)
        if case.streamed:
            stream = pack_payloads(list(codec.encode_stream(
                x, chunk_elems=case.chunk_elems,
                coder_mode=case.coder_mode, device_entropy=True)))
            got = codec.decode_stream(unpack_payloads(stream))
        else:
            stream = codec.encode(x, coder_mode=case.coder_mode,
                                  device_entropy=True)
            got = codec.decode(stream, shape=x.shape)
        np.testing.assert_array_equal(np.asarray(got, np.float32), decoded)

    def test_random_tile_plans_byte_identity(self, backend):
        """Fresh (non-golden) TilePlan geometries: device payload stays
        byte-identical to the host reference on randomly drawn 1-D and
        2-D tilings."""
        import jax.numpy as jnp

        from repro.core import CodecConfig, calibrate

        rng = np.random.default_rng(20260808)
        for trial in range(4):
            c = 2 * int(rng.integers(1, 4))
            h = int(rng.integers(4, 13))
            w = int(rng.integers(4, 13))
            x = rng.normal(0.0, 2.0, (1, c, h, w)).astype(np.float32)
            if trial % 2 == 0:
                tiling = dict(spatial_block_size=int(rng.integers(2, 5)))
            else:
                tiling = dict(spatial_block_hw=(
                    int(rng.integers(2, min(5, h + 1))),
                    int(rng.integers(2, min(5, w + 1)))))
            codec = calibrate(
                CodecConfig(n_levels=int(rng.choice([2, 4, 8])),
                            clip_mode="minmax",
                            constrain_cmin_zero=False,
                            granularity="tile", channel_axis=1,
                            channel_group_size=2, backend=backend,
                            **tiling), samples=x)
            host2 = _host_single_shard(codec, x)
            dev, _ = codec.backend.encode_fused(
                jnp.asarray(x), codec.spec(), codec.bits_per_index(),
                emit_wire=True)
            assert dev[0] == 4 and dev[1:] == host2[1:], (
                f"trial {trial}: tiling {tiling} diverged")

    def test_unsupported_levels_fall_back_to_host_same_container(
            self, backend):
        """n_levels above the device coder's lane budget host-codes the
        planes but ships the identical coder-4 container bytes."""
        from repro.core import CodecConfig, calibrate
        from repro.kernels.rans_coder import MAX_DEVICE_LEVELS, \
            device_supported

        n_levels = MAX_DEVICE_LEVELS + 1
        rng = np.random.default_rng(7)
        x = rng.exponential(1.0, 513).astype(np.float32)
        assert not device_supported(x.size, n_levels)
        codec = calibrate(CodecConfig(n_levels=n_levels,
                                      clip_mode="minmax",
                                      constrain_cmin_zero=False,
                                      backend=backend), samples=x)
        host2 = _host_single_shard(codec, x)
        stream = codec.encode(x, device_entropy=True)
        hdr = parse_header(stream)
        payload = stream[hdr.payload_off:]
        assert payload[0] == 4 and payload[1:] == host2[1:]
        np.testing.assert_array_equal(
            np.asarray(codec.decode(stream, shape=x.shape), np.float32),
            np.asarray(codec.decode(
                stream[:hdr.payload_off] + host2, shape=x.shape),
                np.float32))

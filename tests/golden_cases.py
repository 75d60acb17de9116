"""Shared definitions of the frozen stream-conformance vectors.

Each case pins one wire format the codec has ever shipped (v1 seed
streams through the v4 2-D tile extension) with a deterministic input
tensor and codec construction, so ``tests/test_stream_conformance.py``
can assert *byte-exact* encode and *bit-exact* decode against the
committed files under ``tests/golden/`` -- the compatibility gate that
keeps refactors from silently breaking decode of deployed streams.

Regenerate the files with ``python tests/regen_golden.py`` (only when a
format change is intentional -- a diff in an existing ``.stream.bin`` is
a wire-compatibility break and must bump the format version instead).

Determinism notes: inputs come from ``np.random.default_rng`` (PCG64,
stable by specification); codecs use either explicit manual ranges /
quantizer tables or ``minmax`` calibration (exact elementwise float ops,
no accumulation-order dependence); entropy coder modes are pinned
(never "auto").  Quantizer indices are bit-identical across backends by
the QuantBackend contract, so these cases hold under the jnp, kernel
and kernel_interpret matrices alike.
"""

from __future__ import annotations

import struct

import numpy as np

from repro.core import CodecConfig, calibrate
from repro.core import cabac, rans
from repro.core.binarization import index_to_context_bits
from repro.core.codec import (FeatureCodec, _CHANNEL_EXT_FMT, _HEADER_FMT,
                              FLAG_CHANNEL, FLAG_V2)
from repro.core.ecsq import ECSQQuantizer

GOLDEN_SEED = 20260731


def _flat_input(n=3000, seed=GOLDEN_SEED):
    """Per-tensor calibration-friendly activations (ReLU-like)."""
    rng = np.random.default_rng(seed)
    return rng.exponential(1.5, n).astype(np.float32)


def _conv_input(shape=(1, 6, 11, 9), seed=GOLDEN_SEED + 1):
    """NCHW conv map with channel + row + column statistic drift."""
    rng = np.random.default_rng(seed)
    _, c, h, w = shape
    x = rng.exponential(1.0, shape).astype(np.float32)
    x += np.linspace(0.0, 5.0, c)[None, :, None, None]
    x += np.linspace(0.0, 3.0, h)[None, None, :, None]
    x += np.linspace(0.0, 2.0, w)[None, None, None, :]
    return x.astype(np.float32)


def build_v1_stream(x: np.ndarray, cmin: float, cmax: float,
                    n_levels: int) -> bytes:
    """A seed-format (v1) stream: 16-byte header with *no* flags and a
    bare serial-CABAC payload.  The current encoder always writes v2+
    headers, so v1 is decode-only -- this helper freezes the layout the
    seed encoder used."""
    from repro.core.backend import QuantSpec, get_backend
    idx = np.asarray(get_backend("jnp").quantize(x, QuantSpec(
        float(cmin), float(cmax), n_levels)))
    header = struct.pack(_HEADER_FMT, cmin, cmax, n_levels, 0, x.size)
    return header + cabac.encode_indices_serial(idx.ravel(), n_levels)


def build_v2_channel_stream(x: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                            n_levels: int) -> bytes:
    """A legacy v2 per-channel stream (FLAG_CHANNEL ext, group size 1):
    produced by the PR-1 encoder, decode-only since PR 3 replaced it with
    the v3 tile ext.  ``x`` is (M, C) channel-minor."""
    from repro.core.backend import get_backend, spec_from_numpy
    spec = spec_from_numpy(lo, hi, n_levels, -1)
    idx = np.asarray(get_backend("jnp").quantize(x, spec))
    flags = FLAG_V2 | FLAG_CHANNEL
    header = struct.pack(_HEADER_FMT, float(lo.min()), float(hi.max()),
                         n_levels, flags, x.size)
    header += struct.pack(_CHANNEL_EXT_FMT, x.ndim, x.ndim - 1, 1,
                          lo.size)
    header += np.asarray(x.shape, "<u4").tobytes()
    header += np.stack([lo, hi], axis=-1).astype("<f4").tobytes()
    return header + cabac.encode_indices(idx.ravel(), n_levels,
                                         mode="rans")


def build_sharded_payload(idx: np.ndarray, n_levels: int, n_shards: int,
                          coder_id: int = 2) -> bytes:
    """An entropy payload in the sharded layout of coder ids 2 and 3:
    id byte | <H> n_shards | n_shards x <I> byte length | shard streams,
    each an independent ``rans.encode_planes`` stream over one contiguous
    element range of ``ceil(n / n_shards)`` elements (the last may be
    short; ranges past the end are left out).  The thread- (2) and
    process-pool (3) encoders that wrote it are retired, so both ids are
    decode-only -- this helper freezes their layout.  Coder 4 is the
    same layout at one shard."""
    idx = np.asarray(idx).ravel()
    per = -(-idx.size // n_shards)
    blobs = [rans.encode_planes(index_to_context_bits(idx[a:a + per],
                                                      n_levels))
             for a in range(0, idx.size, max(per, 1))]
    return (bytes([coder_id]) + struct.pack("<H", len(blobs))
            + struct.pack(f"<{len(blobs)}I", *[len(b) for b in blobs])
            + b"".join(blobs))


def build_v2_sharded_stream(x: np.ndarray, cmin: float, cmax: float,
                            n_levels: int, n_shards: int,
                            coder_id: int) -> bytes:
    """A v2 per-tensor stream whose payload is a coder-2 or coder-3
    sharded container (decode-only since those encoders were retired)."""
    from repro.core.backend import QuantSpec, get_backend
    idx = np.asarray(get_backend("jnp").quantize(x, QuantSpec(
        float(cmin), float(cmax), n_levels)))
    header = struct.pack(_HEADER_FMT, cmin, cmax, n_levels, FLAG_V2, x.size)
    return header + build_sharded_payload(idx, n_levels, n_shards, coder_id)


def _v2_uniform_codec(n_levels=4):
    return calibrate(CodecConfig(n_levels=n_levels, clip_mode="manual",
                                 manual_cmin=0.0, manual_cmax=9.0))


def _v2_ecsq_codec():
    """Per-tensor ECSQ with an explicit (non-designed) level table."""
    codec = _v2_uniform_codec()
    codec.ecsq = ECSQQuantizer.from_levels(
        np.array([0.0, 1.0, 2.5, 5.0], np.float32))
    return codec


def _v3_tile_codec(x):
    return calibrate(CodecConfig(n_levels=4, clip_mode="minmax",
                                 constrain_cmin_zero=False,
                                 granularity="tile", channel_axis=1,
                                 channel_group_size=2,
                                 spatial_block_size=32), samples=x)


def _v4_tile2d_codec(x, use_ecsq=False, n_levels=4):
    return calibrate(CodecConfig(n_levels=n_levels, clip_mode="minmax",
                                 constrain_cmin_zero=False,
                                 granularity="tile", channel_axis=1,
                                 channel_group_size=2,
                                 spatial_block_hw=(4, 3),
                                 use_ecsq=use_ecsq), samples=x)


class Case:
    """One conformance vector: a deterministic (input, stream) pair.

    ``encode()`` returns the bytes the *current* encoder produces for the
    input (asserted byte-exact against the committed stream);
    ``decode(stream)`` dequantizes a stream (asserted bit-exact against
    the committed reconstruction).  Legacy formats the current encoder no
    longer writes set ``decode_only`` and freeze their byte layout
    through the manual ``build_*`` helpers instead.
    """

    def __init__(self, name: str, make_input, make_codec, *,
                 coder_mode: str = "rans", decode_only: bool = False,
                 builder=None, streamed: bool = False,
                 chunk_elems: int = 0):
        self.name = name
        self.make_input = make_input
        self.make_codec = make_codec
        self.coder_mode = coder_mode
        self.decode_only = decode_only
        self.builder = builder
        self.streamed = streamed
        self.chunk_elems = chunk_elems

    def encode(self, x: np.ndarray) -> bytes:
        if self.builder is not None:
            return self.builder(x)
        codec = self.make_codec(x)
        if self.streamed:
            return pack_payloads(list(codec.encode_stream(
                x, chunk_elems=self.chunk_elems,
                coder_mode=self.coder_mode)))
        return codec.encode(x, coder_mode=self.coder_mode)

    def decode(self, stream: bytes, x: np.ndarray) -> np.ndarray:
        codec = self.make_codec(x)
        if self.streamed:
            return codec.decode_stream(unpack_payloads(stream))
        return codec.decode(stream, shape=x.shape)


def pack_payloads(payloads: list[bytes]) -> bytes:
    """Serialize a payload sequence as u32-length-prefixed records (the
    golden-file form of an ``encode_stream`` run)."""
    return b"".join(struct.pack("<I", len(p)) + p for p in payloads)


def unpack_payloads(blob: bytes) -> list[bytes]:
    out, off = [], 0
    while off < len(blob):
        (n,) = struct.unpack_from("<I", blob, off)
        off += 4
        out.append(blob[off:off + n])
        off += n
    if off != len(blob):
        raise ValueError("trailing bytes in packed payload stream")
    return out


def _receiver(x):
    """A state-free receiver codec (self-describing formats need no
    calibration match)."""
    return _v2_uniform_codec()


CASES = [
    Case("v1_seed_uniform", _flat_input, _receiver, decode_only=True,
         builder=lambda x: build_v1_stream(x, 0.0, 9.0, 4)),
    Case("v2_uniform_serial", lambda: _flat_input(n=800),
         lambda x: _v2_uniform_codec(), coder_mode="serial"),
    Case("v2_uniform_rans", _flat_input, lambda x: _v2_uniform_codec()),
    Case("v2_uniform_n8", _flat_input,
         lambda x: _v2_uniform_codec(n_levels=8)),
    Case("v2_ecsq", _flat_input, lambda x: _v2_ecsq_codec()),
    Case("v2_channel_legacy",
         lambda: _flat_input(n=1024).reshape(128, 8) +
         np.linspace(0.0, 6.0, 8, dtype=np.float32)[None, :],
         _receiver, decode_only=True,
         builder=lambda x: build_v2_channel_stream(
             x, x.min(axis=0), x.max(axis=0), 4)),
    Case("v3_tile", _conv_input, _v3_tile_codec),
    Case("v3_tile_stream", _conv_input, _v3_tile_codec, streamed=True,
         chunk_elems=128),
    Case("v4_tile2d", _conv_input, _v4_tile2d_codec),
    Case("v4_tile2d_n8", _conv_input,
         lambda x: _v4_tile2d_codec(x, n_levels=8)),
    Case("v4_tile2d_ecsq", _conv_input,
         lambda x: _v4_tile2d_codec(x, use_ecsq=True)),
    Case("v4_tile2d_stream", _conv_input, _v4_tile2d_codec, streamed=True,
         chunk_elems=64),
    Case("v2_rans_shard2", _flat_input, _receiver, decode_only=True,
         builder=lambda x: build_v2_sharded_stream(x, 0.0, 9.0, 4,
                                                   n_shards=4, coder_id=2)),
    Case("v2_rans_proc3", lambda: _flat_input(n=2999), _receiver,
         decode_only=True,
         builder=lambda x: build_v2_sharded_stream(x, 0.0, 9.0, 4,
                                                   n_shards=3, coder_id=3)),
]

"""QuantBackend: one dispatch point for every quantization primitive.

The seed grew three parallel implementations of clip/quantize/histogram --
inline jnp in ``core``, Pallas kernels in ``repro.kernels`` that nothing
called, and numpy helpers on the host.  ``FeatureCodec`` (and everything
above it: split runtime, serving engine, examples) now routes through a
backend object so the hot path picks the fused Pallas kernels on TPU and
the plain-jnp reference everywhere else, from a single code path.

Backends implement seven primitives over a :class:`QuantSpec`:

    quantize(x, spec)             -> int32 indices
    dequantize(idx, spec, dtype)  -> reconstructed values
    quantize_dequantize(x, spec)  -> (indices, reconstruction)  [fused]
    histogram(idx, n_levels)      -> (n_levels,) int32 counts
    tile_histogram(idx, spec)     -> (n_cgroups, n_sblocks, N) counts
    pack_indices(idx, bits)       -> uint8 wire bytes (in-graph pack)
    encode_fused(x, spec, bits)   -> (coded-order indices, per-tile hists)

``encode_fused`` is the host encode path's single-pass contract: on the
kernel backend one fused megakernel pass (clip -> quantize -> bit-pack ->
per-tile histogram) produces wire-width packed bytes plus tile index
counts, so exactly one device->host transfer feeds the entropy stage --
no int32 index tensor ever crosses.  The jnp backend fulfils the same
contract with its vectorized formulas (on CPU there is no transfer to
save).  Both return bit-identical coded-order indices, which keeps the
entropy payload byte-identical to the unfused reference path.

``encode_fused(..., emit_wire=True)`` moves the *entropy stage itself*
onto the device: quantize, coded-order permute and the interleaved-rANS
bit-plane coder (``repro.kernels.rans_coder``) all run in-graph, and the
call returns ``(payload, None)`` where ``payload`` is a finished
coder-id-4 bitstream (or a list of per-chunk payloads when
``chunk_bounds`` is given).  Only the coded wire bytes plus the small
per-span probability side info cross device->host.  Payloads are
byte-identical to the host coder id 2 single-shard stream past the id
byte, and shapes the device coder cannot take (``n_levels`` above
:data:`~repro.kernels.rans_coder.MAX_DEVICE_LEVELS`, oversize tensors)
fall back to the host step loop inside the same container -- the wire
format never depends on where the blob was coded.  ``want_hist`` is
incompatible with ``emit_wire`` (histograms live on the index path).

Selection: ``get_backend()`` picks "kernel" when JAX's default backend is
TPU and "jnp" otherwise; override per-codec via ``CodecConfig.backend`` or
globally with the ``REPRO_QUANT_BACKEND`` environment variable
("jnp" | "kernel" | "kernel_interpret", the latter forcing the Pallas
bodies through the interpreter for CPU validation).

Granularity is a :class:`~repro.core.tiling.TilePlan`: ``spec.plan is
None`` with scalar cmin/cmax is the paper's per-tensor mode; a plan makes
cmin/cmax (n_cgroups, n_sblocks) per-tile tables over the channel-major
view.  The legacy per-channel spec form -- (C,) vectors plus
``channel_axis``, produced by v2 stream headers and direct QuantSpec
users -- is normalized into a one-spatial-block plan on entry, so there
is exactly one granularity code path per backend.  The two backends
produce bit-identical *indices* for every plan (so bitstreams and rate
accounting never depend on the backend); reconstructions agree to ~1 ulp
(fma/ordering differences in ``cmin + q*delta``).  Dequantize-only calls
(receiver side) always use the jnp formula -- there is no dedicated
kernel because on-device decode gets the reconstruction from the fused
quantize_dequantize pass.  Per-tile ECSQ (``spec.ecsq`` a
:class:`~repro.core.tiling.TileECSQ`) runs on the jnp formulas in both
backends: it is a host/receiver path, not the in-graph hot path.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracing import span, tracer
from . import uniform
from .tiling import TileECSQ, TilePlan

_CHANNEL_EPS = 1e-12  # degenerate-range guard, shared with the tile kernel


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """Everything a backend needs to quantize one tensor.

    ``cmin``/``cmax`` are floats (per-tensor), (C,) arrays broadcast
    along ``channel_axis`` (legacy per-channel form), or
    (n_cgroups, n_sblocks) per-tile tables when ``plan`` is set.
    ``ecsq`` optionally carries a designed non-uniform quantizer: an
    ``ECSQQuantizer`` (per-tensor) or a ``TileECSQ`` (per-tile, with
    ``plan``).
    """

    cmin: Any
    cmax: Any
    n_levels: int
    channel_axis: int | None = None
    ecsq: Any = None
    plan: TilePlan | None = None

    @property
    def per_channel(self) -> bool:
        return self.channel_axis is not None or self.plan is not None


def _normalize(spec: QuantSpec) -> QuantSpec:
    """Fold the legacy (C,)-vector per-channel form into a TilePlan, and
    reject spec combinations that would otherwise be silently ignored."""
    if spec.plan is not None or spec.channel_axis is not None:
        if spec.ecsq is not None and not isinstance(spec.ecsq, TileECSQ):
            raise ValueError(
                "a tiled QuantSpec needs per-tile TileECSQ tables; a "
                "per-tensor ECSQQuantizer cannot be combined with a "
                "plan or channel_axis")
    if spec.plan is not None:
        return spec
    if spec.channel_axis is None:
        return spec
    lo = np.asarray(spec.cmin, np.float32).reshape(-1, 1)
    hi = np.asarray(spec.cmax, np.float32).reshape(-1, 1)
    plan = TilePlan(channel_axis=spec.channel_axis, channel_group_size=1,
                    spatial_block_size=0, n_channels=lo.shape[0])
    return dataclasses.replace(spec, cmin=lo, cmax=hi, plan=plan)


def _tile_tables(x_ndim_shape, spec: QuantSpec):
    """Per-element (C, M) range views for a plan spec over ``shape``.

    Returns (axis, C, M, lo, hi) with lo/hi broadcastable against the
    channel-major (C, M) view: (C, 1) when one spatial block (no
    materialized (C, M) table), full (C, M) gathers otherwise.
    """
    plan = spec.plan
    axis, c, m = plan.resolve(x_ndim_shape)
    lo = jnp.asarray(spec.cmin, jnp.float32).reshape(
        plan.n_cgroups, plan.n_sblocks)
    hi = jnp.asarray(spec.cmax, jnp.float32).reshape(
        plan.n_cgroups, plan.n_sblocks)
    cg = plan.cgroup_ids()
    if plan.n_sblocks == 1:
        return axis, c, m, lo[cg], hi[cg]          # (C, 1) broadcast
    sb = plan.sblock_ids(m)
    return axis, c, m, lo[cg][:, sb], hi[cg][:, sb]


def _coded_order(idx: np.ndarray, spec: QuantSpec) -> np.ndarray:
    """Flat coded-order view of quantizer indices (tile-major for plans)."""
    if spec.plan is not None:
        return spec.plan.to_coded_order(idx)
    return np.asarray(idx).ravel()


def _coded_order_device(q, spec: QuantSpec):
    """In-graph mirror of :func:`_coded_order`: device coded-order indices
    with no host round-trip (the spatial permutation is a static gather)."""
    plan = spec.plan
    if plan is None:
        return q.reshape(-1)
    axis, c, m = plan.resolve(q.shape)
    rows = jnp.moveaxis(q, axis, 0).reshape(c, m)
    perm = plan.spatial_perm(m)
    if perm is not None:
        rows = jnp.take(rows, jnp.asarray(perm), axis=1)
    return rows.reshape(-1)


def _unpack_bytes_device(packed, bits: int):
    """In-graph mirror of ``ops.unpack_bytes`` ((R // per, C) uint8 byte
    rows -> (R, C) int32 indices)."""
    from ..kernels.ops import packed_block_rows
    per = 8 // bits if bits in (1, 2, 4) else 1
    if per == 1:
        return packed.astype(jnp.int32)
    rp, c = packed.shape
    s = packed_block_rows(rp, per)
    shifts = (jnp.arange(per, dtype=jnp.uint8) * bits)[None, :, None, None]
    mask = jnp.uint8((1 << bits) - 1)
    vals = (packed.reshape(-1, 1, s, c) >> shifts) & mask
    return vals.reshape(rp * per, c).astype(jnp.int32)


def _unpack_layout_device(idx2d, lay):
    """In-graph mirror of ``PaddedLayout.unpack_indices``: strip the
    megakernel's padded (rows, cols) view down to flat coded order using
    only static slices and gathers."""
    idx2d = idx2d.reshape(lay.rows, lay.cols)
    if lay.flat_n is not None:
        return idx2d.reshape(-1)[:lay.flat_n]
    if lay.band_valid is not None:
        return jnp.take(idx2d[:lay.ch], jnp.asarray(lay.coded_cols()),
                        axis=1).reshape(-1)
    a = idx2d[:lay.ch].reshape(lay.ch, lay.n_sblocks, lay.sb_cols)
    a = a[:, :, :lay.bs].reshape(lay.ch, -1)[:, :lay.m]
    return a.reshape(-1)


def _encode_wire(coded, spec: QuantSpec, chunk_bounds, *, use_kernel: bool,
                 interpret):
    """Device entropy stage: coded-order indices (on device) -> finished
    coder-id-4 payload bytes (one, or one per ``chunk_bounds`` range)."""
    from ..kernels import rans_coder
    if chunk_bounds is None:
        return rans_coder.encode_indices_device(
            coded, spec.n_levels, use_kernel=use_kernel, interpret=interpret)
    return rans_coder.encode_index_chunks_device(
        coded, spec.n_levels, list(chunk_bounds),
        use_kernel=use_kernel, interpret=interpret)


def _tile_hists_np(coded: np.ndarray, spec: QuantSpec) -> np.ndarray:
    """Host per-tile histograms from coded-order indices:
    (n_cgroups, n_sblocks, N); (1, 1, N) for per-tensor specs.

    Works off the coded-order band bounds (every tile is a contiguous
    run of each coded channel row), so 1-D flat runs and 2-D row x column
    blocks are the same loop.
    """
    n = spec.n_levels
    if spec.plan is None:
        return np.bincount(coded, minlength=n).reshape(1, 1, n) \
            .astype(np.int32)
    plan = spec.plan
    c = plan.n_channels
    m = coded.size // max(c, 1)
    arr = coded.reshape(c, m)
    gc = plan.channel_group_size
    bounds = plan.coded_band_bounds(m)
    out = np.zeros((plan.n_cgroups, plan.n_sblocks, n), np.int32)
    for g in range(plan.n_cgroups):
        rows = arr[g * gc:min((g + 1) * gc, c)]
        for b in range(plan.n_sblocks):
            out[g, b] = np.bincount(
                rows[:, bounds[b]:bounds[b + 1]].ravel(), minlength=n)
    return out


class JnpBackend:
    """Pure-jnp reference path (CPU default; numerics identical to seed)."""

    name = "jnp"

    def _tiled_qdq(self, x, spec: QuantSpec, want_deq: bool):
        axis, c, m, lo, hi = _tile_tables(x.shape, spec)
        xm = jnp.moveaxis(x, axis, 0).reshape(c, m).astype(jnp.float32)
        if isinstance(spec.ecsq, TileECSQ):
            tid = spec.plan.tile_ids_2d(m)
            thr = np.asarray(spec.ecsq.thresholds, np.float32)
            xc = jnp.clip(xm, lo, hi)
            idx = jnp.zeros(xm.shape, jnp.int32)
            for k in range(spec.n_levels - 1):
                idx = idx + (xc >= jnp.asarray(thr[:, k])[tid]) \
                    .astype(jnp.int32)
            deq = None
            if want_deq:
                lv = jnp.asarray(spec.ecsq.levels, jnp.float32)
                deq = lv[tid, idx]
        else:
            span = jnp.maximum(hi - lo, _CHANNEL_EPS)
            scale = (spec.n_levels - 1) / span
            xc = jnp.clip(xm, lo, hi)
            q = jnp.floor((xc - lo) * scale + 0.5)
            idx = q.astype(jnp.int32)
            deq = (lo + q * (span / (spec.n_levels - 1))) if want_deq \
                else None

        def restore(a, dtype):
            moved = (c,) + tuple(s for d, s in enumerate(x.shape)
                                 if d != axis)
            return jnp.moveaxis(a.reshape(moved), 0, axis).astype(dtype)
        idx = restore(idx, jnp.int32)
        return idx, (restore(deq, x.dtype) if want_deq else None)

    def quantize(self, x, spec: QuantSpec):
        # index-only path: eager host callers (encode/estimate_rate) would
        # otherwise materialize a discarded reconstruction tensor
        spec = _normalize(spec)
        if spec.plan is not None:
            return self._tiled_qdq(x, spec, want_deq=False)[0]
        if spec.ecsq is not None:
            t = jnp.asarray(spec.ecsq.thresholds, jnp.float32)
            xc = jnp.clip(x.astype(jnp.float32), spec.cmin, spec.cmax)
            return jnp.searchsorted(t, xc, side="right").astype(jnp.int32)
        return uniform.quantize(x, spec.cmin, spec.cmax, spec.n_levels)

    def quantize_dequantize(self, x, spec: QuantSpec):
        spec = _normalize(spec)
        if spec.plan is not None:
            return self._tiled_qdq(x, spec, want_deq=True)
        if spec.ecsq is not None:
            t = jnp.asarray(spec.ecsq.thresholds, jnp.float32)
            lv = jnp.asarray(spec.ecsq.levels, jnp.float32)
            xc = jnp.clip(x.astype(jnp.float32), spec.cmin, spec.cmax)
            idx = jnp.searchsorted(t, xc, side="right").astype(jnp.int32)
            return idx, lv[idx].astype(x.dtype)
        idx = uniform.quantize(x, spec.cmin, spec.cmax, spec.n_levels)
        deq = uniform.dequantize(idx, spec.cmin, spec.cmax,
                                 spec.n_levels, dtype=x.dtype)
        return idx, deq

    def dequantize(self, idx, spec: QuantSpec, dtype=jnp.float32):
        spec = _normalize(spec)
        if spec.plan is not None:
            axis, c, m, lo, hi = _tile_tables(idx.shape, spec)
            im = jnp.moveaxis(idx, axis, 0).reshape(c, m)
            if isinstance(spec.ecsq, TileECSQ):
                lv = jnp.asarray(spec.ecsq.levels, jnp.float32)
                out = lv[spec.plan.tile_ids_2d(m), im]
            else:
                delta = jnp.maximum(hi - lo, _CHANNEL_EPS) \
                    / (spec.n_levels - 1)
                out = lo + im.astype(jnp.float32) * delta
            moved = (c,) + tuple(s for d, s in enumerate(idx.shape)
                                 if d != axis)
            return jnp.moveaxis(out.reshape(moved), 0, axis).astype(dtype)
        if spec.ecsq is not None:
            lv = jnp.asarray(spec.ecsq.levels, jnp.float32)
            return lv[idx].astype(dtype)
        return uniform.dequantize(idx, spec.cmin, spec.cmax,
                                  spec.n_levels, dtype=dtype)

    def histogram(self, idx, n_levels: int):
        from .rate_model import index_histogram
        return index_histogram(idx, n_levels)

    def tile_histogram(self, idx, spec: QuantSpec):
        """(n_cgroups, n_sblocks, N) in-graph per-tile index counts."""
        spec = _normalize(spec)
        if spec.plan is None:
            return self.histogram(idx, spec.n_levels).reshape(1, 1, -1)
        plan = spec.plan
        axis, c, m = plan.resolve(idx.shape)
        im = jnp.moveaxis(idx, axis, 0).reshape(c, m)
        tid = plan.tile_ids_2d(m)
        hist = jnp.zeros((plan.n_tiles, spec.n_levels), jnp.int32) \
            .at[tid, im].add(1)
        return hist.reshape(plan.n_cgroups, plan.n_sblocks, spec.n_levels)

    def coded_indices_device(self, x, spec: QuantSpec, bits: int):
        """Device coded-order indices, no host transfer: quantize +
        coded-order permute stay in-graph (the emit_wire intermediate,
        exposed for cross-session batching)."""
        spec = _normalize(spec)
        return _coded_order_device(self.quantize(x, spec), spec)

    def encode_fused(self, x, spec: QuantSpec, bits: int,
                     want_hist: bool = False, emit_wire: bool = False,
                     chunk_bounds=None):
        """Fused-encode contract on the reference path: coded-order
        indices plus (optionally) host per-tile histograms; with
        ``emit_wire`` the device entropy stage returns finished payload
        bytes instead (see the module docstring)."""
        spec = _normalize(spec)
        tr = tracer()
        if emit_wire:
            if want_hist:
                raise ValueError("emit_wire returns wire bytes; per-tile "
                                 "histograms need the index path")
            with tr.span("fused_launch", backend=self.name), \
                    tr.annotate("repro.encode_fused"):
                coded = self.coded_indices_device(x, spec, bits)
            return _encode_wire(coded, spec, chunk_bounds,
                                use_kernel=False, interpret=False), None
        with tr.span("fused_launch", backend=self.name), \
                tr.annotate("repro.encode_fused"):
            q = self.quantize(x, spec)
            if tr.enabled:
                # bound the launch span at the device sync, so the
                # device_to_host span measures only the transfer
                q = jax.block_until_ready(q)
        with tr.span("device_to_host"):
            q = np.asarray(q)
        with tr.span("host_unpack"):
            coded = _coded_order(q, spec)
        hists = _tile_hists_np(coded, spec) if want_hist else None
        return coded, hists

    def pack_indices(self, idx, bits: int):
        """Host/jnp bit-pack (the wire layout every backend shares)."""
        per = 8 // bits if bits in (1, 2, 4) else 1
        if per == 1:
            return idx.astype(jnp.uint8)
        flat = idx.reshape(-1)
        pad = (-flat.shape[0]) % per
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        flat = flat.reshape(-1, per).astype(jnp.uint8)
        shifts = jnp.arange(per, dtype=jnp.uint8) * bits
        return jnp.sum(flat << shifts, axis=-1).astype(jnp.uint8)


class KernelBackend:
    """Pallas-kernel path (TPU hot path; interpretable on CPU).

    Quantization lowers through the fused clip+quant kernels in
    ``repro.kernels`` (scalar-range or blocked per-tile variant);
    histograms use the on-device reduction kernel and packing the
    on-device pack kernel.  Falls back to the jnp formulas only where no
    kernel exists (dequantize-only, per-tile ECSQ, N > 64).
    """

    name = "kernel"

    def __init__(self, interpret: bool | None = None) -> None:
        # None = interpret exactly when JAX runs on the CPU, resolved here
        # so callers can check which one this backend runs
        self.interpret = (jax.default_backend() == "cpu"
                          if interpret is None else interpret)
        self._jnp = JnpBackend()

    def quantize(self, x, spec: QuantSpec):
        return self.quantize_dequantize(x, spec)[0]

    def quantize_dequantize(self, x, spec: QuantSpec):
        from ..kernels import ops
        from ..kernels.ecsq_assign import MAX_LEVELS
        spec = _normalize(spec)
        if spec.plan is not None:
            if isinstance(spec.ecsq, TileECSQ):
                if spec.n_levels > MAX_LEVELS:
                    return self._jnp.quantize_dequantize(x, spec)
                plan = spec.plan
                plan.resolve(x.shape)
                lo = jnp.asarray(spec.cmin, jnp.float32).reshape(
                    plan.n_cgroups, plan.n_sblocks)
                hi = jnp.asarray(spec.cmax, jnp.float32).reshape(
                    plan.n_cgroups, plan.n_sblocks)
                return ops.ecsq_quantize_tiled(
                    x, lo, hi,
                    jnp.asarray(spec.ecsq.thresholds, jnp.float32),
                    jnp.asarray(spec.ecsq.levels, jnp.float32),
                    n_levels=spec.n_levels, plan=plan,
                    interpret=self.interpret)
            plan = spec.plan
            plan.resolve(x.shape)
            lo = jnp.asarray(spec.cmin, jnp.float32).reshape(
                plan.n_cgroups, plan.n_sblocks)
            hi = jnp.asarray(spec.cmax, jnp.float32).reshape(
                plan.n_cgroups, plan.n_sblocks)
            return ops.clip_quantize_tiled(
                x, lo, hi, n_levels=spec.n_levels, plan=plan,
                interpret=self.interpret)
        if spec.ecsq is not None:
            if spec.n_levels > MAX_LEVELS:
                return self._jnp.quantize_dequantize(x, spec)
            return ops.ecsq_quantize(
                x, jnp.asarray(spec.ecsq.thresholds, jnp.float32),
                jnp.asarray(spec.ecsq.levels, jnp.float32),
                cmin=float(spec.cmin), cmax=float(spec.cmax),
                interpret=self.interpret)
        return ops.clip_quantize(x, cmin=float(spec.cmin),
                                 cmax=float(spec.cmax),
                                 n_levels=spec.n_levels,
                                 interpret=self.interpret)

    def dequantize(self, idx, spec: QuantSpec, dtype=jnp.float32):
        return self._jnp.dequantize(idx, spec, dtype=dtype)

    def histogram(self, idx, n_levels: int):
        from ..kernels import ops
        from ..kernels.rate_hist import MAX_LEVELS
        if n_levels > MAX_LEVELS:
            return self._jnp.histogram(idx, n_levels)
        return ops.index_histogram(idx, n_levels=n_levels,
                                   interpret=self.interpret)

    def tile_histogram(self, idx, spec: QuantSpec):
        from ..kernels import ops
        from ..kernels.rate_hist import MAX_LEVELS
        spec = _normalize(spec)
        if spec.plan is None:
            return self.histogram(idx, spec.n_levels).reshape(1, 1, -1)
        if spec.n_levels > MAX_LEVELS:
            return self._jnp.tile_histogram(idx, spec)
        plan = spec.plan
        plan.resolve(idx.shape)
        return ops.index_histogram_tiled(
            idx, n_levels=spec.n_levels, plan=plan,
            interpret=self.interpret)

    def coded_indices_device(self, x, spec: QuantSpec, bits: int):
        """Device coded-order indices, no host transfer: the megakernel's
        packed output is unpacked and layout-stripped in-graph (the
        emit_wire intermediate, exposed for cross-session batching)."""
        from ..kernels import ops
        from ..kernels.fused_clip_quant import HIST_WIDTH
        spec = _normalize(spec)
        if spec.ecsq is not None or spec.n_levels > HIST_WIDTH:
            return _coded_order_device(self.quantize(x, spec), spec)
        if spec.plan is None:
            packed, _, lay = ops.encode_fused(
                x, float(spec.cmin), float(spec.cmax),
                n_levels=spec.n_levels, bits=bits, interpret=self.interpret)
        else:
            plan = spec.plan
            plan.resolve(x.shape)
            lo = np.asarray(spec.cmin, np.float32).reshape(
                plan.n_cgroups, plan.n_sblocks)
            hi = np.asarray(spec.cmax, np.float32).reshape(
                plan.n_cgroups, plan.n_sblocks)
            packed, _, lay = ops.encode_fused(
                x, lo, hi, n_levels=spec.n_levels, bits=bits,
                plan=plan, interpret=self.interpret)
        return _unpack_layout_device(_unpack_bytes_device(packed, bits), lay)

    def encode_fused(self, x, spec: QuantSpec, bits: int,
                     want_hist: bool = False, emit_wire: bool = False,
                     chunk_bounds=None):
        """One megakernel pass -> (packed bytes + tile hists) on device;
        the np.asarray fetches here are the path's single transfer, and
        the host only unpacks wire-width bytes back to indices.

        ``emit_wire=True`` keeps going on device: the packed megakernel
        output is unpacked and layout-stripped in-graph and fed straight
        into the device rANS stage, so the only device->host traffic is
        the finished coder-id-4 payload."""
        from ..kernels import ops
        from ..kernels.fused_clip_quant import HIST_WIDTH
        spec = _normalize(spec)
        tr = tracer()
        if emit_wire:
            if want_hist:
                raise ValueError("emit_wire returns wire bytes; per-tile "
                                 "histograms need the index path")
            with tr.span("fused_launch", backend=self.name), \
                    tr.annotate("repro.encode_fused"):
                coded = self.coded_indices_device(x, spec, bits)
            return _encode_wire(coded, spec, chunk_bounds,
                                use_kernel=True,
                                interpret=self.interpret), None
        if spec.ecsq is not None or spec.n_levels > HIST_WIDTH:
            # no fused kernel for designed quantizers / wide histograms:
            # kernel-quantize, then the host fallback of the contract
            with tr.span("fused_launch", backend=self.name), \
                    tr.annotate("repro.encode_fused"):
                q = self.quantize(x, spec)
                if tr.enabled:
                    q = jax.block_until_ready(q)
            with tr.span("device_to_host"):
                q = np.asarray(q)
            with tr.span("host_unpack"):
                coded = _coded_order(q, spec)
            return coded, (_tile_hists_np(coded, spec) if want_hist
                           else None)
        with tr.span("fused_launch", backend=self.name), \
                tr.annotate("repro.encode_fused"):
            if spec.plan is None:
                packed, hist, lay = ops.encode_fused(
                    x, float(spec.cmin), float(spec.cmax),
                    n_levels=spec.n_levels, bits=bits,
                    interpret=self.interpret)
            else:
                plan = spec.plan
                plan.resolve(x.shape)
                lo = np.asarray(spec.cmin, np.float32).reshape(
                    plan.n_cgroups, plan.n_sblocks)
                hi = np.asarray(spec.cmax, np.float32).reshape(
                    plan.n_cgroups, plan.n_sblocks)
                packed, hist, lay = ops.encode_fused(
                    x, lo, hi, n_levels=spec.n_levels, bits=bits,
                    plan=plan, interpret=self.interpret)
            if tr.enabled:
                # bound the launch at the device sync so the transfer
                # span below measures only the packed-bytes fetch (the
                # path's single device->host transfer)
                packed = jax.block_until_ready(packed)
        with tr.span("device_to_host"):
            packed = np.asarray(packed)
            hist = np.asarray(hist) if want_hist else hist
        with tr.span("host_unpack"):
            coded = lay.unpack_indices(ops.unpack_bytes(packed, bits))
        hists = lay.group_hists(hist, spec.n_levels,
                                HIST_WIDTH) if want_hist else None
        return coded, hists

    def pack_indices(self, idx, bits: int):
        from ..kernels import ops
        if bits not in (1, 2, 4):
            return self._jnp.pack_indices(idx, bits)
        return ops.pack_indices(idx, bits=bits, interpret=self.interpret)


_BACKENDS: dict[str, Any] = {}


def get_backend(name: str | None = None):
    """Resolve a backend by name, env override, or hardware default."""
    if name is None:
        name = os.environ.get("REPRO_QUANT_BACKEND")
    if name is None:
        name = "kernel" if jax.default_backend() == "tpu" else "jnp"
    if name not in _BACKENDS:
        if name == "jnp":
            _BACKENDS[name] = JnpBackend()
        elif name == "kernel":
            _BACKENDS[name] = KernelBackend()
        elif name == "kernel_interpret":
            _BACKENDS[name] = KernelBackend(interpret=True)
        else:
            raise ValueError(f"unknown quant backend {name!r}")
    return _BACKENDS[name]


def spec_from_numpy(cmin, cmax, n_levels: int, channel_axis: int | None,
                    ecsq=None) -> QuantSpec:
    """Build a QuantSpec from host (numpy/float) calibration state."""
    if channel_axis is None:
        return QuantSpec(float(cmin), float(cmax), n_levels, None, ecsq)
    return QuantSpec(np.asarray(cmin, np.float32),
                     np.asarray(cmax, np.float32),
                     n_levels, channel_axis, ecsq)

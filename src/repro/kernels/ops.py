"""Jitted public wrappers around the Pallas kernels.

Handle arbitrary input shapes (pad + reshape to block-aligned 2-D views),
and select interpret mode automatically on CPU (the kernels' TARGET is
TPU; interpret=True executes the kernel body in Python for validation, as
this container is CPU-only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tiling import PaddedLayout, TilePlan
from . import ref
from .ecsq_assign import ecsq_assign_2d, ecsq_assign_tiles_2d
from .ecsq_assign import MAX_LEVELS as ECSQ_MAX_LEVELS
from .fused_clip_quant import (DEFAULT_BLOCK, HIST_WIDTH, clip_quant_2d,
                               clip_quant_rows_2d, clip_quant_tiles_2d,
                               encode_tiles_2d)
from .pack_bits import pack_rows_2d
from .rate_hist import index_histogram_2d, index_histogram_tiles_2d

_LANE = 128
_ROW = 8


def _pad_lane(n: int, big: int = 512) -> int:
    """Round ``n`` up to a lane multiple; large sizes to a ``big`` multiple
    so the default column block tiles exactly."""
    cols = max(_LANE, ((n + _LANE - 1) // _LANE) * _LANE)
    if cols > big:
        cols = ((cols + big - 1) // big) * big
    return cols


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def flat_layout(n: int) -> PaddedLayout:
    """Geometry of the flat padded (R, C) view ``_to_2d`` builds.

    C is a power-of-two multiple of 128 (<= 1024) and R is rounded up to a
    multiple of min(R, 256), so the (min(256,R), min(512,C)) block grids in
    the wrappers always tile exactly (hypothesis found the n=513 case where
    a 640-wide view left 128 columns outside the grid).
    """
    k = max(1, (n + _LANE - 1) // _LANE)
    cols = _LANE * min(8, 1 << max(0, (k - 1).bit_length()))
    rows = (n + cols - 1) // cols
    align = _ROW if rows <= 256 else 256
    rows = ((rows + align - 1) // align) * align
    return PaddedLayout(rows=rows, cols=cols, ch=rows, m=cols,
                        n_sblocks=1, sb_cols=cols, bs=cols, flat_n=n)


def _to_2d(x, fill: float):
    """Flatten + pad to a block-divisible (R, C) view (see
    :func:`flat_layout`).  Returns (x2d, n_valid)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    lay = flat_layout(n)
    padded = jnp.full((lay.rows * lay.cols,), fill, x.dtype).at[:n].set(flat)
    return padded.reshape(lay.rows, lay.cols), n


def banded_layout(shape, plan: TilePlan) -> PaddedLayout:
    """Geometry of the channel-major banded view the tiled kernels use:
    each spatial block padded to a whole lane-aligned column band, rows
    padded to the sublane multiple.  2-D plans have one band per
    (row-block, column-block) cell, sized for the largest tile; ragged
    edge tiles record their true sizes in ``band_valid``."""
    axis = plan.channel_axis % len(shape)
    ch = shape[axis]
    m = 1
    for d, s in enumerate(shape):
        if d != axis:
            m *= s
    sizes = plan.band_sizes(m)
    bs = int(sizes.max())
    sb_cols = _pad_lane(bs)
    align = _ROW if ch <= 256 else 256
    rows = ((ch + align - 1) // align) * align
    return PaddedLayout(rows=rows, cols=plan.n_sblocks * sb_cols, ch=ch,
                        m=m, n_sblocks=plan.n_sblocks, sb_cols=sb_cols,
                        bs=bs,
                        channel_group_size=max(1, plan.channel_group_size),
                        band_valid=tuple(int(s) for s in sizes)
                        if plan.is_2d else None)


@functools.lru_cache(maxsize=64)
def _padded_cols(plan: TilePlan, lay: PaddedLayout) -> np.ndarray:
    """(m,) original flat spatial position -> column of the banded padded
    view (2-D plans: tile elements land contiguously in their band)."""
    perm = plan.spatial_perm(lay.m)
    out = np.empty(lay.m, np.int64)
    out[perm] = lay.coded_cols()
    out.setflags(write=False)    # shared cache entry: guard the layout map
    return out


def _banded_view(x, lay: PaddedLayout, plan: TilePlan):
    """Scatter ``x`` into the banded device view ``lay`` describes.
    Returns (xp (rows, cols), moved_shape) -- padding is zero-filled and
    masked/stripped downstream.  2-D plans scatter through the coded-
    order column map (each row x column tile contiguous in its band);
    1-D plans keep the cheap reshape path."""
    axis = plan.channel_axis % x.ndim
    xm = jnp.moveaxis(x, axis, 0)
    moved_shape = xm.shape
    x2 = xm.reshape(lay.ch, -1)
    if lay.band_valid is not None:
        pcols = _padded_cols(plan, lay)
        xp = jnp.zeros((lay.rows, lay.cols), x.dtype) \
            .at[:lay.ch, pcols].set(x2)
        return xp, moved_shape
    mp = lay.n_sblocks * lay.bs
    if mp != lay.m:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((lay.ch, mp - lay.m), x.dtype)], axis=1)
    x3 = jnp.zeros((lay.ch, lay.n_sblocks, lay.sb_cols), x.dtype) \
        .at[:, :, :lay.bs].set(x2.reshape(lay.ch, lay.n_sblocks, lay.bs))
    xp = jnp.zeros((lay.rows, lay.cols), x.dtype) \
        .at[:lay.ch].set(x3.reshape(lay.ch, lay.cols))
    return xp, moved_shape


def _row_ranges(lo, hi, lay: PaddedLayout):
    """Expand (n_cgroups, n_sblocks) range tables to per-row columns;
    padding rows get a dummy [0, 1] range."""
    cg = np.arange(lay.ch) // lay.channel_group_size
    lo_r = jnp.zeros((lay.rows, lay.n_sblocks), jnp.float32) \
        .at[:lay.ch].set(lo.astype(jnp.float32)[cg])
    hi_r = jnp.ones((lay.rows, lay.n_sblocks), jnp.float32) \
        .at[:lay.ch].set(hi.astype(jnp.float32)[cg])
    return lo_r, hi_r


def _unband(a, lay: PaddedLayout, moved_shape, axis: int,
            plan: TilePlan | None = None):
    """Inverse of :func:`_banded_view` for a same-shape kernel output."""
    if lay.band_valid is not None:
        pcols = _padded_cols(plan, lay)
        return jnp.moveaxis(
            a[:lay.ch][:, pcols].reshape(moved_shape), 0, axis)
    a = a[:lay.ch].reshape(lay.ch, lay.n_sblocks, lay.sb_cols)[:, :, :lay.bs]
    mp = lay.n_sblocks * lay.bs
    return jnp.moveaxis(
        a.reshape(lay.ch, mp)[:, :lay.m].reshape(moved_shape), 0, axis)


@functools.partial(jax.jit, static_argnames=("cmin", "cmax", "n_levels",
                                             "interpret"))
def clip_quantize(x, *, cmin: float, cmax: float, n_levels: int,
                  interpret: bool | None = None):
    """Fused clip+quantize+dequantize. Returns (idx int32, dequantized)."""
    interpret = _on_cpu() if interpret is None else interpret
    x2d, n = _to_2d(x, cmin)
    br = min(256, x2d.shape[0])
    idx, deq = clip_quant_2d(x2d, cmin, cmax, n_levels,
                             block=(br, min(512, x2d.shape[1])),
                             interpret=interpret)
    shape = x.shape
    return (idx.reshape(-1)[:n].reshape(shape),
            deq.reshape(-1)[:n].reshape(shape))


@functools.partial(jax.jit, static_argnames=("n_levels", "plan",
                                             "interpret"))
def clip_quantize_tiled(x, lo, hi, *, n_levels: int, plan: TilePlan,
                        interpret: bool | None = None):
    """TilePlan fused clip+quantize+dequantize (channel x spatial tiling).

    ``lo``/``hi`` are (n_cgroups, n_sblocks) range tables over the plan's
    channel-major (C, M) view (``plan`` is a static argument: frozen,
    hashable geometry).  The view is laid out with each spatial block
    padded to a whole lane-aligned column band, so the blocked per-tile
    kernel reads one range cell per grid step; rows pad to the sublane
    multiple with a dummy [0, 1] range.  Per-channel granularity is the
    one-spatial-block case; 2-D plans place each row x column tile
    contiguously in its own band (coded-order scatter), so the kernel is
    identical for flat and 2-D spatial splits.
    """
    interpret = _on_cpu() if interpret is None else interpret
    axis = plan.channel_axis % x.ndim
    lay = banded_layout(x.shape, plan)
    xp, moved_shape = _banded_view(x, lay, plan)
    lo_r, hi_r = _row_ranges(lo, hi, lay)
    br = min(256, lay.rows)
    idx, deq = clip_quant_tiles_2d(xp, lo_r, hi_r, n_levels, lay.sb_cols,
                                   block=(br, min(512, lay.cols)),
                                   interpret=interpret)
    return (_unband(idx, lay, moved_shape, axis, plan),
            _unband(deq, lay, moved_shape, axis, plan))


def clip_quantize_channels(x, cmin, cmax, *, n_levels: int,
                           channel_axis: int = -1,
                           interpret: bool | None = None):
    """Per-channel fused clip+quantize+dequantize: the one-spatial-block
    case of :func:`clip_quantize_tiled` (kept as a named entry point)."""
    plan = TilePlan(channel_axis=channel_axis, channel_group_size=1,
                    spatial_block_size=0, n_channels=cmin.size)
    return clip_quantize_tiled(x, cmin.reshape(-1, 1), cmax.reshape(-1, 1),
                               n_levels=n_levels, plan=plan,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cmin", "cmax", "interpret"))
def ecsq_quantize(x, thresholds, levels, *, cmin: float, cmax: float,
                  interpret: bool | None = None):
    """Threshold-based non-uniform quantize + dequantize."""
    interpret = _on_cpu() if interpret is None else interpret
    x2d, n = _to_2d(x, cmin)
    br = min(256, x2d.shape[0])
    idx, deq = ecsq_assign_2d(x2d, thresholds, levels, cmin, cmax,
                              block=(br, min(512, x2d.shape[1])),
                              interpret=interpret)
    shape = x.shape
    return (idx.reshape(-1)[:n].reshape(shape),
            deq.reshape(-1)[:n].reshape(shape))


@functools.partial(jax.jit, static_argnames=("cmin", "cmax", "n_levels",
                                             "bits", "interpret"))
def _encode_fused_flat(x, *, cmin: float, cmax: float, n_levels: int,
                       bits: int, interpret: bool):
    """Jitted flat (per-tensor) megakernel pass.  Pads with ``cmin`` so
    the tail quantizes to index 0 (the histogram correction contract)."""
    x2d, _ = _to_2d(x, cmin)
    r, c = x2d.shape
    lo_r = jnp.full((r, 1), cmin, jnp.float32)
    hi_r = jnp.full((r, 1), cmax, jnp.float32)
    packed, hist = encode_tiles_2d(x2d, lo_r, hi_r, n_levels, bits,
                                   sb_cols=c, bs=c,
                                   block=(min(256, r), min(512, c)),
                                   interpret=interpret)
    return packed.astype(jnp.uint8), hist


@functools.partial(jax.jit, static_argnames=("n_levels", "bits", "plan",
                                             "interpret"))
def _encode_fused_tiled(x, lo, hi, *, n_levels: int, bits: int,
                        plan: TilePlan, interpret: bool):
    """Jitted tiled megakernel pass over the banded view."""
    lay = banded_layout(x.shape, plan)
    xp, _ = _banded_view(x, lay, plan)
    lo_r, hi_r = _row_ranges(lo, hi, lay)
    packed, hist = encode_tiles_2d(xp, lo_r, hi_r, n_levels, bits,
                                   sb_cols=lay.sb_cols, bs=lay.bs,
                                   bs_last=lay.bs_last,
                                   band_valid=lay.band_valid,
                                   block=(min(256, lay.rows),
                                          min(512, lay.cols)),
                                   interpret=interpret)
    return packed.astype(jnp.uint8), hist


def encode_fused(x, lo, hi, *, n_levels: int, bits: int,
                 plan: TilePlan | None = None,
                 interpret: bool | None = None):
    """Single-pass fused encode: clip + quantize + bit-pack + histogram.

    One megakernel dispatch per tile block; the only arrays that leave
    the device are wire-width packed bytes and the per-(row, band)
    histogram -- the encode path's single device->host transfer.
    Returns (packed uint8, hist_raw int32, :class:`PaddedLayout`); the
    host recovers coded-order indices with ``layout.unpack_indices`` and
    per-tile counts with ``layout.group_hists``.

    ``plan is None`` is the per-tensor mode (``lo``/``hi`` floats);
    otherwise ``lo``/``hi`` are (n_cgroups, n_sblocks) range tables over
    the plan's banded view (1-D flat runs or 2-D row x column tiles --
    the megakernel sees only bands either way).
    """
    interpret = _on_cpu() if interpret is None else interpret
    if plan is None:
        lay = flat_layout(int(np.prod(np.shape(x))))
        packed, hist = _encode_fused_flat(x, cmin=float(lo), cmax=float(hi),
                                          n_levels=n_levels, bits=bits,
                                          interpret=interpret)
        return packed, hist, lay
    lay = banded_layout(np.shape(x), plan)
    packed, hist = _encode_fused_tiled(
        x, jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32),
        n_levels=n_levels, bits=bits, plan=plan, interpret=interpret)
    return packed, hist, lay


def packed_block_rows(packed_rows: int, per: int) -> int:
    """Packed rows per kernel row block of the fused encode: the (R, C)
    view runs in blocks of ``min(256, R)`` rows, each packed into
    ``1 / per`` as many byte rows."""
    return min(DEFAULT_BLOCK[0], packed_rows * per) // per


def unpack_bytes(packed: np.ndarray, bits: int) -> np.ndarray:
    """Host inverse of the megakernel bit-pack: (R // per, C) uint8 byte
    rows -> (R, C) int32 indices, ``per = 8 // bits`` per byte (identity
    for ``per == 1``).  Within each row block, byte row ``r`` holds the
    block's rows ``r + k * s`` at bit offset ``k * bits``, with ``s`` the
    block's packed row count (see ``_kernel_encode``)."""
    packed = np.asarray(packed, np.uint8)
    per = 8 // bits if bits in (1, 2, 4) else 1
    if per == 1:
        return packed.astype(np.int32)
    rp, c = packed.shape
    s = packed_block_rows(rp, per)
    shifts = (np.arange(per, dtype=np.uint8) * bits)[None, :, None, None]
    mask = np.uint8((1 << bits) - 1)
    vals = (packed.reshape(-1, 1, s, c) >> shifts) & mask
    return vals.reshape(rp * per, c).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("n_levels", "plan",
                                             "interpret"))
def index_histogram_tiled(idx, *, n_levels: int, plan: TilePlan,
                          interpret: bool | None = None):
    """Per-tile index histogram, in-graph: (n_cgroups, n_sblocks, N).

    The tile-resolved counterpart of :func:`index_histogram` for
    tile-aware rate estimation; runs the banded reduction kernel and
    folds channel rows into their groups in-graph, so per-tile rate
    choices never need the indices on the host.
    """
    interpret = _on_cpu() if interpret is None else interpret
    n_sblocks = plan.n_sblocks
    lay = banded_layout(idx.shape, plan)
    idx_p, _ = _banded_view(idx.astype(jnp.int32), lay, plan)
    hist = index_histogram_tiles_2d(idx_p, n_levels, lay.sb_cols, lay.bs,
                                    bs_last=lay.bs_last,
                                    band_valid=lay.band_valid,
                                    block=(min(256, lay.rows),
                                           min(512, lay.cols)),
                                    interpret=interpret)
    from .rate_hist import MAX_LEVELS
    h = hist.reshape(lay.rows, n_sblocks, MAX_LEVELS)[:lay.ch, :, :n_levels]
    gs = lay.channel_group_size
    n_cgroups = -(-lay.ch // gs)
    pad = n_cgroups * gs - lay.ch
    if pad:
        h = jnp.concatenate(
            [h, jnp.zeros((pad,) + h.shape[1:], h.dtype)], axis=0)
    return h.reshape(n_cgroups, gs, n_sblocks, n_levels).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("n_levels", "plan",
                                             "interpret"))
def ecsq_quantize_tiled(x, lo, hi, thresholds, levels, *, n_levels: int,
                        plan: TilePlan,
                        interpret: bool | None = None):
    """Per-tile ECSQ quantize + dequantize through the Pallas kernel.

    ``thresholds`` (n_tiles, N-1) / ``levels`` (n_tiles, N) are the
    :class:`TileECSQ` tables (flat tile id = cgroup * n_sblocks + sblock);
    ``lo``/``hi`` the (n_cgroups, n_sblocks) clip ranges.  Bit-exact
    indices vs the jnp threshold-compare path (same ``xc >= t`` formula).
    """
    interpret = _on_cpu() if interpret is None else interpret
    axis = plan.channel_axis % x.ndim
    n_sblocks = plan.n_sblocks
    lay = banded_layout(x.shape, plan)
    xp, moved_shape = _banded_view(x, lay, plan)
    lo_r, hi_r = _row_ranges(lo, hi, lay)
    # expand the flat-tile tables to per-(row, band) MAX_LEVELS-wide rows:
    # thresholds pad with +inf (no bin past N), levels zero-pad
    cg = np.arange(lay.ch) // lay.channel_group_size
    thr = jnp.asarray(thresholds, jnp.float32) \
        .reshape(-1, n_sblocks, n_levels - 1)[cg]     # (ch, nb, N-1)
    lvl = jnp.asarray(levels, jnp.float32) \
        .reshape(-1, n_sblocks, n_levels)[cg]
    thr_r = jnp.full((lay.rows, n_sblocks, ECSQ_MAX_LEVELS), jnp.inf,
                     jnp.float32).at[:lay.ch, :, :n_levels - 1].set(thr)
    lvl_r = jnp.zeros((lay.rows, n_sblocks, ECSQ_MAX_LEVELS), jnp.float32) \
        .at[:lay.ch, :, :n_levels].set(lvl)
    idx, deq = ecsq_assign_tiles_2d(
        xp, lo_r, hi_r,
        thr_r.reshape(lay.rows, n_sblocks * ECSQ_MAX_LEVELS),
        lvl_r.reshape(lay.rows, n_sblocks * ECSQ_MAX_LEVELS),
        n_levels, lay.sb_cols,
        block=(min(256, lay.rows), min(512, lay.cols)),
        interpret=interpret)
    return (_unband(idx, lay, moved_shape, axis, plan),
            _unband(deq, lay, moved_shape, axis, plan))


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def pack_indices(idx, *, bits: int, interpret: bool | None = None):
    """Pack int32 indices to ``bits``-wide uint8 lanes on device.

    Same byte layout as the jnp host fallback (see ``pack_bits.py``);
    ``bits`` must be 1, 2 or 4 (wire widths where a byte holds several
    indices).  Returns a flat uint8 array of ``ceil(n / (8 // bits))``
    bytes, zero-padded in the last byte.
    """
    if bits not in (1, 2, 4):
        raise ValueError(f"packable bit widths are 1/2/4, got {bits}")
    interpret = _on_cpu() if interpret is None else interpret
    per = 8 // bits
    flat = idx.reshape(-1).astype(jnp.int32)
    n_out = -(-flat.shape[0] // per)
    cols = _pad_lane(n_out, big=1024)
    lanes = jnp.zeros((cols * per,), jnp.int32).at[:flat.shape[0]].set(flat)
    # lane-view: row j holds the j-th index of every output byte
    lanes = lanes.reshape(cols, per).T                       # (per, cols)
    rows = jnp.zeros((8, cols), jnp.int32).at[:per].set(lanes)
    packed = pack_rows_2d(rows, bits, block_cols=min(1024, cols),
                          interpret=interpret)
    return packed.reshape(-1)[:n_out].astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("n_levels", "interpret"))
def index_histogram(idx, *, n_levels: int, interpret: bool | None = None):
    """Histogram of quantizer indices (padding assigned to bin 0, corrected)."""
    interpret = _on_cpu() if interpret is None else interpret
    idx2d, n = _to_2d(idx, 0)
    br = min(256, idx2d.shape[0])
    hist = index_histogram_2d(idx2d, n_levels,
                              block=(br, min(512, idx2d.shape[1])),
                              interpret=interpret)
    pad = idx2d.size - n
    return hist.at[0].add(-pad)


def estimate_rate_bits(idx, n_levels: int) -> jax.Array:
    """Bits/element the CABAC stage needs, from the kernel histogram."""
    from ..core.rate_model import estimated_bits_from_hist
    hist = index_histogram(idx, n_levels=n_levels)
    return estimated_bits_from_hist(hist, n_levels) / idx.size

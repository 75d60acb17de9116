"""Pallas TPU kernel: fused clip + uniform quantize + dequantize (paper eq. 1).

This is the codec's deploy-time hot path, fused so the feature tensor is
read from HBM exactly once and both outputs (the int index stream for the
entropy coder and the dequantized activations for the next layer /
fake-quant path) are produced in one VMEM pass.  On the edge device this
op fuses into the split layer's output, matching the paper's Sec. III-E
"operations could be fused into the layer" note.

Tiling: 2-D grid over (rows, cols) with (8k, 128m)-aligned blocks sized to
keep input + both outputs within a small fraction of VMEM
(default 256 x 512: f32 in 512 KB + i32 idx 512 KB + out 512 KB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (256, 512)


def _kernel(x_ref, idx_ref, deq_ref, *, cmin: float, cmax: float,
            n_levels: int):
    x = x_ref[...]
    scale = (n_levels - 1) / (cmax - cmin)
    inv_scale = (cmax - cmin) / (n_levels - 1)
    xc = jnp.clip(x.astype(jnp.float32), cmin, cmax)
    q = jnp.floor((xc - cmin) * scale + 0.5)  # round-half-away (q >= 0)
    idx_ref[...] = q.astype(jnp.int32)
    deq_ref[...] = (cmin + q * inv_scale).astype(deq_ref.dtype)


def clip_quant_2d(x, cmin: float, cmax: float, n_levels: int,
                  block=DEFAULT_BLOCK, interpret: bool = False):
    """x: (R, C) with R % block[0] == 0 and C % block[1] == 0."""
    r, c = x.shape
    br, bc = min(block[0], r), min(block[1], c)
    grid = (r // br, c // bc)
    return pl.pallas_call(
        functools.partial(_kernel, cmin=cmin, cmax=cmax, n_levels=n_levels),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                   pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.int32),
                   jax.ShapeDtypeStruct((r, c), x.dtype)],
        interpret=interpret,
    )(x)


def _kernel_tiles(x_ref, cmin_ref, cmax_ref, idx_ref, deq_ref, *,
                  n_levels: int):
    """Per-tile clipping ranges: row r of the (br, bc) data block uses the
    (cmin[r], cmax[r]) column the grid mapped for this column block.

    This is the codec's TilePlan hot path: the tensor is laid out
    channel-major with spatial blocks padded to whole column blocks, so
    every (row, column-block) cell of the grid is covered by exactly one
    tile and the (br, 1) range columns broadcast against the data block on
    the VPU -- the fused pass stays a single HBM read like the
    scalar-range kernel.  Per-row ranges (per-channel granularity) are the
    one-spatial-block special case.
    """
    x = x_ref[...].astype(jnp.float32)
    cmin = cmin_ref[...].astype(jnp.float32)        # (br, 1)
    cmax = cmax_ref[...].astype(jnp.float32)
    span = jnp.maximum(cmax - cmin, 1e-12)
    scale = (n_levels - 1) / span
    xc = jnp.clip(x, cmin, cmax)
    q = jnp.floor((xc - cmin) * scale + 0.5)        # round-half-away (q >= 0)
    idx_ref[...] = q.astype(jnp.int32)
    deq_ref[...] = (cmin + q * (span / (n_levels - 1))).astype(deq_ref.dtype)


def clip_quant_tiles_2d(x, cmin, cmax, n_levels: int, sblock_cols: int,
                        block=DEFAULT_BLOCK, interpret: bool = False):
    """Blocked per-tile clip+quant+dequant.

    x: (R, C) block-aligned, channel-major, spatial blocks padded to
    ``sblock_cols`` columns each (so C == n_sblocks * sblock_cols);
    cmin/cmax: (R, n_sblocks) float32 per-(row, spatial-block) ranges.
    The kernel's column block size divides ``sblock_cols``, so the range
    column for grid step (i, j) is simply ``j * bc // sblock_cols``.
    """
    r, c = x.shape
    if c % sblock_cols:
        raise ValueError(f"C {c} not a multiple of sblock_cols {sblock_cols}")
    br = min(block[0], r)
    bc = min(block[1], c, sblock_cols)
    while sblock_cols % bc:        # largest lane-multiple divisor <= block[1]
        bc -= 128
    grid = (r // br, c // bc)
    return pl.pallas_call(
        functools.partial(_kernel_tiles, n_levels=n_levels),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                  pl.BlockSpec((br, 1), lambda i, j: (i, j * bc
                                                      // sblock_cols)),
                  pl.BlockSpec((br, 1), lambda i, j: (i, j * bc
                                                      // sblock_cols))],
        out_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                   pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.int32),
                   jax.ShapeDtypeStruct((r, c), x.dtype)],
        interpret=interpret,
    )(x, cmin, cmax)


def clip_quant_rows_2d(x, cmin, cmax, n_levels: int, block=DEFAULT_BLOCK,
                       interpret: bool = False):
    """x: (R, C) block-aligned; cmin/cmax: (R, 1) float32 per-row ranges.

    The one-spatial-block case of :func:`clip_quant_tiles_2d`, kept as the
    named per-channel entry point.
    """
    return clip_quant_tiles_2d(x, cmin, cmax, n_levels,
                               sblock_cols=x.shape[1], block=block,
                               interpret=interpret)


# -- fused single-pass encode megakernel --------------------------------------

HIST_WIDTH = 64        # lane width of the per-(row, band) histogram output


def _kernel_encode(x_ref, cmin_ref, cmax_ref, valid_ref, packed_ref,
                   hist_ref, *, n_levels: int, bits: int, bc: int,
                   sb_cols: int):
    """One fused pass per block: clip -> quantize -> bit-pack -> histogram.

    The encode hot path's whole device side: the feature block is read
    from HBM once and leaves as wire-width packed bytes plus a
    per-(row, spatial-band) index histogram -- no int32 index tensor ever
    reaches HBM or the host.  Ranges are (br, 1) per-row columns exactly
    as in :func:`_kernel_tiles`; the scalar per-tensor mode is the
    constant-range one-band case.

    Packing combines ``per = 8 // bits`` rows of the block into one byte
    row: the (br, bc) block splits into ``per`` contiguous (br // per, bc)
    row slabs, and slab ``k`` lands at bit offset ``k * bits``
    (``ops.unpack_bytes`` inverts it).  Whole-slab sublane slices keep
    the lane layout intact, which Mosaic lowers; a lane-dim reshape it
    does not.  ``per == 1`` (bit widths 3/5/6) stores one index per
    byte.  The histogram masks band-column padding against the band's valid
    count (the (1, 1) ``valid_ref`` cell the grid mapped for this band --
    2-D plans have ragged edge tiles, so every band carries its own
    count) so tiles see only real elements; padded rows are dropped
    host-side.
    """
    per = 8 // bits if bits in (1, 2, 4) else 1
    j = pl.program_id(1)
    band_col = (j % (sb_cols // bc)) * bc    # block's column offset in band
    x = x_ref[...].astype(jnp.float32)
    cmin = cmin_ref[...].astype(jnp.float32)
    cmax = cmax_ref[...].astype(jnp.float32)
    span = jnp.maximum(cmax - cmin, 1e-12)
    scale = (n_levels - 1) / span
    q = jnp.floor((jnp.clip(x, cmin, cmax) - cmin) * scale + 0.5) \
        .astype(jnp.int32)

    if per == 1:
        packed_ref[...] = q
    else:
        s = q.shape[0] // per
        acc = q[:s]
        for k in range(1, per):                 # unrolled: per in (2, 4, 8)
            acc = acc + (q[k * s:(k + 1) * s] << (k * bits))
        packed_ref[...] = acc

    @pl.when(band_col == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    # mask band-column padding: each band's tail beyond its valid count
    # holds layout padding, not feature elements
    limit = valid_ref[0, 0]
    valid = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) \
        + band_col < limit
    hlane = jax.lax.broadcasted_iota(jnp.int32, hist_ref.shape, 1)

    def body(n, carry):                         # blocked: N scales to 64
        cnt = jnp.sum(jnp.where(valid & (q == n), 1, 0), axis=1,
                      keepdims=True)
        hist_ref[...] += jnp.where(hlane == n, cnt, 0)
        return carry

    jax.lax.fori_loop(0, n_levels, body, 0)


def band_valid_array(n_sblocks: int, bs: int, bs_last: int | None,
                     band_valid=None):
    """(1, n_sblocks) int32 per-band valid element counts: explicit
    ``band_valid`` (2-D ragged tiles) or the uniform-but-for-the-last
    1-D rule."""
    if band_valid is not None:
        v = jnp.asarray(band_valid, jnp.int32)
    else:
        v = jnp.full((n_sblocks,), bs, jnp.int32) \
            .at[-1].set(bs if bs_last is None else bs_last)
    return v.reshape(1, n_sblocks)


def encode_tiles_2d(x, cmin, cmax, n_levels: int, bits: int, sb_cols: int,
                    bs: int, bs_last: int | None = None, band_valid=None,
                    block=DEFAULT_BLOCK, interpret: bool = False):
    """Fused encode over a banded 2-D view (see ``_kernel_encode``).

    x: (R, C) block-aligned with C == n_sblocks * sb_cols; cmin/cmax:
    (R, n_sblocks) per-(row, band) ranges; ``bs`` is the valid element
    count per band (<= sb_cols) and ``bs_last`` the last band's (its
    tail may be padding when the spatial extent is not a block
    multiple); ``band_valid`` (n_sblocks,) overrides both with explicit
    per-band counts (2-D plans: ragged edge tiles).  Returns
    (packed (R // per, C) int32 byte values, each ``block[0]``-row block
    packed into ``block[0] // per`` rows,
    hist (R, n_sblocks * HIST_WIDTH) int32).
    """
    if n_levels > HIST_WIDTH:
        raise ValueError(f"n_levels {n_levels} > {HIST_WIDTH}")
    per = 8 // bits if bits in (1, 2, 4) else 1
    r, c = x.shape
    if c % sb_cols:
        raise ValueError(f"C {c} not a multiple of sb_cols {sb_cols}")
    n_sblocks = c // sb_cols
    br = min(block[0], r)
    bc = min(block[1], c, sb_cols)
    while sb_cols % bc:            # largest lane-multiple divisor <= block[1]
        bc -= 128
    grid = (r // br, c // bc)
    bpb = sb_cols // bc            # column blocks per band
    valid = band_valid_array(n_sblocks, bs, bs_last, band_valid)
    return pl.pallas_call(
        functools.partial(_kernel_encode, n_levels=n_levels, bits=bits,
                          bc=bc, sb_cols=sb_cols),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                  pl.BlockSpec((br, 1), lambda i, j: (i, j * bc
                                                      // sb_cols)),
                  pl.BlockSpec((br, 1), lambda i, j: (i, j * bc
                                                      // sb_cols)),
                  pl.BlockSpec((1, 1), lambda i, j, bpb=bpb: (0, j // bpb))],
        out_specs=[pl.BlockSpec((br // per, bc), lambda i, j: (i, j)),
                   pl.BlockSpec((br, HIST_WIDTH),
                                lambda i, j, bpb=bpb: (i, j // bpb))],
        out_shape=[jax.ShapeDtypeStruct((r // per, c), jnp.int32),
                   jax.ShapeDtypeStruct((r, n_sblocks * HIST_WIDTH),
                                        jnp.int32)],
        interpret=interpret,
    )(x, cmin, cmax, valid)

"""Device-resident interleaved-rANS entropy stage (entropy coder id 4).

The host coder (:mod:`repro.core.rans`) runs the step loop in numpy, so
every fused encode ships the full packed-index tensor device->host before
a single wire byte exists.  This module moves the whole entropy stage
in-graph: TU bit-plane construction, the chunk-static probability build,
and the lane-parallel rANS step loop all run on device, and only the
coded bytes (plus the small probability table and per-lane state flush)
cross to the host.

Byte identity is the contract: for any coded-order index vector the blob
assembled here is identical to ``rans.encode_planes(
cabac.index_to_context_bits(idx, n_levels))`` -- the golden conformance
suite pins it.  That means every quirk of the host coder is reproduced
exactly:

  * planes are concatenated in TU order with empty planes skipped, each
    plane padded to a step boundary with its most-probable symbol;
  * per-chunk probabilities are ``rint(ones / size * 2^14)`` with
    float64 round-half-even semantics -- reproduced in exact integer
    arithmetic (two-step long division keeps every intermediate in
    int32, which is also what the TPU ALUs have);
  * the step loop runs in reverse with 32-bit states renormalized 16
    bits at a time, and emitted words are gathered in (step asc, lane
    asc) order.

The plane build is scatter-free (XLA scatters serialize; gathers and
scans vectorize): a TU plane's bit vector *is* the next plane's alive
mask, so one inclusive scan per plane yields both the chunk one-counts
and the compaction ranks, and each successive plane is materialized by
a sorted-rank binary search (``searchsorted``) into the previous one --
a pure gather.  Plane sizes ride back to the host on the same tiny
pre-pass that already decides the lane count, so every buffer is sized
to a power-of-two bucket of the live data instead of the all-planes-full
worst case (the bucket is the jit cache key, keeping retraces bounded).

The per-lane state update itself is float-free 32-bit integer arithmetic
(the renorm invariant keeps ``x < 2^32`` and ``q < 2^18``, so nothing
ever needs the uint64 the host coder uses), which is exactly the shape a
TPU vector lane wants.  Two interchangeable step-loop implementations:

  * :func:`_step_loop_jnp` -- a ``lax.while_loop`` over steps, used by
    the jnp backend (and as the reference for the kernel);
  * :func:`_step_loop_pallas` -- a Pallas kernel with a sequential grid
    over blocks of 8 steps and the (1, lanes) state vector carried in a
    revisited output block, used by the kernel backend (interpret mode
    on CPU).

Per stream the stage is a size pre-pass (one reduction; the lane count
and buffer buckets derive from it, so it has to reach the host first),
the fused plane-build + step-loop graph dispatched async, and a
finalize that fetches one word count and launches a small gather to
compact the renorm words before slicing out ~wire-size bytes -- the
``wire_d2h`` span and the ``repro_codec_d2h_bytes_total`` counter
measure exactly that.
"""

from __future__ import annotations

import functools
import struct

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core import rans
from ..obs.metrics import default_registry
from ..obs.tracing import span

_PROB_BITS = 14
_M = 1 << _PROB_BITS
_CHUNK_STEPS = 256
_STATE_LO = 1 << 16
_HEADER_FMT = "<HI"

# the in-graph plane build materializes one compacted array per TU
# plane; past this level count the host coder's compaction loop wins,
# so callers fall back (the wire container is identical either way)
MAX_DEVICE_LEVELS = 16


def _d2h_counter():
    return default_registry().counter(
        "repro_codec_d2h_bytes_total",
        "bytes fetched device->host by the encode path (wire payloads, "
        "probability side info and state flushes on the device-entropy "
        "path; full packed-index tensors on the host-coder path)")


def host_fallback_counter():
    return default_registry().counter(
        "repro_codec_host_rans_fallback_total",
        "coder-id-4 payloads coded by the host step loop because the "
        "device stage cannot take their shape (level count or size)")


def device_supported(n: int, n_levels: int) -> bool:
    """Can the device stage code this stream (host fallback otherwise)?"""
    return (2 <= n_levels <= MAX_DEVICE_LEVELS
            and n * (n_levels - 1) < (1 << 31) - 2)


def _next_pow2(v: int) -> int:
    return 1 << max(0, int(v - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("n_levels",))
def _plane_sizes(coded, n_levels: int):
    """Per-plane element counts: ``sizes[j] = #{i : coded[i] >= j}``.

    The only data-dependent scalars the host needs before dispatch --
    total bits (their sum) picks the lane count, and the counts pick
    the per-plane buffer buckets.
    """
    jv = jnp.arange(n_levels - 1, dtype=jnp.int32)[:, None]
    return jnp.sum((coded[None, :] >= jv).astype(jnp.int8), axis=1,
                   dtype=jnp.int32)


def _round_half_even_div(ones, sizes):
    """Exact ``rint(ones / sizes * 2^14)`` (float64 semantics) in int32.

    ``ones * 2^14`` can reach 2^34, so the division runs as a two-step
    long division by 2^7 factors; the tie is broken to even like
    ``np.rint``.  Exactness of the float path: ``ones / sizes`` rounds
    once in f64, the *2^14 is an exponent shift (exact), and the
    quotient is at least 2^-21 away from any half-integer it is not
    exactly equal to (sizes <= 2^20), far beyond the 2^-39 f64 error.
    """
    t1 = ones * 128
    q1 = t1 // sizes
    t2 = (t1 - q1 * sizes) * 128
    q2 = t2 // sizes
    r2 = t2 - q2 * sizes
    q = q1 * 128 + q2
    twice = 2 * r2
    up = (twice > sizes) | ((twice == sizes) & ((q & 1) == 1))
    return q + up.astype(q.dtype)


def _iscan(v):
    """Inclusive int32 prefix sum.  On CPU associative_scan lowers to
    log-depth passes, ~2x faster than the serial cumsum lowering; on TPU
    the same scan over 2^20 elements takes minutes to compile, while
    cumsum lowers to one reduce-window and compiles in seconds."""
    v = v.astype(jnp.int32)
    return jax.lax.platform_dependent(
        v, cpu=lambda a: jax.lax.associative_scan(jnp.add, a),
        default=lambda a: jnp.cumsum(a, dtype=jnp.int32))


def _build_planes(coded, meta, n_levels: int, lanes: int, caps, t_cap: int,
                  f_cap: int):
    """In-graph mirror of ``index_to_context_bits`` + ``_plane_setup``.

    ``caps[j-1]`` is the (host-chosen, lane-padded) static capacity of
    plane ``j >= 1``; empty planes are already dropped by the host, so
    the chain covers exactly the planes the host coder keeps.  Returns
    the packed step matrix, the per-step probability vector and the
    uint16 probability table.

    Layout scalars (sizes/offsets, all exactly known to the host) come
    in through ``meta`` so they stay dynamic: the jit key is only the
    bucket tuple.  Each plane writes its lane-padded block with a
    dynamic_update_slice; a block's bucket overhang spills into the
    next plane's rows, and the ascending write order repairs it (the
    last plane's overhang lies past ``total_steps`` and is never
    coded).
    """
    n = coded.shape[0]
    chunk_bits = _CHUNK_STEPS * lanes
    n_planes = 1 + len(caps)

    def m(slot, j):
        return meta[1 + 4 * j + slot]

    size = [m(0, j) for j in range(n_planes)]
    off = [m(1, j) for j in range(n_planes)]
    foff = [m(2, j) for j in range(n_planes)]
    nch = [m(3, j) for j in range(n_planes)]

    # compaction chain: a plane's bit vector is the next plane's alive
    # mask, so cb (the masked ones scan) doubles as the rank array the
    # next plane's searchsorted gathers from
    rows0 = -(-n // lanes)
    bits, cbs = [], []
    cur = coded
    for j in range(n_planes):
        b = (cur > j).astype(jnp.int8)
        if j > 0:
            b = jnp.where(jnp.arange(b.shape[0], dtype=jnp.int32)
                          < size[j], b, 0)
        cb = _iscan(b)
        bits.append(b)
        cbs.append(cb)
        if j + 1 < n_planes:
            cap = caps[j]
            sel = jnp.searchsorted(cb, jnp.arange(1, cap + 1,
                                                  dtype=jnp.int32))
            cur = jnp.take(cur, sel, mode="clip")

    # probability table: chunk one-counts read straight off each
    # plane's scan at the (static-capped, dynamically masked) chunk
    # boundaries -- no scatter, sizes are a closed form
    ftab = jnp.zeros(f_cap, jnp.int32)
    for j in range(n_planes):
        cap = n if j == 0 else caps[j - 1]
        nchcap = max(1, -(-cap // chunk_bits))
        c = jnp.arange(nchcap, dtype=jnp.int32)
        start = c * chunk_bits
        hi_i = jnp.clip(jnp.minimum(start + chunk_bits, size[j]) - 1,
                        0, cap - 1)
        hi = jnp.take(cbs[j], hi_i, mode="clip")
        lo = jnp.where(c > 0,
                       jnp.take(cbs[j], jnp.clip(start - 1, 0, cap - 1),
                                mode="clip"),
                       0)
        csize = jnp.clip(size[j] - start, 1, chunk_bits)
        f1 = jnp.clip(_round_half_even_div(hi - lo, csize), 1, _M - 1)
        ftab = jax.lax.dynamic_update_slice(ftab, f1, (foff[j],))

    # step matrix + per-step probability, one padded block per plane
    bits2d = jnp.zeros((t_cap, lanes), jnp.int8)
    f1_steps = jnp.zeros(t_cap, jnp.int32)
    for j in range(n_planes):
        cap = n if j == 0 else caps[j - 1]
        rows = rows0 if j == 0 else cap // lanes
        mps = (jnp.take(ftab, jnp.clip(foff[j] + nch[j] - 1, 0, f_cap - 1))
               >= _M // 2).astype(jnp.int8)
        if j == 0:
            pad0 = rows0 * lanes - n
            vec = bits[0] if pad0 == 0 else jnp.concatenate(
                [bits[0], jnp.broadcast_to(mps, (pad0,))])
        else:
            vec = jnp.where(jnp.arange(cap, dtype=jnp.int32) < size[j],
                            bits[j], mps)
        bits2d = jax.lax.dynamic_update_slice(
            bits2d, vec.reshape(rows, lanes), (off[j], 0))
        fidx = jnp.clip(foff[j] + jnp.arange(rows, dtype=jnp.int32)
                        // _CHUNK_STEPS, 0, f_cap - 1)
        f1_steps = jax.lax.dynamic_update_slice(
            f1_steps, jnp.take(ftab, fidx), (off[j],))

    return bits2d, f1_steps, ftab.astype(jnp.uint16)


def _step_loop_jnp(bits2d, f1_steps, total_steps, lanes: int, t_cap: int):
    """Reverse rANS step loop as a ``lax.while_loop`` (uint32 states)."""
    u = jnp.uint32

    def body(carry):
        t, x, ov_buf, w_buf = carry
        f1 = f1_steps[t].astype(jnp.uint32)
        f0 = u(_M) - f1
        bb = jax.lax.dynamic_slice(bits2d, (t, 0), (1, lanes)) \
            .reshape(lanes).astype(jnp.uint32)
        f = jnp.where(bb == 1, f1, f0)
        over = x >= (f << u(18))    # 18 == 32 - _PROB_BITS
        w = (x & u(0xFFFF)).astype(jnp.uint16)
        x = jnp.where(over, x >> u(16), x)
        q = x // f
        x = (q << u(_PROB_BITS)) + (x - q * f) + f0 * bb
        ov_buf = jax.lax.dynamic_update_slice(
            ov_buf, over[None].astype(jnp.int8), (t, 0))
        w_buf = jax.lax.dynamic_update_slice(w_buf, w[None], (t, 0))
        return t - 1, x, ov_buf, w_buf

    init = (total_steps - 1,
            jnp.full((lanes,), _STATE_LO, jnp.uint32),
            jnp.zeros((t_cap, lanes), jnp.int8),
            jnp.zeros((t_cap, lanes), jnp.uint16))
    _, x, ov, w = jax.lax.while_loop(lambda c: c[0] >= 0, body, init)
    return x, ov, w


_STEP_ROWS = 8      # step-matrix rows per grid step: the int32 sublane tile


def _rans_step_kernel(ns_ref, bits_ref, f1_ref, x_ref, ov_ref, w_ref, *,
                      n_blocks: int):
    """One grid step codes one (reversed) block of ``_STEP_ROWS`` rows of
    the step matrix, last row first.

    The grid is sequential, so the (1, lanes) state block -- an output
    revisited by every step -- carries the per-lane coder states across
    iterations; rows past the stream's dynamic step count (``ns_ref``,
    a prefetched SMEM scalar) are skipped, and their output rows are
    zeroed so the word compaction can treat the full static buffer
    uniformly.  Blocks of whole sublane tiles are what the TPU lowering
    takes; a one-row block is not.
    """
    block = n_blocks - 1 - pl.program_id(0)
    n_steps = ns_ref[0]
    u = jnp.uint32

    @pl.when(pl.program_id(0) == 0)
    def _init():
        x_ref[...] = jnp.full(x_ref.shape, _STATE_LO, jnp.uint32)

    for r in reversed(range(_STEP_ROWS)):       # unrolled, reverse order
        row = pl.ds(r, 1)

        @pl.when(block * _STEP_ROWS + r < n_steps)
        def _code():
            x = x_ref[...]                              # (1, lanes)
            f1 = f1_ref[row, :].astype(jnp.uint32)      # (1, 1)
            f0 = u(_M) - f1
            bb = bits_ref[row, :].astype(jnp.uint32)
            f = jnp.where(bb == 1, f1, f0)
            over = x >= (f << u(18))
            w_ref[row, :] = (x & u(0xFFFF)).astype(jnp.int32)
            x = jnp.where(over, x >> u(16), x)
            q = x // f
            x_ref[...] = (q << u(_PROB_BITS)) + (x - q * f) + f0 * bb
            ov_ref[row, :] = over.astype(jnp.int32)

        @pl.when(block * _STEP_ROWS + r >= n_steps)
        def _skip():
            ov_ref[row, :] = jnp.zeros((1, ov_ref.shape[1]), jnp.int32)
            w_ref[row, :] = jnp.zeros((1, w_ref.shape[1]), jnp.int32)


def _step_loop_pallas(bits2d, f1_steps, total_steps, lanes: int,
                      t_cap: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    n_blocks = -(-t_cap // _STEP_ROWS)
    t_pad = n_blocks * _STEP_ROWS
    bits = jnp.zeros((t_pad, lanes), jnp.int32).at[:t_cap].set(
        bits2d.astype(jnp.int32))
    f1 = jnp.zeros((t_pad, 1), jnp.int32).at[:t_cap, 0].set(
        f1_steps.astype(jnp.int32))
    rev = lambda i, ns: (n_blocks - 1 - i, 0)  # noqa: E731
    x, ov, w = pl.pallas_call(
        functools.partial(_rans_step_kernel, n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((_STEP_ROWS, lanes), rev),
                      pl.BlockSpec((_STEP_ROWS, 1), rev)],
            out_specs=[pl.BlockSpec((1, lanes), lambda i, ns: (0, 0)),
                       pl.BlockSpec((_STEP_ROWS, lanes), rev),
                       pl.BlockSpec((_STEP_ROWS, lanes), rev)]),
        out_shape=[jax.ShapeDtypeStruct((1, lanes), jnp.uint32),
                   jax.ShapeDtypeStruct((t_pad, lanes), jnp.int32),
                   jax.ShapeDtypeStruct((t_pad, lanes), jnp.int32)],
        interpret=interpret,
    )(total_steps.reshape(1).astype(jnp.int32), bits, f1)
    return (x.reshape(lanes), ov[:t_cap].astype(jnp.int8),
            w[:t_cap].astype(jnp.uint16))


@functools.partial(jax.jit,
                   static_argnames=("n_levels", "lanes", "caps", "t_cap",
                                    "f_cap", "use_kernel", "interpret"))
def _entropy_stage(coded, meta, *, n_levels: int, lanes: int, caps,
                   t_cap: int, f_cap: int, use_kernel: bool,
                   interpret: bool):
    """Plane build + step loop + word scan, one fused graph.

    Returns ``(ftab, states, ov_scan, words_raw, n_words)``; the renorm
    words stay uncompacted here (their count is data-dependent), and
    finalize runs the small rank-gather once the count is known.
    """
    bits2d, f1_steps, ftab = _build_planes(
        coded.astype(jnp.int32), meta, n_levels, lanes, caps, t_cap,
        f_cap)
    total_steps = meta[0]
    loop = _step_loop_pallas if use_kernel else _step_loop_jnp
    if use_kernel:
        x, ov, w = loop(bits2d, f1_steps, total_steps, lanes, t_cap,
                        interpret)
    else:
        x, ov, w = loop(bits2d, f1_steps, total_steps, lanes, t_cap)
    ovc = _iscan(ov.reshape(-1))
    return ftab, x, ovc, w.reshape(-1), ovc[-1]


@functools.partial(jax.jit, static_argnames=("cap_w",))
def _compact_words(ovc, w, cap_w: int):
    """Emitted words in (step asc, lane asc) order -- the host coder's
    ``w_rows[over_rows]`` -- as a rank gather off the overflow scan."""
    sel = jnp.searchsorted(ovc, jnp.arange(1, cap_w + 1, dtype=jnp.int32))
    return jnp.take(w, sel, mode="clip")


def _dispatch(coded, n_levels: int, use_kernel: bool, interpret: bool):
    """Size pre-pass, host layout math, async stage launch.

    Returns the pending device buffers plus the host-side layout, or
    None for an empty stream.
    """
    n = int(coded.shape[0])
    if n == 0 or n_levels < 2:
        return None
    sizes = [int(s) for s in np.asarray(_plane_sizes(coded, n_levels))]
    lanes = rans.lane_count(sum(sizes))
    while sizes and sizes[-1] == 0:      # host coder skips empty planes
        sizes.pop()
    caps = tuple(lanes * _next_pow2(-(-s // lanes)) for s in sizes[1:])
    chunk_bits = _CHUNK_STEPS * lanes
    steps = [-(-s // lanes) for s in sizes]
    nch = [-(-s // chunk_bits) for s in sizes]
    t_cap = steps[0] + sum(c // lanes for c in caps)
    f_cap = max(1, -(-n // chunk_bits)) + sum(
        max(1, -(-c // chunk_bits)) for c in caps)
    meta, o, fo = [sum(steps)], 0, 0
    for s, st, nc in zip(sizes, steps, nch):
        meta += [s, o, fo, nc]
        o += st
        fo += nc
    out = _entropy_stage(coded, jnp.asarray(meta, jnp.int32),
                         n_levels=n_levels, lanes=lanes, caps=caps,
                         t_cap=t_cap, f_cap=f_cap, use_kernel=use_kernel,
                         interpret=interpret)
    return (lanes, fo) + tuple(out)


def _finalize(pending) -> bytes:
    """Fetch the word count, compact, slice-fetch, assemble the blob."""
    if pending is None:
        return struct.pack(_HEADER_FMT, 0, 0)
    lanes, nf, ftab, x, ovc, w, n_words = pending
    with span("wire_d2h", lanes=lanes):
        nw = int(n_words)
        words_h = np.asarray(
            _compact_words(ovc, w, _next_pow2(max(16, nw))))[:nw]
        ftab_h = np.asarray(ftab)[:nf]
        x_h = np.asarray(x)
    blob = (struct.pack(_HEADER_FMT, lanes, nf)
            + ftab_h.astype("<u2").tobytes()
            + x_h.astype("<u4").tobytes()
            + words_h.astype("<u2").tobytes())
    _d2h_counter().inc(len(blob) + 4)   # + the word-count scalar
    return blob


def encode_planes_device(coded, n_levels: int, *, use_kernel: bool = False,
                         interpret: bool = False) -> bytes:
    """Device-coded rANS blob, byte-identical to
    ``rans.encode_planes(index_to_context_bits(coded, n_levels))``.

    ``coded`` is a device (or host) coded-order index vector; only the
    coded bytes plus side info return to the host.
    """
    with span("device_entropy", n_elems=int(coded.shape[0])):
        pending = _dispatch(jnp.asarray(coded), n_levels, use_kernel,
                            interpret)
    return _finalize(pending)


def encode_chunks_device(coded, n_levels: int, bounds, *,
                         use_kernel: bool = False,
                         interpret: bool = False) -> list[bytes]:
    """Per-chunk device blobs for ``coded[s:e] for (s, e) in bounds``.

    Two phases so D2H overlaps compute: every chunk's stage is
    dispatched first (async), then the much smaller fetch+assemble pass
    drains them in order -- while chunk k's bytes cross the bus, chunk
    k+1's step loop is already running.
    """
    coded = jnp.asarray(coded)
    with span("device_entropy", chunks=len(bounds)):
        pend = [_dispatch(coded[s:e], n_levels, use_kernel, interpret)
                for s, e in bounds]
    return [_finalize(p) for p in pend]


def encode_indices_device(coded, n_levels: int, *, use_kernel: bool = False,
                          interpret: bool = False) -> bytes:
    """Full coder-id-4 payload for one coded-order index vector.

    Container bytes match host coder id 2 at one shard past the id
    byte; unsupported shapes fall back to the host step loop but keep
    the same container, so the wire format never depends on where the
    blob was coded.
    """
    from ..core import cabac
    n = int(coded.shape[0])
    if n == 0:
        return cabac.wrap_device_blob(b"")
    if not device_supported(n, n_levels):
        from ..core.binarization import index_to_context_bits
        host_fallback_counter().inc()
        blob = rans.encode_planes(
            index_to_context_bits(np.asarray(coded).ravel(), n_levels))
    else:
        blob = encode_planes_device(coded, n_levels, use_kernel=use_kernel,
                                    interpret=interpret)
    return cabac.wrap_device_blob(blob)


def encode_index_chunks_device(coded, n_levels: int, bounds, *,
                               use_kernel: bool = False,
                               interpret: bool = False) -> list[bytes]:
    """Coder-id-4 payloads for each chunk range, dispatch-all then
    finalize-all (the D2H-overlap shape of
    :func:`encode_chunks_device`)."""
    return finalize_index_chunks(dispatch_index_chunks(
        coded, n_levels, bounds, use_kernel=use_kernel,
        interpret=interpret))


def dispatch_index_chunks(coded, n_levels: int, bounds, *,
                          use_kernel: bool = False,
                          interpret: bool = False):
    """Async phase of :func:`encode_index_chunks_device`: launch every
    chunk's entropy stage and return an opaque pending list.

    Nothing blocks on device results here -- callers can dispatch many
    tensors' chunks back to back (a whole serving tick) and only then
    drain the bytes-only D2H with :func:`finalize_index_chunks`, so each
    payload's transfer overlaps the next tensor's step loops.
    Unsupported shapes are host-coded inline (their pending entries are
    already-finished payloads).
    """
    from ..core import cabac
    n = int(coded.shape[0]) if hasattr(coded, "shape") else len(coded)
    if not device_supported(n, n_levels):
        from ..core.binarization import index_to_context_bits
        host_fallback_counter().inc(len(bounds))
        host = np.asarray(coded).ravel()
        return [("host", cabac.wrap_device_blob(
            b"" if s >= e else rans.encode_planes(
                index_to_context_bits(host[s:e], n_levels))))
            for s, e in bounds]
    coded = jnp.asarray(coded)
    with span("device_entropy", chunks=len(bounds)):
        return [("dev", None) if s >= e else
                ("dev", _dispatch(coded[s:e], n_levels, use_kernel,
                                  interpret))
                for s, e in bounds]


def finalize_index_chunks(pending) -> list[bytes]:
    """Drain phase of :func:`dispatch_index_chunks`: fetch each chunk's
    coded bytes (in order) and assemble coder-id-4 payloads."""
    from ..core import cabac
    out = []
    for kind, p in pending:
        if kind == "host":
            out.append(p)
        else:
            out.append(cabac.wrap_device_blob(
                b"" if p is None else _finalize(p)))
    return out

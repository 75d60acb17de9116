"""Generic decoder LM assembled from the config's layer pattern.

Layers are grouped into repeated *periods* and executed with ``lax.scan``
over stacked parameters, so a 95-layer model compiles one period body.
The collaborative-intelligence split point (the paper's edge/cloud
boundary) falls between two scan groups, where the FeatureCodec fake-quant
(or real packed transport, in the split runtime) is applied.

Public entry points:
    init_params / forward / loss_fn / init_cache / prefill / decode_step
All take an optional ``ctx`` (DistContext) for expert parallelism and an
optional ``codec_fn`` applied at the split boundary.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import LayerSpec, ModelConfig
from . import layers as L
from . import moe as MOE
from . import rglru as RG
from . import rwkv6 as RW
from .context import DistContext, constrain


# ---------------------------------------------------------------------------
# group structure
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Group:
    specs: tuple[LayerSpec, ...]
    n_periods: int


def build_groups(cfg: ModelConfig, split: bool = False,
                 split_after: int | None = None) -> tuple[list[Group], int]:
    """Partition layers into scan groups.  Returns (groups, split_boundary)
    where the codec applies after ``groups[:split_boundary]`` (0 = no split).

    ``split_after`` overrides ``cfg.split_after_period`` for this call:
    the boundary lands after that many full periods.  Explicit values
    are validated (1 <= split_after <= n_full_periods - 1) rather than
    clamped, so a scenario sweep over split depths fails loudly on an
    out-of-range tap instead of silently evaluating a different one."""
    n_main = cfg.n_full_periods
    groups: list[Group] = []
    boundary = 0
    if split and n_main >= 2:
        if split_after is not None:
            if not 1 <= split_after <= n_main - 1:
                raise ValueError(
                    f"{cfg.name}: split_after={split_after} out of range "
                    f"(need 1 <= split_after <= {n_main - 1})")
            sp = split_after
        else:
            sp = cfg.split_after_period or max(1, n_main // 4)
            sp = min(sp, n_main - 1)
        groups.append(Group(cfg.pattern, sp))
        groups.append(Group(cfg.pattern, n_main - sp))
        boundary = 1
    else:
        groups.append(Group(cfg.pattern, n_main))
    if cfg.remainder:
        groups.append(Group(cfg.remainder, 1))
    return groups, boundary


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _init_spec(key, spec: LayerSpec, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    p = {"norm1": L.init_norm(cfg.norm, cfg.d_model, dtype),
         "norm2": L.init_norm(cfg.norm, cfg.d_model, dtype)}
    if spec.kind == "attn":
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    elif spec.kind == "rglru":
        p["rec"] = RG.init_rglru(ks[0], cfg, dtype)
    elif spec.kind == "rwkv":
        p["tmix"] = RW.init_rwkv(ks[0], cfg, dtype)
    else:
        raise ValueError(spec.kind)
    if spec.moe:
        p["moe"] = MOE.init_moe(ks[1], cfg, dtype)
    elif spec.kind == "rwkv":
        p["cmix"] = RW.init_channel_mix(ks[1], cfg, dtype)
    else:
        gated = cfg.gated_mlp
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, gated, dtype)
    return p


def _init_period(key, specs, cfg, dtype):
    keys = jax.random.split(key, len(specs))
    return [_init_spec(k, s, cfg, dtype) for k, s in zip(keys, specs)]


def init_params(cfg: ModelConfig, key, split: bool = False):
    dtype = jnp.dtype(cfg.dtype)
    groups, _ = build_groups(cfg, split)
    k_embed, k_head, key = jax.random.split(key, 3)
    params = {}
    # embedding table always exists: audio/vlm archs still have an output
    # vocabulary even though their *input* arrives as precomputed embeddings
    params["embed"] = {"table": jax.random.normal(
        k_embed, (cfg.vocab_size, cfg.d_model), dtype) * 0.02}
    params["final_norm"] = L.init_norm(cfg.norm, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["head"] = {"w": jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size), dtype) / math.sqrt(cfg.d_model)}
    gps = []
    for gi, g in enumerate(groups):
        gkey = jax.random.fold_in(key, gi)
        stacked = jax.vmap(
            lambda k: _init_period(k, g.specs, cfg, dtype)
        )(jax.random.split(gkey, g.n_periods))
        gps.append({"layers": stacked})
    params["groups"] = gps
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _init_spec_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                     max_seq: int, dtype):
    if spec.kind == "attn":
        s = min(spec.window, max_seq) if spec.window else max_seq
        kv = (batch, s, cfg.num_kv_heads, cfg.head_dim)
        if cfg.kv_quant_bits:
            # paper eq. 1 applied to the KV cache: uint8 index storage
            return {"k": jnp.zeros(kv, jnp.uint8), "v": jnp.zeros(kv, jnp.uint8)}
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    if spec.kind == "rglru":
        return RG.init_rglru_cache(cfg, batch, dtype)
    if spec.kind == "rwkv":
        return RW.init_rwkv_cache(cfg, batch)
    raise ValueError(spec.kind)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, split: bool = False):
    dtype = jnp.dtype(cfg.dtype)
    groups, _ = build_groups(cfg, split)
    caches = []
    for g in groups:
        per = [_init_spec_cache(s, cfg, batch, max_seq, dtype) for s in g.specs]
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (g.n_periods,) + a.shape), per)
        caches.append(stacked)
    return caches


def _align_param_groups(params, groups, sliced: bool = False):
    """Match the stacked layer params to a (possibly split) group layout.

    Lets the same params pytree serve both the unsplit and the codec-split
    execution paths.  A group carved out of a longer stack gets the whole
    stack plus its first layer index (``"start"``), and :func:`_apply_group`
    reads each of its layers in place: slicing ``a[lo:lo + n]`` out of a
    jit argument is a copy of those layers on every call, not a view.

    ``sliced`` takes that copy instead, for the gradient path: there a
    slice transposes to one pad, whereas an in-place read inside the scan
    carries a whole-stack cotangent through each carved group (about 1.5x
    the temporaries; pinned in ``tests/test_tpu_compile.py``).
    """
    gp = params["groups"]
    if len(gp) == len(groups):
        return gp
    out = []
    src = list(gp)
    main = src.pop(0)
    n_from_main = len(groups) - len(src)
    offset = 0
    for g in groups[:n_from_main]:
        lo = offset
        if sliced:
            out.append({"layers": jax.tree.map(
                lambda a: a[lo:lo + g.n_periods], main["layers"])})
        else:
            out.append({"layers": main["layers"], "start": lo})
        offset += g.n_periods
    out.extend(src)
    return out


def _kv_enc(cfg: ModelConfig, t):
    """Quantize K/V for cache storage (pinned-boundary uniform, eq. 1)."""
    if not cfg.kv_quant_bits:
        return t
    from ..core import uniform
    n = 1 << cfg.kv_quant_bits
    return uniform.quantize(t, -cfg.kv_clip, cfg.kv_clip, n).astype(jnp.uint8)


def _kv_dec(cfg: ModelConfig, t, dtype):
    if not cfg.kv_quant_bits:
        return t
    from ..core import uniform
    n = 1 << cfg.kv_quant_bits
    return uniform.dequantize(t.astype(jnp.int32), -cfg.kv_clip, cfg.kv_clip,
                              n, dtype=dtype)


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------

def _apply_layer(x, p, spec: LayerSpec, cfg: ModelConfig, *, pos, cache,
                 ctx, positions):
    """x: (B,S,d). cache: per-spec cache dict or None. pos: scalar offset."""
    x = constrain(x, ctx, "dp", None, None)
    h = L.apply_norm(x, p["norm1"], cfg.norm, cfg.norm_eps)
    new_cache = None
    if spec.kind == "attn":
        q, k, v = L.attention_qkv(h, p["attn"], cfg, positions)
        tp_n = ctx.tp_size if ctx is not None else 1
        heads_div = cfg.num_heads % tp_n == 0
        kv_div = cfg.num_kv_heads % tp_n == 0
        expand_kv = False
        if tp_n == 1:
            pass
        elif heads_div and kv_div:
            # classic TP attention: q and kv heads sharded over 'model'
            q = constrain(q, ctx, "dp", None, "tp", None)
            k = constrain(k, ctx, "dp", None, "tp", None)
            v = constrain(v, ctx, "dp", None, "tp", None)
        elif heads_div:
            # GQA with kv_heads < tp: replicate the (small) K/V and expand
            # groups to full heads for train/prefill compute, so attention
            # shards cleanly on q heads.  hd-sharding K/V instead forces a
            # partial-sum AR of the f32 logits each chunk (16x worse in
            # the baseline dry-run).  Decode keeps the compact K-head
            # cache (sequence-sharded by the cache rules).
            q = constrain(q, ctx, "dp", None, "tp", None)
            k = constrain(k, ctx, "dp", None, None, None)
            v = constrain(v, ctx, "dp", None, None, None)
            expand_kv = q.shape[1] > 1
        elif spec.window is None and k.shape[1] % tp_n == 0 and k.shape[1] > 1:
            # sequence-parallel attention for head-indivisible archs:
            # K/V shard along the key axis; softmax reductions over the
            # sharded axis cost only tiny stat all-reduces, and attention
            # FLOPs split tp ways.
            q = constrain(q, ctx, "dp", None, None, None)
            k = constrain(k, ctx, "dp", "tp", None, None)
            v = constrain(v, ctx, "dp", "tp", None, None)
        else:
            # tiny-window fallback: replicate over tp (weights are
            # replicated too for these archs; see sharding.py)
            q = constrain(q, ctx, "dp", None, None, None)
            k = constrain(k, ctx, "dp", None, None, None)
            v = constrain(v, ctx, "dp", None, None, None)

        def _exp(t):
            if not expand_kv:
                return t
            g = cfg.num_heads // cfg.num_kv_heads
            t = jnp.repeat(t, g, axis=2)  # (B,S,K,hd) -> (B,S,H,hd)
            return constrain(t, ctx, "dp", None, "tp", None)

        if cache is None:
            attn = L.multi_head_attention(
                q, _exp(k), _exp(v), q_offset=0, window=spec.window,
                softcap=cfg.attn_logit_softcap)
        else:
            s_new = q.shape[1]
            s_cache = cache["k"].shape[1]
            if s_new == 1:
                # decode: write into ring/linear slot, attend over cache
                slot = pos % s_cache if spec.window else pos
                ck = lax.dynamic_update_slice_in_dim(
                    cache["k"], _kv_enc(cfg, k), slot, axis=1)
                cv = lax.dynamic_update_slice_in_dim(
                    cache["v"], _kv_enc(cfg, v), slot, axis=1)
                if spec.window:
                    idx = jnp.arange(s_cache, dtype=jnp.int32)
                    k_pos = pos - (pos - idx) % s_cache
                else:
                    k_pos = jnp.arange(s_cache, dtype=jnp.int32)
                attn = L.multi_head_attention(
                    q, _kv_dec(cfg, ck, q.dtype), _kv_dec(cfg, cv, q.dtype),
                    q_offset=pos, k_positions=k_pos,
                    window=spec.window, softcap=cfg.attn_logit_softcap)
                new_cache = {"k": ck, "v": cv}
            else:
                # prefill from scratch: attend over fresh K/V, then fill cache
                attn = L.multi_head_attention(
                    q, _exp(k), _exp(v), q_offset=0, window=spec.window,
                    softcap=cfg.attn_logit_softcap)
                kq, vq = _kv_enc(cfg, k), _kv_enc(cfg, v)
                if s_new >= s_cache:
                    tail_pos = jnp.arange(s_new - s_cache, s_new) % s_cache
                    ck = cache["k"].at[:, tail_pos].set(kq[:, -s_cache:])
                    cv = cache["v"].at[:, tail_pos].set(vq[:, -s_cache:])
                else:
                    ck = lax.dynamic_update_slice_in_dim(cache["k"], kq, 0, axis=1)
                    cv = lax.dynamic_update_slice_in_dim(cache["v"], vq, 0, axis=1)
                new_cache = {"k": ck, "v": cv}
        if tp_n == 1 or cfg.num_heads % tp_n == 0:
            attn = constrain(attn, ctx, "dp", None, "tp", "tp")
        else:
            attn = constrain(attn, ctx, "dp", None, None, None)
        x = x + L.attention_out(attn, p["attn"])
    elif spec.kind == "rglru":
        out, new_cache = RG.rglru_block_apply(h, p["rec"], cfg, cache, ctx=ctx)
        x = x + out
    elif spec.kind == "rwkv":
        out, new_tmix = RW.time_mix_apply(h, p["tmix"], cfg,
                                          cache["tmix"] if cache else None,
                                          ctx=ctx)
        x = x + out
        new_cache = {"tmix": new_tmix} if cache is not None else None

    h2 = L.apply_norm(x, p["norm2"], cfg.norm, cfg.norm_eps)
    if spec.moe:
        x = x + MOE.moe_apply(h2, p["moe"], cfg, ctx)
    elif spec.kind == "rwkv":
        out, new_cmix = RW.channel_mix_apply(h2, p["cmix"],
                                             cache["cmix"] if cache else None,
                                             ctx=ctx)
        x = x + out
        if cache is not None:
            new_cache["cmix"] = new_cmix
    else:
        x = x + L.mlp_apply(h2, p["mlp"], cfg.act, cfg.gated_mlp, ctx=ctx)
    return x, new_cache


# ---------------------------------------------------------------------------
# group scan
# ---------------------------------------------------------------------------

def _remat_group_size(n: int) -> int:
    """Largest small divisor g of n: layers are scanned in super-steps of g
    periods under one jax.checkpoint, so only n/g residual carries are
    saved (sqrt-style remat).  The fwd of a super-step is replayed once in
    the backward pass; transient memory grows by g layers' internals."""
    for g in (8, 7, 6, 5, 4, 3, 2):
        if n % g == 0 and n // g >= 2:
            return g
    return 1


def _apply_group(x, gparams, group: Group, cfg: ModelConfig, *, pos, gcache,
                 ctx, positions, remat: bool = False):
    """Scan one group of n_periods over stacked params/caches.

    ``gparams["layers"]`` is either the group's own stack, scanned as
    ``xs``, or, when ``gparams`` has a ``"start"``, a longer stack the group
    was carved from (first layer at that index).  Then the scan runs over
    layer indices and each step indexes its layer out of the whole stack,
    which reads the weights in place instead of copying the group's layers
    first."""
    layers = gparams["layers"]
    n = group.n_periods
    start = gparams.get("start")
    if start is None:
        xs_layers = layers

        def take(pp):
            return pp
    else:
        xs_layers = jnp.arange(start, start + n)

        def take(i):
            return jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
                layers)

    if remat and gcache is None and n >= 64:
        # sqrt-remat pays off only when the residual-carry stack dominates
        # (deep stacks); for shallow models the recompute traffic regressed
        # the memory term in the dry-run (see EXPERIMENTS §Perf).
        g = _remat_group_size(n)
        if g > 1:
            lay = jax.tree.map(
                lambda a: a.reshape(n // g, g, *a.shape[1:]), xs_layers)

            @jax.checkpoint
            def super_body(carry, pp):
                xc = carry
                for j in range(g):
                    pj = take(jax.tree.map(lambda a: a[j], pp))
                    for si, spec in enumerate(group.specs):
                        xc, _ = _apply_layer(xc, pj[si], spec, cfg, pos=pos,
                                             cache=None, ctx=ctx,
                                             positions=positions)
                return xc, None

            x, _ = lax.scan(super_body, x, lay)
            return x, None

    def period_body(carry, xs):
        xc = carry
        pp, cc = xs
        pp = take(pp)
        new_cc = [] if cc is not None else None
        for j, spec in enumerate(group.specs):
            xc, ncj = _apply_layer(
                xc, pp[j], spec, cfg, pos=pos,
                cache=(cc[j] if cc is not None else None),
                ctx=ctx, positions=positions)
            if new_cc is not None:
                new_cc.append(ncj)
        return xc, new_cc

    body = jax.checkpoint(period_body) if remat else period_body
    if gcache is None:
        x, _ = lax.scan(lambda c, xs: (body(c, (xs, None))[0], None),
                        x, xs_layers)
        return x, None
    x, new_cache = lax.scan(lambda c, xs: body(c, xs),
                            x, (xs_layers, gcache))
    return x, new_cache


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def _embed_in(cfg, params, batch_in, pos0=0, ctx=None):
    """batch_in: tokens (B,S) int32 or embeddings (B,S,d)."""
    if batch_in.ndim == 3:
        x = batch_in.astype(jnp.dtype(cfg.dtype))
    else:
        x = params["embed"]["table"][batch_in]
    if cfg.pos_emb == "sinusoidal":
        s = x.shape[1]
        pe = L.sinusoidal_pos_emb(pos0 + jnp.arange(s), cfg.d_model, x.dtype)
        x = x + pe[None]
    return constrain(x, ctx, "dp", None, None)


def _logits_out(cfg, params, x, ctx=None):
    xn = L.apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", xn, params["embed"]["table"])
    else:
        logits = xn @ params["head"]["w"]
    logits = constrain(logits, ctx, "dp", None, "tp")
    logits = logits.astype(jnp.float32)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def forward(cfg: ModelConfig, params, batch_in, *, ctx: DistContext | None = None,
            codec_fn: Callable | None = None, split: bool = False,
            remat: bool = False):
    """Training/scoring forward pass (no cache).  Returns (logits, aux)."""
    groups, boundary = build_groups(cfg, split or codec_fn is not None)
    pgroups = _align_param_groups(params, groups)
    x = _embed_in(cfg, params, batch_in, ctx=ctx)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    aux = {}
    for gi, g in enumerate(groups):
        x, _ = _apply_group(x, pgroups[gi], g, cfg, pos=0,
                            gcache=None, ctx=ctx, positions=positions,
                            remat=remat)
        if codec_fn is not None and boundary and gi == boundary - 1:
            x, rate = codec_fn(x)
            aux["codec_rate_bits"] = rate
    return _logits_out(cfg, params, x, ctx=ctx), aux


def forward_head(cfg: ModelConfig, params, batch_in, *,
                 ctx: DistContext | None = None,
                 split_after: int | None = None):
    """Edge half of the split forward: embed + the groups before the
    collaborative-intelligence boundary.  Returns the raw split-layer
    activations (B, S, d) that cross the edge->cloud link (the transport
    subsystem streams exactly this tensor).  ``split_after`` taps the
    boundary after that many full periods (default: the config's)."""
    groups, boundary = build_groups(cfg, split=True, split_after=split_after)
    if not boundary:
        raise ValueError(f"{cfg.name}: no split boundary (needs >= 2 "
                         "full periods)")
    pgroups = _align_param_groups(params, groups)
    x = _embed_in(cfg, params, batch_in, ctx=ctx)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    for gi in range(boundary):
        x, _ = _apply_group(x, pgroups[gi], groups[gi], cfg, pos=0,
                            gcache=None, ctx=ctx, positions=positions)
    return x


def forward_from_boundary(cfg: ModelConfig, params, x, *,
                          ctx: DistContext | None = None,
                          split_after: int | None = None):
    """Cloud half: the groups after the boundary + final norm/head.

    ``x`` is the (possibly decompressed) split-layer tensor from
    :func:`forward_head` (same ``split_after``); returns logits
    (B, S, V).  Together the two halves are numerically identical to
    :func:`forward` with an identity ``codec_fn`` -- asserted in
    tests/test_transport.py."""
    groups, boundary = build_groups(cfg, split=True, split_after=split_after)
    if not boundary:
        raise ValueError(f"{cfg.name}: no split boundary (needs >= 2 "
                         "full periods)")
    pgroups = _align_param_groups(params, groups)
    x = jnp.asarray(x, jnp.dtype(cfg.dtype))
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    for gi in range(boundary, len(groups)):
        x, _ = _apply_group(x, pgroups[gi], groups[gi], cfg, pos=0,
                            gcache=None, ctx=ctx, positions=positions)
    return _logits_out(cfg, params, x, ctx=ctx)


def _hidden_forward(cfg, params, batch_in, *, ctx, codec_fn, split, remat):
    """Backbone only: returns final hidden states (B, S, d) + aux."""
    groups, boundary = build_groups(cfg, split or codec_fn is not None)
    pgroups = _align_param_groups(params, groups, sliced=True)
    x = _embed_in(cfg, params, batch_in, ctx=ctx)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    aux = {}
    for gi, g in enumerate(groups):
        x, _ = _apply_group(x, pgroups[gi], g, cfg, pos=0,
                            gcache=None, ctx=ctx, positions=positions,
                            remat=remat)
        if codec_fn is not None and boundary and gi == boundary - 1:
            x, rate = codec_fn(x)
            aux["codec_rate_bits"] = rate
    return x, aux


def sharded_xent(cfg: ModelConfig, params, x, labels, ctx: DistContext | None):
    """Vocab-sharded softmax cross entropy.

    The (B, S, V) logits never exist unsharded or in float32: they are
    pinned to P(dp, None, tp) so GSPMD keeps the vocab dimension sharded
    through the max / logsumexp / pick reductions (partial reduce + cheap
    scalar all-reduce) instead of all-gathering a vocab-wide tensor --
    the difference between 68 GB/device and 2 GB/device on a 256k vocab.
    """
    from jax.sharding import PartitionSpec as P

    xn = L.apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", xn, params["embed"]["table"])
    else:
        logits = xn @ params["head"]["w"]
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = (c * jnp.tanh(logits.astype(jnp.float32) / c)).astype(logits.dtype)
    if ctx is not None and ctx.mesh is not None:
        import numpy as _np
        dp_n = int(_np.prod([ctx.mesh.shape[a] for a in ctx.dp_axes]))
        spec = P(ctx.dp_axes if labels.shape[0] % dp_n == 0 else None,
                 None, ctx.tp_axis if cfg.vocab_size % ctx.tp_size == 0 else None)
        logits = jax.lax.with_sharding_constraint(
            logits, jax.sharding.NamedSharding(ctx.mesh, spec))
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    sumexp = jnp.sum(jnp.exp(shifted.astype(jnp.float32)), axis=-1)
    lse = m[..., 0].astype(jnp.float32) + jnp.log(sumexp)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    picked = jnp.sum(jnp.where(vocab_iota == labels[..., None],
                               shifted.astype(jnp.float32), 0.0), axis=-1)
    picked = picked + m[..., 0].astype(jnp.float32)
    return jnp.mean(lse - picked)


def loss_fn(cfg: ModelConfig, params, tokens, *, ctx=None, codec_fn=None,
            split: bool = False, remat: bool = True, inputs=None):
    """Next-token cross entropy.  ``inputs`` overrides the embedded input
    stream (audio/vlm stubs); labels always come from ``tokens``."""
    batch_in = inputs if inputs is not None else tokens
    x, aux = _hidden_forward(cfg, params, batch_in, ctx=ctx, codec_fn=codec_fn,
                             split=split, remat=remat)
    loss = sharded_xent(cfg, params, x[:, :-1], tokens[:, 1:], ctx)
    return loss, aux


def prefill(cfg: ModelConfig, params, batch_in, cache, *, ctx=None,
            codec_fn=None, split: bool = False):
    """Process a prompt, filling the cache.  Returns (last_logits, cache)."""
    groups, boundary = build_groups(cfg, split or codec_fn is not None)
    pgroups = _align_param_groups(params, groups)
    x = _embed_in(cfg, params, batch_in, ctx=ctx)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    new_caches = []
    for gi, g in enumerate(groups):
        x, nc = _apply_group(x, pgroups[gi], g, cfg, pos=0,
                             gcache=cache[gi], ctx=ctx, positions=positions)
        new_caches.append(nc)
        if codec_fn is not None and boundary and gi == boundary - 1:
            x, _ = codec_fn(x)
    logits = _logits_out(cfg, params, x[:, -1:], ctx=ctx)
    return logits[:, 0], new_caches


def prefill_to_boundary(cfg: ModelConfig, params, batch_in, cache, *,
                        ctx=None):
    """Edge half of a split prefill: embed + the pre-boundary groups.

    Returns (split-layer activations (B, S, d), pre-boundary caches).
    Together with :func:`prefill_from_boundary` this is :func:`prefill`
    cut at the collaborative-intelligence boundary, so a host round-trip
    (e.g. a real transport socket) can run *between* two jitted programs
    instead of inside one -- no host callback ever blocks an in-flight
    program while nested jax work waits for the dispatch thread.
    """
    groups, boundary = build_groups(cfg, split=True)
    if not boundary:
        raise ValueError(f"{cfg.name}: no split boundary (needs >= 2 "
                         "full periods)")
    pgroups = _align_param_groups(params, groups)
    x = _embed_in(cfg, params, batch_in, ctx=ctx)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    new_caches = []
    for gi in range(boundary):
        x, nc = _apply_group(x, pgroups[gi], groups[gi], cfg, pos=0,
                             gcache=cache[gi], ctx=ctx, positions=positions)
        new_caches.append(nc)
    return x, new_caches


def prefill_from_boundary(cfg: ModelConfig, params, x, cache, *, ctx=None):
    """Cloud half of a split prefill: post-boundary groups + head.

    ``x`` is the (possibly codec-round-tripped) boundary tensor from
    :func:`prefill_to_boundary`; ``cache`` is the full per-group cache
    list (only the post-boundary entries are read).  Returns
    (last-token logits (B, V), post-boundary caches)."""
    groups, boundary = build_groups(cfg, split=True)
    pgroups = _align_param_groups(params, groups)
    x = jnp.asarray(x, jnp.dtype(cfg.dtype))
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    new_caches = []
    for gi in range(boundary, len(groups)):
        x, nc = _apply_group(x, pgroups[gi], groups[gi], cfg, pos=0,
                             gcache=cache[gi], ctx=ctx, positions=positions)
        new_caches.append(nc)
    logits = _logits_out(cfg, params, x[:, -1:], ctx=ctx)
    return logits[:, 0], new_caches


def decode_to_boundary(cfg: ModelConfig, params, token_in, cache, pos, *,
                       ctx=None):
    """Edge half of a split decode step (see :func:`prefill_to_boundary`).

    Returns (boundary activations (B, 1, d), pre-boundary caches)."""
    groups, boundary = build_groups(cfg, split=True)
    if not boundary:
        raise ValueError(f"{cfg.name}: no split boundary (needs >= 2 "
                         "full periods)")
    pgroups = _align_param_groups(params, groups)
    batch_in = token_in[:, None] if token_in.ndim == 1 else token_in
    x = _embed_in(cfg, params, batch_in, pos0=pos, ctx=ctx)
    positions = jnp.full((1,), pos, dtype=jnp.int32)
    new_caches = []
    for gi in range(boundary):
        x, nc = _apply_group(x, pgroups[gi], groups[gi], cfg, pos=pos,
                             gcache=cache[gi], ctx=ctx, positions=positions)
        new_caches.append(nc)
    return x, new_caches


def decode_from_boundary(cfg: ModelConfig, params, x, cache, pos, *,
                         ctx=None):
    """Cloud half of a split decode step: post-boundary groups + head.
    Returns (logits (B, V), post-boundary caches)."""
    groups, boundary = build_groups(cfg, split=True)
    pgroups = _align_param_groups(params, groups)
    x = jnp.asarray(x, jnp.dtype(cfg.dtype))
    positions = jnp.full((1,), pos, dtype=jnp.int32)
    new_caches = []
    for gi in range(boundary, len(groups)):
        x, nc = _apply_group(x, pgroups[gi], groups[gi], cfg, pos=pos,
                             gcache=cache[gi], ctx=ctx, positions=positions)
        new_caches.append(nc)
    logits = _logits_out(cfg, params, x, ctx=ctx)
    return logits[:, 0], new_caches


def decode_step(cfg: ModelConfig, params, token_in, cache, pos, *, ctx=None,
                codec_fn=None, split: bool = False):
    """One decode step.  token_in: (B,) int32 or (B,1,d) embeddings;
    pos: scalar int32 absolute position.  Returns (logits (B,V), cache)."""
    groups, boundary = build_groups(cfg, split or codec_fn is not None)
    pgroups = _align_param_groups(params, groups)
    if token_in.ndim == 1:
        batch_in = token_in[:, None]
    else:
        batch_in = token_in
    x = _embed_in(cfg, params, batch_in, pos0=pos, ctx=ctx)
    positions = jnp.full((1,), pos, dtype=jnp.int32)
    aux = {}
    new_caches = []
    for gi, g in enumerate(groups):
        x, nc = _apply_group(x, pgroups[gi], g, cfg, pos=pos,
                             gcache=cache[gi], ctx=ctx, positions=positions)
        new_caches.append(nc)
        if codec_fn is not None and boundary and gi == boundary - 1:
            x, rate = codec_fn(x)
            aux["codec_rate_bits"] = rate
    logits = _logits_out(cfg, params, x, ctx=ctx)
    return logits[:, 0], new_caches, aux

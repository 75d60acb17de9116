"""Collaborative-intelligence split runtime: the paper's edge/cloud system
mapped onto TPU pods.

Pod 0 plays the *edge* (front half of the network), pod 1 the *cloud*
(back half).  At the split boundary the activations are clipped, coarsely
quantized (paper eq. 1), bit-packed to uint8 lanes (2x4b / 8x1b per byte),
and crossed over the inter-pod links with ``lax.ppermute`` -- so the
inter-pod wire bytes drop by 4-16x vs raw bf16, which the dry-run measures
directly in the HLO collective-permute sizes.

The codec ops all route through the codec's ``QuantBackend``
(``repro.core.backend``): inside the jitted superstep body the quantize
lowers to the fused Pallas clip+quant kernel on TPU (the blocked per-tile
variant when the codec carries a TilePlan) and the jnp reference on CPU
hosts, and tiled codecs (``granularity="channel"`` with the d_model axis
as the channel axis, or ``"tile"`` when the boundary shape is static, as
it is inside a fixed-shape decode step) work unchanged -- the per-tile
range tables are baked into the program as constants.  ``codec.pack``
likewise dispatches to the in-graph Pallas pack kernel on TPU, so
clip+quant+pack is a fused on-device pipeline and only wire-width bytes
cross the inter-pod links.

Execution model is the paper's *serial* edge->cloud flow expressed in SPMD
as two supersteps over a shard_map'd 'pod' axis (stage weights are
pod-sharded; each pod applies its own half):

  superstep A: y = stage_local(x_embed)       (pod0 result is real)
               t = ppermute(pack(quant(y)), pod0 -> pod1)
  superstep B: y = stage_local(select(pod==1, dequant(t), x_embed))
               (pod1 result is now cloud(edge(x)))

Caches are pod-sharded alongside the stage weights; each pod keeps the
cache update from its own real superstep.  Supported for homogeneous
(period-1) architectures with >= 2 layers; odd layer counts put the extra
tail layer on the cloud side.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.codec import FeatureCodec
from ..models import transformer as T
from ..models.context import DistContext, shard_map


def split_supported(cfg: ModelConfig) -> bool:
    return cfg.period == 1 and cfg.num_layers >= 2


def stage_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(layers per stage, tail layers appended on the cloud side)."""
    half = cfg.num_layers // 2
    return half, cfg.num_layers - 2 * half


def init_split_params(cfg: ModelConfig, key):
    """Params with layer stack reshaped to (2, half, ...) + tail (t, ...)."""
    if not split_supported(cfg):
        raise ValueError(f"{cfg.name}: split runtime needs a period-1 arch")
    params = T.init_params(cfg, key)
    half, tail = stage_layout(cfg)
    stack = params["groups"][0]["layers"]

    def split_leaf(a):
        main = a[: 2 * half].reshape(2, half, *a.shape[1:])
        return main

    out = dict(params)
    out["stages"] = jax.tree.map(split_leaf, stack)
    out["tail"] = jax.tree.map(lambda a: a[2 * half:], stack) if tail else None
    del out["groups"]
    return out


def _stage_apply(cfg, layers, x, cache, pos, positions, ctx):
    group = T.Group(cfg.pattern, layers_n(layers))
    return T._apply_group(x, {"layers": layers}, group, cfg, pos=pos,
                          gcache=cache, ctx=ctx, positions=positions)


def layers_n(layers) -> int:
    return jax.tree.leaves(layers)[0].shape[0]


def make_split_decode_step(cfg: ModelConfig, mesh, codec: FeatureCodec,
                           *, transport: str = "packed"):
    """Returns a jittable (params, token, caches, pos) -> (logits, caches).

    transport: 'packed' (quantized uint8 lanes), 'quantized_f16' (fake-quant
    but full-width transfer, the ablation), or 'raw' (bf16 baseline).
    """
    assert "pod" in mesh.axis_names, "split runtime needs the multi-pod mesh"
    # sharding hints inside the region name the auto (non-'pod') axes
    inner_ctx = DistContext(mesh, ("data",))
    half, tail = stage_layout(cfg)
    d = cfg.d_model
    # GSPMD cannot partition a Pallas (Mosaic) call, so the codec runs in
    # a nested region manual over the auto axes too: each device of a pod
    # codes the whole (small) boundary tensor, replicated
    rest = frozenset(mesh.axis_names) - {"pod"}

    def local(fn):
        if not rest:
            return fn
        # nested inside the 'pod' region: map over its (abstract) mesh
        return shard_map(fn, jax.sharding.get_abstract_mesh(), P(), P(),
                         manual_axes=rest)

    def body(pod_arr, stages, tail_p, embed, final_norm, head, token,
             stage_cache, tail_cache, pos):
        pod = pod_arr[0]                # pod-sharded iota: this pod's id
        my_layers = jax.tree.map(lambda a: a[0], stages)  # (half, ...)
        base = {"embed": embed, "final_norm": final_norm}
        if head is not None:
            base["head"] = head
        x = T._embed_in(cfg, base, token[:, None], pos0=pos, ctx=inner_ctx)
        positions = jnp.full((1,), pos, dtype=jnp.int32)
        my_cache = jax.tree.map(lambda a: a[0], stage_cache)

        # ---- superstep A: edge half (pod 0's result is the real one) ----
        y_a, cache_a = _stage_apply(cfg, my_layers, x, my_cache, pos,
                                    positions, inner_ctx)
        # ---- transfer across the pod boundary ----
        if transport == "raw":
            recv = lax.ppermute(y_a, "pod", [(0, 1)])
            x_b = recv
            rate_bits = jnp.float32(jnp.finfo(jnp.bfloat16).bits)
        else:
            # backend-dispatched: fused Pallas clip+quant on TPU, jnp on CPU
            shape = y_a.shape

            def encode(y):
                idx = codec.quantize(y)
                wire = codec.pack(idx.reshape(-1)) \
                    if transport == "packed" else idx
                return wire, codec.rate_from_indices(idx, shape)

            def decode(recv):
                idx_r = codec.unpack(recv, y_a.size).reshape(shape) \
                    if transport == "packed" else recv
                return codec.dequantize(idx_r, dtype=y_a.dtype)

            # 'packed' sends uint8 lanes; otherwise the quantized transfer
            # at full index width
            wire, rate_bits = local(encode)(y_a)
            x_b = local(decode)(lax.ppermute(wire, "pod", [(0, 1)]))

        # ---- superstep B: cloud half ----
        x_in_b = jnp.where(pod == 1, x_b, x)
        y_b, cache_b = _stage_apply(cfg, my_layers, x_in_b, my_cache, pos,
                                    positions, inner_ctx)
        new_stage_cache = jax.tree.map(
            lambda a, b: jnp.where(pod == 0, a, b)[None], cache_a, cache_b)

        # ---- tail layers + head (valid on pod 1) ----
        y = y_b
        new_tail_cache = tail_cache
        if tail_p is not None:
            y, new_tail_cache = _stage_apply(
                cfg, tail_p, y, tail_cache, pos, positions, inner_ctx)
        logits = T._logits_out(cfg, base, y, ctx=inner_ctx)[:, 0]
        # broadcast pod 1's logits to everyone (pod 0 holds garbage);
        # bf16 is plenty for the sampler and halves the return-path bytes
        lb = logits.astype(jnp.bfloat16)
        lb = lax.ppermute(lb, "pod", [(1, 0)]) * (pod == 0) + lb * (pod == 1)
        return lb.astype(jnp.float32), new_stage_cache, new_tail_cache, rate_bits

    pod_spec = lambda tree: jax.tree.map(lambda _: P("pod"), tree)
    rep = lambda tree: jax.tree.map(lambda _: P(), tree)

    def step(params, token, caches, pos):
        stage_cache, tail_cache = caches
        head = params.get("head")
        n_pods = mesh.shape["pod"]
        pod_ids = jnp.arange(n_pods, dtype=jnp.int32)
        in_specs = (P("pod"), pod_spec(params["stages"]),
                    rep(params["tail"]) if params["tail"] is not None else None,
                    rep(params["embed"]), rep(params["final_norm"]),
                    rep(head) if head is not None else None,
                    P(), pod_spec(stage_cache),
                    rep(tail_cache) if tail_cache is not None else None, P())
        out_specs = (P(), pod_spec(stage_cache),
                     rep(tail_cache) if tail_cache is not None else None, P())
        logits, sc, tc, rate = shard_map(
            body, mesh, in_specs, out_specs, manual_axes={"pod"},
        )(pod_ids, params["stages"], params["tail"], params["embed"],
          params["final_norm"], head, token, stage_cache, tail_cache, pos)
        return logits, (sc, tc), rate

    return step


def init_split_cache(cfg: ModelConfig, batch: int, max_seq: int):
    """Stage caches stacked (2, half, ...) + tail cache."""
    half, tail = stage_layout(cfg)
    dtype = jnp.dtype(cfg.dtype)
    one = T._init_spec_cache(cfg.pattern[0], cfg, batch, max_seq, dtype)
    # _apply_group expects a list with one cache entry per pattern position
    stage = [jax.tree.map(
        lambda a: jnp.broadcast_to(a, (2, half) + a.shape), one)]
    tail_c = None
    if tail:
        tail_c = [jax.tree.map(
            lambda a: jnp.broadcast_to(a, (tail,) + a.shape), one)]
    return stage, tail_c

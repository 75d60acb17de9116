"""Configuration files and the seeded weights the cell serves.

The weights are the benchmark's, not the program's: one jitted call
makes every leaf on the device from ``--seed`` in the served dtype, in
the parameter layout the program's model reads.  The plain reference
reads the same arrays, so it takes nothing the program has made.

Every seed serves the same model in another order.  A base draw from a
fixed key is permuted by the seed along the hidden channels and the
feed-forward units, which leaves the function the same and the boundary
tensor's channels permuted.  Independent draws per seed do not do the
same work: the boundary's level histogram moves with the draw, and the
host entropy coder's time with it (PERF.md, section 6).
"""

from __future__ import annotations

import dataclasses
import math


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: every
    size and constant is taken from the file."""
    from repro.configs import get_config

    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return dataclasses.replace(
        get_config(conf["program_arch"]),
        num_layers=conf["num_hidden_layers"],
        d_model=d, num_heads=h,
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim", d // h),
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        act=conf["hidden_act"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"],
        split_after_period=conf["split_after_layer"],
        use_qk_norm=False, norm="rmsnorm", gated_mlp=True,
        pos_emb="rope", kv_quant_bits=0)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also past 32 bits."""
    import jax
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


BASE_KEY = 0    # the base draw every seed permutes


def make_weights(conf: dict, seed: int):
    """Every weight made on the device in one jitted call: the base draw,
    its hidden channels and feed-forward units permuted by ``seed``."""
    import jax
    import jax.numpy as jnp

    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], conf.get("head_dim", d // h)
    f, v, n = (conf["intermediate_size"], conf["vocab_size"],
               conf["num_hidden_layers"])
    dt = jnp.dtype(conf["torch_dtype"])

    def build(base, key):
        ks = iter(jax.random.split(base, 10))
        pd, pf = (jax.random.permutation(k, m)
                  for k, m in zip(jax.random.split(key), (d, f)))

        def normal(shape, scale, **perm):
            w = jax.random.normal(next(ks), shape, dt) * jnp.asarray(
                scale, dt)
            for axis, p in perm.items():
                w = jnp.take(w, p, axis=int(axis[1:]))
            return w

        layer = {
            "norm1": {"scale": jnp.ones((n, d), dt)},
            "norm2": {"scale": jnp.ones((n, d), dt)},
            "attn": {"wq": normal((n, d, h, hd), 1 / math.sqrt(d), a1=pd),
                     "wk": normal((n, d, kv, hd), 1 / math.sqrt(d), a1=pd),
                     "wv": normal((n, d, kv, hd), 1 / math.sqrt(d), a1=pd),
                     "wo": normal((n, h, hd, d), 1 / math.sqrt(h * hd),
                                  a3=pd)},
            "mlp": {"w1": normal((n, d, f), 1 / math.sqrt(d), a1=pd, a2=pf),
                    "w2": normal((n, f, d), 1 / math.sqrt(f), a1=pf, a2=pd),
                    "w3": normal((n, d, f), 1 / math.sqrt(d), a1=pd, a2=pf)},
        }
        return {"embed": {"table": normal((v, d), 0.02, a1=pd)},
                "final_norm": {"scale": jnp.ones((d,), dt)},
                "head": {"w": normal((d, v), 1 / math.sqrt(d), a0=pd)},
                "groups": [{"layers": [layer]}]}

    return jax.jit(build)(seed_key(BASE_KEY), seed_key(seed))


def check_layout(cfg, weights) -> None:
    """The weights must have the tree, shapes and dtypes the program's
    own ``init_params`` would give (checked on shapes only)."""
    import jax
    from repro.models import init_params

    want = jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")

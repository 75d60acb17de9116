"""The split programs read the unsplit parameter layout in place.

Serving hands every split entry point one stack of all main-group
layers (``init_params(cfg, key)``, ``split=False``).  Each half reads its
layers out of that stack by index.  These tests hold the outputs to
those of the same programs given the pre-split layout (the stack sliced
on the host into the two groups), bit for bit, for an attention, two
recurrent and a MoE configuration, also on the super-step remat path of
a deep stack.  The gradient path (``loss_fn``) still slices the stack;
its gradient is held to the pre-split one the same way.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import (build_groups, decode_from_boundary,
                          decode_to_boundary, forward, forward_from_boundary,
                          forward_head, init_cache, init_params, loss_fn,
                          prefill_from_boundary, prefill_to_boundary)

B, S = 2, 12

# name -> config.  Each is cut so that its split is uneven (the tail group
# starts past layer 0 and is longer than the head), and recurrentgemma
# keeps a remainder group behind the two carved ones.
CONFIGS = {
    "attn": dataclasses.replace(reduced(ARCHS["codeqwen1.5-7b"]),
                                num_layers=4, split_after_period=1),
    "rwkv": dataclasses.replace(reduced(ARCHS["rwkv6-3b"]),
                                num_layers=3, split_after_period=1),
    "rglru": dataclasses.replace(reduced(ARCHS["recurrentgemma-2b"]),
                                 num_layers=8, split_after_period=1),
    "moe": dataclasses.replace(reduced(ARCHS["qwen3-moe-235b-a22b"]),
                               num_layers=3, split_after_period=1),
}


def _presplit(cfg, params):
    """``params`` in the split group layout, its main stack sliced on the
    host."""
    groups, _ = build_groups(cfg, split=True)
    main, *rest = params["groups"]
    carved, lo = [], 0
    for g in groups[:len(groups) - len(rest)]:
        carved.append({"layers": jax.tree.map(
            lambda a, lo=lo, n=g.n_periods: jnp.asarray(
                np.asarray(a)[lo:lo + n]), main["layers"])})
        lo += g.n_periods
    return {**params, "groups": carved + rest}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    cfg = CONFIGS[request.param]
    params = init_params(cfg, jax.random.PRNGKey(0))
    groups, boundary = build_groups(cfg, split=True)
    assert boundary and len(params["groups"]) < len(groups)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    return cfg, params, _presplit(cfg, params), toks


def _split_programs(cfg, toks):
    """Each split entry point as a function of the params alone, its other
    inputs made from the pre-split reference so both layouts see the same
    boundary tensor and caches."""
    cache = init_cache(cfg, B, S + 1, split=True)
    x_pre = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model),
                              jnp.dtype(cfg.dtype))
    x_dec = x_pre[:, :1]
    return {
        "forward_head": lambda p: forward_head(cfg, p, toks),
        "forward_from_boundary": lambda p: forward_from_boundary(cfg, p,
                                                                 x_pre),
        "prefill_to_boundary": lambda p: prefill_to_boundary(cfg, p, toks,
                                                             cache),
        "prefill_from_boundary": lambda p: prefill_from_boundary(
            cfg, p, x_pre, cache),
        "decode_to_boundary": lambda p: decode_to_boundary(
            cfg, p, toks[:, 0], cache, jnp.int32(S)),
        "decode_from_boundary": lambda p: decode_from_boundary(
            cfg, p, x_dec, cache, jnp.int32(S)),
    }


PROGRAMS = sorted(_split_programs(CONFIGS["attn"], jnp.zeros((B, S),
                                                             jnp.int32)))


@pytest.mark.parametrize("program", PROGRAMS)
def test_unsplit_layout_equals_presplit(model, program):
    cfg, params, pre, toks = model
    fn = jax.jit(_split_programs(cfg, toks)[program])
    got, want = fn(params), fn(pre)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_head_plus_tail_equals_forward(model):
    cfg, params, _, toks = model
    ref, _ = forward(cfg, params, toks, codec_fn=lambda x: (x, 0.0))
    tail = forward_from_boundary(cfg, params, forward_head(cfg, params, toks))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(tail),
                               rtol=1e-5, atol=1e-5)


def _split_grads(cfg, params, toks, remat):
    def loss(p):
        return loss_fn(cfg, p, toks, codec_fn=lambda x: (x, 0.0),
                       remat=remat)[0]
    return jax.jit(jax.grad(loss))(params)


def _assert_grads_equal(cfg, params, pre, toks, remat):
    got = _split_grads(cfg, params, toks, remat)
    # the pre-split gradient, its carved groups joined back into one
    # stack, is the reference
    want = _split_grads(cfg, pre, toks, remat)
    n_carved = len(want["groups"]) - len(got["groups"]) + 1
    joined = jax.tree.map(lambda *a: np.concatenate(a),
                          *[g["layers"] for g in want["groups"][:n_carved]])
    want = {**want, "groups": [{"layers": joined}]
            + want["groups"][n_carved:]}
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_split_loss_gradient_unchanged(model):
    cfg, params, pre, toks = model
    _assert_grads_equal(cfg, params, pre, toks, remat=False)


@pytest.fixture(scope="module")
def deep():
    """Groups of 64 and more periods take the super-step remat branch."""
    cfg = dataclasses.replace(
        reduced(ARCHS["codeqwen1.5-7b"]), num_layers=130,
        split_after_period=64, d_model=16, d_ff=32, num_heads=2,
        num_kv_heads=1, head_dim=8, vocab_size=32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 8), 0,
                              cfg.vocab_size)
    return cfg, params, _presplit(cfg, params), toks


def test_split_loss_gradient_unchanged_deep_remat(deep):
    _assert_grads_equal(*deep, remat=True)


def test_split_forward_remat_deep_equals_presplit(deep):
    cfg, params, pre, toks = deep
    fn = jax.jit(lambda p: forward(cfg, p, toks, split=True, remat=True)[0])
    np.testing.assert_array_equal(np.asarray(fn(params)),
                                  np.asarray(fn(pre)))

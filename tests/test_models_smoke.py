"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, asserting output shapes and finiteness.  Full configs are exercised
only via the dry-run (eval_shape, no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import forward, init_params, loss_fn

ARCH_NAMES = sorted(ARCHS)


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


def _make_inputs(cfg, batch=2, seq=32):
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    if cfg.input_mode == "embeddings":
        emb = jax.random.normal(key, (batch, seq, cfg.d_model), jnp.float32)
        return tokens, emb
    return tokens, None


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_and_finite(name, rng):
    cfg = reduced(ARCHS[name])
    params = init_params(cfg, rng)
    tokens, emb = _make_inputs(cfg)
    logits, aux = forward(cfg, params, emb if emb is not None else tokens)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{name} produced non-finite logits"


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_train_step_no_nans(name, rng):
    cfg = reduced(ARCHS[name])
    params = init_params(cfg, rng)
    tokens, emb = _make_inputs(cfg)

    def loss(p):
        l, _ = loss_fn(cfg, p, tokens, inputs=emb, remat=False)
        return l

    val, grads = jax.value_and_grad(loss)(params)
    assert bool(jnp.isfinite(val))
    # a sane CE magnitude for random init: close to log(vocab)
    assert 0.2 * np.log(cfg.vocab_size) < float(val) < 3 * np.log(cfg.vocab_size)
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)
    assert any(float(jnp.abs(g).max()) > 0 for g in leaves)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_param_count_formula_matches_init(name, rng):
    cfg = reduced(ARCHS[name])
    params = init_params(cfg, rng)
    actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert actual == cfg.param_count(), \
        f"{name}: param_count()={cfg.param_count()} vs actual {actual}"


def test_full_config_param_counts_sane():
    """Full-size param counts should be in the ballpark the names imply."""
    expect = {"dbrx-132b": (110e9, 150e9), "deepseek-llm-67b": (60e9, 72e9),
              "qwen3-moe-235b-a22b": (200e9, 260e9), "gemma2-9b": (8e9, 12e9),
              "gemma3-1b": (0.7e9, 1.6e9), "codeqwen1.5-7b": (6e9, 9e9),
              "rwkv6-3b": (2e9, 4.5e9), "recurrentgemma-2b": (2e9, 3.6e9),
              "qwen2-vl-2b": (1.2e9, 2.4e9), "musicgen-large": (1.5e9, 2.6e9)}
    for name, (lo, hi) in expect.items():
        n = ARCHS[name].param_count()
        assert lo < n < hi, f"{name}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"


def test_moe_active_params_less_than_total():
    cfg = ARCHS["qwen3-moe-235b-a22b"]
    assert cfg.active_param_count() < 0.2 * cfg.param_count()

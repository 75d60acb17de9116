"""Multi-device tests (subprocess: the main test process owns 1 CPU device).

Exercises on an 8-device (pod=2, data=2, model=2) host mesh:
  * the split runtime: edge/cloud pod split with packed uint8 transport —
    logits must match the unsplit model (up to codec quantization);
  * expert-parallel MoE (shard_map all_to_all path) vs the local oracle.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import ARCHS, reduced
    from repro.core import CodecConfig, calibrate
    from repro.models import init_params, init_cache, decode_step
    from repro.models.context import DistContext
    from repro.compression import split_runtime as SR
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = dataclasses.replace(
        reduced(ARCHS["codeqwen1.5-7b"]), num_layers=4, vocab_size=64)
    params = init_params(cfg, jax.random.PRNGKey(0))

    # ---- reference: unsplit decode on one device ----
    cache = init_cache(cfg, batch=4, max_seq=16)
    tok = jnp.arange(4, dtype=jnp.int32)
    ref_logits, _, _ = decode_step(cfg, params, tok, cache, jnp.int32(0))

    # ---- split runtime across the pod axis ----
    sp = SR.init_split_params(cfg, jax.random.PRNGKey(0))
    codec = calibrate(CodecConfig(n_levels=256, clip_mode="manual",
                                  manual_cmin=-8.0, manual_cmax=8.0))
    step = SR.make_split_decode_step(cfg, mesh, codec, transport="packed")
    caches = SR.init_split_cache(cfg, batch=4, max_seq=16)
    logits, caches, rate = jax.jit(step)(sp, tok, caches, jnp.int32(0))
    err = float(jnp.max(jnp.abs(logits - ref_logits)))
    print("SPLIT_MAX_ERR", err)
    assert err < 0.2, f"split logits diverged: {err}"

    # ---- EP MoE vs local oracle ----
    from repro.models import moe as MOE
    mcfg = dataclasses.replace(reduced(ARCHS["qwen3-moe-235b-a22b"]),
                               num_experts=8, experts_per_token=2)
    mp = MOE.init_moe(jax.random.PRNGKey(1), mcfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, mcfg.d_model))
    ctx = DistContext(mesh, ("pod", "data"))
    ep = MOE.moe_apply(x, mp, mcfg, ctx)
    local = MOE.moe_local(x.reshape(32, -1), mp, mcfg,
                          cap=64).reshape(x.shape)
    d = float(jnp.max(jnp.abs(ep - local)))
    print("MOE_EP_MAX_ERR", d)
    assert d < 0.05, f"EP MoE diverged from oracle: {d}"
    print("DISTRIBUTED_OK")
""")


@pytest.mark.timeout(600)
def test_split_runtime_and_ep_moe_multidevice():
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", ""),
           # force the host platform: without this, containers that ship
           # libtpu spend 60s+ probing for TPU metadata before falling back
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    res = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=Path(__file__).resolve().parents[1])
    assert "DISTRIBUTED_OK" in res.stdout, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"

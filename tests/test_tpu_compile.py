"""Main-path kernels compile for a TPU v5e chip.

The chip is described, not attached: the TPU compiler installed with
JAX compiles for ``v5e:2x2`` here, so the lowering refusals that
interpret mode cannot show (block shapes off the (8, 128) tiling,
unsupported vector reshapes, too much VMEM) fail tier-1 instead of a
chip run.  Shapes are the codeqwen1.5-7b split boundary at its
published width: a prefill tensor (4, 64, 4096) and a decode-step
tensor (4, 1, 4096), in bf16.

The topology is described inside a module fixture (only the worker
that runs this file loads the TPU library), and the persistent
compilation cache is off around these compiles: an entry written for
a described chip cannot be read back without one.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tiling import TilePlan
from repro.kernels import ops
from repro.kernels import rans_coder as RC

SHAPES = [(4, 64, 4096), (4, 1, 4096)]
D_MODEL = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler otherwise writes its logs under the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    """Lower and compile ``fn`` for the described chip; returns the HLO
    text of the compiled program."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _channel_plan():
    return TilePlan(channel_axis=-1, channel_group_size=1,
                    spatial_block_size=0, n_channels=D_MODEL)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("bits", [1, 2, 3, 4])
@pytest.mark.parametrize("granularity", ["tensor", "channel"])
def test_encode_megakernel(one_chip, shape, bits, granularity):
    n_levels = 1 << bits
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    if granularity == "tensor":
        fn = functools.partial(ops._encode_fused_flat, cmin=-1.0, cmax=1.0,
                               n_levels=n_levels, bits=bits, interpret=False)
        hlo = _compile(fn, x)
    else:
        rng = jax.ShapeDtypeStruct((D_MODEL, 1), jnp.float32,
                                   sharding=one_chip)
        fn = functools.partial(ops._encode_fused_tiled, n_levels=n_levels,
                               bits=bits, plan=_channel_plan(),
                               interpret=False)
        hlo = _compile(fn, x, rng, rng)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("kernel", ["clip_quantize", "clip_quantize_tiled",
                                    "ecsq_quantize", "index_histogram",
                                    "pack_indices"])
def test_codec_kernel(one_chip, shape, kernel):
    def sds(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    x = sds(shape, jnp.bfloat16)
    idx = sds(shape, jnp.int32)
    if kernel == "clip_quantize":
        hlo = _compile(functools.partial(
            ops.clip_quantize, cmin=-1.0, cmax=1.0, n_levels=4,
            interpret=False), x)
    elif kernel == "clip_quantize_tiled":
        rng = sds((D_MODEL, 1), jnp.float32)
        hlo = _compile(functools.partial(
            ops.clip_quantize_tiled, n_levels=4, plan=_channel_plan(),
            interpret=False), x, rng, rng)
    elif kernel == "ecsq_quantize":
        hlo = _compile(functools.partial(
            ops.ecsq_quantize, cmin=-1.0, cmax=1.0, interpret=False),
            x, sds((3,), jnp.float32), sds((4,), jnp.float32))
    elif kernel == "index_histogram":
        hlo = _compile(functools.partial(
            ops.index_histogram, n_levels=4, interpret=False), idx)
    else:
        hlo = _compile(functools.partial(
            ops.pack_indices, bits=2, interpret=False), idx)
    assert "tpu_custom_call" in hlo


def _stage_layout(n: int, n_levels: int, monkeypatch) -> dict:
    """The static layout ``_dispatch`` picks for a 2-bit boundary tensor
    of ``n`` elements (its size pre-pass runs on the host CPU here)."""
    rng = np.random.default_rng(0)
    coded = jnp.asarray(np.clip(np.rint(rng.normal(1.5, 0.7, n)), 0,
                                n_levels - 1).astype(np.int32))
    seen = {}

    def capture(coded, meta, **kw):
        seen.update(kw, meta_len=int(meta.shape[0]))
        return (None,) * 5

    monkeypatch.setattr(RC, "_entropy_stage", capture)
    RC._dispatch(coded, n_levels, True, False)
    return seen


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_rans_step_loop(one_chip, shape, monkeypatch):
    """The Pallas rANS step loop (device entropy coder id 4) at the lane
    count and step capacity the stage picks for this tensor."""
    kw = _stage_layout(int(np.prod(shape)), 4, monkeypatch)
    lanes, t_cap = kw["lanes"], kw["t_cap"]
    hlo = _compile(
        functools.partial(RC._step_loop_pallas, lanes=lanes, t_cap=t_cap,
                          interpret=False),
        jax.ShapeDtypeStruct((t_cap, lanes), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((t_cap,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in hlo


def test_rans_entropy_stage_decode_step(one_chip, monkeypatch):
    """The whole fused device entropy graph (plane build, Pallas step
    loop, word scan) for the decode-step boundary tensor."""
    n = int(np.prod(SHAPES[1]))
    stage = RC._entropy_stage
    kw = _stage_layout(n, 4, monkeypatch)
    meta_len = kw.pop("meta_len")
    compiled = stage.lower(
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((meta_len,), jnp.int32, sharding=one_chip),
        **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()

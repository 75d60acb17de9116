"""Smoke run of the split-inference serving path on a TPU chip.

    python chip_smoke.py               # one chip: serve + codec identity
    python chip_smoke.py --four-chips  # four chips: pod split runtime + EP MoE

One chip.  codeqwen1.5-7b at its published widths (d_model 4096, 32 MHA
heads x 128, FFN 13440, vocab 92416, bf16), cut to 4 layers and split
after layer 2, random weights from ``--seed``.  The model is served
through the code ``python -m repro.launch.serve --transport loopback``
runs (``serve.serve``: warm-up calibration, an in-process cloud server
on a localhost socket, ``ServeEngine`` with the host round-trip hookup):
4 requests of 64 prompt and 8 new tokens at 4 levels (2 bits), once per
tensor and once per channel.  Then, on a boundary tensor computed on the
chip, the kernel backend's streams must be byte-identical to the jnp
backend's (host and device entropy coder), and the decode must equal
the jnp dequantize of the same indices.

Four chips.  ``split_runtime.make_split_decode_step`` on a
(pod=2, data=1, model=2) mesh at the same model, against the unsplit
one-device ``decode_step``; expert-parallel ``moe_apply`` against
``moe_local``.  Nothing else runs.

The run fails (non-zero exit, no result line) without an accelerator,
outside a checkout, or on any failed check.  Its last line is one JSON
object naming the device JAX reports.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# what the run must find: the chip, with its kernels compiled (a CPU run
# of these phases interprets them)
PLATFORM = "tpu"
INTERPRET = False

ARCH = "codeqwen1.5-7b"
N_LAYERS = 4
SPLIT_AFTER = 2
REQUESTS = 4
PROMPT_LEN = 64
NEW_TOKENS = 8
CODEC_LEVELS = 4


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(ok, msg: str) -> None:
    if not ok:
        fail(msg)


# -- compile accounting ------------------------------------------------------

_COMPILE = {"programs": 0, "seconds": 0.0}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _count_compiles() -> None:
    import jax

    def on_event(event, duration, **_):
        if event == _COMPILE_EVENT:
            _COMPILE["programs"] += 1
            _COMPILE["seconds"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)


def _phase(name: str, t0: float) -> None:
    print(f"phase {name}: ok ({time.perf_counter() - t0:.1f}s wall, "
          f"compile included; {_COMPILE['programs']} programs compiled so "
          f"far in {_COMPILE['seconds']:.1f}s)", flush=True)


# -- phases --------------------------------------------------------------------

def device_info() -> dict:
    """The device JAX reports; fails unless it is a ``PLATFORM`` one."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    check(info["platform"] == PLATFORM,
          f"JAX found no {PLATFORM} device (platform {info['platform']!r})")
    return info


def smoke_config(n_layers: int = N_LAYERS):
    """codeqwen1.5-7b at published widths, depth cut to ``n_layers``,
    split after layer ``SPLIT_AFTER``."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), num_layers=n_layers,
                               split_after_period=SPLIT_AFTER)


def serve_args(granularity: str, seed: int):
    from repro.launch.serve import build_parser
    return build_parser().parse_args([
        "--arch", ARCH, "--requests", str(REQUESTS),
        "--prompt-len", str(PROMPT_LEN), "--new-tokens", str(NEW_TOKENS),
        "--codec-levels", str(CODEC_LEVELS), "--transport", "loopback",
        "--granularity", granularity, "--seed", str(seed)])


def check_kernel_backend(codec) -> None:
    """The codec runs the Pallas kernels, compiled -- no env override, no
    interpret mode."""
    from repro.core.backend import KernelBackend
    be = codec.backend
    check(isinstance(be, KernelBackend),
          f"codec resolved to backend {be.name!r}, not 'kernel'")
    check(be.interpret is INTERPRET,
          f"kernel backend interpret={be.interpret}, expected {INTERPRET}")
    print(f"codec backend: {be.name} (interpret={be.interpret})")


def serve_phase(cfg, granularity: str, seed: int, params=None) -> dict:
    """Serve the smoke requests through ``serve.serve`` and check them."""
    import numpy as np
    from repro.launch.serve import serve

    out = serve(cfg, serve_args(granularity, seed), params=params)
    check_kernel_backend(out["codec"])
    reqs, eng = out["requests"], out["engine"]
    check(len(reqs) == REQUESTS, f"{len(reqs)} requests served")
    for i, r in enumerate(reqs):
        check(r.done and len(r.out_tokens) == NEW_TOKENS,
              f"request {i} returned {len(r.out_tokens)} tokens")
    check(eng.logits_finite, "non-finite logits")
    check(eng.rate_log, "no boundary tensor crossed the transport")
    bpe = float(np.mean(eng.rate_log))
    # no upper bound: per-channel streams carry every channel's range in
    # each tensor's header, which dominates small decode-step tensors
    check(np.isfinite(bpe) and bpe > 0, f"wire rate {bpe} bits/element")
    print(f"served [{granularity}]: {len(reqs)} requests x {NEW_TOKENS} "
          f"tokens, logits finite, wire {bpe:.4f} bits/element over "
          f"{len(eng.rate_log)} decode-step tensors")
    return out


def boundary_tensor(cfg, params, seed: int):
    """Split-layer activations of a prefill batch, computed on device."""
    import jax
    import numpy as np
    from repro.models import forward_head

    rng = np.random.default_rng(seed + 1)
    toks = rng.integers(0, cfg.vocab_size, size=(REQUESTS, PROMPT_LEN),
                        dtype=np.int32)
    x = jax.jit(functools.partial(forward_head, cfg))(params, toks)
    return np.asarray(x, np.float32)


def identity_phase(codec, x) -> float:
    """Kernel-vs-jnp stream identity and the decode check on ``x``.

    Returns the stream's bits/element."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.backend import JnpBackend
    from repro.kernels.rans_coder import host_fallback_counter

    check_kernel_backend(codec)
    ref = dataclasses.replace(
        codec, config=dataclasses.replace(codec.config, backend="jnp"))
    grain = codec.config.granularity
    fallback0 = host_fallback_counter().value()
    for entropy, dev in (("host", False), ("device", True)):
        sk = codec.encode(x, device_entropy=dev)
        sj = ref.encode(x, device_entropy=dev)
        check(sk == sj, f"[{grain}] {entropy}-entropy stream differs: "
              f"kernel {len(sk)} B vs jnp {len(sj)} B")
        print(f"identity [{grain}, {entropy} entropy]: kernel stream == "
              f"jnp stream ({len(sk)} bytes)")
    stream = codec.encode(x)
    spec = codec.spec()
    jb = JnpBackend()
    want = np.asarray(jb.dequantize(jb.quantize(jnp.asarray(x), spec), spec))
    got = codec.decode(stream, shape=x.shape)
    check(np.array_equal(got, want),
          f"[{grain}] decode(encode(x)) != jnp dequantize, max diff "
          f"{float(np.max(np.abs(got - want)))}")
    fallbacks = host_fallback_counter().value() - fallback0
    bpe = 8.0 * len(stream) / x.size
    print(f"identity [{grain}]: decode(encode(x)) == jnp dequantize of the "
          f"same indices; {bpe:.4f} bits/element on {x.shape}; host-rANS "
          f"fallback payloads: {fallbacks:.0f}")
    return bpe


def one_chip(seed: int) -> None:
    cfg = smoke_config()
    print(f"model: {ARCH} d_model={cfg.d_model} layers={cfg.num_layers} "
          f"(split after {SPLIT_AFTER}) heads={cfg.num_heads}x"
          f"{cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"{cfg.dtype}, {cfg.param_count() / 1e9:.2f} B params", flush=True)
    t0 = time.perf_counter()
    out = serve_phase(cfg, "tensor", seed)
    _phase("serve[tensor]", t0)
    t0 = time.perf_counter()
    chan = serve_phase(cfg, "channel", seed, params=out["params"])
    _phase("serve[channel]", t0)
    t0 = time.perf_counter()
    x = boundary_tensor(cfg, out["params"], seed)
    for codec in (out["codec"], chan["codec"]):
        identity_phase(codec, x)
    _phase("identity", t0)


def four_chips(seed: int) -> None:
    """Pod split runtime and EP MoE on a (pod=2, data=1, model=2) mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compression import split_runtime as SR
    from repro.configs import ARCHS, reduced
    from repro.core import CodecConfig, calibrate
    from repro.launch.mesh import make_mesh
    from repro.models import decode_step, forward_head, init_cache, init_params
    from repro.models import moe as MOE
    from repro.models.context import DistContext

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, need 4")
    mesh = make_mesh((2, 1, 2), ("pod", "data", "model"))
    cfg = smoke_config()
    key = jax.random.PRNGKey(seed)
    tok = jnp.arange(REQUESTS, dtype=jnp.int32)

    t0 = time.perf_counter()
    params = init_params(cfg, key)
    cache = init_cache(cfg, batch=REQUESTS, max_seq=16)
    ref, _, _ = jax.jit(functools.partial(decode_step, cfg))(
        params, tok, cache, jnp.int32(0))
    # the decode step's boundary at position 0 is the head of the token
    x = jax.jit(functools.partial(forward_head, cfg))(params, tok[:, None])
    del params, cache
    codec = calibrate(CodecConfig(n_levels=256, clip_mode="minmax",
                                  constrain_cmin_zero=False),
                      samples=jax.device_get(x).astype("float32").ravel())
    check_kernel_backend(codec)

    # weights resident as a pod deployment holds them: each pod's stage
    # on its chips, the rest replicated (left on device 0, jit would
    # move them on every call)
    by_pod, rep = NamedSharding(mesh, P("pod")), NamedSharding(mesh, P())
    sp = {k: jax.device_put(v, by_pod if k == "stages" else rep)
          for k, v in SR.init_split_params(cfg, key).items()}
    step = jax.jit(SR.make_split_decode_step(cfg, mesh, codec,
                                             transport="packed"))
    caches = SR.init_split_cache(cfg, batch=REQUESTS, max_seq=16)
    logits, caches, rate = step(sp, tok, caches, jnp.int32(0))
    err = float(jnp.max(jnp.abs(logits - ref)))
    print(f"split runtime: logits max |err| vs unsplit = {err:.5f} "
          f"(limit 0.2); rate {float(rate):.4f} bits/element; logits on "
          f"{sorted(d.id for d in logits.sharding.device_set)}, stage "
          f"cache on {sorted(d.id for d in caches[0][0]['k'].sharding.device_set)}")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"  device {d.id}: {stats.get('bytes_in_use', 0) / 2**30:.2f} "
              f"GiB in use")
    check(bool(jnp.all(jnp.isfinite(logits))), "non-finite split logits")
    check(err < 0.2, f"split logits diverged: {err}")
    _phase("split_runtime", t0)

    t0 = time.perf_counter()
    mcfg = dataclasses.replace(reduced(ARCHS["qwen3-moe-235b-a22b"]),
                               num_experts=8, experts_per_token=2)
    mp = MOE.init_moe(jax.random.PRNGKey(seed + 1), mcfg, jnp.float32)
    xm = jax.random.normal(jax.random.PRNGKey(seed + 2), (4, 8, mcfg.d_model))
    ep = MOE.moe_apply(xm, mp, mcfg, DistContext(mesh, ("pod", "data")))
    local = MOE.moe_local(xm.reshape(32, -1), mp, mcfg,
                          cap=64).reshape(xm.shape)
    d = float(jnp.max(jnp.abs(ep - local)))
    print(f"EP MoE: max |err| vs moe_local = {d:.6f} (limit 0.05)")
    check(d < 0.05, f"EP MoE diverged from moe_local: {d}")
    _phase("ep_moe", t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip split runtime and EP MoE")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, str(SRC))

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    info = device_info()
    print(f"device: {info['platform']} {info['kind']} x{info['count']}, "
          f"jax {jax.__version__}")
    print(f"compile cache: {enable_compile_cache()}")
    _count_compiles()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(f"compile: {_COMPILE['programs']} programs, "
          f"{_COMPILE['seconds']:.1f}s backend compile; run "
          f"{time.perf_counter() - t0:.1f}s wall")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

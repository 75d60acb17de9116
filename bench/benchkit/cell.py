"""One run of one cell: set-up, the measured window, the check.

The entry the window drives is the one ``python -m repro.launch.serve
--transport loopback`` runs, built from the program's own pieces:

- ``repro.launch.serve._calibrate_warmup`` calibrates the codec at its
  defaults (clip mode ``model``, granularity ``tensor``);
- ``repro.launch.serve._loopback_codec_fn`` starts the in-process
  ``CloudServer`` on a localhost socket with the tick drain and returns
  the host round trip and its clean-up;
- ``repro.serving.ServeEngine`` (4 slots, ``refill_align`` 1) runs each
  wave through ``generate`` with that round trip between its halves.

The harness wraps the round trip it gets back (a host timer and a
``bench.roundtrip`` trace annotation) and hands each request an
``out_tokens`` list of its own that stamps every append.  It touches no
private attribute of the engine.
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import sys
import tempfile
import time

from . import model as M
from . import registry
from .peaks import peaks

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
LABELS = ("bench.roundtrip", "bench.wave", "repro.encode_fused",
          "bench.window")


class StampList(list):
    """``Request.out_tokens``: stamps ``time.perf_counter()`` on every
    append."""

    def __init__(self):
        super().__init__()
        self.stamps: list[float] = []

    def append(self, token):
        self.stamps.append(time.perf_counter())
        super().append(token)


class CompileCounter:
    """Counts programs compiled or loaded from the cache."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0

        def on_event(event, duration, **_):
            if event in COMPILE_EVENTS:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)


def device_info(platform: str, chips: int) -> dict:
    """The devices JAX reports; exits without a result if they are not
    ``chips`` devices of ``platform``."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != platform or info["count"] < chips:
        raise SystemExit(f"bench: need {chips} {platform} device(s), JAX "
                         f"reports {info['count']} {info['platform']} "
                         f"({info['kind']})")
    return info


def serve_args(conf: dict, mix: dict):
    """``serve.py``'s own arguments for this cell (its defaults for
    everything the cell does not set)."""
    from repro.launch.serve import build_parser
    c = conf["codec"]
    return build_parser().parse_args([
        "--arch", conf["program_arch"], "--transport", "loopback",
        "--codec-levels", str(c["n_levels"]), "--clip-mode", c["clip_mode"],
        "--granularity", c["granularity"],
        "--prompt-len", str(max(mix["prompt_buckets"])),
        "--new-tokens", str(max(mix["output_buckets"]))])


class Served:
    """The program's serving stack for one cell, as ``serve.py`` builds it."""

    def __init__(self, conf, cfg, weights, mix, max_seq):
        from repro.launch.serve import _calibrate_warmup, _loopback_codec_fn
        from repro.serving import ServeEngine

        args = serve_args(conf, mix)
        self.codec = _calibrate_warmup(cfg, weights, args)
        inner, self._cleanup = _loopback_codec_fn(
            self.codec, args.chunk_elems, args.tick_ms)
        self.crossings: list[dict] = []
        self.wave = -1      # the window's wave in flight, set by ``run``

        def roundtrip(x):
            import jax
            w0, t0 = time.time(), time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.roundtrip"):
                recon, bpe = inner(x)
            t1 = time.perf_counter()
            self.crossings.append({
                "kind": "prefill" if x.shape[1] > 1 else "decode",
                "t0": t0, "t1": t1, "wall0": w0, "wall1": time.time(),
                "elems": int(x.size), "bits_per_elem": float(bpe),
                "wave": self.wave, "x": x})
            return recon, bpe

        self.engine = ServeEngine(cfg, weights, slots=mix["slots"],
                                  max_seq=max_seq, codec_host_fn=roundtrip)

    def close(self):
        self.engine = None
        self._cleanup()


def run_wave(engine, wave):
    """Hand one wave to ``generate``; returns (requests, t_wave, status)
    with status ``done`` or the failure."""
    import jax
    from repro.serving import Request
    reqs = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                    out_tokens=StampList()) for r in wave]
    t_wave = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.wave"):
            engine.generate(reqs)
        status = "done" if engine.logits_finite else "non-finite logits"
    except Exception as e:              # noqa: BLE001 - counted as failed
        status = f"{type(e).__name__}: {e}"
    return reqs, t_wave, status


def counters(engine) -> dict:
    return {k: v for k, v in engine.counters.items()
            if isinstance(v, int)}


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        platform: str = "tpu", t_start: float | None = None,
        bench: dict | None = None, spec: dict | None = None,
        control: bool = False, log=print) -> dict:
    """Run ``cell`` once; returns the result line's fields.

    ``bench`` and ``spec`` stand in for ``BENCHMARK.json`` and the cell's
    files (a test's smoke-width cell).  ``control`` also reads the float8
    control's gap, under ``checks`` (never in a benchmark run)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench if bench is not None else registry.benchmark()
    spec = spec if spec is not None else registry.cell(cell, bench)
    conf, mix, wl = spec["conf"], spec["mix"], spec["workload"]

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import configure_tracing
    from repro.obs.tracing import tracer

    dev = device_info(platform, spec["chips"])
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    gen = registry.traffic_generator(mix["generator"])

    cfg = M.model_config(conf)
    weights = M.make_weights(conf, seed)
    M.check_layout(cfg, weights)
    served = Served(conf, cfg, weights, mix, wl["max_seq"])
    for wave in gen.warmup_waves(mix, conf["vocab_size"]):
        _, _, status = run_wave(served.engine, wave)
        if status != "done":
            raise RuntimeError(f"warm-up wave failed: {status}")
    served.crossings.clear()
    served.wave = -1
    c0 = counters(served.engine)
    n_compiled_setup = compiles.count

    trace_dir = None
    if trace:
        configure_tracing(enabled=True, jax_trace=True)
        tracer().reset()
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    # -- the measured window ---------------------------------------------
    # Waves are handed out until ``seconds`` have passed; the wave then in
    # flight runs to its end, and every token counts, over the time from
    # the first hand-off to that end.  A rate over whole waves does not
    # jump by a wave's tokens with where the deadline falls.
    waves = gen.waves(mix, seed, conf["vocab_size"])
    records, attempted, failed, errors = [], 0, 0, []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    deadline = t0 + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() < deadline:
            wave = next(waves)
            served.wave += 1
            reqs, t_wave, status = run_wave(served.engine, wave)
            attempted += len(reqs)
            if status != "done":
                failed += len(reqs)
                errors.append(status)
            padded = max(len(r.prompt) for r in reqs)
            records += [{"prompt": r.prompt, "padded_len": padded,
                         "max_new": r.max_new_tokens,
                         "tokens": list(r.out_tokens),
                         "stamps": r.out_tokens.stamps, "t_wave": t_wave,
                         "done": r.done and status == "done",
                         "wave": served.wave, "slot": i}
                        for i, r in enumerate(reqs)]
    t_end = time.perf_counter()
    compiles_in_window = compiles.count - n_compiled_setup

    run_rec = {"window_s": t_end - t0, "t0": t0, "requests": records,
               "crossings": list(served.crossings),
               "counters": {k: v - c0.get(k, 0)
                            for k, v in counters(served.engine).items()},
               "conf": conf, "index_bits": served.codec.bits_per_index(),
               "peaks": peaks(dev["kind"]) if platform == "tpu" else None,
               "spans": [], "trace": None}
    if trace:
        jax.profiler.stop_trace()
        run_rec["spans"] = tracer().snapshot_events()
        configure_tracing(enabled=False, jax_trace=False)
        run_rec["trace"] = _reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    dev["memory_peak_bytes"] = int(max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:spec["chips"]]))
    served.close()
    del served
    gc.collect()

    log(f"window: {attempted} requests attempted, {failed} failed, "
        f"{sum(len(r['stamps']) for r in records)} tokens in "
        f"{t_end - t0:.3f}s ({t_end - deadline:.3f}s past the last "
        f"hand-off's deadline)")
    log(f"compiles_in_window: {compiles_in_window}")
    for e in errors[:3]:
        log(f"failed wave: {e}")

    from . import correct
    check = correct.check(conf, wl, weights, records, run_rec["crossings"],
                          seed, control=control, log=log)
    for c in run_rec["crossings"]:
        del c["x"]
    limits = wl["limits"]
    prog = check["program"] or dict.fromkeys(correct.NUMBERS)
    checks = {k: {"value": prog[k], "limit": limits[k]}
              for k in correct.NUMBERS}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    if control:
        checks.update({f"control_{k}": {"value": v, "limit": limits[k]}
                       for k, v in (check["control"] or {}).items()})
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for k, c in checks.items() if not k.startswith("control_"))
    metrics = registry.metrics_for(cell, bench, trace, run_rec,
                                   setup_s=setup_s)
    out = {"correct": bool(ok), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if trace and run_rec["trace"] is not None:
        t = run_rec["trace"]
        dev["busy_s"] = t["busy_s"]
        dev["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = checks
    return out


def _reduce_trace(trace_dir):
    from . import trace as T
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    ev = T.load_xplane(paths[0])
    if not ev.device:
        return None
    return T.reduce_events(ev, T.window_of(ev, "bench.window"), LABELS)


def emit(result: dict, log_err=None) -> None:
    """Checks as the last lines on stderr; the result as the last line
    on stdout."""
    import json
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

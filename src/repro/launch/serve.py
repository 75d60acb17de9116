"""Serving launcher: `python -m repro.launch.serve --arch <id> [...]`.

Runs the continuous-batching engine on a (reduced by default) config, with
the paper's codec applied at the split boundary, and prints tokens/s, the
measured split-link rate, and each request's time to first token.

The codec is calibrated from a *warm-up batch of real split-layer
activations* (``--clip-mode model|empirical|minmax|aciq``, the paper's
calibration modes) instead of a hardcoded manual range; ``--clip-mode
manual`` keeps the old [-8, 8] behavior.  ``--granularity channel``
(with ``--channel-group``) calibrates a TilePlan codec -- one clipping
range per group of d_model channels, shipped in the v3 self-describing
stream header.

``--transport loopback`` wires the split boundary through a real socket
pair: a CloudServer in a child process (``python -m
repro.transport.worker --echo``, pinned to the host CPU, ended with this
process) receives the streamed, framed bitstream on localhost and echoes
the reconstruction, and the engine round-trips every boundary tensor
through it *between* its two jitted halves
(``ServeEngine(codec_host_fn=...)``) -- the transport stack under a live
serving load, safe on single-CPU hosts.  The edge's encode and the
cloud's decode run in two interpreters, as in a deployment, so neither
waits for the other's interpreter lock.
"""

from __future__ import annotations

import argparse
import os
import select
import subprocess
import sys
import time
from pathlib import Path

import numpy as np


def _calibrate_warmup(cfg, params, args):
    """Calibrate the codec on a warm-up batch of split-layer activations.

    Tiled granularities keep the d_model channel axis in the calibration
    samples (reshaped to (tokens, d_model)), so per-channel-group /
    per-tile ranges come from real per-feature statistics.
    """
    import jax

    from ..core import CodecConfig
    from ..data import DataConfig, stream
    from ..models import forward
    from ..transport import shared_bank

    # "tile" (fixed spatial extent -- 1-D spatial_block_size or the 2-D
    # spatial_block_hw row x column split) is not offered here: serving
    # tensors change spatial size between prefill and decode steps, so
    # only the extent-free granularities calibrate from a warm-up pass
    # (fixed-shape deployments get 2-D tiles via CodecConfig directly;
    # see examples/edge_cloud_demo.py --granularity tile2d)
    ccfg = CodecConfig(n_levels=args.codec_levels, clip_mode=args.clip_mode,
                       constrain_cmin_zero=False,
                       granularity=args.granularity, channel_axis=-1,
                       channel_group_size=args.channel_group)
    if args.clip_mode == "manual":
        if args.granularity != "tensor":
            raise SystemExit("--clip-mode manual implies per-tensor "
                             "granularity")
        # manual ranges ignore samples; dummy samples let the bank cache
        # still dedupe repeated workers
        bank = shared_bank(
            CodecConfig(n_levels=args.codec_levels, clip_mode="manual",
                        manual_cmin=-8.0, manual_cmax=8.0),
            np.zeros(1, np.float32), ladder=(args.codec_levels,))
        return bank.get(args.codec_levels)
    probe = {}

    def probe_fn(x):
        probe["x"] = x
        return x, 0.0

    dcfg = DataConfig(vocab_size=cfg.vocab_size, batch=4,
                      seq_len=min(64, args.prompt_len + args.new_tokens))
    chunks = []
    for _, batch in zip(range(args.warmup_batches), stream(dcfg)):
        forward(cfg, params, jax.numpy.asarray(batch["tokens"]),
                codec_fn=probe_fn)
        chunks.append(np.asarray(probe["x"], np.float32)
                      .reshape(-1, cfg.d_model))
    samples = np.concatenate(chunks, axis=0)
    if args.granularity == "tensor":
        samples = samples.reshape(-1)
    # rung tables are immutable -- one worker-level bank serves every
    # session with this (config, warm-up samples) pair
    codec = shared_bank(ccfg, samples,
                        ladder=(args.codec_levels,)).get(args.codec_levels)
    grain = args.granularity if args.granularity == "tensor" else \
        f"{args.granularity}(g={args.channel_group})"
    print(f"calibrated codec on {samples.size} warm-up activations: "
          f"clip_mode={args.clip_mode} granularity={grain} "
          f"range=[{float(np.min(codec.cmin)):.3f},"
          f" {float(np.max(codec.cmax)):.3f}]")
    return codec


def _roundtrip(client, x):
    """One boundary tensor through ``client``: (reconstruction, wire
    bits per element)."""
    res = client.submit(np.asarray(x, np.float32))
    recon = np.asarray(res.arrays[0], np.float32).reshape(x.shape)
    return recon, float(res.bits_per_elem)


_CLOUD_START_TIMEOUT_S = 60.0
_SRC = str(Path(__file__).resolve().parents[2])


class LoopbackCloud:
    """The loopback transport's cloud in a host-only child process.

    Starts ``python -m repro.transport.worker --echo`` with the tick
    window, metrics port, admission bound, TLS certificate and secret
    given here, under :func:`~repro.hostenv.cpu_env` (the edge process
    holds the chip, so the child must never initialise it), reads its
    ``PORT`` line, and connects a :class:`SyncEdgeClient` straight to
    that port.  The child's stdin is a pipe from this process: it exits
    when the pipe closes, in :meth:`close` or when this process dies, so
    it never outlives the edge.  If the child dies mid-run, the next
    :meth:`roundtrip` raises the client's ``TransportError``.
    """

    def __init__(self, codec, chunk_elems: int, tick_ms: float = 0.0,
                 metrics_port: int | None = None,
                 max_queue: int | None = None,
                 tls_cert: str | None = None, tls_key: str | None = None,
                 secret: str | None = None, client_ssl=None):
        from ..hostenv import cpu_env
        from ..serving import TickConfig
        from ..transport import SyncEdgeClient

        cmd = [sys.executable, "-m", "repro.transport.worker", "--echo",
               "--tick-ms", str(tick_ms)]
        for flag, val in (("--metrics-port", metrics_port),
                          ("--max-queue", max_queue),
                          ("--tls-cert", tls_cert), ("--tls-key", tls_key),
                          ("--secret", secret)):
            if val is not None:
                cmd += [flag, str(val)]
        env = cpu_env()
        # the child imports this package from where this process found it
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (_SRC, env.get("PYTHONPATH")) if p)
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True,
                                     env=env)
        try:
            ready, _, _ = select.select([self.proc.stdout], [], [],
                                        _CLOUD_START_TIMEOUT_S)
            hello = self.proc.stdout.readline().split() if ready else []
            if hello[:1] != ["PORT"]:
                raise RuntimeError(
                    f"cloud worker did not start within "
                    f"{_CLOUD_START_TIMEOUT_S:.0f}s (exit code "
                    f"{self.proc.poll()}, said {hello})")
            self.port = int(hello[1])
            self.metrics_port = int(hello[3]) if len(hello) > 3 else None
            self.client = SyncEdgeClient(
                "127.0.0.1", self.port, codec=codec,
                chunk_elems=chunk_elems,
                tick=(TickConfig(max_wait_s=tick_ms / 1e3)
                      if tick_ms > 0 else None),
                ssl=client_ssl, secret=secret)
        except BaseException:
            self._end_child()
            raise

    def roundtrip(self, x):
        return _roundtrip(self.client, x)

    def close(self) -> None:
        """Read the cloud's counters, close the client, end the child."""
        from ..transport import TransportError
        try:
            c = self.client.fetch_cloud_metrics()["counters"]
        except (TransportError, ConnectionError) as e:   # child gone
            c = None
            print(f"cloud counters unavailable: {e}")
        finally:
            self.client.close()
            self._end_child()
        if c is not None:
            print(f"cloud ticks: {c.get('ticks', 0)} "
                  f"(occupancy {c.get('batch_occupancy_avg', 0.0):.2f}, "
                  f"entropy calls {c.get('entropy_calls', 0)}, "
                  f"bpe {c.get('bpe_avg', 0.0):.3f}, header cache "
                  f"{c.get('header_cache', {})})")

    def _end_child(self) -> None:
        self.proc.stdin.close()        # end of file: the worker exits
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self.proc.stdout.close()


def _loopback_codec_fn(codec, chunk_elems: int, tick_ms: float = 0.0,
                       metrics_port: int | None = None,
                       workers: int = 1, max_queue: int | None = None,
                       tls_cert: str | None = None,
                       tls_key: str | None = None,
                       secret: str | None = None):
    """Split-boundary host hook that streams every tensor over localhost.

    Returns a *host* round trip ``x -> (recon, bits_per_elem)`` for
    ``ServeEngine(codec_host_fn=...)`` and its clean-up: the engine runs
    each stage as two jitted halves split at the boundary and calls the
    round trip eagerly in between, so the client's own jax encode never
    executes beneath an in-flight jitted program.  (The old
    ``io_callback`` hookup deadlocked on single-CPU hosts: the callback
    held XLA's only dispatch thread while the nested encode waited for
    it.  Running the round-trip *between* programs removes that cycle
    structurally -- tests/test_serve_loopback.py pins it on 1 CPU.)  The
    reported rate is the true wire bits/element (frames, headers and
    all).

    The cloud (``workers == 1``) is a :class:`LoopbackCloud`: a
    ``repro.transport.worker`` child process echoing reconstructions, so
    its decode of one chunk overlaps the edge's encode of the next
    instead of waiting for this process's interpreter lock.  It always
    runs the cross-session tick drain (one batched entropy call per
    tick); ``tick_ms`` sets the tick window.  The engine keeps one
    tensor in flight per boundary crossing, so the default window is 0
    (drain as soon as the loop is idle) and client-side encode
    coalescing only engages for ``tick_ms > 0``.

    Hardened-serving knobs (DESIGN.md, "Hardened scale-out serving"):
    ``workers > 1`` puts a session-affine :class:`Dispatcher` over a
    pool of in-process CloudServers on a thread of this process (worker
    kill/restart tolerant; the client gets a retry policy so restarts
    replay transparently); ``max_queue`` bounds in-flight sessions (BUSY
    shedding); ``tls_cert``/``tls_key`` wrap the edge-facing socket in
    TLS; and ``secret`` requires the authenticated HELLO handshake.
    """
    import ssl as ssl_mod

    client_ssl = None
    if tls_cert is not None:
        # self-signed deployments pin the cert itself as the CA; the
        # hostname check is skipped (loopback certs rarely carry SANs)
        client_ssl = ssl_mod.create_default_context(cafile=tls_cert)
        client_ssl.check_hostname = False
    tls = ", TLS" if tls_cert is not None else ""
    auth = ", authenticated" if secret is not None else ""

    if workers == 1:
        cloud = LoopbackCloud(codec, chunk_elems, tick_ms,
                              metrics_port=metrics_port,
                              max_queue=max_queue, tls_cert=tls_cert,
                              tls_key=tls_key, secret=secret,
                              client_ssl=client_ssl)
        print(f"loopback transport: streaming split tensors via cloud "
              f"worker pid {cloud.proc.pid} on 127.0.0.1:{cloud.port} (tick "
              f"window {tick_ms:.1f}ms{tls}{auth})")
        if cloud.metrics_port is not None:
            print(f"metrics: http://127.0.0.1:{cloud.metrics_port}/metrics")
        return cloud.roundtrip, cloud.close

    import asyncio
    import threading

    from ..serving import TickConfig
    from ..transport import (CloudServer, Dispatcher, RetryPolicy,
                             SyncEdgeClient)
    from ..transport.worker import server_ssl_context

    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, name="dispatcher",
                              daemon=True)
    thread.start()
    tick = TickConfig(max_wait_s=tick_ms / 1e3)
    server = Dispatcher(
        workers=workers,
        worker_factory=lambda i: CloudServer(echo_features=True, tick=tick),
        max_queue=max_queue,
        ssl=(server_ssl_context(tls_cert, tls_key)
             if tls_cert is not None else None),
        secret=secret)
    asyncio.run_coroutine_threadsafe(server.start(), loop).result()
    client = SyncEdgeClient("127.0.0.1", server.port, codec=codec,
                            chunk_elems=chunk_elems,
                            tick=tick if tick_ms > 0 else None,
                            ssl=client_ssl, secret=secret,
                            retry=RetryPolicy())   # restarts replay
    print(f"loopback transport: streaming split tensors via dispatcher "
          f"x{workers} workers on 127.0.0.1:{server.port} (tick window "
          f"{tick_ms:.1f}ms{tls}{auth})")

    def cleanup():
        snap = server.metrics.snapshot()

        def val(name):
            s = snap.get(name, {}).get("series", [])
            return s[0]["value"] if s else 0

        client.close()
        asyncio.run_coroutine_threadsafe(server.close(), loop).result()
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=5)
        loop.close()
        print(f"dispatcher: "
              f"{val('repro_dispatcher_routed_sessions_total'):.0f} "
              f"sessions routed, "
              f"{val('repro_dispatcher_worker_restarts_total'):.0f} "
              f"worker restarts, "
              f"{val('repro_dispatcher_shed_sessions_total'):.0f} shed")

    return (lambda x: _roundtrip(client, x)), cleanup


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and request prompts")
    ap.add_argument("--codec-levels", type=int, default=0,
                    help="0 = no split codec; else N quantizer levels")
    ap.add_argument("--clip-mode", default="model",
                    choices=["model", "empirical", "minmax", "aciq",
                             "manual"],
                    help="codec calibration mode (warm-up activations; "
                         "'manual' keeps the legacy [-8, 8] range)")
    ap.add_argument("--warmup-batches", type=int, default=4)
    ap.add_argument("--granularity", default="tensor",
                    choices=["tensor", "channel"],
                    help="codec granularity at the split boundary: "
                         "'channel' calibrates one range per d_model "
                         "channel group (TilePlan, v3 streams).  Spatial "
                         "('tile') granularities -- incl. the 2-D "
                         "spatial_block_hw split, v4 streams -- pin the "
                         "spatial extent at calibration and are for "
                         "fixed-shape boundaries, not the varying "
                         "prefill/decode shapes served here")
    ap.add_argument("--channel-group", type=int, default=1,
                    help="channels per range group for "
                         "--granularity channel")
    ap.add_argument("--transport", default="none",
                    choices=["none", "loopback"],
                    help="'loopback' streams every split tensor through "
                         "the framed transport over a localhost socket")
    ap.add_argument("--chunk-elems", type=int, default=1 << 16)
    ap.add_argument("--tick-ms", type=float, default=0.0,
                    help="cross-session batching tick window for the "
                         "loopback transport (0 = drain immediately; the "
                         "engine keeps one tensor in flight per boundary "
                         "crossing, so >0 only helps with several "
                         "engines sharing the worker)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus-text telemetry on this port "
                         "alongside the loopback CloudServer (0 = pick a "
                         "free one); needs --transport loopback")
    ap.add_argument("--workers", type=int, default=1,
                    help=">1 puts a session-affine Dispatcher over a "
                         "pool of in-process cloud workers (heartbeats, "
                         "crash restart, client-side retry); needs "
                         "--transport loopback")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission-control bound on concurrently open "
                         "sessions; saturated servers answer new streams "
                         "with a retryable BUSY error")
    ap.add_argument("--tls-cert", default=None, metavar="PEM",
                    help="serve the loopback transport over TLS with "
                         "this certificate (also pinned as the client "
                         "CA -- self-signed certs work)")
    ap.add_argument("--tls-key", default=None, metavar="PEM",
                    help="private key for --tls-cert (default: key is "
                         "in the cert PEM)")
    ap.add_argument("--secret", default=None,
                    help="require the authenticated HELLO handshake "
                         "with this shared secret")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable pipeline stage tracing and mirror the "
                         "JSON span log to PATH")
    ap.add_argument("--full", action="store_true")
    return ap


def _check_args(ap: argparse.ArgumentParser, args) -> None:
    if args.metrics_port is not None and args.transport != "loopback":
        ap.error("--metrics-port needs --transport loopback")
    if args.transport != "loopback":
        for flag, val in (("--workers", args.workers != 1),
                          ("--max-queue", args.max_queue is not None),
                          ("--tls-cert", args.tls_cert is not None),
                          ("--secret", args.secret is not None)):
            if val:
                ap.error(f"{flag} needs --transport loopback")
    if args.transport == "loopback" and not args.codec_levels:
        ap.error("--transport loopback needs --codec-levels")
    if args.workers < 1:
        ap.error("--workers must be >= 1")
    if args.tls_key is not None and args.tls_cert is None:
        ap.error("--tls-key needs --tls-cert")
    if args.workers > 1 and args.metrics_port is not None:
        ap.error("--metrics-port is per-worker; not supported with "
                 "--workers > 1 (scrape the dispatcher registry instead)")


def serve(cfg, args, params=None) -> dict:
    """Serve ``args.requests`` random prompts on the model ``cfg``.

    The body of ``main`` with the configuration passed in: weights are
    drawn from ``args.seed`` unless ``params`` are given.  Returns the
    pieces a caller checks -- ``params``, the calibrated ``codec`` (None
    without ``--codec-levels``), the ``engine`` and its served
    ``requests``.
    """
    import jax

    from ..models import init_params
    from ..serving import Request, ServeEngine

    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(args.seed))

    codec = codec_host_fn = cleanup = None
    if args.codec_levels:
        codec = _calibrate_warmup(cfg, params, args)
        if args.transport == "loopback":
            codec_host_fn, cleanup = _loopback_codec_fn(
                codec, args.chunk_elems, args.tick_ms,
                metrics_port=args.metrics_port,
                workers=args.workers, max_queue=args.max_queue,
                tls_cert=args.tls_cert, tls_key=args.tls_key,
                secret=args.secret)

    eng = ServeEngine(cfg, params, slots=4,
                      max_seq=args.prompt_len + args.new_tokens + 8,
                      codec=None if codec_host_fn else codec,
                      codec_host_fn=codec_host_fn)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        size=args.prompt_len).astype(np.int32),
                    max_new_tokens=args.new_tokens)
            for _ in range(args.requests)]
    try:
        t0 = time.time()
        eng.generate(reqs)
        dt = time.time() - t0
    finally:
        if cleanup is not None:
            cleanup()
    total = sum(len(r.out_tokens) for r in reqs)
    print(f"{total} tokens in {dt:.2f}s = {total / dt:.1f} tok/s "
          f"({args.requests} requests)")
    if eng.rate_log:
        print(f"split-link rate: {np.mean(eng.rate_log):.3f} bits/element "
              f"({16 / max(np.mean(eng.rate_log), 1e-9):.1f}x vs bf16)")
    ttft = [r.ttft_s for r in reqs if r.ttft_s is not None]
    if ttft:
        print(f"time to first token: mean={np.mean(ttft):.3f}s "
              f"p50={np.percentile(ttft, 50):.3f}s "
              f"max={np.max(ttft):.3f}s")
    ec = eng.counters
    print(f"engine: {ec['steps']} steps, occupancy "
          f"{ec['batch_occupancy_avg']:.2f}, {ec['refills']} refills, "
          f"{ec['epochs']} epochs")
    if args.codec_levels:
        from ..transport import bank_cache_stats
        print(f"codec bank cache: {bank_cache_stats()}")
    return {"params": params, "codec": codec, "engine": eng,
            "requests": reqs}


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    _check_args(ap, args)
    if args.trace is not None:
        from ..obs import configure_tracing
        configure_tracing(enabled=True, event_log_path=args.trace)
        print(f"stage tracing on: span log -> {args.trace}")

    from ..configs import get_config, reduced
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    serve(cfg, args)


if __name__ == "__main__":
    main()

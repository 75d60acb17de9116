"""Standalone CloudServer worker process (``python -m repro.transport.worker``).

The :class:`~repro.transport.dispatcher.Dispatcher` spawns N of these as
subprocesses for real fault isolation (a worker SIGKILL cannot take the
front-end down), and ``serve.py``'s loopback transport runs its one
cloud as such a child, so the cloud's decode never competes with the
edge's encode for one interpreter lock.  The worker binds an ephemeral
loopback port, prints ``PORT <n>`` on stdout -- ``PORT <n> METRICS <m>``
when it also serves ``/metrics`` -- as the parent's only startup
handshake, then serves the ordinary frame protocol until killed.  When
its stdin is a pipe, the worker also exits at the pipe's end of file:
the parent holds the other end, so the worker never outlives it.

``--tail module:attr`` resolves an importable callable to use as the
cloud-side ``tail_fn``; ``--echo`` echoes the reconstructed split-layer
tensor back (what the chaos tests, the degraded-mode benchmark and the
loopback transport use, since a closure can't cross a process boundary).
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import os
import ssl as ssl_mod
import stat
import sys

from ..serving.batcher import TickConfig
from .server import CloudServer


def resolve_tail(spec: str):
    """``module:attr`` -> callable (the worker's ``tail_fn``)."""
    mod, _, attr = spec.partition(":")
    if not mod or not attr:
        raise SystemExit(f"--tail wants module:attr, got {spec!r}")
    fn = getattr(importlib.import_module(mod), attr)
    if not callable(fn):
        raise SystemExit(f"--tail target {spec!r} is not callable")
    return fn


def server_ssl_context(cert: str, key: str | None = None):
    """TLS context of a listener serving ``cert`` (the key defaults to
    the cert PEM itself)."""
    ctx = ssl_mod.SSLContext(ssl_mod.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(cert, key or cert)
    return ctx


def build_server(args: argparse.Namespace) -> CloudServer:
    tick = (TickConfig() if args.tick_ms is None
            else TickConfig(max_wait_s=args.tick_ms / 1e3))
    return CloudServer(
        tail_fn=resolve_tail(args.tail) if args.tail else None,
        echo_features=args.echo,
        host=args.host, port=args.port,
        tick=tick,
        max_queue=args.max_queue,
        secret=args.secret,
        ssl=(server_ssl_context(args.tls_cert, args.tls_key)
             if args.tls_cert else None),
        resume_ttl_s=args.resume_ttl_s,
        metrics_port=args.metrics_port,
    )


def _on_stdin_eof(callback) -> None:
    """Call ``callback`` on the running loop when stdin, if it is a
    pipe, reaches end of file (its writer, the parent, closed it or
    died).  Any other stdin -- a terminal, ``/dev/null`` -- is left
    alone."""
    try:
        fd = sys.stdin.fileno()
        if not stat.S_ISFIFO(os.fstat(fd).st_mode):
            return
    except (AttributeError, OSError, ValueError):   # no usable stdin
        return
    loop = asyncio.get_running_loop()

    def readable():
        if not os.read(fd, 4096):
            loop.remove_reader(fd)
            callback()

    loop.add_reader(fd, readable)


async def amain(args: argparse.Namespace) -> None:
    server = await build_server(args).start()
    hello = f"PORT {server.port}"
    if server.metrics_port is not None:
        hello += f" METRICS {server.metrics_port}"
    print(hello, flush=True)
    parent_gone = asyncio.Event()
    _on_stdin_eof(parent_gone.set)
    serving = asyncio.ensure_future(server.wait_closed())
    gone = asyncio.ensure_future(parent_gone.wait())
    await asyncio.wait({serving, gone}, return_when=asyncio.FIRST_COMPLETED)
    gone.cancel()
    # no parent is left to serve: drop what is still connected
    server.abort()
    serving.cancel()
    try:
        await serving
    except asyncio.CancelledError:
        pass


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="listen port (0 = ephemeral, printed on stdout)")
    p.add_argument("--echo", action="store_true",
                   help="echo the reconstructed tensor in RESULT")
    p.add_argument("--tail", default=None, metavar="MODULE:ATTR",
                   help="importable callable to run as the cloud tail")
    p.add_argument("--tick-ms", type=float, default=None,
                   help="tick window of the batching drain in ms "
                        "(default: TickConfig's)")
    p.add_argument("--max-queue", type=int, default=None,
                   help="admission bound (sessions in flight)")
    p.add_argument("--secret", default=None,
                   help="require an authenticated HELLO")
    p.add_argument("--tls-cert", default=None, metavar="PEM",
                   help="serve the frame protocol over TLS with this "
                        "certificate")
    p.add_argument("--tls-key", default=None, metavar="PEM",
                   help="private key for --tls-cert (default: key is in "
                        "the cert PEM)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="also serve /metrics and /events on this port "
                        "(0 = ephemeral, printed on stdout)")
    p.add_argument("--resume-ttl-s", type=float, default=30.0,
                   help="how long disconnected sessions stay resumable")
    return p


def main(argv=None) -> None:
    p = build_parser()
    args = p.parse_args(argv)
    if args.tls_key is not None and args.tls_cert is None:
        p.error("--tls-key needs --tls-cert")
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main(sys.argv[1:])

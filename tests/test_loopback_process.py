"""The loopback transport's cloud runs in a child process.

``serve.py``'s ``--transport loopback`` starts ``python -m
repro.transport.worker --echo`` as a host-only child and streams every
boundary tensor to it.  These tests pin what the edge relies on: the
child is another process pinned to the CPU, the bytes and the
reconstruction are those of the codec's own stream, a dead child makes
``submit`` raise instead of hang, and no child outlives its edge.
"""

import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import CodecConfig, calibrate
from repro.launch.serve import LoopbackCloud, _loopback_codec_fn
from repro.transport import TransportError, tensor_to_frames
from repro.transport.worker import build_parser, build_server

CHUNK = 1 << 12


@pytest.fixture(scope="module")
def codec():
    rng = np.random.default_rng(0)
    return calibrate(CodecConfig(n_levels=4, clip_mode="model",
                                 constrain_cmin_zero=False),
                     samples=rng.normal(0, 1, 8192).astype(np.float32))


@pytest.fixture(scope="module")
def cloud(codec):
    host_fn, cleanup = _loopback_codec_fn(codec, CHUNK)
    yield host_fn.__self__
    cleanup()


def _proc_file(pid: int, name: str) -> list[bytes]:
    return Path(f"/proc/{pid}/{name}").read_bytes().split(b"\0")


def _exited(pid: int, timeout_s: float) -> bool:
    """Has ``pid`` (not a child of this process) ended within the time?
    Gone, or a zombie awaiting its reaper."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            stat = Path(f"/proc/{pid}/stat").read_text()
        except FileNotFoundError:
            return True
        if stat.rsplit(")", 1)[1].split()[0] == "Z":
            return True
        time.sleep(0.05)
    return False


def test_cloud_is_a_cpu_child_process(cloud):
    assert cloud.proc.pid != os.getpid()
    assert cloud.proc.poll() is None
    assert b"JAX_PLATFORMS=cpu" in _proc_file(cloud.proc.pid, "environ")
    argv = _proc_file(cloud.proc.pid, "cmdline")
    assert b"repro.transport.worker" in argv and b"--echo" in argv
    # the loopback's tick window reaches the child, not the worker's
    # 2 ms default
    assert argv[argv.index(b"--tick-ms") + 1] == b"0.0"


def test_worker_takes_the_loopback_settings():
    args = build_parser().parse_args(
        ["--echo", "--tick-ms", "0", "--metrics-port", "0",
         "--max-queue", "3"])
    srv = build_server(args)
    assert srv.echo_features and srv.tick.max_wait_s == 0.0
    assert srv.metrics_port == 0 and srv.max_queue == 3
    srv = build_server(build_parser().parse_args(["--echo"]))
    assert srv.tick.max_wait_s == 0.002 and srv.metrics_port is None


@pytest.mark.parametrize("shape", [(4, 64, 256), (4, 1, 256)],
                         ids=["prefill", "decode"])
def test_roundtrip_is_the_codec_stream(cloud, codec, shape):
    """A multi-chunk prefill-shaped tensor and a one-chunk decode-shaped
    one come back as ``decode_stream`` of the codec's own payloads, at
    the wire rate of the frames that carry them."""
    x = np.random.default_rng(1).normal(0, 1.5, shape).astype(np.float32)
    recon, bpe = cloud.roundtrip(x)
    payloads = list(codec.encode_stream(x, chunk_elems=CHUNK))
    assert len(payloads) == 1 + -(-x.size // CHUNK)
    np.testing.assert_array_equal(
        recon, codec.decode_stream(payloads, shape=shape))
    wire = sum(len(f) for f in tensor_to_frames(codec, x, 1, CHUNK))
    assert bpe == 8.0 * wire / x.size


def test_tls_and_secret_reach_the_child(codec, tmp_path):
    cert, key = tmp_path / "cert.pem", tmp_path / "key.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "1",
         "-subj", "/CN=127.0.0.1"],
        check=True, capture_output=True, timeout=60)
    host_fn, cleanup = _loopback_codec_fn(
        codec, CHUNK, tls_cert=str(cert), tls_key=str(key), secret="s3")
    try:
        argv = _proc_file(host_fn.__self__.proc.pid, "cmdline")
        for flag, val in (("--tls-cert", cert), ("--tls-key", key),
                          ("--secret", "s3")):
            assert argv[argv.index(flag.encode()) + 1] == str(val).encode()
        x = np.random.default_rng(2).normal(0, 1, (4, 1, 256))
        recon, _ = host_fn(x.astype(np.float32))
        payloads = codec.encode_stream(x.astype(np.float32),
                                       chunk_elems=CHUNK)
        np.testing.assert_array_equal(
            recon, codec.decode_stream(payloads, shape=x.shape))
    finally:
        cleanup()


def _raises_within(fn, timeout_s: float):
    """Run ``fn`` on a daemon thread; the exception it raised, or None
    if it returned.  Fails the test if it is still running."""
    out = {}

    def run():
        try:
            fn()
        except BaseException as e:             # noqa: BLE001 - reported
            out["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout_s)
    assert not t.is_alive(), f"still running after {timeout_s}s"
    return out.get("error")


def test_dead_child_makes_submit_raise(codec, capsys):
    cloud = LoopbackCloud(codec, CHUNK)
    try:
        x = np.ones((4, 1, 256), np.float32)
        cloud.roundtrip(x)
        cloud.proc.kill()
        cloud.proc.wait(timeout=10)
        err = _raises_within(lambda: cloud.roundtrip(x), 30)
        assert isinstance(err, TransportError), err
    finally:
        cloud.close()
    assert cloud.proc.poll() is not None
    assert "cloud counters unavailable" in capsys.readouterr().out


def test_cleanup_ends_the_child(codec, capsys):
    host_fn, cleanup = _loopback_codec_fn(codec, CHUNK)
    proc = host_fn.__self__.proc
    host_fn(np.ones((4, 1, 256), np.float32))
    cleanup()
    assert proc.poll() is not None
    out = capsys.readouterr().out
    assert f"cloud worker pid {proc.pid}" in out
    assert "cloud ticks: " in out and "entropy calls 1," in out


def test_child_exits_at_stdin_eof(codec):
    cloud = LoopbackCloud(codec, CHUNK)
    try:
        cloud.proc.stdin.close()
        assert cloud.proc.wait(timeout=30) is not None
    finally:
        cloud.client.close()
        cloud.proc.stdout.close()


_EDGE = """
import numpy as np
from repro.core import CodecConfig, calibrate
from repro.launch.serve import LoopbackCloud
codec = calibrate(CodecConfig(n_levels=4, clip_mode="manual",
                              manual_cmin=-6.0, manual_cmax=6.0))
cloud = LoopbackCloud(codec, 1 << 12)
print(cloud.proc.pid, flush=True)
input()
"""


def test_child_exits_when_the_edge_is_killed():
    """An edge killed with no clean-up (a benchmark run cut at its time
    limit) leaves no cloud worker behind."""
    edge = subprocess.Popen(
        [sys.executable, "-c", _EDGE], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        pid = int(edge.stdout.readline())
        assert not _exited(pid, 0.5)
        edge.send_signal(signal.SIGKILL)
        edge.wait(timeout=10)
        assert _exited(pid, 30)
    finally:
        edge.kill()
        edge.wait()
        edge.stdin.close()
        edge.stdout.close()

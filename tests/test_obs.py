"""Observability layer tests: metrics registry semantics, span tracing,
the Prometheus exposition endpoint, the FT_METRICS in-band snapshot, and
the counter-migration invariants (legacy dict shapes, per-session label
series lifecycle across completion / eviction / disconnect)."""

import asyncio
import dataclasses
import json
import pathlib
import sys
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from repro.core import CodecConfig, calibrate
from repro.obs import (BPE_BUCKETS, LATENCY_BUCKETS, MetricsExposition,
                       MetricsRegistry, configure_tracing,
                       parse_prometheus_text, tracer)
from repro.obs.tracing import _NULL_SPAN, span
from repro.serving import TickConfig
from repro.transport import (CloudServer, EdgeClient, encode_frame,
                             tensor_to_frames)
from repro.transport.framing import FT_METRICS


@pytest.fixture(scope="module")
def features():
    rng = np.random.default_rng(11)
    mu = np.linspace(0.0, 6.0, 16).astype(np.float32)
    return (mu[None, :] + rng.exponential(1.0, (512, 16))).astype(np.float32)


def _live_codec(features, n_levels=8):
    return calibrate(CodecConfig(n_levels=n_levels, clip_mode="minmax",
                                 constrain_cmin_zero=False,
                                 granularity="channel", channel_axis=-1,
                                 channel_group_size=4), samples=features)


def _series(snap: dict, name: str) -> dict:
    """The single label series of ``name`` in a registry snapshot."""
    series = snap[name]["series"]
    assert len(series) == 1, (name, series)
    return series[0]


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("repro_t_things_total", "things")
        c.inc()
        c.inc(4)
        assert c.value() == 5
        g = reg.gauge("repro_t_depth_count", "depth")
        g.set(3)
        g.dec()
        assert g.value() == 2
        h = reg.histogram("repro_t_lat_seconds", "lat")
        for v in (0.001, 0.01, 0.01, 5.0):
            h.observe(v)
        assert h.count() == 4
        assert h.sum() == pytest.approx(5.021)

    def test_labels_and_removal(self):
        reg = MetricsRegistry()
        g = reg.gauge("repro_t_pending_count", "pending",
                      labelnames=("session",))
        g.set(2, session="1:0")
        g.set(7, session="1:1")
        assert g.value(session="1:1") == 7
        assert len(g.series()) == 2
        g.remove(session="1:0")
        assert len(g.series()) == 1
        g.remove(session="no-such")          # idempotent
        assert len(g.series()) == 1

    def test_get_or_create_conflicts(self):
        reg = MetricsRegistry()
        reg.counter("repro_t_x_total", "x")
        # same name, same kind -> same instrument
        assert reg.counter("repro_t_x_total", "x") is \
            reg.counter("repro_t_x_total", "x")
        with pytest.raises(ValueError):
            reg.gauge("repro_t_x_total", "x")
        with pytest.raises(ValueError):
            reg.counter("repro_t_x_total", "x", labelnames=("a",))

    def test_render_parse_roundtrip(self):
        reg = MetricsRegistry()
        reg.counter("repro_t_events_total", "evts",
                    labelnames=("kind",)).inc(3, kind='a"b\\c')
        reg.gauge("repro_t_level_count", "lvl").set(1.5)
        h = reg.histogram("repro_t_bpe", "bpe", buckets=BPE_BUCKETS)
        h.observe(2.0)
        fams = parse_prometheus_text(reg.render())
        assert fams["repro_t_events_total"]["type"] == "counter"
        assert fams["repro_t_level_count"]["type"] == "gauge"
        assert fams["repro_t_bpe"]["type"] == "histogram"
        # cumulative buckets + +Inf
        buckets = [(k, v) for (k, labels), v
                   in fams["repro_t_bpe"]["samples"].items()
                   if k == "repro_t_bpe_bucket"]
        assert len(buckets) == len(BPE_BUCKETS) + 1
        assert all(v <= 1.0 for _, v in buckets)

    def test_quantile(self):
        reg = MetricsRegistry()
        h = reg.histogram("repro_t_q_seconds", "q", buckets=LATENCY_BUCKETS)
        for _ in range(99):
            h.observe(0.002)
        h.observe(9.0)
        assert h.quantile(0.5) <= 0.01
        assert h.quantile(0.995) > 1.0


class TestTracing:
    def test_disabled_is_shared_noop(self):
        configure_tracing(enabled=False)
        assert span("anything") is _NULL_SPAN
        assert span("other", k=1) is _NULL_SPAN

    def test_events_nest_and_feed_histogram(self):
        configure_tracing(enabled=True)
        try:
            tracer().reset()
            with span("tick_drain", sessions=2):
                with span("entropy_decode", chunks=3):
                    time.sleep(0.001)
            events = tracer().snapshot_events()
        finally:
            configure_tracing(enabled=False)
        assert [e["stage"] for e in events] == ["entropy_decode",
                                                "tick_drain"]
        child, parent = events
        assert child["parent_id"] == parent["span_id"]
        assert parent["parent_id"] is None
        assert child["chunks"] == 3
        assert child["dur_s"] > 0
        totals = tracer().stage_totals(stages={"entropy_decode"})
        assert totals["entropy_decode"] >= child["dur_s"]
        from repro.obs import default_registry
        hist = default_registry().get(
            "repro_pipeline_stage_latency_seconds")
        assert hist.count(stage="entropy_decode") >= 1

    def test_error_annotation_and_dump(self, tmp_path):
        configure_tracing(enabled=True)
        try:
            tracer().reset()
            with pytest.raises(RuntimeError):
                with span("tail"):
                    raise RuntimeError("boom")
            path = tmp_path / "events.json"
            n = tracer().dump_events(str(path))
        finally:
            configure_tracing(enabled=False)
        assert n == 1
        events = json.loads(path.read_text())["events"]
        assert events[0]["error"] == "RuntimeError"


class TestCoderAndResultSpans:
    """The entropy coder each ``entropy_encode`` span's elements took, and
    the cloud's ``result_send`` span with its frame count."""

    @staticmethod
    def _spans(fn):
        configure_tracing(enabled=True)
        try:
            tracer().reset()
            out = fn()
            return out, tracer().snapshot_events()
        finally:
            configure_tracing(enabled=False)

    @pytest.mark.parametrize("n,coder", [(8192, "serial"),
                                         (1 << 16, "rans")])
    def test_entropy_encode_names_its_coder(self, features, n, coder):
        codec = _live_codec(features)
        x = np.resize(features, (n // 16, 16))
        _, events = self._spans(lambda: (
            codec.encode(x), list(codec.encode_stream(x,
                                                      chunk_elems=1 << 16))))
        enc = [e for e in events if e["stage"] == "entropy_encode"]
        assert len(enc) == 2        # the one-shot and the streamed encode
        for e in enc:
            assert (e["coder"], e["n_elems"]) == (coder, n)
            assert e["rans_elems"] == (n if coder == "rans" else 0)

    def test_mixed_batch_counts_its_rans_elements(self, features):
        # per-tensor: chunk boundaries fall exactly every 2^16 elements
        codec = calibrate(CodecConfig(n_levels=4, clip_mode="minmax",
                                      constrain_cmin_zero=False),
                          samples=features)
        x = np.resize(features, ((1 << 16) // 16 + 512, 16))
        _, events = self._spans(
            lambda: list(codec.encode_stream(x, chunk_elems=1 << 16)))
        (e,) = [e for e in events if e["stage"] == "entropy_encode"]
        assert (e["coder"], e["n_elems"], e["rans_elems"]) == (
            "mixed", x.size, 1 << 16)

    def test_result_send_counts_its_frames(self, features, monkeypatch):
        from repro.transport import framing
        monkeypatch.setattr(framing, "MAX_PAYLOAD", 4096)
        codec = _live_codec(features)

        async def run():
            async with CloudServer(echo_features=True) as srv:
                async with EdgeClient("127.0.0.1", srv.port,
                                      codec=codec) as client:
                    await asyncio.wait_for(client.submit(features), 20)
                return srv.metrics.get(
                    "repro_server_result_frames_total").value()

        frames, events = self._spans(lambda: asyncio.run(run()))
        (send,) = [e for e in events if e["stage"] == "result_send"]
        assert send["frames"] == frames == -(-send["bytes"] // 4096)
        assert send["frames"] > 1
        assert send["bytes"] > features.size * 4


class TestExposition:
    def test_scrape_routes(self):
        reg = MetricsRegistry()
        reg.counter("repro_t_hits_total", "hits").inc(2)
        pulled = []

        async def run():
            exp = MetricsExposition([reg],
                                    collectors=[lambda: pulled.append(1)])
            await exp.start()
            url = f"http://127.0.0.1:{exp.port}"
            try:
                def get(path):
                    with urllib.request.urlopen(url + path,
                                                timeout=5) as r:
                        return r.status, r.read().decode()
                out = {p: await asyncio.to_thread(get, p)
                       for p in ("/metrics", "/events", "/healthz")}
                with pytest.raises(urllib.error.HTTPError):
                    await asyncio.to_thread(get, "/nope")
            finally:
                await exp.close()
            return out

        out = asyncio.run(run())
        fams = parse_prometheus_text(out["/metrics"][1])
        assert fams["repro_t_hits_total"]["samples"][
            ("repro_t_hits_total", frozenset())] == 2.0
        assert pulled                       # collector ran before render
        assert "events" in json.loads(out["/events"][1])
        assert out["/healthz"] == (200, "ok\n")


class TestServerTelemetry:
    def test_metrics_port_scrape_and_ft_metrics(self, features):
        codec = _live_codec(features)
        tick = TickConfig(max_wait_s=0.01)

        async def run():
            async with CloudServer(echo_features=True, tick=tick,
                                   metrics_port=0) as srv:
                async with EdgeClient("127.0.0.1", srv.port,
                                      codec=codec) as client:
                    await client.submit(features)
                    snap = await client.fetch_cloud_metrics()
                url = f"http://127.0.0.1:{srv.metrics_port}/metrics"
                text = await asyncio.to_thread(
                    lambda: urllib.request.urlopen(url, timeout=5)
                    .read().decode())
            return snap, text

        snap, text = asyncio.run(run())
        assert snap["counters"]["sessions_served"] == 1
        assert _series(snap["metrics"],
                       "repro_server_ticks_total")["value"] >= 1
        fams = parse_prometheus_text(text)
        for name in ("repro_server_sessions_served_total",
                     "repro_server_ticks_total",
                     "repro_server_coded_bytes_total",
                     "repro_server_measured_bpe",
                     "repro_server_header_cache_hits_count",
                     "repro_decode_entropy_calls_total",
                     "repro_bank_cache_hits_total"):
            assert name in fams, name
        served = fams["repro_server_sessions_served_total"]["samples"]
        assert served[("repro_server_sessions_served_total",
                       frozenset())] == 1.0

    def test_legacy_tick_none_registry_counts_errors(self, features):
        """A protocol error is counted in the registry and in the one
        ``counters`` shape (the server has one receive discipline)."""
        codec = _live_codec(features)

        async def run():
            async with CloudServer(echo_features=True) as srv:
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600) as client:
                    await client.submit(features)
                # a second connection sends garbage: CHUNK before HEADER
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                from repro.transport.framing import FT_CHUNK
                writer.write(encode_frame(FT_CHUNK, 9, 0, b"\x00\x01"))
                await writer.drain()
                await reader.read()         # server replies ERROR+closes
                writer.close()
                for _ in range(50):         # until the close is observed
                    if srv.open_connections == 0:
                        break
                    await asyncio.sleep(0.05)
                return srv.counters, srv.metrics.snapshot()

        counters, snap = asyncio.run(run())
        # the dict shape is pinned: registry-only telemetry must not
        # leak new keys into it
        assert set(counters) == {
            "sessions_served", "open_connections", "ticks",
            "batch_occupancy_avg", "queue_depth", "entropy_calls",
            "entropy_chunks", "entropy_melem_per_s", "bpe_avg",
            "decode_errors", "header_cache", "shed_sessions",
            "resumed_sessions", "duplicate_frames"}
        assert counters["sessions_served"] == 1
        assert counters["decode_errors"] == 1
        assert _series(snap,
                       "repro_server_sessions_served_total")["value"] == 1
        assert _series(snap,
                       "repro_server_decode_errors_total")["value"] == 1

    def test_eviction_clears_per_session_series(self, features):
        codec = _live_codec(features)
        tick = TickConfig(max_wait_s=0.05, max_chunks=1 << 30)

        async def run():
            async with CloudServer(echo_features=True, tick=tick) as srv:
                # half a stream, then vanish mid-tick
                frames = list(tensor_to_frames(codec, features, session=0,
                                               chunk_elems=600))
                _, writer = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                for fb in frames[:max(2, len(frames) // 2)]:
                    writer.write(fb)
                await writer.drain()
                await asyncio.sleep(0.01)
                pending_mid = len(srv.metrics.get(
                    "repro_server_session_pending_chunks_count").series())
                writer.close()
                await writer.wait_closed()
                # a healthy session completes alongside
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600) as client:
                    await client.submit(0.5 * features)
                await asyncio.sleep(0.2)
                srv._sync_gauges()
                return pending_mid, srv.metrics.snapshot()

        pending_mid, snap = asyncio.run(run())
        assert pending_mid == 1             # tracked while in flight
        # disconnect + completion both drop their label series: nothing
        # leaks across sessions
        assert snap["repro_server_session_pending_chunks_count"][
            "series"] == []
        assert _series(snap,
                       "repro_server_queue_depth_count")["value"] == 0
        assert _series(snap,
                       "repro_server_sessions_served_total")["value"] == 1

    def test_kill_paths_clear_per_session_series(self, features):
        """PR-9 kill paths: a mid-stream disconnect of a *resumable*
        (token'd) connection parks its sessions -- and the parked TTL
        expiry must release the pending-chunks series and the inflight
        accounting exactly like a plain disconnect does."""
        import json

        from repro.transport.framing import FT_HELLO
        codec = _live_codec(features)
        tick = TickConfig(max_wait_s=0.05, max_chunks=1 << 30)

        async def run():
            async with CloudServer(echo_features=True, tick=tick,
                                   resume_ttl_s=0.1) as srv:
                frames = list(tensor_to_frames(codec, features, session=1,
                                               chunk_elems=600))
                _, writer = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                writer.write(encode_frame(
                    FT_HELLO, 0, 0,
                    json.dumps({"token": "obs-kill"}).encode()))
                for fb in frames[:max(2, len(frames) // 2)]:
                    writer.write(fb)
                await writer.drain()
                await asyncio.sleep(0.02)
                pending_mid = len(srv.metrics.get(
                    "repro_server_session_pending_chunks_count").series())
                writer.close()                 # vanish mid-tick
                await writer.wait_closed()
                await asyncio.sleep(0.05)
                srv._sync_gauges()
                parked_mid = srv.metrics.get(
                    "repro_server_parked_sessions_count").value()
                await asyncio.sleep(0.25)      # resume TTL expires
                srv._sync_gauges()
                return pending_mid, parked_mid, srv.metrics.snapshot(), \
                    srv.load

        pending_mid, parked_mid, snap, load = asyncio.run(run())
        assert pending_mid == 1
        assert parked_mid == 1
        assert snap["repro_server_session_pending_chunks_count"][
            "series"] == []
        assert _series(snap,
                       "repro_server_parked_sessions_count")["value"] == 0
        assert _series(snap,
                       "repro_server_queue_depth_count")["value"] == 0
        assert load == 0

    def test_ft_metrics_frame_raw(self, features):
        # protocol level: an empty METRICS frame gets a JSON METRICS
        # frame back, no client machinery required
        codec = _live_codec(features)

        async def run():
            async with CloudServer(echo_features=True) as srv:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                writer.write(encode_frame(FT_METRICS, 0, 0, b""))
                await writer.drain()
                from repro.transport import FrameReader
                frames = FrameReader()
                while True:
                    data = await asyncio.wait_for(reader.read(1 << 16),
                                                  timeout=10)
                    frames.feed(data)
                    for frame in frames:
                        writer.close()
                        return frame

        frame = asyncio.run(run())
        assert frame.ftype == FT_METRICS
        snap = json.loads(frame.payload.decode())
        assert "counters" in snap and "metrics" in snap


class TestClientTelemetry:
    def test_encode_counters_backed_by_registry(self, features):
        codec = _live_codec(features)
        tick = TickConfig(max_wait_s=0.01, max_batch=8)

        async def run():
            async with CloudServer(echo_features=True) as srv:
                async with EdgeClient("127.0.0.1", srv.port, codec=codec,
                                      chunk_elems=600,
                                      tick=tick) as client:
                    await asyncio.gather(*[
                        client.submit(t)
                        for t in (features, 0.5 * features)])
                    return dict(client.encode_counters), \
                        client.metrics.snapshot()

        counters, snap = asyncio.run(run())
        assert set(counters) == {"ticks", "sessions", "stacked_sessions",
                                 "fused_launches", "entropy_calls",
                                 "elems", "coded_bytes", "encode_s"}
        assert counters["sessions"] == 2
        assert _series(snap, "repro_client_sessions_total")["value"] == 2
        assert _series(snap,
                       "repro_client_submit_latency_seconds")["count"] == 2


def _tiny_cfg():
    from repro.configs import ARCHS, reduced
    return dataclasses.replace(reduced(ARCHS["codeqwen1.5-7b"]),
                               vocab_size=128, d_model=32, d_ff=64,
                               num_heads=2, num_kv_heads=2, head_dim=16)


class TestEngineTelemetry:
    def test_ttft_stamped_on_epoch_and_refill_paths(self):
        import jax

        from repro.models import init_params
        from repro.serving import Request, ServeEngine
        cfg = _tiny_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, slots=2, max_seq=64)
        rng = np.random.default_rng(0)
        reqs = [Request(prompt=rng.integers(0, 128, size=4)
                        .astype(np.int32), max_new_tokens=m)
                for m in (2, 5, 3, 2, 4)]
        eng.generate(reqs)
        assert all(r.done for r in reqs)
        c = eng.counters
        assert c["epochs"] >= 1 and c["refills"] >= 1
        assert c["requests_done"] == 5
        assert not any("latency" in k for k in c)
        epoch_admit = reqs[0].t_admit
        refilled = [r for r in reqs if r.t_admit != epoch_admit]
        assert refilled, "no request came in through a refill"
        for r in reqs:
            assert r.t_admit <= r.t_first <= r.t_done
            assert r.ttft_s == r.t_first - r.t_admit
        snap = eng.metrics.snapshot()
        assert _series(snap, "repro_engine_requests_total")["value"] == 5
        assert _series(snap, "repro_engine_ttft_seconds")["count"] == 5
        assert not any("request_latency" in name for name in snap)


@pytest.fixture(scope="module")
def loopback_cloud():
    """``serve.py``'s loopback transport (edge client, socket, cloud
    worker process).  The child traces its own spans
    (``REPRO_OBS_TRACE=1``) and serves them at ``/events``."""
    from repro.launch.serve import _loopback_codec_fn
    rng = np.random.default_rng(3)
    codec = calibrate(CodecConfig(n_levels=4, clip_mode="minmax",
                                  constrain_cmin_zero=False),
                      samples=rng.normal(0, 1, 4096).astype(np.float32))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_OBS_TRACE", "1")
        host_fn, cleanup = _loopback_codec_fn(codec, 1 << 16,
                                              metrics_port=0)
    yield host_fn.__self__
    cleanup()


def _cloud_events(cloud) -> list[dict]:
    url = f"http://127.0.0.1:{cloud.metrics_port}/events"
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())["events"]


@pytest.fixture(scope="module")
def loopback_engine(loopback_cloud):
    """A smoke-width split engine whose boundary round trip runs through
    ``loopback_cloud``."""
    import jax

    from repro.models import init_params
    from repro.serving import ServeEngine
    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, slots=2, max_seq=32,
                       codec_host_fn=loopback_cloud.roundtrip)


def _serve(eng, n_new=4):
    from repro.serving import Request
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=rng.integers(0, 128, 6).astype(np.int32),
                    max_new_tokens=n_new) for _ in range(2)]
    eng.generate(reqs)
    assert all(r.done for r in reqs)
    return reqs


def _traced(eng, n_new=4, jax_trace=False):
    _serve(eng, n_new)                  # compile outside the traced run
    steps0 = eng.counters["steps"]
    configure_tracing(enabled=True, jax_trace=jax_trace)
    try:
        tracer().reset()
        _serve(eng, n_new)
        return tracer().snapshot_events(), eng.counters["steps"] - steps0
    finally:
        configure_tracing(enabled=False, jax_trace=False)


class TestServedStepTracing:
    STAGES = ("split_head", "split_roundtrip", "split_tail")

    def test_stage_spans_of_each_decode_step(self, loopback_engine,
                                             loopback_cloud):
        events, steps = _traced(loopback_engine)
        assert steps == 3
        by_id = {e["span_id"]: e for e in events}
        dec = [e for e in events if e.get("kind") == "decode"]
        for stage in self.STAGES + ("token_read",):
            assert sum(e["stage"] == stage for e in dec) == steps, stage
        # the three stages of one crossing share its number, in order
        for n in {e["crossing"] for e in dec if "crossing" in e}:
            trio = sorted((e for e in events if e.get("crossing") == n),
                          key=lambda e: e["t_start"])
            assert [e["stage"] for e in trio] == list(self.STAGES)
            assert len({e["kind"] for e in trio}) == 1
        # the edge client's spans parent under the engine's round trip
        # and carry the session id the cloud's spans carry; the cloud's
        # spans of this run are those its process opened after this
        # run's first edge span (both stamp ``time.time()``)
        rts = {e["span_id"] for e in events
               if e["stage"] == "split_roundtrip"}
        edge = [e for e in events
                if e["stage"] in ("edge_send", "result_wait")]
        assert len(edge) == 2 * len(rts)
        assert {e["parent_id"] for e in edge} == rts
        sessions = {e["session"] for e in edge}
        t_run = min(e["t_start"] for e in edge)
        cloud = {e["session"].split(":")[1]
                 for e in _cloud_events(loopback_cloud)
                 if e["stage"] == "socket_write" and ":" in e["session"]
                 and e["t_start"] >= t_run}
        assert sessions == cloud and len(sessions) == len(rts)
        for e in edge:
            rt = by_id[e["parent_id"]]
            assert rt["t_start"] <= e["t_start"]
            assert e["t_start"] + e["dur_s"] <= rt["t_start"] + rt["dur_s"]

    def test_stage_spans_cover_the_decode_steps(self, loopback_engine):
        """From each decode step's head to the read of its tokens, the
        stage spans leave under a tenth of the wall time untraced."""
        events, _ = _traced(loopback_engine)
        dec = sorted((e for e in events if e.get("kind") == "decode"
                      and e["stage"] in self.STAGES + ("token_read",)),
                     key=lambda e: e["t_start"])
        wall = covered = 0.0
        for i in range(0, len(dec), 4):
            step = dec[i:i + 4]
            assert [e["stage"] for e in step] == list(self.STAGES) \
                + ["token_read"]
            end = step[-1]["t_start"] + step[-1]["dur_s"]
            wall += end - step[0]["t_start"]
            covered += sum(e["dur_s"] for e in step)
        assert covered >= 0.9 * wall, (covered, wall)

    def test_disabled_makes_no_span_and_no_annotation(self, loopback_engine,
                                                      monkeypatch):
        import jax.profiler

        from repro.obs import tracing
        _serve(loopback_engine)
        made = []
        monkeypatch.setattr(tracing, "Span",
                            lambda *a, **k: made.append(a))
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            lambda *a, **k: made.append(a))
        configure_tracing(enabled=False, jax_trace=False)
        _serve(loopback_engine)
        assert made == []

    def test_spans_are_profiler_annotations(self, loopback_engine, tmp_path):
        import glob

        import jax
        _serve(loopback_engine)
        jax.profiler.start_trace(str(tmp_path))
        try:
            events, _ = _traced(loopback_engine, jax_trace=True)
        finally:
            jax.profiler.stop_trace()
        assert events
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        names = {e.name for plane in pd.planes
                 if plane.name.startswith("/host:CPU")
                 for line in plane.lines for e in line.events}
        for name in ("repro.split_roundtrip", "repro.encode_fused",
                     "repro.edge_send", "repro.token_read"):
            assert name in names, name

    @pytest.mark.parametrize("attr,program", [
        ("_prefill_pre", "jit_prefill_to_boundary"),
        ("_prefill_post", "jit_prefill_from_boundary"),
        ("_decode_pre", "jit_decode_to_boundary"),
        ("_decode_post", "jit_decode_from_boundary"),
        ("_prefill", "jit_prefill"),
        ("_decode", "jit_decode_step"),
    ])
    def test_programs_are_named(self, loopback_engine, attr, program):
        import jax
        import jax.numpy as jnp

        from repro.models import init_cache
        from repro.serving import ServeEngine
        eng = loopback_engine
        if attr in ("_prefill", "_decode"):
            eng = ServeEngine(eng.cfg, eng.params, slots=2, max_seq=32)
        cache = init_cache(eng.cfg, batch=2, max_seq=32,
                           split=eng.codec_host_fn is not None)
        toks, pos = jnp.zeros((2, 6), jnp.int32), jnp.int32(6)
        cur = jnp.zeros((2,), jnp.int32)
        if attr == "_prefill_pre" or attr == "_prefill":
            args = (eng.params, toks, cache)
        elif attr == "_decode_pre" or attr == "_decode":
            args = (eng.params, cur, cache, pos)
        elif attr == "_prefill_post":
            x, _ = jax.eval_shape(eng._prefill_pre, eng.params, toks, cache)
            args = (eng.params, x, cache)
        else:
            x, _ = jax.eval_shape(eng._decode_pre, eng.params, cur, cache,
                                  pos)
            args = (eng.params, x, cache, pos)
        text = getattr(eng, attr).lower(*args).compile().as_text()
        assert text.startswith(f"HloModule {program},"), text[:80]

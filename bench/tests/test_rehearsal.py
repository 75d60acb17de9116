"""CPU rehearsal: a cell through the harness's own code at smoke width,
with the platform check steered to the CPU from here."""

import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchkit import cell, registry
from conftest import smoke_spec

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def quiet(_msg):
    pass


def test_result_line_has_exactly_the_result_keys(bench):
    spec = smoke_spec("codeqwen7b.prefill", bench,
                      prompt_buckets=[16, 32, 64])
    logged = []
    res = cell.run("codeqwen7b.prefill", 2**32 + 5, 3.0, False,
                   platform="cpu", bench=bench, spec=spec,
                   log=logged.append)
    assert "compiles_in_window: 0" in logged
    buf = io.StringIO()
    with redirect_stdout(buf):
        cell.emit(res)
    line = json.loads(buf.getvalue().splitlines()[-1])
    assert list(line) == KEYS
    assert set(line["metrics"]) == {"ttft_p90_ms", "output_tokens_per_s",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line["checks"]) == ["boundary_err", "logit_gap",
                                    "failed_requests"]
    assert line["correct"] is True


def test_no_result_without_the_platform(bench):
    spec = smoke_spec("codeqwen7b.prefill", bench)
    with pytest.raises(SystemExit):
        cell.run("codeqwen7b.prefill", 1, 1.0, False, platform="tpu",
                 bench=bench, spec=spec, log=quiet)


def test_window_closes_after_the_wave_in_flight(bench, monkeypatch):
    """No wave is handed out past the deadline; the one in flight runs to
    its end, and the rate counts all of its tokens over all the time."""
    from benchkit import correct
    spec = smoke_spec("codeqwen7b.decode", bench, prompt_buckets=[32, 64],
                      output_buckets=[4, 8, 16, 32])
    seen = {}
    check = correct.check

    def spy(conf, wl, weights, records, crossings, seed, **kw):
        seen["records"] = records
        return check(conf, wl, weights, records, crossings, seed, **kw)

    monkeypatch.setattr(correct, "check", spy)
    logged = []
    seconds = 2.0
    res = cell.run("codeqwen7b.decode", 11, seconds, False, platform="cpu",
                   bench=bench, spec=spec, log=logged.append)
    recs = seen["records"]
    t0 = min(r["t_wave"] for r in recs)
    assert max(r["t_wave"] for r in recs) < t0 + seconds
    for r in recs:
        assert r["done"] and len(r["tokens"]) == r["max_new"]
        assert len(r["stamps"]) == len(r["tokens"])
    t_end = max(r["stamps"][-1] for r in recs)
    n_tok = sum(len(r["stamps"]) for r in recs)
    rate = res["metrics"]["output_tokens_per_s"]["value"]
    # the window runs from the first hand-off to the end of the last wave
    assert n_tok / rate >= t_end - t0
    assert n_tok / rate == pytest.approx(t_end - t0, abs=0.5)


def test_percentiles_over_every_sample():
    rng = np.random.default_rng(0)
    reqs = []
    for w in range(7):
        t_wave = 10.0 * w
        for _ in range(4):
            n = int(rng.integers(0, 9))
            stamps = list(t_wave + np.cumsum(rng.uniform(0.01, 0.5, n)))
            reqs.append({"stamps": stamps, "t_wave": t_wave})
    run = {"requests": reqs, "window_s": 70.0}
    ttft = [r["stamps"][0] - r["t_wave"] for r in reqs if r["stamps"]]
    gaps = [b - a for r in reqs
            for a, b in zip(r["stamps"], r["stamps"][1:])]
    assert registry.end_to_end("ttft_p90_ms", run, 0.0) == \
        pytest.approx(1e3 * np.percentile(ttft, 90))
    assert registry.end_to_end("itl_p95_ms", run, 0.0) == \
        pytest.approx(1e3 * np.percentile(gaps, 95))
    assert registry.end_to_end("output_tokens_per_s", run, 0.0) == \
        pytest.approx(sum(len(r["stamps"]) for r in reqs) / 70.0)
    assert registry.end_to_end("setup_s", run, 12.5) == 12.5
    with pytest.raises(ValueError):
        registry.end_to_end("ttft_mean_ms", run, 0.0)

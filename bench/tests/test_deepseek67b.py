"""The ``deepseek67b.decode`` cell: its check at smoke width on the CPU,
the configuration's size by hand, and the ``rans_elem_share.decode``
reader on recorded spans."""

import pytest

from benchkit import cell, registry, work
from conftest import smoke_spec
from test_correctness import FAULTS

CELL = "deepseek67b.decode"
SMOKE = dict(prompt_buckets=[32, 64], output_buckets=[4, 8, 16, 32])
# smoke width: a test's own limits between the program's and the
# control's readings there (seeds 21-23: program <= 0.0206 and 0,
# control >= 0.284 and >= 0.79); the cell's limits come from chip runs
# (PERF.md)
LIMITS = {"boundary_err": 0.08, "logit_gap": 0.2}


def run(bench, seed=21, control=False):
    spec = smoke_spec(CELL, bench, **SMOKE)
    spec["workload"]["limits"] = LIMITS
    return cell.run(CELL, seed, 3.0, False, platform="cpu", bench=bench,
                    spec=spec, control=control, log=lambda m: None)


def test_program_passes_and_control_fails(bench):
    res = run(bench, control=True)
    c = res["checks"]
    assert res["correct"] is True
    for k, limit in LIMITS.items():
        assert c[k]["value"] < limit
    assert any(c[f"control_{k}"]["value"] > max(limit, 3 * c[k]["value"])
               for k, limit in LIMITS.items())


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_fails_the_check(bench, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    res = run(bench)
    assert res["correct"] is False
    assert any(res["checks"][k]["value"] > limit
               for k, limit in LIMITS.items())


def test_layer_params_by_hand():
    d = registry.config("deepseek-llm-67b")
    # q, o: 8192 x 64 x 128; k, v: 8192 x 8 x 128; MLP: 3 x 8192 x 22016
    assert work.layer_params(d) == (2 * 67_108_864 + 2 * 8_388_608
                                    + 541_065_216) == 692_060_160
    # 95 published layers and the untied embedding and head give the
    # model card's 67.4 B parameters (norms aside)
    total = 95 * work.layer_params(d) + 2 * 102_400 * 8192
    assert total == 67_423_436_800
    assert round(total / 1e9, 1) == 67.4


def span(t, n, rans, coder=None, stage="entropy_encode"):
    e = {"stage": stage, "t_start": t, "dur_s": 0.001, "n_elems": n,
         "rans_elems": rans}
    if coder is not None:
        e["coder"] = coder
    return e


def run_of(spans):
    return {"crossings": [
        {"kind": "prefill", "wall0": 10.0, "wall1": 12.0},
        {"kind": "decode", "wall0": 13.0, "wall1": 13.1},
        {"kind": "decode", "wall0": 13.2, "wall1": 13.3}],
        "spans": list(spans)}


def read(run):
    return registry.metric_reader("rans_elem_share.decode").read(run)


def test_rans_share_of_decode_elements():
    run = run_of([
        span(13.01, 32_768, 0, "serial"),
        span(13.21, 98_304, 65_536, "mixed"),
        # outside every decode crossing, or not the client's encode
        span(10.5, 1 << 24, 1 << 24, "rans"),
        span(13.15, 32_768, 32_768, "rans"),
        span(13.05, 32_768, 32_768, "rans", stage="entropy_decode"),
    ])
    assert read(run) == pytest.approx(100.0 * 65_536 / 131_072)


def test_rans_share_left_out_without_coder_spans():
    # a program whose entropy_encode spans name no coder
    assert read(run_of([span(13.01, 32_768, 0)])) is None
    assert read(run_of([])) is None

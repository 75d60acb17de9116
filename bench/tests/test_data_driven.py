"""The harness finds cells, traffic and metrics by name, and every name
and unit in BENCHMARK.json keeps to the benchmark format's alphabet."""

import json
import re

import pytest

from benchkit import registry

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_names_and_units(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.fullmatch(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert len(set(names[:len(bench["end_to_end"])
                         + len(bench["per_layer"])])) == \
        len(bench["end_to_end"]) + len(bench["per_layer"])


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        spec = registry.cell(w["name"], bench)
        assert spec["conf"]["name"] == w["config"]
        gen = registry.traffic_generator(spec["mix"]["generator"])
        waves = gen.waves(spec["mix"], 2**33 + 7, spec["conf"]["vocab_size"])
        wave = next(waves)
        assert len(wave) == spec["mix"]["slots"]
        need = max(len(r.prompt) + r.max_new_tokens
                   for r in wave)
        assert need <= spec["workload"]["max_seq"]


def test_every_per_layer_metric_has_its_reader(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        mod = registry.metric_reader(m["name"])
        assert mod.UNIT == m["unit"], m["name"]
        assert mod.SOURCE == m["source"], m["name"]
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            # the cell reports the end-to-end metric this one moves
            moved = next(x for x in bench["end_to_end"]
                         if x["name"] == m["moves"])
            assert registry.applies(moved, cell), (m["name"], cell)


def test_every_cell_reports_setup_and_more(bench):
    for w in bench["workloads"]:
        e2e = [m for m in bench["end_to_end"]
               if registry.applies(m, w["name"])]
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert any(registry.applies(m, w["name"])
                   for m in bench["per_layer"])


def test_new_metric_file_is_found_by_name(bench, tmp_path, monkeypatch):
    """A reader dropped into a metrics directory is read with no edit to
    the harness; one that finds nothing is left out of the line."""
    mdir = tmp_path / "metrics"
    mdir.mkdir()
    (mdir / "probe_ms.new.py").write_text(
        'UNIT = "ms"\nSOURCE = "host_clock"\n\n\n'
        'def read(run):\n    return 2.0 * len(run["crossings"])\n')
    (mdir / "silent.py").write_text(
        'UNIT = "%"\nSOURCE = "device_trace"\n\n\n'
        'def read(run):\n    return None\n')
    monkeypatch.setattr(registry, "BENCH", tmp_path)
    b = dict(bench, per_layer=[
        {"name": "probe_ms.new", "unit": "ms", "workloads": ["x"]},
        {"name": "silent", "unit": "%"}])
    got = registry.metrics_for("x", b, True, {"crossings": [1, 2, 3]},
                               setup_s=0.0)
    assert got == {"probe_ms.new": {"value": 6.0, "unit": "ms"}}


def test_new_workload_file_is_found_by_name(bench, tmp_path, monkeypatch):
    for sub in ("workloads", "configs", "traffic"):
        (tmp_path / sub).mkdir()
    (tmp_path / "workloads" / "m.new.json").write_text(json.dumps(
        {"config": "c", "traffic": "t", "max_seq": 64, "check_waves": 1,
         "limits": {"boundary_err": 0.05, "logit_gap": 0.5}}))
    (tmp_path / "configs" / "c.json").write_text('{"name": "c"}')
    (tmp_path / "traffic" / "t.json").write_text('{"generator": "waves"}')
    monkeypatch.setattr(registry, "BENCH", tmp_path)
    b = dict(bench, workloads=[{"name": "m.new", "config": "c",
                                "traffic": "t", "chips": 1}])
    spec = registry.cell("m.new", b)
    assert spec["conf"] == {"name": "c"} and spec["chips"] == 1
    with pytest.raises(SystemExit):
        registry.cell("absent", b)

"""Find a cell's files, its traffic generator and its metric readers by
name, as ``BENCHMARK.json`` lists them.  Nothing here names a cell.

- ``bench/workloads/<cell>.json``: the cell's configuration and traffic
  names, ``max_seq``, the check's sample size and limit;
- ``bench/configs/<config>.json``: the configuration as it is run;
- ``bench/traffic/<traffic>.json``: a traffic mix, read by
  ``bench/traffic/<generator>.py``;
- ``bench/metrics/<metric>.py``: a per-layer metric's reader, with
  ``UNIT``, ``SOURCE`` and ``read(run) -> float | None``; ``bench/`` is
  on the path, so a reader may import ``benchkit``.

End-to-end metrics are the host clock's and are computed here, by name:
``setup_s``, ``output_tokens_per_s``, ``ttft_p<q>_ms`` and
``itl_p<q>_ms``.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def config(name: str) -> dict:
    """``bench/configs/<name>.json``: the configuration as it is run."""
    return _json(BENCH / "configs" / f"{name}.json")


def cell(name: str, bench: dict) -> dict:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    wl = _json(BENCH / "workloads" / f"{name}.json")
    if (wl["config"], wl["traffic"]) != (entry["config"], entry["traffic"]):
        raise SystemExit(f"bench: {name}: workload file and BENCHMARK.json "
                         "disagree on config or traffic")
    return {"entry": entry, "workload": wl, "chips": entry["chips"],
            "conf": config(wl["config"]),
            "mix": _json(BENCH / "traffic" / f"{wl['traffic']}.json")}


def _module(path: Path):
    name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(BENCH)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def traffic_generator(kind: str):
    return _module(BENCH / "traffic" / f"{kind}.py")


def metric_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py")


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


# -- end-to-end metrics (host clock) ------------------------------------------

def percentile(values, q: float) -> float | None:
    """``q``-th percentile (linear interpolation) over every sample."""
    return float(np.percentile(values, q)) if len(values) else None


def ttfts(run: dict) -> list[float]:
    """First token minus the wave's hand-off, for every request whose
    first token was stamped in the window (seconds)."""
    return [r["stamps"][0] - r["t_wave"] for r in run["requests"]
            if r["stamps"]]


def itls(run: dict) -> list[float]:
    """Every gap between consecutive tokens of one request (seconds)."""
    return [b - a for r in run["requests"]
            for a, b in zip(r["stamps"][:-1], r["stamps"][1:])]


def end_to_end(name: str, run: dict, setup_s: float) -> float | None:
    if name == "setup_s":
        return setup_s
    if name == "output_tokens_per_s":
        return sum(len(r["stamps"]) for r in run["requests"]) \
            / run["window_s"]
    m = re.fullmatch(r"(ttft|itl)_p(\d+)_ms", name)
    if m:
        vals = ttfts(run) if m.group(1) == "ttft" else itls(run)
        p = percentile(vals, float(m.group(2)))
        return None if p is None else 1e3 * p
    raise ValueError(f"no end-to-end metric {name!r} in the harness")


def metrics_for(cell_name: str, bench: dict, trace: bool, run: dict, *,
                setup_s: float) -> dict:
    """The cell's end-to-end metrics (``trace`` off) or per-layer ones."""
    out = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if not applies(m, cell_name):
            continue
        if trace:
            value = metric_reader(m["name"]).read(run)
        else:
            value = end_to_end(m["name"], run, setup_s)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out

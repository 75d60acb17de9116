"""Readings that set a cell's limits: the check's numbers for the
program and for the float8 control, over several seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Each seed is a whole run (set-up, a window of ``--seconds``, the check)
with the control read beside the program.  One JSON line per seed, then
the program's largest reading and the control's smallest, per number.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from benchkit import cell

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    from benchkit.correct import NUMBERS
    prog, ctl = {k: [] for k in NUMBERS}, {k: [] for k in NUMBERS}
    for seed in args.seeds:
        res = cell.run(args.workload, seed, args.seconds, False,
                       t_start=time.perf_counter(), control=True, log=log)
        c = res["checks"]
        for k in NUMBERS:
            prog[k].append(c[k]["value"])
            ctl[k].append(c.get(f"control_{k}", {}).get("value"))
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program": {k: c[k]["value"] for k in NUMBERS},
            "control": {k: ctl[k][-1] for k in NUMBERS},
            # as for the program: correct only with every number
            # within its limit
            "control_correct": all(
                v is not None and v <= c[k]["limit"]
                for k, v in ((k, ctl[k][-1]) for k in NUMBERS)),
            "limits": {k: c[k]["limit"] for k in NUMBERS},
            "attempted": res["attempted"], "metrics": res["metrics"]}),
            flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_max": {k: max(v for v in prog[k]
                                             if v is not None)
                                      for k in NUMBERS if any(prog[k])},
                      "control_min": {k: min(v for v in ctl[k]
                                             if v is not None)
                                      for k in NUMBERS if any(ctl[k])}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark entry: one run of one cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Set-up (weights from the seed, codec
calibration, the loopback cloud server, one warm-up wave per prompt
bucket) is timed as ``setup_s``; then waves are handed out for
``--seconds``, the last one runs to its end, and the served tokens are
checked against the plain reference.  The last
line of standard output is the result as one JSON object; the checks'
numbers and limits are the last lines of standard error.  Without the
chips the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench: no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))

    from benchkit import cell

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = cell.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, log=log)
    cell.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

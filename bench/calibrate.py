"""Readings for the limits of ``correct``: the program's and the control's.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3

Serves the cell's traffic for a short window at the cell's own load on
each seed, all in one process, and prints one JSON line per seed with the
compared numbers of the program and of each control (``check.CONTROLS``:
the reference with one stage one precision below the configuration's),
and the verdict that the cell's limits give each, as a run would. The
limit of each number is set above the largest program reading and below
the smallest control reading, as ``PERF.md`` records.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import check, spec  # noqa: E402
from bench import run as runmod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--ramp", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cell = spec.Cell(**{**vars(cell), "mix": spec.merge(
        cell.mix, {"ramp_s": args.ramp})})
    for seed in args.seeds:
        out: dict = {}
        try:
            res = runmod.run_cell(cell, seed, args.seconds, False, out=out,
                                  controls=tuple(check.CONTROLS))
        except runmod.NoAccelerator as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        nums = out["numbers"]
        limits = cell.config["correct"]["limits"]
        controls = {name: {**c, "correct": check.verdict(c, limits)[0]}
                    for name, c in nums.pop("controls").items()}
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "failed": res["failed"],
                          "attempted": res["attempted"], **nums,
                          "controls": controls}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings for the limits of a cell's comparison, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3

For each seed the cell is set up at its own size, driven through enough
steps to reach every sampled round, and compared with the plain reference:
once as the program ran (the lower reading of each number is the largest of
these), and, for the first ``--control-seeds`` seeds, with the reference in
bfloat16 put in the program's place (the upper reading is the smallest of
these).  Each seed prints one JSON line; the last line holds both readings
of every number.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed0", type=int, default=3 * 2 ** 31 + 101)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--steps", type=int, default=64)
    args = ap.parse_args(argv)
    import ml_dtypes

    bench = harness.load_json(harness.REPO / "BENCHMARK.json")
    cell = harness.resolve(bench, args.workload)
    harness.find_devices(cell.chips)
    harness.use_compile_cache()
    harness.import_program()
    lower, upper = {}, {}
    for i in range(args.seeds):
        seed = args.seed0 + 7919 * i
        mode = harness.load_module("modes", cell.traffic["mode"]).Mode(
            cell, seed, harness.Spans())
        for _ in range(args.steps if cell.traffic["mode"] == "online"
                       else 1):
            mode.step()
        line = {"seed": seed,
                "program": {c.name: c.value for c in mode.check()}}
        if i < args.control_seeds:
            line["control"] = {c.name: c.value
                               for c in mode.check(ml_dtypes.bfloat16)}
        for k, v in line["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in line.get("control", {}).items():
            upper[k] = min(upper.get(k, float("inf")), v)
        print(json.dumps(line), flush=True)
        del mode
        gc.collect()
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

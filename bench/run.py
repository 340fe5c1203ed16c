"""The benchmark's entry point.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on: sets the cell up from its files and the seed, warms up every
shape it uses, measures for ``--seconds`` (with ``--trace 1`` a shorter
traced window instead), checks what the timed path produced against the
plain reference, prints each number compared beside its limit as the last
lines of standard error, and prints one JSON result as the last line of
standard output.  Without a TPU, with fewer chips than the cell asks for,
on a device kind with no published peaks, or without the program beside
it, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
# the TPU runtime writes its logs to a fixed /tmp directory unless told not to
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = harness.load_json(harness.REPO / "BENCHMARK.json")
        cell = harness.resolve(bench, args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILS"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{verdict}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

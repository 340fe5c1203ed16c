"""Benchmark harness: one entry per paper table/figure plus the
window-engine roofline (scan vs fused vs mega).  Prints
``name,us_per_call,derived`` CSV rows followed by the detailed JSON per
benchmark."""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import fleet_sweep
import jax
import paper_figures
import roofline_report

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    benches = [
        ("ivd_token_allocation_fig3_4", paper_figures.fig3_4_token_allocation),
        ("ive_redistribution_fig5_6", paper_figures.fig5_6_redistribution),
        ("ivf_recompensation_fig7_8", paper_figures.fig7_8_recompensation),
        ("ivh_frequency_fig9", paper_figures.fig9_allocation_frequency),
        ("ivg_overhead_scaling", paper_figures.overhead_scaling),
        ("fleet_scenarios_x_modes_sweep",
         lambda: fleet_sweep.sweep(duration_s=10.0)),
    ]
    print("name,us_per_call,derived")
    details = {}
    for name, fn in benches:
        t0 = time.perf_counter()
        result = fn()
        us = (time.perf_counter() - t0) * 1e6
        details[name] = result
        derived = json.dumps(result, default=float)
        short = derived if len(derived) < 120 else derived[:117] + "..."
        print(f"{name},{us:.0f},{short}")

    print()
    print("=== details ===")
    print(json.dumps(details, indent=2, default=float))
    print()
    print("## window-engine roofline (small cell; full grid: "
          "benchmarks/roofline_report.py --out BENCH_roofline.json)")
    kind = jax.devices()[0].device_kind
    if kind not in roofline_report.DEVICE_PEAKS:
        print(f"not measured: no published peaks for device kind {kind!r}")
        return
    roof = roofline_report.sweep(shapes=((8, 128),), n_windows=2)
    print(json.dumps(roof["cells"], indent=2, default=float))


if __name__ == "__main__":
    main()

"""Multi-device scaling sweep for the sharded window engine
(``FleetConfig(partition="ost_shard")``).

The XLA host backend fixes its device count at process start, so the sweep
spawns one fresh worker process per device count with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and aggregates the
JSON each worker prints.  Each worker runs the same long-horizon streaming
workload (``benchmarks/long_horizon.build_case``) under ``shard_map`` on an
N-way ``ost`` mesh; the 1-device cell also times the unsharded engine so
the report shows the layer's overhead at mesh size 1.

This is a CPU rehearsal of the mesh logic and never measures the chip:
the parent imports JAX and starts children, and a chip belongs to one
process, so every worker runs with ``JAX_PLATFORMS=cpu`` and the forced
"devices" are host threads.  The sweep proves the sharded path's scaling
*shape* and the mesh-invariant physics; on chips the sharded engine runs
in one process that drives every chip (``chip_smoke.py --four-chips``).

Run:  PYTHONPATH=src python benchmarks/shard_scaling.py \
          [--devices 1 2 4 8] [--ost 256] [--jobs 1024] [--windows 60] \
          [--smoke] [--out BENCH_shard_scaling.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def worker(ost: int, jobs: int, windows: int, trace_windows: int,
           policy: str, devices: int) -> dict:
    """Runs inside the flag-forced subprocess: time sharded (and, at one
    device, unsharded) streaming fleet runs."""
    import jax
    import numpy as np

    from repro.launch.compile_cache import use_compile_cache
    from repro.storage import FleetConfig, simulate_fleet

    use_compile_cache()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _harness import blocking, timeit_steady
    from long_horizon import build_case

    if jax.device_count() != devices:
        raise RuntimeError(
            f"worker expected {devices} devices, got {jax.device_count()}")

    window_ticks = 10
    nodes, rates, volume = build_case(ost, jobs, trace_windows, window_ticks)

    def timed(cfg):
        go = blocking(simulate_fleet, cfg, nodes, rates, volume,
                      n_windows=windows)
        t = timeit_steady(go)
        res = jax.block_until_ready(simulate_fleet(
            cfg, nodes, rates, volume, n_windows=windows))
        total = float(np.asarray(res.stats.served_sum, np.float64).sum())
        return {"windows_per_s": windows / t["wall_s"],
                "served_total": total, **t}

    base = FleetConfig(control=policy, telemetry="streaming",
                       window_ticks=window_ticks)
    cell = {"devices": devices, "o": ost, "j": jobs, "windows": windows,
            **timed(base._replace(partition="ost_shard"))}
    if devices == 1:
        cell["unsharded"] = timed(base)
    return cell


def sweep(args) -> dict:
    from _harness import provenance

    cells = []
    for n in args.devices:
        env = dict(os.environ)
        # replace (not append) any ambient force flag so nested sweeps and
        # flag-forced CI runners cannot hand the worker two conflicting counts
        kept = [f for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")]
        env["XLA_FLAGS"] = " ".join(
            kept + [f"--xla_force_host_platform_device_count={n}"])
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, os.path.abspath(__file__), "--worker",
               "--devices", str(n), "--ost", str(args.ost),
               "--jobs", str(args.jobs), "--windows", str(args.windows),
               "--trace-windows", str(args.trace_windows),
               "--policy", args.policy]
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                              timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(
                f"worker for {n} devices failed:\n{proc.stdout}\n"
                f"{proc.stderr}")
        cell = json.loads(proc.stdout.splitlines()[-1])
        print(f"devices={n}: {cell['windows_per_s']:.2f} windows/s "
              f"(compile {cell['compile_s']:.1f}s)")
        cells.append(cell)

    base = next((c for c in cells if c["devices"] == 1), None)
    if base is not None:  # only meaningful when the sweep includes devices=1
        for cell in cells:
            cell["speedup_vs_1dev"] = cell["windows_per_s"] \
                / base["windows_per_s"]
    # every worker moves identical traffic: the sweep must not change physics
    served = {c["served_total"] for c in cells}
    assert len(served) == 1, f"served totals drifted across meshes: {served}"
    return {
        "shape": {"o": args.ost, "j": args.jobs, "windows": args.windows,
                  "trace_windows": args.trace_windows,
                  "policy": args.policy, "telemetry": "streaming"},
        "cells": cells,
        "provenance": provenance(backend_note="cpu-forced-host-devices"),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one device-count cell and print JSON")
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--ost", type=int, default=256)
    ap.add_argument("--jobs", type=int, default=1024)
    ap.add_argument("--windows", type=int, default=60)
    ap.add_argument("--trace-windows", type=int, default=5)
    ap.add_argument("--policy", default="adaptbf")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI grid: O=16, J=128, 20 windows, 1+2 devices")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.worker:
        cell = worker(args.ost, args.jobs, args.windows, args.trace_windows,
                      args.policy, args.devices[0])
        print(json.dumps(cell))
        return

    if args.smoke:
        args.ost, args.jobs, args.windows = 16, 128, 20
        args.devices = [1, 2]

    report = sweep(args)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

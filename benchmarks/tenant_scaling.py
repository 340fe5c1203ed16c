"""Tenant-axis scaling: batched ``simulate_tenants`` dispatch vs the
F-iteration Python loop it replaces.

The question this artifact answers: how many windows/second does one
compiled tenant batch sustain as the fleet count F grows, against the
obvious alternative -- a host loop of F jitted ``simulate_fleet`` calls
(same compiled program per fleet, loop on the host)?  The loop pays per-
iteration dispatch, host sync, and result reassembly F times; the batch
pays one dispatch for the whole axis and lets XLA fuse across fleets.
The ROADMAP's adversarial-search and policy-zoo items need thousands of
candidate scenarios per dispatch, which is exactly the F >= 256 regime.

Per F in the ladder (default 1, 16, 256, 1024), both modes run the same
F heterogeneous streaming fleets (per-fleet seeded demand, shared rate
trace shape) and report aggregate windows/s = F * W / wall.  The loop
baseline is measured on the smaller rungs and its per-fleet cost
extrapolated linearly for any rung it would make intractable on CPU --
marked ``extrapolated`` in the JSON, never silently.

The default shape is MANY SMALL TENANTS on a SHORT horizon (O=4, J=8,
W=20 per dispatch): the regime the tenant axis exists for.  The sweep
loops that need F >= 256 (adversarial scenario search, policy-zoo
scoring, an online controller redispatching its whole population every
few windows) re-enter the dispatch boundary every few windows, so the
loop baseline pays its per-call overhead at exactly this cadence; long
single-fleet horizons are ``long_horizon.py``'s benchmark, not this one.

Run:  PYTHONPATH=src python benchmarks/tenant_scaling.py \
          [--fleets 1 16 256 1024] [--n-ost 4] [--n-jobs 8] \
          [--windows 20] [--loop-cap 256] [--reps 3] \
          [--out BENCH_tenant_scaling.json]

``--smoke`` shrinks to F in {1, 8} at W=20 for the CI bench-smoke job.
"""
from __future__ import annotations

import argparse
import json

import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.storage import FleetConfig, simulate_fleet, simulate_tenants
from repro.storage.scengen import random_fleet

from _harness import blocking, provenance, timeit_steady


def build_problem(n_fleets: int, n_ost: int, n_jobs: int, windows: int,
                  window_ticks: int):
    """F heterogeneous fleets: per-fleet seeded nodes/volume (the control
    state diverges per tenant), one shared rate trace (the common case --
    a provider stress-testing one demand profile across tenant configs --
    and the memory-flat layout the rank-based broadcasting exists for)."""
    duration_s = windows * window_ticks * 0.01
    base = random_fleet(seed=0, n_ost=n_ost, n_jobs=n_jobs,
                        duration_s=duration_s)
    rates = jnp.asarray(base.issue_rate, jnp.float32)
    rng = np.random.default_rng(7)
    nodes = jnp.asarray(
        rng.integers(1, 32, (n_fleets, n_ost, n_jobs)), jnp.float32)
    volume = jnp.where(
        jnp.asarray(rng.random((n_fleets, n_ost, n_jobs))) < 0.2,
        jnp.float32(500.0), jnp.float32(np.inf))
    cap = jnp.asarray(base.capacity_per_tick, jnp.float32)
    return nodes, rates, volume, cap


def measure_batched(cfg, nodes, rates, volume, cap, reps: int):
    run = blocking(simulate_tenants, cfg, nodes, rates, volume,
                   capacity_per_tick=cap)
    return timeit_steady(run, reps=reps)


def measure_loop(cfg, nodes, rates, volume, cap, reps: int):
    """The per-fleet host loop: F jitted simulate_fleet calls.  One
    compiled program total (shapes are identical across fleets), so this
    measures dispatch/sync overhead, not recompilation."""
    n_fleets = nodes.shape[0]

    def loop():
        return [simulate_fleet(cfg, nodes[i], rates, volume[i],
                               capacity_per_tick=cap)
                for i in range(n_fleets)]

    return timeit_steady(blocking(loop), reps=reps)


def sweep(fleets=(1, 16, 256, 1024), n_ost: int = 4, n_jobs: int = 8,
          windows: int = 20, window_ticks: int = 10, loop_cap: int = 256,
          reps: int = 3):
    cfg = FleetConfig(telemetry="streaming", window_ticks=window_ticks)
    rows = []
    loop_per_fleet_s = None
    for f in fleets:
        nodes, rates, volume, cap = build_problem(
            f, n_ost, n_jobs, windows, window_ticks)
        batched = measure_batched(cfg, nodes, rates, volume, cap, reps)
        row = {
            "n_fleets": f,
            "batched": batched,
            "batched_windows_per_s": f * windows / batched["wall_s"],
        }
        if f <= loop_cap:
            loop = measure_loop(cfg, nodes, rates, volume, cap, reps)
            row["loop"] = loop
            row["loop_windows_per_s"] = f * windows / loop["wall_s"]
            row["loop_extrapolated"] = False
            loop_per_fleet_s = loop["wall_s"] / f
        elif loop_per_fleet_s is not None:
            wall = loop_per_fleet_s * f
            row["loop"] = {"wall_s": wall, "extrapolated_from_per_fleet_s":
                           loop_per_fleet_s}
            row["loop_windows_per_s"] = f * windows / wall
            row["loop_extrapolated"] = True
        if "loop_windows_per_s" in row:
            row["batched_speedup_vs_loop"] = (
                row["batched_windows_per_s"] / row["loop_windows_per_s"])
        rows.append(row)
        print(f"  F={f:5d}: batched {row['batched_windows_per_s']:12.1f} w/s"
              + (f"  loop {row['loop_windows_per_s']:12.1f} w/s"
                 f"  speedup {row['batched_speedup_vs_loop']:.2f}x"
                 + (" (extrapolated)" if row["loop_extrapolated"] else "")
                 if "loop_windows_per_s" in row else ""), flush=True)
    return {
        "config": {
            "fleets": list(fleets),
            "n_ost": n_ost,
            "n_jobs": n_jobs,
            "windows": windows,
            "window_ticks": window_ticks,
            "loop_cap": loop_cap,
            "reps": reps,
            "telemetry": "streaming",
        },
        "provenance": provenance(cfg),
        "results": rows,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--fleets", nargs="+", type=int,
                    default=[1, 16, 256, 1024])
    ap.add_argument("--n-ost", type=int, default=4)
    ap.add_argument("--n-jobs", type=int, default=8)
    ap.add_argument("--windows", type=int, default=20)
    ap.add_argument("--loop-cap", type=int, default=256,
                    help="largest F to actually run the Python loop at "
                         "(larger rungs extrapolate its per-fleet cost)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI ladder: F in {1, 8} at W=20")
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        report = sweep(fleets=(1, 8), n_ost=args.n_ost, n_jobs=8,
                       windows=20, loop_cap=8, reps=2)
    else:
        report = sweep(fleets=tuple(args.fleets), n_ost=args.n_ost,
                       n_jobs=args.n_jobs, windows=args.windows,
                       loop_cap=args.loop_cap, reps=args.reps)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

"""Long-horizon streaming-telemetry benchmark: fleet runs whose horizon far
exceeds what materialized ``[W, O, J]`` trajectories could hold.

Builds a periodic bursty trace of ``--trace-windows`` windows and extends it
to ``--windows`` via the engine's periodic horizon override
(``simulate_fleet(..., n_windows=W)``) under ``telemetry="streaming"`` --
every metric below is finalized from the carry-resident ``StreamStats``, so
peak memory is independent of the horizon (DESIGN.md section 7).  At the
acceptance shape (W=2000, O=64, J=1024) the trajectory equivalent would be
~2 GB of output arrays; the streaming carry is ~2 MB.

The CI bench-smoke job runs this at (W=2000, O=16, J=256) so the streaming
path cannot rot; the committed ``BENCH_long_horizon.json`` records the
acceptance shape.

Run:  PYTHONPATH=src python benchmarks/long_horizon.py \
          [--windows 2000] [--ost 64] [--jobs 1024] [--trace-windows 25] \
          [--policy adaptbf] [--serve scan|fused] [--out report.json]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.storage import FleetConfig, metrics, simulate_fleet

from _harness import provenance


def build_case(o: int, j: int, trace_windows: int, window_ticks: int,
               seed: int = 0):
    """Periodic bursty fleet demand: half the jobs stream continuously,
    half burst in staggered phases, aggregate ~2x the service capacity."""
    rng = np.random.default_rng(seed)
    t = trace_windows * window_ticks
    nodes = rng.integers(1, 64, (j,)).astype(np.float32)
    base = rng.integers(0, 3, (t, o, j)).astype(np.float32)
    bursty = rng.random(j) < 0.5
    phase = rng.integers(0, trace_windows, j)
    w_idx = np.arange(t) // window_ticks
    on = ((w_idx[:, None] + phase[None, :]) % trace_windows) \
        < max(1, trace_windows // 4)
    base[:, :, bursty] *= (3.0 * on[:, bursty])[:, None, :]
    volume = np.full((o, j), np.inf, np.float32)
    return (jnp.asarray(nodes), jnp.asarray(base), jnp.asarray(volume))


def run(windows: int, o: int, j: int, trace_windows: int, policy: str,
        serve_backend: str, window_ticks: int = 10):
    cfg = FleetConfig(control=policy, telemetry="streaming",
                      serve_backend=serve_backend, window_ticks=window_ticks)
    nodes, rates, volume = build_case(o, j, trace_windows, window_ticks)
    cap_w = cfg.capacity_per_tick * window_ticks

    go = lambda: jax.block_until_ready(simulate_fleet(
        cfg, nodes, rates, volume, n_windows=windows))
    t0 = time.perf_counter()
    res = go()  # compile + first run
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = go()
    wall = time.perf_counter() - t0

    stats = res.stats
    slow = metrics.streaming_job_slowdown(stats, cap_w)
    carry_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(stats))
    return {
        "windows": int(stats.windows),
        "o": o, "j": j,
        "trace_windows": trace_windows,
        "policy": policy,
        "serve_backend": serve_backend,
        "wall_s": wall,
        "windows_per_s": windows / wall,
        "compile_s": compile_s,
        "stats_carry_bytes": carry_bytes,
        "trajectory_equivalent_bytes": windows * o * j * 4 * 4,
        "metrics": {
            "aggregate_mb": metrics.streaming_aggregate_mb(stats),
            "mean_utilization": metrics.streaming_mean_utilization(stats),
            "fairness_jain": metrics.streaming_fairness(
                stats, np.asarray(nodes)),
            "p99_backlog_growth": metrics.streaming_p99_queue(stats),
            "slowdown_mean": float(np.nanmean(slow)),
        },
        "provenance": provenance(),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--windows", type=int, default=2000)
    ap.add_argument("--ost", type=int, default=64)
    ap.add_argument("--jobs", type=int, default=1024)
    ap.add_argument("--trace-windows", type=int, default=25)
    ap.add_argument("--policy", default="adaptbf")
    ap.add_argument("--serve", choices=("scan", "fused"), default="scan")
    args = ap.parse_args()
    use_compile_cache()
    report = run(args.windows, args.ost, args.jobs, args.trace_windows,
                 args.policy, args.serve)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

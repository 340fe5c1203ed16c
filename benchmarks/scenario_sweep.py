"""Seed-grid envelope sweep: generated scenarios x every registered policy.

Where ``fleet_sweep.py`` scores the hand-written scenario registry,
this harness asks the generator question: across a *grid of seeds* drawn
from one ``storage/scengen`` profile, what envelope of utilization,
fairness, and slowdown does each control policy guarantee?  A policy that
looks good on four curated scenarios but collapses on seed 13 of the
saturation profile is exactly what the paper's "even under extreme
conditions" claim must exclude.

Per seed, all policies run as ONE coded streaming invocation through the
tenant axis (``storage.simulate_tenants``: scenario arrays shared, policy
codes batched -- the [J]/[T, O, J] inputs are never copied per policy), so
the grid reuses a single compiled program across every seed -- the arrays
change, the shapes do not.
Streaming telemetry keeps the memory flat regardless of horizon, which is
what makes the committed (O=64, J=1024) x 16-seed artifact
(``BENCH_scenario_sweep.json``) tractable on CPU.

The report carries, per policy: the per-seed metric table and the
min/mean/max envelope over seeds (fairness minima and slowdown maxima are
the headline numbers -- envelopes, not averages, are what a QoS mechanism
promises).

Run:  PYTHONPATH=src python benchmarks/scenario_sweep.py \
          [--profile mixed] [--seeds 16] [--seed0 0] \
          [--n-ost 64] [--n-jobs 1024] [--duration-s 5] \
          [--policies adaptbf static ...] [--out BENCH_scenario_sweep.json]

``--smoke`` shrinks to 2 seeds at (O=8, J=64) for the CI bench-smoke job.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.storage import (
    FleetConfig,
    list_policies,
    metrics,
    random_fleet,
    scengen,
    simulate_tenants,
)
from _harness import provenance


def run_policy_batch(cfg: FleetConfig, args, codes):
    """One compiled streaming program over the policy-code axis via the
    tenant entry point (scenario arrays shared, codes batched): returns
    (StreamStats with a leading [C] axis, queue_final [C, O, J]).
    ``simulate_tenants`` is jitted on (cfg, n_fleets), so every seed of a
    sweep reuses one compilation."""
    nodes, rates, vol, caps, backlog = args
    res = simulate_tenants(cfg, nodes, rates, vol, capacity_per_tick=caps,
                           max_backlog=backlog, control_code=codes)
    return res.stats, res.queue_final


def _metrics_for(stats, nodes, cap_w):
    slow = metrics.streaming_job_slowdown(stats, cap_w)
    finite = np.isfinite(slow)
    return {
        "aggregate_mb": metrics.streaming_aggregate_mb(stats),
        "mean_utilization": metrics.streaming_mean_utilization(stats),
        "fairness_jain": metrics.streaming_fairness(stats, nodes),
        "p99_backlog_growth": metrics.streaming_p99_queue(stats),
        "slowdown_mean": float(np.nanmean(slow)) if finite.any() else None,
        "slowdown_max": float(np.nanmax(slow)) if finite.any() else None,
    }


def _envelope(values):
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    return {"min": float(np.min(vals)), "mean": float(np.mean(vals)),
            "max": float(np.max(vals))}


def sweep(profile: str = "mixed", seeds: int = 16, seed0: int = 0,
          n_ost: int = 64, n_jobs: int = 1024, duration_s: float = 5.0,
          window_ticks: int = 10, policies=None):
    policies = tuple(policies) if policies else tuple(list_policies())
    cfg = FleetConfig(control="coded", window_ticks=window_ticks,
                      telemetry="streaming", coded_policies=policies)
    codes = jnp.arange(len(policies), dtype=jnp.int32)

    per_seed = []
    wall_total = 0.0
    for seed in range(seed0, seed0 + seeds):
        scn = random_fleet(seed, n_ost=n_ost, n_jobs=n_jobs, profile=profile,
                           duration_s=duration_s)
        args = (jnp.asarray(scn.nodes), jnp.asarray(scn.issue_rate),
                jnp.asarray(scn.volume), jnp.asarray(scn.capacity_per_tick),
                jnp.asarray(scn.max_backlog))
        t0 = time.perf_counter()
        stats_c, _ = jax.block_until_ready(run_policy_batch(cfg, args, codes))
        wall = time.perf_counter() - t0
        wall_total += wall
        cap_w = np.asarray(scn.capacity_per_tick) * window_ticks
        row = {"seed": seed, "wall_s": wall}
        for ci, policy in enumerate(policies):
            stats = jax.tree.map(lambda x: x[ci], stats_c)
            row[policy] = _metrics_for(stats, scn.nodes, cap_w)
        per_seed.append(row)
        print(f"  seed {seed}: {wall:6.2f}s  " + "  ".join(
            f"{p}:util={row[p]['mean_utilization']:.3f}"
            f"/jain={row[p]['fairness_jain']:.3f}" for p in policies),
            flush=True)

    envelopes = {}
    for policy in policies:
        env = {}
        for key in ("aggregate_mb", "mean_utilization", "fairness_jain",
                    "p99_backlog_growth", "slowdown_mean", "slowdown_max"):
            env[key] = _envelope([row[policy][key] for row in per_seed])
        envelopes[policy] = env

    return {
        "config": {
            "profile": profile,
            "seeds": seeds,
            "seed0": seed0,
            "n_ost": n_ost,
            "n_jobs": n_jobs,
            "duration_s": duration_s,
            "window_ticks": window_ticks,
            "policies": list(policies),
            "wall_s_total": wall_total,
        },
        "provenance": provenance(cfg),
        "envelopes": envelopes,
        "per_seed": per_seed,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--profile", default="mixed",
                    choices=sorted(scengen.PROFILES))
    ap.add_argument("--seeds", type=int, default=16,
                    help="size of the seed grid")
    ap.add_argument("--seed0", type=int, default=0)
    ap.add_argument("--n-ost", type=int, default=64)
    ap.add_argument("--n-jobs", type=int, default=1024)
    ap.add_argument("--duration-s", type=float, default=5.0)
    ap.add_argument("--policies", nargs="+", default=None, metavar="NAME",
                    help="policy subset (default: every registered policy)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI grid: 2 seeds at (O=8, J=64)")
    args = ap.parse_args()
    use_compile_cache()
    if args.policies:
        unknown = set(args.policies) - set(list_policies())
        if unknown:
            ap.error(f"unknown policies {sorted(unknown)}; "
                     f"registered: {list_policies()}")
    if args.smoke:
        report = sweep(profile=args.profile, seeds=2, seed0=args.seed0,
                       n_ost=8, n_jobs=64, duration_s=2.0,
                       policies=args.policies)
    else:
        report = sweep(profile=args.profile, seeds=args.seeds,
                       seed0=args.seed0, n_ost=args.n_ost,
                       n_jobs=args.n_jobs, duration_s=args.duration_s,
                       policies=args.policies)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

"""Fault-severity sweep: every policy under escalating chaos.

The paper's claim is that AdapTBF "maintains high storage utilization
even under extreme conditions"; ``scenario_sweep.py`` stresses the
demand side, this harness stresses the *infrastructure* side with the
``storage/faults`` plan primitives: OST outages (Markov MTBF/MTTR),
capacity droop (RAID-rebuild stretches), and lost controller telemetry.

Two measurements per (severity, policy):

* **chaos envelope** -- across a seed grid of random fault plans overlaid
  on generated demand, the min/mean/max of utilization (of *surviving*
  capacity -- the engine scores service against the fault-adjusted
  budget), fairness, and delivered volume.  All policies run as ONE
  coded streaming invocation per seed through the tenant axis
  (``storage.simulate_tenants``, scenario + plan shared, codes batched),
  the fault plan riding along as a traced argument, so the whole grid
  reuses one compiled program.
* **recovery time** -- a deterministic single-outage trajectory (25% of
  OSTs down for a fixed stretch): how many windows after the outage
  lifts until per-window utilization is back to >= 90% of its pre-outage
  mean.  This is the adaptivity headline: a policy that survives the
  outage but re-converges slowly still fails the QoS story.

Run:  PYTHONPATH=src python benchmarks/fault_sweep.py \
          [--seeds 4] [--n-ost 32] [--n-jobs 256] [--duration-s 5] \
          [--policies adaptbf aimd ...] [--out BENCH_fault_sweep.json]

``--smoke`` shrinks to 2 severities x 2 seeds at (O=8, J=32) for the CI
bench-smoke job.
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
    faults,
    list_policies,
    metrics,
    random_fleet,
    simulate_tenants,
)
from _harness import provenance

#: The severity ladder: MTBF/MTTR in windows, droop hit-rate and floor,
#: telemetry loss probability.  "calm" is the faultless control row --
#: everything a policy loses between calm and a chaos row is fault cost.
SEVERITIES = {
    "calm":     dict(mtbf_windows=1e9,  mttr_windows=1.0,
                     droop_frac=0.0,  droop_scale=1.0, loss_p=0.0),
    "mild":     dict(mtbf_windows=200.0, mttr_windows=5.0,
                     droop_frac=0.15, droop_scale=0.5, loss_p=0.02),
    "moderate": dict(mtbf_windows=60.0, mttr_windows=8.0,
                     droop_frac=0.3,  droop_scale=0.4, loss_p=0.08),
    "severe":   dict(mtbf_windows=20.0, mttr_windows=10.0,
                     droop_frac=0.5,  droop_scale=0.3, loss_p=0.2),
    "extreme":  dict(mtbf_windows=8.0,  mttr_windows=12.0,
                     droop_frac=0.8,  droop_scale=0.2, loss_p=0.4),
}


def run_chaos_batch(cfg: FleetConfig, args, plan, codes):
    """One compiled streaming program over the policy-code axis via the
    tenant entry point: scenario arrays and the fault plan shared, codes
    batched.  The plan is a traced argument, so every severity and seed
    reuses one compilation (``simulate_tenants`` is jitted on
    (cfg, n_fleets))."""
    nodes, rates, vol, caps, backlog = args
    res = simulate_tenants(cfg, nodes, rates, vol, capacity_per_tick=caps,
                           max_backlog=backlog, control_code=codes,
                           fault_plan=plan)
    return res.stats, res.queue_final


def run_trajectory_batch(cfg: FleetConfig, args, plan, codes):
    nodes, rates, vol, caps, backlog = args
    res = simulate_tenants(cfg, nodes, rates, vol, capacity_per_tick=caps,
                           max_backlog=backlog, control_code=codes,
                           fault_plan=plan)
    return res.served


def _scenario_args(scn):
    return (jnp.asarray(scn.nodes), jnp.asarray(scn.issue_rate),
            jnp.asarray(scn.volume), jnp.asarray(scn.capacity_per_tick),
            jnp.asarray(scn.max_backlog))


def _jplan(plan):
    return faults.FaultPlan(*(jnp.asarray(x) for x in plan))


def _envelope(values):
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    return {"min": float(np.min(vals)), "mean": float(np.mean(vals)),
            "max": float(np.max(vals))}


def chaos_grid(policies, seeds, seed0, n_ost, n_jobs, duration_s,
               window_ticks):
    """Random fault plans x generated demand, all policies per dispatch."""
    cfg = FleetConfig(control="coded", window_ticks=window_ticks,
                      telemetry="streaming", coded_policies=policies)
    codes = jnp.arange(len(policies), dtype=jnp.int32)
    out = {}
    for severity, knobs in SEVERITIES.items():
        rows = []
        for seed in range(seed0, seed0 + seeds):
            scn = random_fleet(seed, n_ost=n_ost, n_jobs=n_jobs,
                               profile="mixed", duration_s=duration_s)
            n_windows = scn.issue_rate.shape[0] // window_ticks
            plan = faults.random_fault_plan(seed, n_windows, n_ost, **knobs)
            t0 = time.perf_counter()
            stats_c, _ = jax.block_until_ready(run_chaos_batch(
                cfg, _scenario_args(scn), _jplan(plan), codes))
            wall = time.perf_counter() - t0
            row = {"seed": seed, "wall_s": wall,
                   "down_window_frac":
                       float((np.asarray(plan.up) <= 0).mean()),
                   "lost_obs_frac":
                       float((np.asarray(plan.telem_ok) <= 0).mean())}
            for ci, policy in enumerate(policies):
                stats = jax.tree.map(lambda x: x[ci], stats_c)
                row[policy] = {
                    "degraded_utilization":
                        metrics.streaming_mean_utilization(stats),
                    "fairness_jain":
                        metrics.streaming_fairness(stats, scn.nodes),
                    "aggregate_mb": metrics.streaming_aggregate_mb(stats),
                }
            rows.append(row)
            print(f"  {severity:>9} seed {seed}: {wall:6.2f}s  " + "  ".join(
                f"{p}:util={row[p]['degraded_utilization']:.3f}"
                for p in policies), flush=True)
        out[severity] = rows
    return out


def recovery_times(policies, n_ost, n_jobs, duration_s, window_ticks,
                   seed=0, down_frac=0.25, util_target=0.9):
    """Deterministic single-outage trajectories: windows-to-recover per
    policy per severity's MTTR-sized outage.

    Recovery is measured against the policy's own *faultless twin* on
    the same demand (same compiled program, all-ones plan): the first
    post-outage window whose fleet utilization regains >= 90% of what
    that window achieves with no outage.  Comparing window-for-window
    controls for demand nonstationarity (bursts, volume-bounded jobs
    finishing) that a pre-outage mean would confound.
    """
    cfg = FleetConfig(control="coded", window_ticks=window_ticks,
                      telemetry="trajectory", coded_policies=policies)
    codes = jnp.arange(len(policies), dtype=jnp.int32)
    scn = random_fleet(seed, n_ost=n_ost, n_jobs=n_jobs, profile="mixed",
                       duration_s=duration_s)
    n_windows = scn.issue_rate.shape[0] // window_ticks
    cap_total = float(np.asarray(scn.capacity_per_tick).sum()) * window_ticks
    n_down = max(1, int(round(down_frac * n_ost)))
    base_plan = faults.no_faults(n_windows, n_ost)
    served_base = np.asarray(jax.block_until_ready(run_trajectory_batch(
        cfg, _scenario_args(scn), _jplan(base_plan), codes)))
    util_base = served_base.sum(axis=(2, 3)) / cap_total      # [C, W]
    out = {}
    for severity, knobs in SEVERITIES.items():
        if severity == "calm":
            continue
        dur = min(max(1, int(round(knobs["mttr_windows"]))), n_windows // 3)
        w0 = n_windows // 3
        w1 = w0 + dur
        plan = faults.outage(n_windows, n_ost, w0, w1,
                             osts=np.arange(n_down))
        served_c = np.asarray(jax.block_until_ready(run_trajectory_batch(
            cfg, _scenario_args(scn), _jplan(plan), codes)))  # [C, W, O, J]
        util_w = served_c.sum(axis=(2, 3)) / cap_total        # [C, W]
        row = {}
        for ci, policy in enumerate(policies):
            target = util_target * util_base[ci, w1:]
            recovered = np.nonzero((util_w[ci, w1:] >= target)
                                   | (util_base[ci, w1:] <= 1e-9))[0]
            row[policy] = {
                "faultless_utilization": float(util_base[ci, 1:].mean()),
                "outage_utilization": float(util_w[ci, w0:w1].mean()),
                "recovery_windows":
                    int(recovered[0]) if recovered.size else None,
            }
        out[severity] = {"outage_windows": [w0, w1], "osts_down": n_down,
                         "policies": row}
        print(f"  recovery {severity:>9}: " + "  ".join(
            f"{p}={row[p]['recovery_windows']}" for p in policies),
            flush=True)
    return out


def sweep(policies=None, seeds=4, seed0=0, n_ost=32, n_jobs=256,
          duration_s=5.0, window_ticks=10, severities=None):
    policies = tuple(policies) if policies else tuple(list_policies())
    if severities:
        dropped = [s for s in SEVERITIES if s not in severities]
        for s in dropped:
            SEVERITIES.pop(s)
        if dropped:
            print(f"  (severities restricted; dropped {dropped})",
                  flush=True)
    grid = chaos_grid(policies, seeds, seed0, n_ost, n_jobs, duration_s,
                      window_ticks)
    recovery = recovery_times(policies, n_ost, n_jobs, duration_s,
                              window_ticks, seed=seed0)

    envelopes = {}
    for policy in policies:
        env = {}
        for severity, rows in grid.items():
            env[severity] = {
                key: _envelope([row[policy][key] for row in rows])
                for key in ("degraded_utilization", "fairness_jain",
                            "aggregate_mb")}
        env["recovery_windows"] = {
            severity: rec["policies"][policy]["recovery_windows"]
            for severity, rec in recovery.items()}
        envelopes[policy] = env

    # ranking: mean degraded utilization at the worst common severity
    worst = [s for s in ("extreme", "severe", "moderate", "mild", "calm")
             if s in grid][0]
    ranking = sorted(
        policies,
        key=lambda p: -envelopes[p][worst]["degraded_utilization"]["mean"])

    cfg = FleetConfig(control="coded", window_ticks=window_ticks,
                      telemetry="streaming", coded_policies=policies)
    return {
        "config": {
            "seeds": seeds, "seed0": seed0, "n_ost": n_ost,
            "n_jobs": n_jobs, "duration_s": duration_s,
            "window_ticks": window_ticks, "policies": list(policies),
            "severities": {k: v for k, v in SEVERITIES.items()},
        },
        "provenance": provenance(cfg),
        "ranking_by_degraded_utilization": ranking,
        "envelopes": envelopes,
        "recovery": recovery,
        "per_seed": grid,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--seed0", type=int, default=0)
    ap.add_argument("--n-ost", type=int, default=32)
    ap.add_argument("--n-jobs", type=int, default=256)
    ap.add_argument("--duration-s", type=float, default=5.0)
    ap.add_argument("--policies", nargs="+", default=None, metavar="NAME")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI grid: calm+severe x 2 seeds at (O=8, J=32)")
    args = ap.parse_args()
    use_compile_cache()
    if args.policies:
        unknown = set(args.policies) - set(list_policies())
        if unknown:
            ap.error(f"unknown policies {sorted(unknown)}; "
                     f"registered: {list_policies()}")
    if args.smoke:
        report = sweep(policies=args.policies, seeds=2, seed0=args.seed0,
                       n_ost=8, n_jobs=32, duration_s=2.0,
                       severities=("calm", "severe"))
    else:
        report = sweep(policies=args.policies, seeds=args.seeds,
                       seed0=args.seed0, n_ost=args.n_ost,
                       n_jobs=args.n_jobs, duration_s=args.duration_s)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

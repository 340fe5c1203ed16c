"""Fleet-scale parameter sweep: every registered fleet scenario x every
registered control policy in ONE vmapped, jitted invocation.

Scenarios are padded to a common (T, O, J) shape and stacked on a scenario
axis; the policy rides the traced ``control_code`` path (the generic
``CodedPolicy`` combinator over the chosen subset).  The [S, C] grid is
flattened to one fleet axis F = S*C -- scenario s repeated per policy,
codes tiled per scenario -- and dispatched as a single compiled tenant
batch through ``storage.simulate_tenants``.

A policy registered via ``@register_policy`` shows up in the grid with no
change here and none in the engine.  Emits a JSON report with utilization,
fairness (Jain), backlog-tail, and per-job slowdown metrics per
(scenario, policy), adaptbf-vs-baseline comparisons, and provenance (jax
version, git SHA, full config).

Run:  PYTHONPATH=src python benchmarks/fleet_sweep.py [--out report.json]
                                                      [--duration-s 20]
                                                      [--backend core|pallas]
                                                      [--serve scan|fused]
                                                      [--policies adaptbf static ...]
                                                      [--generator PROFILE ...]
                                                      [--gen-count 4] [--gen-seed0 0]
                                                      [--gen-ost 8] [--gen-jobs 8]

With ``--generator`` the scenario axis becomes a procedural grid instead of
the registry list: ``gen-count`` seeds drawn from each named
``storage/scengen`` profile (same shape for every cell, so the whole grid
still compiles once).
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
    get_scenario,
    list_fleet_scenarios,
    list_policies,
    random_fleet,
    scengen,
    simulate_tenants,
)
from repro.storage import metrics

from _harness import provenance

BASELINE_TRIO = ("adaptbf", "static", "nobw")


def _pad_axis(x: np.ndarray, size: int, axis: int, value=0.0) -> np.ndarray:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return np.pad(x, cfg, constant_values=value)


def stack_scenarios(scenarios):
    """Pad every FleetScenario to a common (T, O, J) and stack on axis 0.
    Padded jobs get zero nodes/rate/volume -> permanently inactive."""
    t = max(s.issue_rate.shape[0] for s in scenarios)
    o = max(s.issue_rate.shape[1] for s in scenarios)
    j = max(s.issue_rate.shape[2] for s in scenarios)
    nodes = np.stack([_pad_axis(s.nodes, j, 0) for s in scenarios])
    rates = np.stack([
        _pad_axis(_pad_axis(_pad_axis(s.issue_rate, t, 0), o, 1), j, 2)
        for s in scenarios])
    vol = np.stack([_pad_axis(_pad_axis(s.volume, o, 0), j, 1)
                    for s in scenarios])
    backlog = np.stack([_pad_axis(_pad_axis(s.max_backlog, o, 0), j, 1)
                        for s in scenarios])
    # padded OSTs get a tiny nonzero capacity so per-OST divides stay finite
    caps = np.stack([_pad_axis(s.capacity_per_tick, o, 0, value=1.0)
                     for s in scenarios])
    return (jnp.asarray(nodes), jnp.asarray(rates), jnp.asarray(vol),
            jnp.asarray(caps), jnp.asarray(backlog))


def run_grid(cfg: FleetConfig, args, codes):
    """The [S, C] grid as ONE tenant batch (F = S*C): scenario arrays
    repeated per policy, policy codes tiled per scenario, dispatched
    through ``storage.simulate_tenants``.  Returns served/demand
    trajectories of shape [S, C, W, O, J].

    ``simulate_tenants`` is jitted on (cfg, n_fleets), so repeated
    invocations -- several sweeps in one process, or sweep() called from
    other harnesses -- reuse the compiled program."""
    nodes, rates, vol, caps, backlog = args
    s_count, c_count = nodes.shape[0], codes.shape[0]
    # the stacked nodes are [S, J]; the batched entry point reads rank-2 as
    # a *shared* [O, J], so lift to the explicit per-fleet [S, O, J] form
    nodes = jnp.broadcast_to(nodes[:, None, :],
                             (s_count, rates.shape[2], nodes.shape[1]))

    def rep(x):
        return jnp.repeat(x, c_count, axis=0)

    res = simulate_tenants(cfg, rep(nodes), rep(rates), rep(vol),
                           capacity_per_tick=rep(caps),
                           max_backlog=rep(backlog),
                           control_code=jnp.tile(codes, s_count))
    grid = (s_count, c_count) + res.served.shape[1:]
    return res.served.reshape(grid), res.demand.reshape(grid)


def generator_grid(profiles, gen_count: int, gen_seed0: int, gen_ost: int,
                   gen_jobs: int, duration_s: float):
    """(names, scenarios) for a procedural profile x seed grid."""
    names, scenarios = [], []
    for profile in profiles:
        # unknown profiles raise inside random_fleet on the first draw
        for seed in range(gen_seed0, gen_seed0 + gen_count):
            names.append(f"gen_{profile}_s{seed}")
            scenarios.append(random_fleet(
                seed, n_ost=gen_ost, n_jobs=gen_jobs, profile=profile,
                duration_s=duration_s))
    return names, scenarios


def sweep(duration_s: float = 20.0, window_ticks: int = 10,
          backend: str = "core", serve_backend: str = "scan",
          policies=None, generator=None, gen_count: int = 4,
          gen_seed0: int = 0, gen_ost: int = 8, gen_jobs: int = 8):
    policies = tuple(policies) if policies else tuple(list_policies())
    if generator:
        names, scenarios = generator_grid(
            generator, gen_count, gen_seed0, gen_ost, gen_jobs, duration_s)
    else:
        names = list_fleet_scenarios()
        scenarios = [get_scenario(n, duration_s=duration_s) for n in names]
    cfg = FleetConfig(control="coded", window_ticks=window_ticks,
                      alloc_backend=backend, serve_backend=serve_backend,
                      coded_policies=policies)
    args = stack_scenarios(scenarios)
    codes = jnp.arange(len(policies), dtype=jnp.int32)

    t0 = time.perf_counter()
    served, demand = jax.block_until_ready(run_grid(cfg, args, codes))
    wall_s = time.perf_counter() - t0

    served = np.asarray(served)   # [S, C, W, O, J]
    demand = np.asarray(demand)
    report = {
        "config": {
            "duration_s": duration_s,
            "window_ticks": window_ticks,
            "alloc_backend": backend,
            "serve_backend": serve_backend,
            "generator": list(generator) if generator else None,
            "scenarios": names,
            "policies": list(policies),
            "grid_shape": list(served.shape),
            "wall_s_one_invocation": wall_s,
        },
        "provenance": provenance(cfg),
        "results": {},
    }
    for si, (name, scn) in enumerate(zip(names, scenarios)):
        n_jobs = scn.nodes.shape[0]
        n_ost = scn.n_ost
        cap_w = scn.capacity_per_tick * window_ticks
        per_mode = {}
        for ci, mode in enumerate(policies):
            s = served[si, ci, :, :n_ost, :n_jobs]
            d = demand[si, ci, :, :n_ost, :n_jobs]
            slow = metrics.job_slowdown(s, cap_w)
            per_mode[mode] = {
                "aggregate_mb": metrics.aggregate_mb(s),
                "mean_utilization": metrics.mean_utilization(s, cap_w),
                "fairness_jain": metrics.fairness(       # aggregate over OSTs
                    s.sum(axis=1), scn.nodes, d.sum(axis=1)),
                "p99_backlog_growth": metrics.p99_queue(d, s),
                "slowdown_mean": float(np.nanmean(slow))
                    if np.isfinite(slow).any() else None,
                "slowdown_max": float(np.nanmax(slow))
                    if np.isfinite(slow).any() else None,
            }
        if all(m in per_mode for m in BASELINE_TRIO):
            ad, st, nb = (per_mode[m] for m in BASELINE_TRIO)
            per_mode["adaptbf_vs_baselines"] = {
                "throughput_gain_vs_static":
                    ad["aggregate_mb"] / max(st["aggregate_mb"], 1e-9),
                "utilization_gain_vs_static":
                    ad["mean_utilization"] / max(st["mean_utilization"], 1e-9),
                "fairness_gain_vs_nobw":
                    ad["fairness_jain"] / max(nb["fairness_jain"], 1e-9),
                "slowdown_gain_vs_static":
                    (st["slowdown_mean"] / max(ad["slowdown_mean"], 1e-9))
                    if ad["slowdown_mean"] and st["slowdown_mean"] else None,
            }
        report["results"][name] = per_mode
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--duration-s", type=float, default=20.0)
    ap.add_argument("--backend", choices=("core", "pallas"), default="core",
                    help="allocation backend (FleetConfig.alloc_backend)")
    ap.add_argument("--serve", choices=("scan", "fused"), default="scan",
                    help="window-service backend (FleetConfig.serve_backend)")
    ap.add_argument("--policies", nargs="+", default=None,
                    metavar="NAME", help="policy subset to sweep (default: "
                    "every registered policy); names from "
                    "repro.storage.list_policies()")
    ap.add_argument("--generator", nargs="+", default=None,
                    metavar="PROFILE",
                    help="sweep a procedural profile x seed grid instead of "
                         "the scenario registry; profiles from "
                         "repro.storage.scengen.PROFILES")
    ap.add_argument("--gen-count", type=int, default=4,
                    help="seeds per generator profile")
    ap.add_argument("--gen-seed0", type=int, default=0)
    ap.add_argument("--gen-ost", type=int, default=8)
    ap.add_argument("--gen-jobs", type=int, default=8)
    args = ap.parse_args()
    use_compile_cache()
    if args.policies:
        unknown = set(args.policies) - set(list_policies())
        if unknown:
            ap.error(f"unknown policies {sorted(unknown)}; "
                     f"registered: {list_policies()}")
    if args.generator:
        unknown = set(args.generator) - set(scengen.PROFILES)
        if unknown:
            ap.error(f"unknown generator profiles {sorted(unknown)}; "
                     f"have {sorted(scengen.PROFILES)}")
    report = sweep(duration_s=args.duration_s, backend=args.backend,
                   serve_backend=args.serve, policies=args.policies,
                   generator=args.generator, gen_count=args.gen_count,
                   gen_seed0=args.gen_seed0, gen_ost=args.gen_ost,
                   gen_jobs=args.gen_jobs)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

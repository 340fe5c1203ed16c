"""Online-service smoke benchmark: the long-lived ``FleetService`` loop
against its offline oracle, with a crash in the middle.

Drives a registered fleet scenario window by window through
``FleetService.step`` (the production online path: one jitted, donated-carry
``window_step`` per observation window), checkpoints the full carry at the
midpoint, *discards the service*, restores into a fresh one, finishes the
horizon -- and asserts the stitched online run equals one offline
``simulate_fleet`` scan of the same trace **bitwise**.  That is the
deployment story of DESIGN.md section 10 exercised end to end: step
incrementally for days, crash, resume exactly.

The CI bench-smoke job runs ``--smoke`` (a short horizon of the
``fleet_noisy_neighbor`` scenario) and asserts the JSON report says
``bitwise_match: true`` for both telemetry modes.  With ``--fault-plan``
the whole exercise runs under injected faults, and ``--crash-window``
moves the crash -- CI points it *inside* an OST outage, so the restored
carry must resume mid-disturbance and still match the uninterrupted
offline scan bitwise.

Fault-plan specs (windows index the observation-window axis):

* ``outage:start=A,end=B,osts=K``  -- the first K OSTs down for [A, B)
* ``markov:mtbf=M,mttr=R,loss=P,seed=S`` -- a seeded random plan
  (MTBF/MTTR in windows, telemetry loss probability P)

Run:  PYTHONPATH=src python benchmarks/online_service.py \
          [--scenario fleet_noisy_neighbor] [--duration-s 20] \
          [--policy adaptbf] [--fault-plan SPEC] [--crash-window N] \
          [--smoke] [--out report.json]
"""
from __future__ import annotations

import argparse
import json
import shutil
import tempfile
import time

import jax
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.storage import (
    FleetConfig,
    FleetService,
    faults,
    get_scenario,
    simulate_fleet,
)

from _harness import provenance


def parse_fault_plan(spec, n_windows: int, n_ost: int):
    """``kind:k=v,...`` -> FaultPlan (see module docstring for kinds)."""
    if not spec:
        return None
    kind, _, body = spec.partition(":")
    kv = dict(item.split("=", 1) for item in body.split(",") if item)
    if kind == "outage":
        return faults.outage(
            n_windows, n_ost, start=int(kv.get("start", 0)),
            end=int(kv.get("end", n_windows)),
            osts=np.arange(min(int(kv.get("osts", 1)), n_ost)))
    if kind == "markov":
        return faults.random_fault_plan(
            int(kv.get("seed", 0)), n_windows, n_ost,
            mtbf_windows=float(kv.get("mtbf", 80.0)),
            mttr_windows=float(kv.get("mttr", 10.0)),
            loss_p=float(kv.get("loss", 0.05)))
    raise ValueError(f"unknown fault-plan kind {kind!r} "
                     "(have: outage, markov)")


def run_mode(scn, policy: str, telemetry: str, ckpt_dir: str,
             fault_spec=None, crash_window=None) -> dict:
    cfg = FleetConfig(control=policy, telemetry=telemetry)
    wt = cfg.window_ticks
    n_windows = scn.issue_rate.shape[0] // wt
    crash = n_windows // 2 if crash_window is None else int(crash_window)
    if not 1 <= crash < n_windows:
        raise ValueError(f"--crash-window must be in [1, {n_windows}), "
                         f"got {crash}")
    rates = scn.issue_rate[: n_windows * wt]
    plan = parse_fault_plan(fault_spec, n_windows, scn.n_ost)

    offline = simulate_fleet(cfg, scn.nodes, rates, scn.volume,
                             scn.capacity_per_tick, scn.max_backlog,
                             fault_plan=plan)
    offline = jax.tree.map(np.asarray, offline)

    def make_service():
        return FleetService(cfg, scn.nodes, scn.volume,
                            scn.capacity_per_tick, scn.max_backlog,
                            checkpoint_dir=ckpt_dir, fault_plan=plan,
                            checkpoint_on_fault=False)

    svc = make_service()
    outs = []
    t0 = time.perf_counter()
    for w in range(crash):
        outs.append(svc.step(rates[w * wt:(w + 1) * wt]))
    svc.save()
    del svc                                   # the "crash"

    svc = make_service()
    restored_step = svc.restore()
    for w in range(crash, n_windows):
        outs.append(svc.step(rates[w * wt:(w + 1) * wt]))
    jax.block_until_ready(svc.carry)
    wall = time.perf_counter() - t0

    if telemetry == "trajectory":
        online_leaves = [np.stack([np.asarray(o[i]) for o in outs])
                         for i in range(4)] + [np.asarray(svc.queue)]
        offline_leaves = [offline.served, offline.demand, offline.alloc,
                          offline.record, offline.queue_final]
    else:
        online_leaves = [np.asarray(x) for x in jax.tree.leaves(svc.stats)]
        online_leaves.append(np.asarray(svc.queue))
        offline_leaves = list(jax.tree.leaves(offline.stats))
        offline_leaves.append(offline.queue_final)
    match = all(np.array_equal(a, b)
                for a, b in zip(offline_leaves, online_leaves)) \
        and len(offline_leaves) == len(online_leaves)

    return {
        "telemetry": telemetry,
        "windows": n_windows,
        "restored_at_window": restored_step,
        "bitwise_match": bool(match),
        "wall_s": wall,
        "windows_per_s": n_windows / wall,
    }


def run(scenario: str, duration_s: float, policy: str,
        fault_spec=None, crash_window=None) -> dict:
    scn = get_scenario(scenario, duration_s=duration_s)
    ckpt_root = tempfile.mkdtemp(prefix="online_service_bench_")
    try:
        modes = [run_mode(scn, policy, t, f"{ckpt_root}/{t}",
                          fault_spec=fault_spec, crash_window=crash_window)
                 for t in ("trajectory", "streaming")]
    finally:
        shutil.rmtree(ckpt_root, ignore_errors=True)
    return {
        "scenario": scenario,
        "policy": policy,
        "o": scn.n_ost,
        "j": scn.nodes.shape[0],
        "fault_plan": fault_spec,
        "crash_window": crash_window,
        "modes": modes,
        "all_bitwise": all(m["bitwise_match"] for m in modes),
        "provenance": provenance(),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--scenario", default="fleet_noisy_neighbor")
    ap.add_argument("--duration-s", type=float, default=20.0)
    ap.add_argument("--policy", default="adaptbf")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="inject faults: outage:start=A,end=B,osts=K or "
                         "markov:mtbf=M,mttr=R,loss=P,seed=S")
    ap.add_argument("--crash-window", type=int, default=None, metavar="N",
                    help="save/kill/restore at window N "
                         "(default: mid-horizon)")
    ap.add_argument("--smoke", action="store_true",
                    help="short horizon for CI (duration-s=4)")
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        args.duration_s = min(args.duration_s, 4.0)
    report = run(args.scenario, args.duration_s, args.policy,
                 fault_spec=args.fault_plan, crash_window=args.crash_window)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if not report["all_bitwise"]:
        raise SystemExit("online run diverged from the offline oracle")


if __name__ == "__main__":
    main()

"""Chip smoke test: the fleet controller's main path on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded engine on four chips

One chip.  A seeded fleet of 256 OSTs x 4096 jobs (``scengen.random_fleet``,
profile "mixed") runs through the normal entry points:

* offline: ``simulate_fleet`` with the window megakernel
  (``serve_backend="mega"``, streaming telemetry, ``control="adaptbf"``)
  against the plain reference (per-tick scan engine, ``core`` allocator) on
  the same chip, compared on horizon totals under the rule the kernel tests
  use for generated scenarios; the lowered program must hold the Pallas
  kernel (``tpu_custom_call``);
* online: ``FleetService.ingest`` window by window, one ``save()``, a
  ``restore()`` into a fresh service, and on to the end; its streaming
  stats must equal the offline megakernel run bitwise (same program).

Four chips (``--four-chips``, this phase only).  ``simulate_fleet`` with
``partition="ost_shard"`` on the 4-chip ``ost`` mesh against
``partition="none"`` on one chip, and ``simulate_tenants`` with
``partition="fleet_shard"`` on a (2, 2) mesh against its unsharded run,
both bitwise; each device's ``bytes_in_use`` shows the state is spread.

Everything runs in this one process, and any failure exits non-zero.  The
script refuses to run without a TPU, with ``REPRO_FORCE_REF_KERNELS`` set
(which would route every kernel to its reference), or without the repo's
``src/`` beside it.  Timings it prints are smoke timings, not benchmark
numbers.  The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_OST, N_JOBS = 256, 4096
TRACE_S = 0.5          # generated trace: 5 windows of 10 ticks ...
N_WINDOWS = 36         # ... tiled over this horizon
CKPT_DIR = ROOT / ".chip_smoke"


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def preflight(n_chips: int):
    """Refuse to run anywhere but on ``n_chips`` TPU chips with the repo's
    own kernels."""
    if "REPRO_FORCE_REF_KERNELS" in os.environ:
        fail("REPRO_FORCE_REF_KERNELS is set; it routes every kernel to "
             "its reference, so the chip path would not run")
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    try:
        import repro.storage
    except ImportError:
        fail(f"the repro package is not under {src}; run from a checkout")
    found = Path(repro.storage.__file__).resolve().parents[2]
    if found != src:
        fail(f"imported repro from {found}, not from {src}")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < n_chips:
        fail(f"needs {n_chips} TPU chips, JAX found {len(devices)}")
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    return devices


def build_fleet(seed: int):
    from repro.storage.scengen import random_fleet

    t0 = time.perf_counter()
    scn = random_fleet(seed, n_ost=N_OST, n_jobs=N_JOBS, profile="mixed",
                       duration_s=TRACE_S)
    print(f"fleet: seed {seed}, (O, J) = ({N_OST}, {N_JOBS}), "
          f"{scn.issue_rate.shape[0]} ticks tiled to {N_WINDOWS} windows, "
          f"built in {time.perf_counter() - t0:.2f} s")
    return scn


def fleet_args(scn):
    """``simulate_fleet``'s array arguments, in order."""
    return (scn.nodes, scn.issue_rate, scn.volume, scn.capacity_per_tick,
            scn.max_backlog)


def served_totals(stats):
    """[O, J] horizon totals from the Kahan-compensated carry, float64."""
    import numpy as np
    return (np.asarray(stats.served_sum, np.float64)
            + np.asarray(stats.comp.served_sum, np.float64))


def assert_bitwise(a, b, what: str):
    import jax
    import numpy as np
    pa, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree.flatten(b)
    if ta != tb:
        raise AssertionError(f"{what}: pytree structures differ")
    bad = []
    for (path, x), y in zip(pa, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            bad.append(f"{jax.tree_util.keystr(path)}: {x.shape} {x.dtype} "
                       f"vs {y.shape} {y.dtype}")
        elif x.tobytes() != y.tobytes():
            n = int(np.sum(x != y)) if x.ndim else 1
            bad.append(f"{jax.tree_util.keystr(path)}: {n} of {x.size} "
                       "elements differ")
    if bad:
        raise AssertionError(f"{what}: " + "; ".join(bad))
    print(f"{what}: bitwise equal ({len(lb)} leaves)")


def assert_holds_kernel(fn, *args, what: str, **kwargs):
    """The lowered program of the jitted ``fn`` holds a Pallas kernel."""
    if "tpu_custom_call" not in fn.lower(*args, **kwargs).as_text():
        raise AssertionError(f"{what}: the lowered program holds no Pallas "
                             "kernel (tpu_custom_call)")
    print(f"{what}: lowered program holds tpu_custom_call")


def timed(fn, *args, **kwargs):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def offline_phase(scn, cfg):
    """Megakernel engine vs the plain reference on the same chip."""
    import numpy as np
    from repro.storage import simulate_fleet

    args = fleet_args(scn)
    assert_holds_kernel(simulate_fleet, cfg, *args, n_windows=N_WINDOWS,
                        what="offline mega")

    mega, first = timed(simulate_fleet, cfg, *args, n_windows=N_WINDOWS)
    _, steady = timed(simulate_fleet, cfg, *args, n_windows=N_WINDOWS)
    ref_cfg = cfg._replace(serve_backend="scan", alloc_backend="core")
    ref, ref_first = timed(simulate_fleet, ref_cfg, *args,
                           n_windows=N_WINDOWS)
    _, ref_steady = timed(simulate_fleet, ref_cfg, *args,
                          n_windows=N_WINDOWS)
    print(f"smoke timings (not benchmark numbers): mega compile+run "
          f"{first:.2f} s, steady {N_WINDOWS / steady:.1f} windows/s; "
          f"reference compile+run {ref_first:.2f} s, steady "
          f"{N_WINDOWS / ref_steady:.1f} windows/s")

    meg_oj, ref_oj = served_totals(mega.stats), served_totals(ref.stats)
    for name, x in (("mega", meg_oj), ("reference", ref_oj)):
        if x.shape != (N_OST, N_JOBS) or not np.isfinite(x).all() \
                or (x < 0).any():
            raise AssertionError(f"{name} served totals: shape {x.shape}, "
                                 "non-finite or negative entries")
    if int(mega.stats.windows) != N_WINDOWS:
        raise AssertionError(f"mega ran {int(mega.stats.windows)} windows")
    # the generated-scenario rule of tests/test_kernel_window_mega.py: a
    # remainder tie one ulp apart can flip an integer token and fork the
    # closed loop, so horizon totals carry the equivalence claim
    meg_j, ref_j = meg_oj.sum(axis=0), ref_oj.sum(axis=0)
    np.testing.assert_allclose(meg_j, ref_j, rtol=2e-2, atol=20.0,
                               err_msg="per-job served totals")
    np.testing.assert_allclose(meg_j.sum(), ref_j.sum(), rtol=5e-3,
                               err_msg="fleet served total")
    cap_h = (np.asarray(scn.capacity_per_tick, np.float64)
             * cfg.window_ticks * N_WINDOWS)
    if (meg_oj.sum(axis=1) > cap_h * (1 + 1e-5) + 1e-3).any():
        raise AssertionError("an OST served more than its capacity")
    print(f"offline mega vs reference: pass (fleet total {meg_j.sum():.1f}"
          f" vs {ref_j.sum():.1f} RPCs, largest per-job gap "
          f"{np.abs(meg_j - ref_j).max():.2f})")
    return mega


def online_phase(scn, cfg, offline):
    """FleetService through ingest, save, restore into a fresh service."""
    import jax
    import numpy as np
    from repro.storage import FleetService

    wt = cfg.window_ticks
    trace_windows = scn.issue_rate.shape[0] // wt
    shutil.rmtree(CKPT_DIR, ignore_errors=True)

    def service():
        return FleetService(cfg, scn.nodes, scn.volume,
                            capacity_per_tick=scn.capacity_per_tick,
                            max_backlog=scn.max_backlog,
                            checkpoint_dir=str(CKPT_DIR))

    def drive(svc, upto):
        times = []
        while svc.window < upto:
            s = (svc.window % trace_windows) * wt
            t0 = time.perf_counter()
            res = svc.ingest(lambda: scn.issue_rate[s:s + wt])
            jax.block_until_ready(svc.carry)
            times.append(time.perf_counter() - t0)
            if not res.delivered:
                raise AssertionError(f"window {svc.window} not delivered")
        return times

    svc = service()
    times = drive(svc, N_WINDOWS // 2)
    svc.save()
    del svc
    fresh = service()
    restored = fresh.restore()
    if restored != N_WINDOWS // 2:
        raise AssertionError(f"restored window {restored}")
    times_after = drive(fresh, N_WINDOWS)
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    # the first ingest of each service compiles its step
    steady = times[1:] + times_after[1:]
    print(f"smoke timings (not benchmark numbers): first ingest "
          f"{times[0]:.2f} s, median ingest "
          f"{1e3 * float(np.median(steady)):.2f} ms over {len(steady)} "
          "windows")
    assert_bitwise((fresh.stats, fresh.queue),
                   (offline.stats, offline.queue_final),
                   f"online (save at window {N_WINDOWS // 2}, restore) "
                   "vs offline mega")


def memory_spread(devices, what: str):
    used = [d.memory_stats()["bytes_in_use"] for d in devices]
    print(f"{what}: bytes_in_use per device {used}")
    if min(used) < 0.5 * max(used):
        raise AssertionError(f"{what}: state is not spread across devices")


def four_chip_phase(scn, cfg, devices):
    """The sharded engines on four chips against one chip, bitwise."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import ost_mesh
    from repro.storage import simulate_fleet, simulate_tenants

    devices = devices[:4]
    mesh = ost_mesh()
    if mesh.devices.size != 4:
        raise AssertionError(f"ost mesh over {mesh.devices.size} devices")
    specs = (P(), P(None, "ost", None), P("ost", None), P("ost"),
             P("ost", None))
    sharded_args = [jax.device_put(x, NamedSharding(mesh, s))
                    for x, s in zip(fleet_args(scn), specs)]
    shard_cfg = cfg._replace(partition="ost_shard")
    assert_holds_kernel(simulate_fleet, shard_cfg, *sharded_args,
                        n_windows=N_WINDOWS, what="ost_shard mega")
    sharded, t_shard = timed(simulate_fleet, shard_cfg, *sharded_args,
                             n_windows=N_WINDOWS)
    memory_spread(devices, "ost_shard")
    del sharded_args
    one_args = [jax.device_put(x, devices[0]) for x in fleet_args(scn)]
    single, t_one = timed(simulate_fleet, cfg, *one_args,
                          n_windows=N_WINDOWS)
    del one_args
    print(f"smoke timings (not benchmark numbers): ost_shard compile+run "
          f"{t_shard:.2f} s, one chip compile+run {t_one:.2f} s")
    assert_bitwise(sharded, single, "ost_shard on 4 chips vs one chip")
    del sharded, single

    # two tenants that differ in capacity; every other argument shared
    caps = np.stack([scn.capacity_per_tick,
                     np.float32(0.8) * scn.capacity_per_tick])
    args = (scn.nodes, scn.issue_rate, scn.volume, caps, scn.max_backlog)
    tenant_cfg = cfg._replace(partition="fleet_shard")
    batched, t_shard = timed(simulate_tenants, tenant_cfg, *args,
                             n_windows=N_WINDOWS, mesh_shape=(2, 2))
    memory_spread(devices, "fleet_shard (2, 2)")
    plain, t_one = timed(simulate_tenants, cfg, *args, n_windows=N_WINDOWS)
    print(f"smoke timings (not benchmark numbers): fleet_shard compile+run "
          f"{t_shard:.2f} s, unsharded compile+run {t_one:.2f} s")
    assert_bitwise(batched, plain, "fleet_shard on a (2, 2) mesh vs "
                   "unsharded")


def main():
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engines on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated fleet")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    devices = preflight(n_chips)

    from repro.storage import FleetConfig
    cfg = FleetConfig(control="adaptbf", serve_backend="mega",
                      telemetry="streaming")
    scn = build_fleet(args.seed)
    if args.four_chips:
        four_chip_phase(scn, cfg, devices)
    else:
        offline = offline_phase(scn, cfg)
        online_phase(scn, cfg, offline)
    device = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

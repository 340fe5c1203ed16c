"""Window-engine roofline: analytic bytes/FLOPs per control window per
serve backend vs the measured engine, against the reference accelerator's
memory-bandwidth bound.

For each serve backend (``scan`` | ``fused`` | ``mega``) and fleet shape
(O, J) this harness:

* builds an **analytic traffic model** of one control window -- how many
  HBM bytes must cross each backend's fusion boundaries (the whole point
  of the megakernel is shrinking exactly this number) and how many VPU
  flops the round executes;
* derives the **attainable windows/sec** on the device it runs on from
  that device's published peaks (``DEVICE_PEAKS``, keyed by
  ``device_kind``) as ``1 / max(bytes/BW, flops/peak)`` -- the
  better-of-neither bound a perfectly overlapped kernel cannot beat;
* **measures the achieved windows/sec** of ``simulate_fleet`` on that
  same device (compile excluded, median-of-k steady reps via
  ``_harness``).

A device whose kind has no entry in ``DEVICE_PEAKS`` (the CPU among them)
is refused before anything runs: a roofline share is a time on one chip
over that chip's own peaks, never a host time over an accelerator's.

Run:  PYTHONPATH=src:benchmarks python benchmarks/roofline_report.py \
          [--out BENCH_roofline.json] [--smoke] [--n-windows 5]

``--smoke`` shrinks to one (8, 128) cell per backend for the CI
bench-smoke job, which asserts the per-backend achieved/attainable
fields are present and finite.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.launch.roofline import HBM_BW, PEAK_FLOPS
from repro.storage import FleetConfig, simulate_fleet

from _harness import blocking, provenance, timeit_steady

SHAPES = ((64, 1024), (256, 4096))
BACKENDS = ("scan", "fused", "mega")

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
DEVICE_PEAKS = {
    "TPU v5 lite": {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
                    "source": "Google Cloud documentation, TPU v5e: "
                              "197 TFLOP/s bf16, 819 GB/s HBM"},
}


def device_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error, never a
    default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); a roofline share needs the chip it "
            "ran on") from None

#: Elementwise VPU ops per element per tick of the serve loop.  The scan
#: oracle's ``_serve_tick`` runs ~22 arithmetic passes (issue: 4, phase 1:
#: 7 + reduction, phase 2: 7 + reduction, clamps: 2); the megakernel's
#: runtime-specialized loop averages ~14 (ruledness hoisted, dead phase
#: and volume tracking skipped, final clamp proven away).
SERVE_OPS_PER_TICK = {"scan": 22.0, "fused": 22.0, "mega": 14.0}

#: Elementwise ops per element for one three-step allocation round.  Each
#: ``core/remainder.integerize`` costs ~160 passes (floor/delta bookkeeping
#: ~10, top-k threshold probe search ~25 probes x 3, excess bit-descent
#: ~25 iterations x 3) and the surrounding ``_alloc_block`` body ~60.  The
#: full round pays three distributions; the megakernel's specialized round
#: (merged up/down top-k, ``lax.cond``-gated surplus/re-compensation
#: distributions that a saturated steady state skips every window) pays
#: about one.
ALLOC_OPS = {"scan": 540.0, "fused": 540.0, "mega": 220.0}

#: gate + observation select + policy-state update, all backends.
ROUND_OPS = 20.0


def window_model(backend: str, o: int, j: int, w: int) -> dict:
    """Analytic HBM bytes and VPU flops for ONE control window.

    Traffic inventory (f32, E = O*J elements; every backend reads the
    [W, O, J] rate trace once and writes 4 trajectory rows):

    * ``scan``: the per-tick ``lax.scan`` round-trips its carry (queue,
      volume, budget, served-accumulator) through HBM every tick -- 8 E
      per tick -- plus the gate/observe/allocate phase boundaries (~25 E).
    * ``fused``: the serve kernel holds the carry in VMEM across the
      window (3 E in + 3 E out, total) but the control round still
      crosses gate -> serve -> observe -> allocate boundaries (~31 E).
    * ``mega``: one invocation for the whole round -- engine state and
      policy state stream in once (11 E) and out once (11 E); only the
      trajectory stack (4 E) is extra.
    """
    e = float(o) * j
    b = 4.0
    rates = w * e * b
    traffic = {
        "scan": (8.0 * w + 25.0) * e * b,
        "fused": 31.0 * e * b,
        "mega": 26.0 * e * b,
    }[backend]
    telemetry = 4.0 * e * b
    hbm_bytes = rates + traffic + telemetry
    flops = (SERVE_OPS_PER_TICK[backend] * w + ALLOC_OPS[backend]
             + ROUND_OPS) * e
    return {
        "hbm_bytes_per_window": hbm_bytes,
        "flops_per_window": flops,
        "arithmetic_intensity": flops / hbm_bytes,
    }


def attainable(model: dict, peaks: dict) -> dict:
    """Roofline of the device with ``peaks``: windows/sec if the only cost
    were HBM traffic (memory bound) or VPU issue (compute bound), and the
    binding minimum of the two."""
    mem_s = model["hbm_bytes_per_window"] / peaks["hbm_bw"]
    comp_s = model["flops_per_window"] / peaks["peak_flops"]
    bound_s = max(mem_s, comp_s)
    return {
        "memory_bound_windows_per_s": 1.0 / mem_s,
        "compute_bound_windows_per_s": 1.0 / comp_s,
        "attainable_windows_per_s": 1.0 / bound_s,
        "attainable_bound": "memory" if mem_s >= comp_s else "compute",
    }


def _case(o: int, j: int, n_windows: int, window_ticks: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    t = n_windows * window_ticks
    nodes = jnp.asarray(rng.integers(1, 64, (j,)), jnp.float32)
    rates = jnp.asarray(rng.integers(0, 4, (t, o, j)), jnp.float32)
    volume = jnp.full((o, j), jnp.inf, jnp.float32)
    return nodes, rates, volume


def run_cell(o: int, j: int, backend: str, n_windows: int, peaks: dict,
             window_ticks: int = 10, reps: int = 3) -> dict:
    cfg = FleetConfig(control="adaptbf", serve_backend=backend,
                      window_ticks=window_ticks)
    nodes, rates, volume = _case(o, j, n_windows, window_ticks)
    t = timeit_steady(blocking(simulate_fleet, cfg, nodes, rates, volume),
                      reps=reps)
    model = window_model(backend, o, j, window_ticks)
    bound = attainable(model, peaks)
    achieved = n_windows / t["wall_s"]
    return {
        "o": o,
        "j": j,
        "serve_backend": backend,
        "n_windows": n_windows,
        "window_ticks": window_ticks,
        "model": model,
        **bound,
        "achieved_windows_per_s": achieved,
        "achieved_frac_of_attainable":
            achieved / bound["attainable_windows_per_s"],
        **t,
    }


def sweep(shapes=SHAPES, backends=BACKENDS, n_windows: int = 5,
          window_ticks: int = 10) -> dict:
    device_kind = jax.devices()[0].device_kind
    peaks = device_peaks(device_kind)
    cells = []
    for o, j in shapes:
        for backend in backends:
            cell = run_cell(o, j, backend, n_windows, peaks, window_ticks)
            cells.append(cell)
            print(f"  O={o:4d} J={j:5d} {backend:5s}: "
                  f"achieved {cell['achieved_windows_per_s']:8.2f} w/s, "
                  f"attainable {cell['attainable_windows_per_s']:10.1f} w/s "
                  f"({cell['attainable_bound']}-bound, "
                  f"{cell['model']['hbm_bytes_per_window'] / 2**20:.1f} "
                  f"MiB/window)", flush=True)

    # the headline: per shape, bytes-ratio and measured-ratio scan -> mega
    headline = {}
    for o, j in shapes:
        by = {c["serve_backend"]: c for c in cells
              if (c["o"], c["j"]) == (o, j)}
        if "scan" in by and "mega" in by:
            headline[f"{o}x{j}"] = {
                "bytes_ratio_scan_over_mega":
                    by["scan"]["model"]["hbm_bytes_per_window"]
                    / by["mega"]["model"]["hbm_bytes_per_window"],
                "achieved_ratio_mega_over_scan":
                    by["mega"]["achieved_windows_per_s"]
                    / by["scan"]["achieved_windows_per_s"],
            }
    return {
        "config": {
            "shapes": [list(s) for s in shapes],
            "backends": list(backends),
            "n_windows": n_windows,
            "window_ticks": window_ticks,
        },
        "hardware_model": {"device_kind": device_kind, **peaks},
        "provenance": provenance(),
        "cells": cells,
        "headline": headline,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny (8, 128) cell per backend for CI")
    ap.add_argument("--n-windows", type=int, default=5)
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        report = sweep(shapes=((8, 128),), n_windows=2)
    else:
        report = sweep(n_windows=args.n_windows)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

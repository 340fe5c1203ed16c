"""Allocation + service hot-path scaling benchmark: fleet shape (O, J) x
backend sweep for ``simulate_fleet``.

For every grid cell it runs a saturated adaptbf fleet (every job demanding
more than its share, so all three allocator steps and both service phases
stay hot) under each (alloc_backend, serve_backend) combination, measures
steady-state wall clock (compile excluded via a warmup run), and writes
``BENCH_alloc_scaling.json`` with windows/sec, wall-clock per simulated
second, and the OST block shapes the kernel dispatchers picked -- the
"peak shape" record that J=4096 runs with 8-row blocks, which the old
O(J^2) rank matrix could not fit at any block size.

The ``--reference-windows-per-s`` flag embeds an externally measured
baseline (e.g. the pre-PR simulator on the same machine) so the report can
state the speedup at the canonical (O=64, J=1024) cell; committed artifacts
should note the provenance in ``--reference-note``.

Run:  PYTHONPATH=src python benchmarks/alloc_scaling.py \
          [--out BENCH_alloc_scaling.json] [--smoke] \
          [--reference-windows-per-s 12.59] [--reference-note "..."]

``--smoke`` shrinks the grid to one tiny cell per backend combination --
seconds on CPU (Pallas interpret mode), used by the CI bench-smoke job so
this harness cannot rot.

Backend provenance off-TPU: ``alloc_backend="pallas"`` cells time the
Pallas *interpret* trace (the blocked kernel math lowered through XLA --
a real, often faster formulation on CPU, but not the Mosaic artifact),
while ``serve_backend="fused"`` cells time the fused XLA fallback the
simulator actually dispatches to off-TPU.  The ``serve_backend="mega"``
cells time the whole-round megakernel's blocked XLA fallback
(``kernels/window_mega``): gate + ticks + observation + allocation in one
invocation per window, runtime-specialized serve/alloc branches included.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.launch.compile_cache import use_compile_cache
from repro.storage import FleetConfig, simulate_fleet

from _harness import blocking, provenance, timeit_steady

GRID_O = (16, 64, 256)
GRID_J = (128, 1024, 4096)
BACKENDS = (  # (alloc_backend, serve_backend)
    ("core", "scan"),    # the pre-PR configuration (vmapped core + tick scan)
    ("core", "fused"),
    ("pallas", "scan"),
    ("pallas", "fused"),
    ("core", "mega"),    # fused control round (alloc_backend is ignored:
                         #   the allocator runs inside the megakernel)
)
REFERENCE_SHAPE = (64, 1024)  # the acceptance cell for speedup reporting


def _case(o: int, j: int, n_windows: int, window_ticks: int, seed: int = 0):
    """Saturated fleet inputs: integer rate traces with aggregate demand a
    few times the service capacity."""
    rng = np.random.default_rng(seed)
    t = n_windows * window_ticks
    nodes = jnp.asarray(rng.integers(1, 64, (j,)), jnp.float32)
    rates = jnp.asarray(rng.integers(0, 4, (t, o, j)), jnp.float32)
    volume = jnp.full((o, j), jnp.inf, jnp.float32)
    return nodes, rates, volume


def run_cell(o: int, j: int, alloc_backend: str, serve_backend: str,
             n_windows: int, window_ticks: int = 10, reps: int = 3):
    cfg = FleetConfig(control="adaptbf", window_ticks=window_ticks,
                      alloc_backend=alloc_backend,
                      serve_backend=serve_backend)
    nodes, rates, volume = _case(o, j, n_windows, window_ticks)
    t = timeit_steady(blocking(simulate_fleet, cfg, nodes, rates, volume),
                      reps=reps)

    sim_seconds = n_windows * window_ticks * cfg.tick_seconds
    # every kernel blocks OST rows by the same rule; the megakernel blocks
    # serve AND alloc at once
    block = dispatch.block_rows(o)
    return {
        "o": o,
        "j": j,
        "alloc_backend": alloc_backend,
        "serve_backend": serve_backend,
        "n_windows": n_windows,
        "windows_per_s": n_windows / t["wall_s"],
        "wall_per_sim_s": t["wall_s"] / sim_seconds,
        "alloc_block_o": block,
        "serve_block_o": block,
        **t,
    }


def sweep(grid_o=GRID_O, grid_j=GRID_J, backends=BACKENDS,
          n_windows: int = 10, window_ticks: int = 10,
          reference_windows_per_s: float = None, reference_note: str = ""):
    cells = []
    for o in grid_o:
        for j in grid_j:
            # bound the biggest cells: fewer simulated windows, same math
            nw = n_windows if o * j < 256 * 4096 else max(2, n_windows // 2)
            for alloc_backend, serve_backend in backends:
                cell = run_cell(o, j, alloc_backend, serve_backend, nw,
                                window_ticks)
                cells.append(cell)
                print(f"  O={o:4d} J={j:5d} {alloc_backend}+{serve_backend}"
                      f": {cell['windows_per_s']:8.2f} windows/s "
                      f"(block_o alloc={cell['alloc_block_o']} "
                      f"serve={cell['serve_block_o']})", flush=True)

    peak = {}
    for c in cells:
        key = f"{c['alloc_backend']}+{c['serve_backend']}"
        if key not in peak or c["o"] * c["j"] > peak[key]["o"] * peak[key]["j"]:
            peak[key] = {k: c[k] for k in
                         ("o", "j", "alloc_block_o", "serve_block_o")}

    report = {
        "config": {
            "grid_o": list(grid_o),
            "grid_j": list(grid_j),
            "backends": [list(b) for b in backends],
            "window_ticks": window_ticks,
        },
        "provenance": provenance(),
        "cells": cells,
        "peak_shape": peak,
    }

    ref_cells = [c for c in cells
                 if (c["o"], c["j"]) == REFERENCE_SHAPE]
    if ref_cells:
        best = max(ref_cells, key=lambda c: c["windows_per_s"])
        report["reference_cell"] = {
            "o": REFERENCE_SHAPE[0], "j": REFERENCE_SHAPE[1],
            "best_backend":
                f"{best['alloc_backend']}+{best['serve_backend']}",
            "best_windows_per_s": best["windows_per_s"],
        }
        if reference_windows_per_s:
            report["reference_cell"]["baseline_windows_per_s"] = (
                reference_windows_per_s)
            report["reference_cell"]["baseline_note"] = reference_note
            report["reference_cell"]["speedup_vs_baseline"] = (
                best["windows_per_s"] / reference_windows_per_s)
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI grid: one (8, 128) cell per backend combo")
    ap.add_argument("--n-windows", type=int, default=10)
    ap.add_argument("--reference-windows-per-s", type=float, default=None,
                    help="externally measured baseline windows/sec at "
                         "(O=64, J=1024) to report speedup against")
    ap.add_argument("--reference-note", default="",
                    help="provenance of the baseline measurement")
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        report = sweep(grid_o=(8,), grid_j=(128,), n_windows=2)
    else:
        report = sweep(n_windows=args.n_windows,
                       reference_windows_per_s=args.reference_windows_per_s,
                       reference_note=args.reference_note)
    text = json.dumps(report, indent=2, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

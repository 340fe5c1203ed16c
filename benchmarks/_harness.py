"""Shared measurement harness for the benchmark scripts.

Every harness in this directory needs the same two things and they must
not drift per-script:

* **timing discipline** -- one warmup invocation (compile + first run,
  reported separately as ``compile_s``) followed by ``reps`` steady-state
  repetitions under ``jax.block_until_ready``, reporting the *median* (a
  single descheduled rep skews a mean; a lucky rep skews a min) plus the
  raw samples so a reader can judge the spread;
* **provenance stamping** -- jax version, the device the run used
  (``platform``, ``device_kind``, device count), the repo git SHA, and
  the exact argv, so a committed ``BENCH_*.json`` can be re-run and
  compared years later.

Import as ``from _harness import ...`` (benchmark scripts run with this
directory on ``sys.path``).
"""
from __future__ import annotations

import subprocess
import sys
import time

import jax
import numpy as np


def git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, check=True).stdout.strip()
    except Exception:
        return "unknown"


def provenance(cfg=None, **extra) -> dict:
    """The stamp every committed benchmark artifact carries.  ``cfg`` is
    an optional ``FleetConfig`` (recorded as a dict); ``extra`` lands in
    the stamp verbatim."""
    device = jax.devices()[0]
    info = {
        "jax_version": jax.__version__,
        "jax_backend": jax.default_backend(),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": jax.device_count(),
        "git_sha": git_sha(),
        "argv": list(sys.argv),
    }
    if cfg is not None:
        info["fleet_config"] = cfg._asdict()
    info.update(extra)
    return info


def timeit_steady(run, reps: int = 3) -> dict:
    """Compile-vs-steady timing split with median-of-``reps`` steady wall.

    ``run`` must block until its results are ready (wrap the jitted call
    in ``blocking``).  The first invocation pays compilation and is
    reported as ``compile_s``; ``wall_s`` is the median of the steady
    repetitions and ``walls_s`` the raw samples.
    """
    t0 = time.perf_counter()
    run()
    compile_s = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        walls.append(time.perf_counter() - t0)
    return {"compile_s": compile_s, "wall_s": float(np.median(walls)),
            "walls_s": walls}


def blocking(fn, *args, **kwargs):
    """A zero-argument thunk that runs ``fn(*args, **kwargs)`` and blocks
    until every output buffer is ready -- the only shape ``timeit_steady``
    accepts, so async dispatch can never leak into a timing."""
    return lambda: jax.block_until_ready(fn(*args, **kwargs))

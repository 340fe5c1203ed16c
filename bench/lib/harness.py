"""Find a cell's files by name, run it, and print its result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  The configuration's file is the one its
entry in ``configs`` gives; the traffic mix is ``traffic/<name>.json``, which
names its profile (``profiles/<name>.json``) and the mode that drives it
(``modes/<name>.py``); each per-layer metric is read by
``metrics/<name>.py``, or, where there is none, by the reader of its base
name, the part before the first dot (``device_idle_share.replay`` by
``metrics/device_idle_share.py``).  Nothing here names a cell, so a later
change adds a cell, a mix, a profile or a metric by adding files and
entries.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, NamedTuple

ROOT = Path(__file__).resolve().parents[1]    # the benchmark's directory
REPO = ROOT.parent                            # the checkout


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown device, missing
    program or file).  The entry point exits non-zero on it."""


# ---------------------------------------------------------------- loading


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(kind: str, name: str, root: Path = ROOT):
    """``<root>/<kind>/<name>.py`` as a module (names may hold dots); a
    metric without a file of its own is read by its base name's."""
    path = Path(root) / kind / f"{name}.py"
    if not path.is_file() and kind == "metrics":
        path = Path(root) / kind / f"{name.split('.')[0]}.py"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} file for {name!r} in "
                         f"{Path(root) / kind}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic mix's contents
    profile: dict         # the profile the mix draws from
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def resolve(bench: dict, name: str, repo: Path = REPO,
            root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``bench`` with its files loaded."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    wl = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = load_json(Path(repo) / entry["file"])
    traffic = load_json(Path(root) / "traffic" / f"{wl['traffic']}.json")
    profile = load_json(Path(root) / "profiles" / f"{traffic['profile']}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, int(wl["chips"]), config, traffic, profile, e2e,
                per_layer, Path(root))


# ------------------------------------------------------------ the window


class Spans:
    """The benchmark's own host spans: durations on the host clock, and,
    while a trace runs, ``TraceAnnotation``s on the profiler's clock."""

    def __init__(self):
        self.tracing = False
        self.seconds: Dict[str, List[float]] = defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.seconds[name].append(time.perf_counter() - t0)


class Check(NamedTuple):
    """One number compared with its limit; it passes when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class TraceRun(NamedTuple):
    """What a per-layer metric reader may read."""

    reduction: object         # tracing.Reduction of the traced window
    windows: int              # control windows completed while tracing
    floor_bytes: int          # bytes one window must move at the least
    peaks: dict               # the chip's published peaks
    spans: Dict[str, List[float]]   # host span durations, seconds


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0..1) with linear interpolation."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return statistics.median(values) if values else float("nan")


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory in the checkout.  Every program is
    cached, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_devices(chips: int, require_tpu: bool = True):
    """The devices the cell runs on; refuses anything but enough TPUs."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def import_program():
    """Put the checkout's ``src/`` first on the path and import the
    program."""
    src = REPO / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"the program is not in {src}")
    sys.path.insert(0, str(src))
    import repro.storage  # noqa: F401


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> dict:
    """Set the cell up, measure it, check it, and return its result."""
    from lib.peaks import device_peaks

    spans = Spans()
    with spans("bench.setup.devices"):
        devices = find_devices(cell.chips, require_tpu)
    kind = devices[0].device_kind
    try:
        peaks = device_peaks(kind)
    except KeyError as e:
        if require_tpu:
            raise BenchError(str(e)) from None
        peaks = None
    if require_tpu:
        use_compile_cache()
    with spans("bench.setup.program"):
        import_program()
    mode = load_module("modes", cell.traffic["mode"], cell.root).Mode(
        cell, seed, spans)

    window_s = float(seconds)
    if trace:
        window_s = min(window_s, float(cell.traffic["trace_seconds"]))
        trace_dir = REPO / ".bench_trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    # set-up's garbage goes now, and what survives it stays out of the
    # window's collections: a full pass over the traced programs' objects
    # stalls the host for about 0.1 s
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    setup_parts = {k[len("bench.setup."):]: sum(v)
                   for k, v in spans.seconds.items()
                   if k.startswith("bench.setup.")}
    mode.start_window()
    spans.seconds.clear()
    if trace:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the benchmark's spans only
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        spans.tracing = True
    t0 = time.perf_counter()
    with spans("bench.window"):
        while time.perf_counter() - t0 < window_s:
            mode.step()
    elapsed = time.perf_counter() - t0
    if trace:
        spans.tracing = False
        jax.profiler.stop_trace()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    metrics = {}
    if trace:
        from lib import tracing
        xspace = tracing.find_xspace(trace_dir)
        dev_events, host_spans = tracing.read_xspace(xspace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = tracing.reduce_events(dev_events, host_spans)
        run = TraceRun(red, mode.windows, mode.floor_bytes, peaks,
                       dict(spans.seconds))
        for m in cell.per_layer:
            value = load_module("metrics", m["name"], cell.root).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = dict(mode.metrics(elapsed), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    with spans("bench.check"):
        checks = mode.check()
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    print("setup " + " ".join(f"{k} {v:.3f} s" for k, v in
                              setup_parts.items()) + f" of {setup_s:.3f} s",
          file=sys.stderr)
    for name, secs in sorted(spans.seconds.items()):
        print(f"span {name} n {len(secs)} median {1e3 * median(secs):.3f} ms"
              f" min {1e3 * min(secs):.3f} max {1e3 * max(secs):.3f}",
              file=sys.stderr)
    result = {"correct": bool(checks) and all(c.ok for c in checks)
              and mode.attempted > 0,
              "attempted": mode.attempted, "failed": mode.failed,
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
    result["checks"] = {c.name: {"value": finite(c.value), "limit": c.limit}
                        for c in checks}
    return result


def finite(x: float) -> float:
    """A JSON-safe number: a non-finite reading prints as the largest
    float, which fails every limit."""
    x = float(x)
    return x if math.isfinite(x) else sys.float_info.max

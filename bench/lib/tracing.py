"""From a profiler trace to the benchmark's device numbers.

The JAX profiler writes an XSpace (``*.xplane.pb``).  Its device planes
(``/device:TPU:<n>``) carry one event per device operation; the host plane
carries the benchmark's own spans (``jax.profiler.TraceAnnotation`` named
``bench.*``) on the same clock.  This module reduces them to:

* busy time: the union of the intervals in which an operation ran on a
  device, clipped to the traced window and averaged over the chips used;
* the idle share: 1 - busy / window;
* the device time per control window: busy time over windows completed;
* the device operations that took the most time (``breakdown.device_ops``),
  by self time: a loop's events enclose its body's, and each second counts
  once, for the innermost operation;
* the idle gaps, cut where a benchmark span starts or ends, each piece named
  by the innermost span that covers it, summed by name
  (``breakdown.idle_gaps``).

Gaps that fall in the benchmark's own read-back for its correctness check
(``bench.check_copy``) are not the system's idle time: they leave the
traced window, which shrinks by their length.

The reduction works on plain ``(name, start_ns, end_ns)`` lists, so it can be
checked on hand-made events as well as on a recorded trace.
"""
from __future__ import annotations

import bisect
import glob
from pathlib import Path
from typing import Dict, List, NamedTuple, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, end ns

#: the device line that holds one event per operation
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NOT_MEASURED = ("bench.check_copy",)
UNNAMED_GAP = "host:unannotated"
TOP = 10


class Reduction(NamedTuple):
    busy_s: float                        # device busy, mean over chips
    window_s: float                      # traced window, less read-backs
    device_ops: List[Tuple[str, float]]  # top operations by device seconds
    idle_gaps: List[Tuple[str, float]]   # idle seconds by host span
    n_device_events: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union_ns(intervals: Sequence[Tuple[float, float]], lo: float,
             hi: float) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as sorted
    disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps_ns(busy: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """The complement of disjoint sorted ``busy`` within ``[lo, hi]``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def self_ns(events: Sequence[Event], lo: float, hi: float) -> Dict[str,
                                                                   float]:
    """Per-name device time clipped to ``[lo, hi]``, less the time of the
    events nested inside each one."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, own ns so far]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + entry[2]

    for name, a, b in sorted(((n, max(a, lo), min(b, hi))
                              for n, a, b in events), key=lambda e:
                             (e[1], -e[2])):
        if b <= a:
            continue
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    for entry in stack:
        close(entry)
    return out


def _name_gap(spans: Sequence[Event], mid: float) -> str:
    """The innermost (shortest) span that covers ``mid``."""
    best, best_len = UNNAMED_GAP, float("inf")
    for name, a, b in spans:
        if a <= mid <= b and b - a < best_len and name != WINDOW_SPAN:
            best, best_len = name, b - a
    return best


def _gap_pieces(a: float, b: float, cuts: Sequence[float],
                names: Sequence[str]):
    """(name, ns) pieces of the gap ``[a, b]`` along the named timeline."""
    i = bisect.bisect_right(cuts, a) - 1
    t = a
    while t < b:
        inside = 0 <= i < len(names)
        end = min(cuts[i + 1] if i + 1 < len(cuts) else b, b)
        yield (names[i] if inside else UNNAMED_GAP), end - t
        t, i = end, i + 1


def reduce_events(device_planes: Sequence[Sequence[Event]],
                  host_spans: Sequence[Event],
                  window: Tuple[float, float] = None) -> Reduction:
    """Reduce device operation events (one list per chip) and host spans.

    ``window`` defaults to the ``bench.window`` span, else to the extent of
    the device events."""
    if window is None:
        marks = [(a, b) for n, a, b in host_spans if n == WINDOW_SPAN]
        if marks:
            window = (min(a for a, _ in marks), max(b for _, b in marks))
        else:
            evs = [e for plane in device_planes for e in plane]
            window = (min(e[1] for e in evs), max(e[2] for e in evs))
    lo, hi = window
    if not device_planes or hi <= lo:
        raise ValueError("no device events or an empty window")
    busy_ns, op_ns, gap_ns, skipped_ns = 0.0, {}, {}, 0.0
    # the timeline cut at every span boundary, each piece named once
    cuts = sorted({t for _, a, b in host_spans for t in (a, b)})
    names = [_name_gap(host_spans, 0.5 * (a + b))
             for a, b in zip(cuts, cuts[1:])]
    for plane in device_planes:
        busy = union_ns([(a, b) for _, a, b in plane], lo, hi)
        busy_ns += sum(b - a for a, b in busy)
        for name, d in self_ns(plane, lo, hi).items():
            op_ns[name] = op_ns.get(name, 0.0) + d
        for a, b in gaps_ns(busy, lo, hi):
            for name, d in _gap_pieces(a, b, cuts, names):
                if name in NOT_MEASURED:
                    skipped_ns += d
                else:
                    gap_ns[name] = gap_ns.get(name, 0.0) + d
    n = len(device_planes)

    def top(d: Dict[str, float]):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v / n * 1e-9] for k, v in items]

    return Reduction(busy_s=busy_ns / n * 1e-9,
                     window_s=(hi - lo - skipped_ns / n) * 1e-9,
                     device_ops=top(op_ns), idle_gaps=top(gap_ns),
                     n_device_events=sum(len(p) for p in device_planes))


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read_xspace(path) -> Tuple[List[List[Event]], List[Event]]:
    """(device op events per TPU plane, ``bench.*`` host spans) of one
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(op_name(e.name), e.start_ns, e.end_ns)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def find_xspace(trace_dir) -> Path:
    found = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])

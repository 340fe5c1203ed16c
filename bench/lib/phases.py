"""The program's own spans and phases, read from a profiler trace.

The program names the parts of its control round.  On the device,
``storage/simulator.window_step`` lowers each phase inside
``jax.named_scope``: ``fleet.serve`` (the gate and the serve ticks),
``fleet.allocate`` (the observation select and the policy step) and
``fleet.telemetry`` (the streaming fold, or the trajectory outputs), so
every HLO instruction's ``op_name`` holds the phase it came from.  On the
host, ``storage/service.FleetService`` marks each round with
``TraceAnnotation``s (``fleet.fetch``, ``fleet.copy``, ``fleet.dispatch``)
on the clock of the device planes.  From one ``.xplane.pb`` this module
reads:

* the ``fleet.*`` host spans;
* the ``XLA Modules`` events of each TPU plane, one per launch of a
  compiled program;
* the device self time of each operation, put down to a phase.

The phase rule: an operation's HLO instruction, and every instruction of
the computations it calls, recursively (a fusion's fused computation, a
loop's body, a scatter's combiner), each give the outermost
``fleet.<phase>`` segment of their ``op_name``.  One phase found places
the operation there; several make it ``mixed``; none leaves it
``unplaced``.  The instructions come from each module's ``HloProto`` on the
``/host:metadata`` plane, decoded here from the protobuf wire format (the
benchmark imports no TensorFlow), so nothing keys on fusion names, which
change with every compile.  Self time is ``tracing.self_ns`` over the
traced window, as for ``breakdown.device_ops``.
"""
from __future__ import annotations

import bisect
import re
import statistics
from pathlib import Path
from typing import Dict, Iterator, List, NamedTuple, Tuple

from lib import tracing
from lib.tracing import Event

SPAN_PREFIX = "fleet."
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
MIXED = "mixed"
UNPLACED = "unplaced"
TOP = 10

#: the phase scope within one ``op_name`` path segment
_PHASE = re.compile(r"(?:^|\()(fleet\.[A-Za-z_]+)")


class Phases(NamedTuple):
    """What one trace says of the program's round.

    self_s:    device self seconds by phase, ``mixed`` and ``unplaced``,
               mean over chips.
    loose_ops: the largest mixed and unplaced operations, (label,
               ``module/op``, seconds).
    spans:     the program's ``fleet.*`` host spans, in order of start.
    modules:   the ``XLA Modules`` events of each TPU plane, in order.
    """

    self_s: Dict[str, float]
    loose_ops: List[Tuple[str, str, float]]
    spans: List[Event]
    modules: List[List[Event]]

    @property
    def placed_share(self) -> float:
        """The share of device self time placed in one phase."""
        total = sum(self.self_s.values())
        loose = self.self_s.get(MIXED, 0.0) + self.self_s.get(UNPLACED, 0.0)
        return (total - loose) / total if total > 0 else float("nan")


# ----------------------------------------------------- protobuf wire format


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _ints(value) -> List[int]:
    """A repeated integer field's one occurrence, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def _str(value) -> str:
    return bytes(value).decode("utf-8", "replace")


# ------------------------------------------------------------ the HLO side


def phase_of(op_name: str) -> frozenset:
    """The outermost ``fleet.<phase>`` of each name in an ``op_name``
    (XLA joins the names of merged instructions with ``;``)."""
    found = set()
    for name in op_name.split(";"):
        for segment in name.split("/"):
            m = _PHASE.search(segment)
            if m:
                found.add(m.group(1))
                break
    return frozenset(found)


def _label(found) -> str:
    if not found:
        return UNPLACED
    return next(iter(found)) if len(found) == 1 else MIXED


def hlo_labels(hlo_proto) -> Dict[str, str]:
    """Each instruction of a serialized ``HloProto`` by name, with its
    phase label under the phase rule.

    Fields read: HloProto.hlo_module (1); HloModuleProto.computations (3);
    HloComputationProto.instructions (2), id (5); HloInstructionProto.name
    (1), metadata (7), called_computation_ids (38); OpMetadata.op_name
    (2)."""
    comps: Dict[int, List[Tuple[str, frozenset, List[int]]]] = {}
    for f, module in _fields(memoryview(hlo_proto)):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            cid, insts = None, []
            for h, v in _fields(comp):
                if h == 5:
                    cid = v
                elif h == 2:
                    name, own, called = "", frozenset(), []
                    for k, x in _fields(v):
                        if k == 1:
                            name = _str(x)
                        elif k == 7:
                            own = frozenset().union(*(
                                phase_of(_str(y)) for a, y in _fields(x)
                                if a == 2))
                        elif k == 38:
                            called.extend(_ints(x))
                    insts.append((name, own, called))
            comps[cid] = insts

    memo: Dict[int, frozenset] = {}

    def inside(cid: int) -> frozenset:
        """Every phase of a computation and of what it calls."""
        if cid not in memo:
            memo[cid] = frozenset()          # HLO calls form no cycles
            memo[cid] = frozenset().union(*(
                own.union(*(inside(c) for c in called))
                for _, own, called in comps.get(cid, ())))
        return memo[cid]

    return {name: _label(own.union(*(inside(c) for c in called)))
            for insts in comps.values() for name, own, called in insts}


def read_modules(raw) -> Dict[str, bytes]:
    """Each module's serialized ``HloProto`` by module name (``jit_f(<fp>)``,
    as the device's ``XLA Modules`` events name it), from the bytes of an
    XSpace.

    Fields read: XSpace.planes (1); XPlane.name (2), event_metadata (4,
    map entries: value 2), stat_metadata (5, map entries: value 2);
    XEventMetadata.name (2), stats (5); XStatMetadata.id (1), name (2);
    XStat.metadata_id (1), bytes_value (6)."""
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name = next((_str(v) for g, v in _fields(plane) if g == 2), "")
        if name != METADATA_PLANE:
            continue
        events, stat_names = [], {}
        for g, entry in _fields(plane):
            if g not in (4, 5):
                continue
            value = next((v for h, v in _fields(entry) if h == 2), b"")
            if g == 5:
                meta = dict(_fields(value))
                stat_names[meta.get(1, 0)] = _str(meta.get(2, b""))
            else:
                events.append(value)
        out = {}
        for value in events:
            module, stats = "", []
            for h, v in _fields(value):
                if h == 2:
                    module = _str(v)
                elif h == 5:
                    stats.append(dict(_fields(v)))
            for stat in stats:
                if stat_names.get(stat.get(1)) == HLO_PROTO_STAT and \
                        6 in stat:
                    out[module] = bytes(stat[6])
        return out
    return {}


# --------------------------------------------------------------- the trace


def _module_at(starts, modules, t):
    """The module event on one plane that covers time ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][2]:
        return modules[i][0]
    return None


def _window(ops, marks):
    """The ``bench.window`` span, else the extent of the device events, as
    ``tracing.reduce_events`` takes it."""
    if marks:
        return min(a for _, a, _ in marks), max(b for _, _, b in marks)
    return (min(e[2] for plane in ops for e in plane),
            max(e[3] for plane in ops for e in plane))


def reduce_phases(ops: List[List[Tuple[str, str, float, float]]],
                  modules: List[List[Event]],
                  labels: Dict[str, Dict[str, str]],
                  spans: List[Event], window) -> Phases:
    """Device self time by phase.  ``ops`` holds one list per chip of
    (module, op, start ns, end ns); ``labels`` each module's phase label of
    each instruction; ``spans`` the host spans in order of start;
    ``window`` the (start, end) ns to clip to."""
    lo, hi = window
    by_op: Dict[Tuple[str, str], float] = {}
    for plane in ops:
        events = [((m, o), a, b) for m, o, a, b in plane]
        for key, ns in tracing.self_ns(events, lo, hi).items():
            by_op[key] = by_op.get(key, 0.0) + ns
    n = max(len(ops), 1)
    self_s: Dict[str, float] = {}
    loose = []
    for (module, op), ns in by_op.items():
        label = labels.get(module, {}).get(op, UNPLACED)
        self_s[label] = self_s.get(label, 0.0) + ns / n * 1e-9
        if label in (MIXED, UNPLACED):
            loose.append((label, f"{module}/{op}", ns / n * 1e-9))
    loose.sort(key=lambda x: -x[2])
    return Phases(self_s, loose[:TOP], spans, modules)


def read_phases(path) -> Phases:
    """The program's spans and phases of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    labels = {name: hlo_labels(proto)
              for name, proto in read_modules(raw).items()}
    pd = ProfileData.from_serialized_xspace(raw)
    ops, modules, spans, marks = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            lines = {line.name: line for line in plane.lines}
            mods = sorted(((e.name, e.start_ns, e.end_ns)
                           for e in (lines[MODULES_LINE].events
                                     if MODULES_LINE in lines else ())),
                          key=lambda e: e[1])
            starts = [a for _, a, _ in mods]
            plane_ops = []
            if tracing.OPS_LINE in lines:
                for e in lines[tracing.OPS_LINE].events:
                    module = _module_at(starts, mods, e.start_ns)
                    plane_ops.append((module, tracing.op_name(e.name),
                                      e.start_ns, e.end_ns))
            ops.append(plane_ops)
            modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif e.name == tracing.WINDOW_SPAN:
                        marks.append((e.name, e.start_ns, e.end_ns))
    spans.sort(key=lambda e: e[1])
    if not any(ops):
        return Phases({}, [], spans, modules)
    return reduce_phases(ops, modules, labels, spans, _window(ops, marks))


# ------------------------------------------------------------ the readings


def fetches_ns(ph: Phases) -> List[float]:
    """For each round, the time in its ``fleet.fetch`` spans, every attempt
    together: the round's wait on the source of the window's rates (the
    OSTs' job statistics in a deployment, host memory in the benchmark),
    which comes out of the round's window like the step.

    A round's fetches are those before its ``fleet.dispatch``; a round
    whose fetch began before the trace did is left out."""
    out, acc, seen = [], 0.0, False
    for name, a, b in ph.spans:
        if name == "fleet.fetch":
            acc, seen = acc + (b - a), True
        elif name == "fleet.dispatch":
            if seen:
                out.append(acc)
            acc, seen = 0.0, False
    return out


def device_gaps_ns(ph: Phases) -> List[float]:
    """On each chip, the time from the end of one ``XLA Modules`` event to
    the start of the next one of the same program: the device's wait
    between two steps.  In the online loop that is the completion path of
    one round and the caller's work, the fetch, copy, dispatch and
    transfer of the next.  Both ends are read on the device's clock, so no
    offset between the device planes and the host clock enters it."""
    return [b[1] - a[2] for plane in ph.modules
            for a, b in zip(plane, plane[1:]) if a[0] == b[0]]


def start_lags_ns(ph: Phases) -> List[float]:
    """For each ``fleet.dispatch`` span, the time from its end to the start
    of the ``XLA Modules`` event it launched: the device's wait for the
    window's rates and the launch.

    The k-th dispatch launched the k-th module event of each chip (the
    earliest chip counts).  The lags take a host time from a device time,
    so the offset between the device planes and the host clock enters them
    whole; nothing in the trace certifies it.  Causal bounds on full-size
    traces leave it between -2.8 and +1.7 ms (PERF.md), against a lag of
    about 2.9 ms: the lag splits ``device_gaps_ns`` between the wait for the
    rates and the rest, as a diagnostic, and a gain is read on the gap.  A
    module that starts before the dispatch that launched it has returned
    shows an offset large enough to reorder events, and then no lag is
    returned.  Nor is one when a chip holds another number of module
    events than there are dispatches."""
    ends = [b for name, _, b in ph.spans if name == "fleet.dispatch"]
    if not ends or not ph.modules or any(len(plane) != len(ends)
                                         for plane in ph.modules):
        return []
    lags = [min(plane[k][1] for plane in ph.modules) - end
            for k, end in enumerate(ends)]
    return lags if min(lags) > 0 else []


def readings(ph: Phases, windows: int) -> Dict[str, float]:
    """The per-layer readings of one traced run, in ms; a reading whose
    spans or phase the trace does not hold is left out.

    ``serve_ms``, ``alloc_ms``, ``telemetry_ms``: the device self time
    placed in that phase over the windows completed; ``fetch_host_ms``:
    the median of ``fetches_ns``; ``copy_host_ms``, ``dispatch_host_ms``:
    the median ``fleet.copy`` and ``fleet.dispatch`` span;
    ``device_gap_ms``, ``start_lag_ms``: the medians of ``device_gaps_ns``
    and ``start_lags_ns``."""
    out = {}
    if windows > 0:
        for name, phase in (("serve_ms", "fleet.serve"),
                            ("alloc_ms", "fleet.allocate"),
                            ("telemetry_ms", "fleet.telemetry")):
            if phase in ph.self_s:
                out[name] = 1e3 * ph.self_s[phase] / windows
    fetches = fetches_ns(ph)
    if fetches:
        out["fetch_host_ms"] = 1e-6 * statistics.median(fetches)
    for name, span in (("copy_host_ms", "fleet.copy"),
                       ("dispatch_host_ms", "fleet.dispatch")):
        durations = [b - a for n, a, b in ph.spans if n == span]
        if durations:
            out[name] = 1e-6 * statistics.median(durations)
    gaps = device_gaps_ns(ph)
    if gaps:
        out["device_gap_ms"] = 1e-6 * statistics.median(gaps)
    lags = start_lags_ns(ph)
    if lags:
        out["start_lag_ms"] = 1e-6 * statistics.median(lags)
    return out

"""The program's phases and spans: the phase rule on hand-made HLO, the
reduction on hand-made events, and both on recorded TPU traces."""
import gzip

import pytest

from conftest import BENCH
from lib import harness, phases, tracing
from lib.floor import window_floor_bytes
from lib.peaks import device_peaks

DATA = BENCH / "tests" / "data"
#: the online mode at test size on one TPU v5e, before the program named
#: its phases and spans
UNSCOPED = DATA / "tiny_online.xplane.pb.gz"


# ---------------------------------------------- a protobuf writer for tests


def varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def ld(field: int, payload) -> bytes:
    """A length-delimited field."""
    if isinstance(payload, str):
        payload = payload.encode()
    return varint(field << 3 | 2) + varint(len(payload)) + payload


def vi(field: int, value: int) -> bytes:
    return varint(field << 3) + varint(value)


def instruction(name, op_name=None, called=(), packed=True) -> bytes:
    out = ld(1, name)
    if op_name is not None:
        out += ld(7, ld(1, "op_type") + ld(2, op_name))
    if called:
        out += (ld(38, b"".join(varint(c) for c in called)) if packed
                else b"".join(vi(38, c) for c in called))
    return out


def computation(cid: int, *insts) -> bytes:
    return ld(1, f"comp.{cid}") + b"".join(ld(2, i) for i in insts) + \
        vi(5, cid)


def hlo_proto(*comps) -> bytes:
    return ld(1, ld(1, "jit_f") + b"".join(ld(3, c) for c in comps))


TEL = "jit(step_fn)/fleet.telemetry"
SERVE = "jit(step_fn)/fleet.serve"
ALLOC = "jit(step_fn)/fleet.allocate/vmap(jit(allocate))"

#: the fold's custom scatter fusion as the TPU compiler leaves it: no
#: op_name on the fusion or its nested fusion, a path-less scatter, and
#: the phase only on the combiner's add
MODULE = hlo_proto(
    computation(
        0,
        instruction("fusion", called=[1]),
        instruction("copy-start.3"),
        instruction("scatter-add.9", op_name="scatter-add"),
        instruction("select_fusion", called=[4]),
        instruction("loop", op_name="jit(step_fn)/while", called=[5, 6],
                    packed=False),
        instruction("merged", op_name=f"{SERVE}/add;{ALLOC}/sub"),
        instruction("nested", op_name=f"{SERVE}/while/body/fleet.allocate/x")),
    computation(1, instruction("param_0"),
                instruction("fusion.94", called=[2])),
    computation(2, instruction("scatter.2", op_name="scatter-add",
                               called=[3])),
    computation(3, instruction("scatter-add.2", op_name="scatter-add"),
                instruction("add.67", op_name=f"{TEL}/add")),
    computation(4, instruction("gt", op_name=f"{SERVE}/gt"),
                instruction("sel", op_name=f"{ALLOC}/select_n")),
    computation(5, instruction("body_add", op_name=f"{ALLOC}/add")),
    computation(6, instruction("cond_lt", op_name="jit(step_fn)/while/lt")),
)


def test_phase_of_takes_the_outermost_scope_of_each_merged_name():
    assert phases.phase_of(f"{TEL}/scatter-add") == {"fleet.telemetry"}
    assert phases.phase_of(f"{SERVE}/while/body/fleet.allocate/x") == {
        "fleet.serve"}
    assert phases.phase_of("jit(f)/vmap(fleet.allocate)/add") == {
        "fleet.allocate"}
    assert phases.phase_of(f"{SERVE}/add;{ALLOC}/sub") == {
        "fleet.serve", "fleet.allocate"}
    # names that only look like a phase, and names with no path
    assert phases.phase_of("jit(f)/fleet_allocate/init_fleet_state") == set()
    assert phases.phase_of("scatter-add") == set()
    assert phases.phase_of("") == set()


def test_the_phase_rule_walks_into_called_computations():
    labels = phases.hlo_labels(MODULE)
    # nested fusion, path-less scatter: placed by its combiner
    assert labels["fusion"] == "fleet.telemetry"
    assert labels["scatter.2"] == "fleet.telemetry"
    # a path-less op_name on its own, and no op_name at all
    assert labels["scatter-add.9"] == phases.UNPLACED
    assert labels["copy-start.3"] == phases.UNPLACED
    # a fusion across a phase boundary, and merged names of two phases
    assert labels["select_fusion"] == phases.MIXED
    assert labels["merged"] == phases.MIXED
    # the outermost scope decides; a loop's body and condition both count
    assert labels["nested"] == "fleet.serve"
    assert labels["loop"] == "fleet.allocate"
    assert labels["body_add"] == "fleet.allocate"


def test_modules_are_read_from_the_metadata_plane():
    other = ld(2, "/device:TPU:0") + ld(3, ld(2, "XLA Ops"))
    event_meta = ld(4, vi(1, 3) + ld(2, vi(1, 3) + ld(2, "jit_f(17)") + ld(
        5, vi(1, 9) + ld(6, MODULE)) + ld(5, vi(1, 8) + ld(5, "text"))))
    stat_meta = ld(5, vi(1, 9) + ld(2, vi(1, 9) + ld(2, "Hlo Proto"))) + \
        ld(5, vi(1, 8) + ld(2, vi(1, 8) + ld(2, "other")))
    metadata = ld(2, phases.METADATA_PLANE) + event_meta + stat_meta
    space = ld(1, other) + ld(1, metadata) + ld(4, "host")
    assert phases.read_modules(space) == {"jit_f(17)": MODULE}
    assert phases.read_modules(ld(1, other)) == {}


def test_self_time_by_phase_and_the_loose_operations():
    labels = {"m": phases.hlo_labels(MODULE)}
    ops = [[("m", "loop", 0, 100), ("m", "fusion", 10, 40),
            ("m", "select_fusion", 50, 60), ("m", "copy-start.3", 60, 65),
            (None, "copy", 120, 132), ("m", "fusion", 190, 210)]]
    ph = phases.reduce_phases(ops, [[("m", 0, 100)]], labels, [],
                              window=(0, 200))
    assert ph.self_s == {"fleet.allocate": pytest.approx(55e-9),
                         "fleet.telemetry": pytest.approx(40e-9),
                         phases.MIXED: pytest.approx(10e-9),
                         phases.UNPLACED: pytest.approx(17e-9)}
    assert [(lab, op) for lab, op, _ in ph.loose_ops] == [
        (phases.UNPLACED, "None/copy"), (phases.MIXED, "m/select_fusion"),
        (phases.UNPLACED, "m/copy-start.3")]
    assert ph.placed_share == pytest.approx(95 / 122)
    # every second of busy time is counted once
    busy = tracing.reduce_events([[(o, a, b) for _, o, a, b in ops[0]]], [],
                                 window=(0, 200)).busy_s
    assert sum(ph.self_s.values()) == pytest.approx(busy)


def test_readings_from_spans_and_modules():
    spans = [("fleet.fetch", 0, 1), ("fleet.copy", 2, 12),
             ("fleet.dispatch", 13, 15), ("fleet.fetch", 100, 101),
             ("fleet.copy", 102, 106), ("fleet.dispatch", 107, 111)]
    modules = [[("jit_step_fn(1)", 40, 90), ("jit_step_fn(1)", 131, 180)]]
    ph = phases.Phases({"fleet.serve": 4e-6, "fleet.telemetry": 2e-6,
                        phases.UNPLACED: 1e-6}, [], spans, modules)
    assert phases.start_lags_ns(ph) == [25, 20]
    assert phases.fetches_ns(ph) == [1, 1]
    assert phases.device_gaps_ns(ph) == [41]
    r = phases.readings(ph, windows=2)
    assert r == {"serve_ms": pytest.approx(2e-3),
                 "telemetry_ms": pytest.approx(1e-3),
                 "fetch_host_ms": pytest.approx(1e-6),
                 "copy_host_ms": pytest.approx(7e-6),
                 "dispatch_host_ms": pytest.approx(3e-6),
                 "device_gap_ms": pytest.approx(41e-6),
                 "start_lag_ms": pytest.approx(22.5e-6)}
    # the planes off the host's clock: a module starts before the dispatch
    # that launched it returned, so no lag is read
    early = ph._replace(modules=[[("jit_step_fn(1)", 14, 90),
                                  ("jit_step_fn(1)", 131, 180)]])
    assert phases.start_lags_ns(early) == []
    assert "start_lag_ms" not in phases.readings(early, windows=2)
    # a module with no dispatch of its own: no pairing
    # and no gap across programs
    extra = ph._replace(modules=[modules[0] + [("jit_g(2)", 190, 200)]])
    assert phases.start_lags_ns(extra) == []
    assert phases.device_gaps_ns(extra) == [41]
    # no windows, no phase readings; no spans, no span readings
    assert set(phases.readings(ph, windows=0)) == {
        "fetch_host_ms", "copy_host_ms", "dispatch_host_ms",
        "device_gap_ms", "start_lag_ms"}
    assert phases.readings(phases.Phases({}, [], [], []), 5) == {}


def test_a_rounds_fetch_time_counts_every_attempt():
    """A retried fetch counts both attempts; a round whose fetch began
    before the trace did counts none."""
    spans = [("fleet.copy", 0, 3), ("fleet.dispatch", 4, 6),
             ("fleet.fetch", 10, 12), ("fleet.fetch", 20, 25),
             ("fleet.copy", 26, 30), ("fleet.dispatch", 31, 33),
             ("fleet.fetch", 40, 43), ("fleet.copy", 44, 47),
             ("fleet.dispatch", 48, 50), ("fleet.fetch", 60, 61)]
    ph = phases.Phases({}, [], spans, [])
    assert phases.fetches_ns(ph) == [7, 3]
    assert phases.readings(ph, windows=3)["fetch_host_ms"] == \
        pytest.approx(5e-6)


def unpack(path, tmp_path):
    out = tmp_path / "trace.xplane.pb"
    out.write_bytes(gzip.decompress(path.read_bytes()))
    return out


def test_a_program_without_names_reads_nothing(tmp_path):
    """The trace of a program whose round has no scopes or spans: every
    operation is unplaced and no reading that rests on the program's names
    is made, without an error.  The device gap rests on the device's
    module events alone, and is read."""
    ph = phases.read_phases(unpack(UNSCOPED, tmp_path))
    assert set(ph.self_s) == {phases.UNPLACED} and ph.spans == []
    assert len(ph.modules) == 1 and len(ph.modules[0]) == 5
    assert phases.readings(ph, windows=5) == {
        "device_gap_ms": pytest.approx(5.241249, rel=1e-9)}


#: the online mode at full size (248 OSTs, 4096 jobs; a 0.02 s traced
#: window, two rounds) on one TPU v5e, with the program's phase scopes and
#: host spans
SCOPED = DATA / "online_scoped.xplane.pb.gz"


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    path = unpack(SCOPED, tmp_path_factory.mktemp("scoped"))
    return path, phases.read_phases(path)


def test_the_recorded_fold_is_placed_in_telemetry(scoped):
    path, ph = scoped
    red = tracing.reduce_events(*tracing.read_xspace(path))
    (labels,) = [phases.hlo_labels(proto) for proto in
                 phases.read_modules(path.read_bytes()).values()]
    # the largest operation: the fold's custom scatter fusion, whose
    # fusion, nested fusion and scatter carry no phase of their own
    top = red.device_ops[0][0]
    assert labels[top] == "fleet.telemetry"
    assert set(ph.self_s) == {"fleet.serve", "fleet.allocate",
                              "fleet.telemetry", phases.MIXED,
                              phases.UNPLACED}
    assert ph.placed_share >= 0.9
    assert sum(ph.self_s.values()) == pytest.approx(red.busy_s)


def test_the_recorded_rounds_are_fetch_copy_dispatch(scoped):
    path, ph = scoped
    _, spans = tracing.read_xspace(path)
    rounds = sum(1 for name, _, _ in spans if name == "bench.round")
    assert [name for name, _, _ in ph.spans] == [
        "fleet.fetch", "fleet.copy", "fleet.dispatch"] * rounds
    assert [len(plane) for plane in ph.modules] == [rounds]
    r = phases.readings(ph, windows=rounds)
    assert {"serve_ms", "alloc_ms", "telemetry_ms", "fetch_host_ms",
            "copy_host_ms", "dispatch_host_ms", "device_gap_ms"} <= set(r)
    assert r["telemetry_ms"] > r["alloc_ms"] > r["serve_ms"] > 0


def test_the_recorded_dispatches_end_before_their_modules(scoped):
    """The shared clock: each dispatch returns before the module it
    launched starts, the window's 40.6 MB transfer between them."""
    _, ph = scoped
    lags = phases.start_lags_ns(ph)
    assert len(lags) == 2 and min(lags) > 1e6
    assert phases.readings(ph, windows=2)["start_lag_ms"] == pytest.approx(
        1e-6 * sum(lags) / 2)


def test_the_recorded_device_gap_holds_the_next_rounds_lag(scoped):
    """Read on the device's clock alone, the gap between the two steps
    holds the second round's fetch, copy, dispatch and transfer."""
    _, ph = scoped
    (gap,) = phases.device_gaps_ns(ph)
    assert gap > phases.start_lags_ns(ph)[1] > 0
    assert phases.readings(ph, windows=2)["device_gap_ms"] == \
        pytest.approx(1e-6 * gap)


def test_the_program_spans_leave_the_reduction_as_it_was(scoped):
    """The benchmark's reduction and its readers read the scoped trace as
    they read it before the program had spans (values from the parent
    commit's code on this trace)."""
    path, _ = scoped
    devices, spans = tracing.read_xspace(path)
    assert not any(name.startswith("fleet.") for name, _, _ in spans)
    red = tracing.reduce_events(devices, spans)
    assert red.busy_s == pytest.approx(0.019137188, rel=1e-12)
    assert red.window_s == pytest.approx(0.031987998, rel=1e-12)
    assert red.n_device_events == 1654
    assert red.device_ops[:3] == [
        ["fusion", pytest.approx(0.017735005, rel=1e-12)],
        ["fusion.133", pytest.approx(0.000149699, rel=1e-12)],
        ["subtract_subtract_fusion", pytest.approx(7.9479e-05, rel=1e-12)]]
    assert red.idle_gaps == [
        ["bench.wait", pytest.approx(0.007945, rel=1e-12)],
        ["bench.ingest", pytest.approx(0.004720009, rel=1e-12)],
        ["bench.round", pytest.approx(0.000100541, rel=1e-12)],
        [tracing.UNNAMED_GAP, pytest.approx(7.112e-05, rel=1e-12)],
        ["bench.fetch", pytest.approx(1.414e-05, rel=1e-12)]]
    run = harness.TraceRun(red, 2, window_floor_bytes(
        248, 4096, 10, ("adaptbf",), "streaming"), device_peaks(
        "TPU v5 lite"), {})
    read = {name: harness.load_module("metrics", name).read(run)
            for name in ("device_idle_share", "round_roofline_share")}
    assert read == {"device_idle_share": pytest.approx(40.17384895422339),
                    "round_roofline_share": pytest.approx(
                        2.9102732797758413)}

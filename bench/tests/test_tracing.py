"""The trace reduction on hand-made events and on a small recorded trace."""
import gzip

import pytest

from conftest import BENCH
from lib import tracing

#: the XSpace of a ``--trace 1`` run of the online mode at test size (8 OSTs,
#: 4096 jobs, a 0.02 s traced window) on one TPU v5e
RECORDED = BENCH / "tests" / "data" / "tiny_online.xplane.pb.gz"


def test_busy_union_idle_share_and_gaps():
    ops = [("a", 0, 10), ("b", 5, 20), ("a", 30, 40), ("c", 45, 70)]
    spans = [("bench.window", 0, 50), ("bench.round", 0, 50),
             ("bench.fetch", 18, 32), ("bench.wait", 35, 50)]
    red = tracing.reduce_events([ops], spans)
    # busy: [0, 20] and [30, 40] and [45, 50] (clipped to the window)
    assert red.busy_s == pytest.approx(35e-9)
    assert red.window_s == pytest.approx(50e-9)
    assert red.idle_share == pytest.approx(15 / 50)
    # idle [20, 30] falls in bench.fetch (innermost), [40, 45] in bench.wait
    assert red.idle_gaps == [["bench.fetch", pytest.approx(10e-9)],
                             ["bench.wait", pytest.approx(5e-9)]]
    # per-operation device time, clipped to the window, each second counted
    # once (for the later of two overlapping events), largest first
    assert [n for n, _ in red.device_ops] == ["a", "b", "c"] or \
        [n for n, _ in red.device_ops] == ["b", "a", "c"]
    assert dict(red.device_ops) == {"a": pytest.approx(15e-9),
                                    "b": pytest.approx(15e-9),
                                    "c": pytest.approx(5e-9)}
    assert sum(s for _, s in red.device_ops) == pytest.approx(red.busy_s)


def test_the_checks_read_back_leaves_the_window():
    ops = [("a", 0, 10), ("a", 40, 50)]
    spans = [("bench.window", 0, 50), ("bench.check_copy", 10, 30),
             ("bench.wait", 30, 40)]
    red = tracing.reduce_events([ops], spans)
    assert red.window_s == pytest.approx(30e-9)
    assert red.idle_share == pytest.approx(1 / 3)
    assert red.idle_gaps == [["bench.wait", pytest.approx(10e-9)]]


def test_operations_count_their_self_time():
    # a loop [0, 100] whose body ops cover [10, 30] and [50, 60]
    ops = [("loop", 0, 100), ("body", 10, 30), ("body", 50, 60)]
    red = tracing.reduce_events([ops], [], window=(0, 100))
    assert red.busy_s == pytest.approx(100e-9)
    assert red.device_ops == [["loop", pytest.approx(70e-9)],
                              ["body", pytest.approx(30e-9)]]


def test_chips_are_averaged_and_unnamed_gaps_are_marked():
    one = [("x", 0, 100)]
    two = [("x", 0, 50)]
    red = tracing.reduce_events([one, two], [], window=(0, 100))
    assert red.busy_s == pytest.approx(75e-9)
    assert red.idle_gaps == [[tracing.UNNAMED_GAP, pytest.approx(25e-9)]]


def test_union_and_gaps():
    busy = tracing.union_ns([(5, 8), (0, 3), (2, 4), (9, 12)], 1, 10)
    assert busy == [(1, 4), (5, 8), (9, 10)]
    assert tracing.gaps_ns(busy, 0, 11) == [(0, 1), (4, 5), (8, 9),
                                            (10, 11)]


def test_op_names_are_the_hlo_instruction_names():
    hlo = "%fusion.133 = (f32[248]{0}) fusion(f32[248] %x), kind=kLoop"
    assert tracing.op_name(hlo) == "fusion.133"


def test_recorded_tpu_trace(tmp_path):
    """A real trace of the online mode at test size on one TPU v5e."""
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    devices, spans = tracing.read_xspace(path)
    assert len(devices) == 1 and len(devices[0]) > 100
    names = {s[0] for s in spans}
    assert {"bench.window", "bench.round", "bench.ingest",
            "bench.wait"} <= names
    red = tracing.reduce_events(devices, spans)
    rounds = sum(1 for s in spans if s[0] == "bench.round")
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1
    assert 0 < red.busy_s / rounds < red.window_s / rounds
    secs = [s for _, s in red.device_ops]
    assert secs == sorted(secs, reverse=True) and len(secs) <= tracing.TOP
    assert all(n.startswith("bench.") or n == tracing.UNNAMED_GAP
               for n, _ in red.idle_gaps)
    # idle time adds up to the window less the busy time
    assert sum(s for _, s in red.idle_gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)

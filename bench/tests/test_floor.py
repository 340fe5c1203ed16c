"""The byte floor against a hand count, and the peak table."""
import inspect

import pytest

from lib.floor import window_floor_bytes
from lib.peaks import device_peaks


def test_floor_matches_hand_count():
    # O = 2 targets, J = 3 jobs, 10 ticks a window; every element 4 bytes.
    # rates read once: 10 * 2 * 3 = 60 elements.
    # adaptbf carry: queue, vol_left, alloc, held served/demand/alloc and
    # record, remainder, alloc_prev = 9 [O, J] leaves = 54 elements, plus
    # the window counter = 55; read and written: 110.
    # trajectory outputs: served, demand, alloc, record = 4 * 6 = 24.
    assert window_floor_bytes(2, 3, 10, ("adaptbf",), "trajectory") == \
        4 * (60 + 110 + 24)
    # streaming adds 14 [O, J] leaves (six sums, six Kahan terms,
    # alloc_windows, last_served) = 84, 10 [O] leaves = 20, the histogram
    # and its Kahan term 2 * 2 * 128 = 512, and two counters: the carry is
    # 55 + 84 + 20 + 512 + 2 = 673 elements, no outputs.
    assert window_floor_bytes(2, 3, 10, ("adaptbf",), "streaming") == \
        4 * (60 + 2 * 673)
    # five coded tenants share the rates; each carries adaptbf's three
    # state leaves and aimd's one: (6 + 4 + 14) * 6 + 20 + 512 + 3 = 679.
    policies = ("adaptbf", "aimd", "nobw", "static", "static_wc")
    assert window_floor_bytes(2, 3, 10, policies, "streaming",
                              n_fleets=5) == 4 * (60 + 2 * 5 * 679)


def test_floor_takes_no_engine_argument():
    params = inspect.signature(window_floor_bytes).parameters
    for word in ("engine", "backend", "serve", "alloc", "kernel"):
        assert not any(word in p for p in params), params


def test_unknown_device_kind_is_refused():
    assert device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            device_peaks(kind)

"""The HBM byte floor of one control window, from the cell's shapes alone.

A window must at least read its rates once, read and write the semantic
carry once, and write its outputs once.  The count depends on the fleet's
shape (O targets, J jobs, F fleets, W ticks a window), on which policies'
states the carry holds, and on the telemetry mode; it never depends on the
engine that runs the window, so a later change that fuses or replaces a
kernel is credited with the same work.

The carry counted here is the window engine's carry as it stood when the
benchmark was written (``WindowCarry`` at that commit, checked once against
``jax.eval_shape`` of ``init_carry``; see PERF.md).  The formula is frozen:
it does not follow later changes to the program's carry.

Every element is 4 bytes (float32 or int32).
"""
from __future__ import annotations

ELEMENT_BYTES = 4
HIST_BINS = 128

#: [O, J] leaves of each policy's state
POLICY_STATE_OJ = {"adaptbf": 3, "aimd": 1, "nobw": 0, "static": 0,
                   "static_wc": 0}

#: carry leaves of one fleet, by shape
BASE_OJ = 6           # queue, vol_left, alloc, held served/demand/alloc
BASE_SCALARS = 1      # windows completed
STREAM_OJ = 14        # six sums, six Kahan terms, alloc_windows, last_served
STREAM_O = 10         # util, lag sum/sumsq/max, three Kahan, three faults
STREAM_OH = 2         # the backlog histogram and its Kahan term
STREAM_SCALARS = 2    # windows, busy_windows
TRAJECTORY_OUT_OJ = 4  # served, demand, alloc, record of every window


def carry_elements(n_ost: int, n_jobs: int, policies, telemetry: str) -> int:
    """Elements of one fleet's carry."""
    oj = BASE_OJ + sum(POLICY_STATE_OJ[p] for p in policies)
    o = oh = 0
    scalars = BASE_SCALARS
    if telemetry == "streaming":
        oj += STREAM_OJ
        o += STREAM_O
        oh += STREAM_OH
        scalars += STREAM_SCALARS
    elif telemetry != "trajectory":
        raise ValueError(f"unknown telemetry {telemetry!r}")
    return (oj * n_ost * n_jobs + o * n_ost + oh * n_ost * HIST_BINS
            + scalars)


def window_floor_bytes(n_ost: int, n_jobs: int, window_ticks: int,
                       policies, telemetry: str, n_fleets: int = 1) -> int:
    """Bytes one window of ``n_fleets`` fleets must move at the least.

    ``policies`` lists the policies whose state each fleet carries (one
    name, or every member of a coded policy).  Rates shared by the fleets
    are read once."""
    rates = window_ticks * n_ost * n_jobs
    carry = 2 * n_fleets * carry_elements(n_ost, n_jobs, policies, telemetry)
    out = (TRAJECTORY_OUT_OJ * n_fleets * n_ost * n_jobs
           if telemetry == "trajectory" else 0)
    return ELEMENT_BYTES * (rates + carry + out)

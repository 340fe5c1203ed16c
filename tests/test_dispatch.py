"""Unit pins for ``kernels/dispatch`` -- the single sizing authority every
kernel package (adaptbf_alloc, fleet_window, window_mega) defers to.  A
silent change here re-blocks every kernel at once, so the rule is pinned
explicitly: one 8-row tile (Mosaic refuses a block whose row extent is
neither a multiple of 8 nor the whole array), capped at the
sharded-local row count, with wide job axes raising the scoped-VMEM limit
instead of shrinking the block."""
import pytest

from repro.kernels import dispatch
from repro.kernels.adaptbf_alloc import ops as alloc_ops
from repro.kernels.window_mega import ops as mega_ops


def test_pad_lanes_multiples():
    assert dispatch.pad_lanes(1) == 128
    assert dispatch.pad_lanes(128) == 128
    assert dispatch.pad_lanes(129) == 256
    assert dispatch.pad_lanes(4096) == 4096
    assert dispatch.pad_lanes(16384) == 16384


def test_block_rows_caps_at_local_row_count():
    """partition="ost_shard" hands each device O/n_devices rows; the block
    must shrink to the local slice (the whole row extent, which Mosaic
    accepts), never pad a small shard to 8 rows."""
    # O=8 fleet on a 2-way mesh: 4 local rows -> block 4
    assert dispatch.block_rows(4) == 4
    # O=8 fleet on a 4-way mesh: 2 local rows -> block 2
    assert dispatch.block_rows(2) == 2
    # degenerate 1-row shard (8-way mesh on O=8)
    assert dispatch.block_rows(1) == 1
    # O=256 on the 4-chip mesh: 64 local rows -> one tile
    assert dispatch.block_rows(64) == 8


def test_block_rows_upper_end_j16384():
    """At the J=16384 upper end the block stays one 8-row tile for every
    kernel; the working set raises the scoped-VMEM request above Mosaic's
    default, and stays under the cap."""
    j = dispatch.pad_lanes(16384)
    assert j == 16384
    assert dispatch.block_rows(256) == 8
    for live in (alloc_ops._LIVE_ROWS, 10 + 10, mega_ops._live_rows(3, 10)):
        limit = dispatch.vmem_limit_bytes(8, j, live)
        assert limit >= 5 * live * 8 * j * 4 // 2, live
        assert dispatch.DEFAULT_SCOPED_VMEM <= limit
        assert limit <= dispatch.MAX_SCOPED_VMEM
    assert dispatch.vmem_limit_bytes(
        8, j, mega_ops._live_rows(3, 10)) > dispatch.DEFAULT_SCOPED_VMEM


def test_block_rows_mega_live_rows_monotone():
    """The megakernel keeps the whole round resident: its live-row count
    grows with window length and policy-state size, and the VMEM request
    must grow with it while the block stays one tile -- this is the VMEM
    budget table in DESIGN.md section 12."""
    j = dispatch.pad_lanes(4096)
    lives = [mega_ops._live_rows(3, w) for w in (10, 40, 160)]
    assert lives == sorted(lives)
    limits = [dispatch.vmem_limit_bytes(8, j, lv) for lv in lives]
    assert limits == sorted(limits)
    assert limits[-1] > limits[0]
    assert dispatch.block_rows(256) == 8


def test_block_rows_budget_boundary_exact():
    """The request never drops below Mosaic's default, and a working set
    exactly at the cap is accepted while one byte-row over it is refused
    with a message that names the shape."""
    j = 128
    assert dispatch.vmem_limit_bytes(8, j, 1) == dispatch.DEFAULT_SCOPED_VMEM
    row_bytes = 5 * 8 * j * 4 // 2
    live_at_cap = dispatch.MAX_SCOPED_VMEM // row_bytes
    assert dispatch.vmem_limit_bytes(8, j, live_at_cap) <= \
        dispatch.MAX_SCOPED_VMEM
    with pytest.raises(ValueError, match=r"\[8, 128\] row block"):
        dispatch.vmem_limit_bytes(8, j, live_at_cap + 1)


def test_block_rows_floor_is_one():
    """An empty row count is clamped to a 1-row block, never 0; and a
    window too long for VMEM at one tile is refused rather than blocked
    below the tile."""
    assert dispatch.block_rows(0) == 1
    with pytest.raises(ValueError, match="VMEM"):
        dispatch.vmem_limit_bytes(8, 16384, 10_000)

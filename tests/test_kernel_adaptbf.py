"""Pallas adaptbf_alloc kernel vs the core-allocator oracle: shape/dtype
sweep, exact integer-token agreement (interpret mode on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.adaptbf_alloc import ops


def _case(o, j, seed, cap=1000.0):
    rng = np.random.default_rng(seed)
    demand = rng.integers(0, 3000, (o, j)).astype(np.float32)
    demand[rng.random((o, j)) < 0.3] = 0.0        # inactive jobs
    nodes = rng.integers(1, 128, (o, j)).astype(np.float32)
    record = rng.integers(-200, 200, (o, j)).astype(np.float32)
    remainder = np.zeros((o, j), np.float32)
    alloc_prev = rng.integers(0, 500, (o, j)).astype(np.float32)
    capacity = np.full((o,), cap, np.float32)
    return tuple(jnp.asarray(x) for x in
                 (demand, nodes, record, remainder, alloc_prev, capacity))


@pytest.mark.parametrize("o,j", [(1, 4), (3, 16), (8, 128), (17, 100),
                                 (5, 256), (2, 300)])
def test_matches_core_allocator(o, j):
    args = _case(o, j, seed=o * 100 + j)
    a_k, rec_k, rem_k = ops.fleet_alloc(*args, interpret=True)
    a_r, rec_r, rem_r, _ = ops.fleet_alloc_ref(*args)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), atol=1e-3)
    np.testing.assert_allclose(np.asarray(rec_k), np.asarray(rec_r), atol=1e-3)
    np.testing.assert_allclose(np.asarray(rem_k), np.asarray(rem_r), atol=1e-3)


@pytest.mark.parametrize("cap", [1.0, 17.0, 999.0, 100000.0])
def test_capacity_sweep(cap):
    args = _case(4, 64, seed=int(cap) % 97, cap=cap)
    a_k, rec_k, _ = ops.fleet_alloc(*args, interpret=True)
    a_r, rec_r, _, _ = ops.fleet_alloc_ref(*args)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), atol=1e-3)
    # conservation on every OST row
    act = np.asarray(args[0]) > 0
    for row in range(4):
        total = np.asarray(a_k)[row].sum()
        assert total == pytest.approx(cap if act[row].any() else 0.0, abs=0.01)


def test_block_o_stays_wide_at_fleet_scale():
    """O(J)-memory selection: the shared dispatcher keeps 8-row blocks out
    to J=16384 (and beyond), where the old [block_o, J, J] rank matrix
    forced block_o=1 by J~1448 and could not fit J=4096 at any block size.
    It also never blocks wider than the (possibly sharded-local) row count,
    so a ``partition="ost_shard"`` shard dispatches exactly its own rows."""
    from repro.kernels.dispatch import (
        DEFAULT_SCOPED_VMEM,
        block_rows,
        vmem_limit_bytes,
    )
    assert block_rows(8) == 8
    assert block_rows(256) == 8
    assert block_rows(1) == 1
    assert block_rows(2) == 2
    # the allocator's working set fits Mosaic's default scoped VMEM at
    # 8 rows out to J=8192; J=16384 raises the limit, not the block
    for j in (128, 1536, 4096, 8192):
        assert vmem_limit_bytes(8, j, ops._LIVE_ROWS) == DEFAULT_SCOPED_VMEM
    assert vmem_limit_bytes(8, 16384, ops._LIVE_ROWS) > DEFAULT_SCOPED_VMEM


@pytest.mark.slow
def test_runs_at_j4096_matching_oracle():
    """The acceptance shape the rank-matrix kernel could never allocate."""
    o, j = 2, 4096
    args = _case(o, j, seed=97, cap=50000.0)
    a_k, rec_k, rem_k = ops.fleet_alloc(*args, interpret=True)
    a_r, rec_r, rem_r, _ = ops.fleet_alloc_ref(*args)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), atol=1e-3)
    np.testing.assert_allclose(np.asarray(rec_k), np.asarray(rec_r), atol=1e-3)
    np.testing.assert_allclose(np.asarray(rem_k), np.asarray(rem_r), atol=1e-3)


def test_multi_window_state_evolution():
    """Drive the kernel across windows; records must stay zero-sum and the
    trajectory must match the oracle step for step."""
    o, j = 4, 32
    args = list(_case(o, j, seed=7))
    args[2] = jnp.zeros((o, j))  # start with clean records
    rng = np.random.default_rng(3)
    for w in range(5):
        demand = jnp.asarray(
            rng.integers(0, 2500, (o, j)).astype(np.float32))
        a_k, rec_k, rem_k = ops.fleet_alloc(
            demand, args[1], args[2], args[3], args[4], args[5],
            interpret=True)
        a_r, rec_r, rem_r, prev_r = ops.fleet_alloc_ref(
            demand, args[1], args[2], args[3], args[4], args[5])
        np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), atol=1e-3)
        np.testing.assert_allclose(np.asarray(rec_k), np.asarray(rec_r),
                                   atol=1e-3)
        assert np.abs(np.asarray(rec_k).sum(axis=1)).max() < 0.01
        args[2], args[3], args[4] = rec_k, rem_k, a_k

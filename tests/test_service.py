"""The online serving mode and its bitwise oracle.

``FleetService`` (storage/service.py) steps the SAME ``window_step`` the
offline ``lax.scan`` uses, so streaming N windows online must equal one
offline ``simulate_fleet`` scan of the same trace **bitwise** -- for every
registered policy, both telemetry modes, and across a save -> kill ->
restore at a mid-horizon window.  These tests are that oracle, plus the
checkpoint pytree-path naming contract the restore path depends on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.storage import (
    FLEET_CONTROL_CODES,
    FleetConfig,
    FleetService,
    WindowCarry,
    list_policies,
    simulate_fleet,
    telemetry,
)

W, O, J, WT = 12, 4, 8, 10   # windows, OSTs, jobs, ticks per window


def small_fleet(seed=0):
    """A small but non-trivial fleet: overloaded targets, heterogeneous
    capacities, ~30% volume-bounded jobs (so vol_left actually decrements),
    integer rates (so adaptbf's integer-token path is exercised)."""
    rng = np.random.default_rng(seed)
    nodes = rng.integers(1, 32, (J,)).astype(np.float32)
    rates = rng.integers(0, 8, (W * WT, O, J)).astype(np.float32)
    volume = np.where(rng.random((O, J)) < 0.3, 40.0, np.inf).astype(
        np.float32)
    cap = np.linspace(6.0, 12.0, O).astype(np.float32)
    backlog = np.full((O, J), 64.0, np.float32)
    return nodes, rates, volume, cap, backlog


def assert_results_bitwise(offline, online, telemetry_mode):
    if telemetry_mode == "trajectory":
        for field in ("served", "demand", "alloc", "record", "queue_final"):
            np.testing.assert_array_equal(
                np.asarray(getattr(offline, field)),
                np.asarray(getattr(online, field)), err_msg=field)
    else:
        off_leaves = jax.tree_util.tree_flatten_with_path(offline.stats)[0]
        on_leaves = jax.tree.leaves(online.stats)
        assert len(off_leaves) == len(on_leaves)
        for (path, a), b in zip(off_leaves, on_leaves):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=jax.tree_util.keystr(path))
        np.testing.assert_array_equal(np.asarray(offline.queue_final),
                                      np.asarray(online.queue_final))


@pytest.mark.parametrize("policy", list_policies() + ["coded"])
def test_initial_carry_leaves_are_distinct_buffers(policy):
    """The online step donates its carry on an accelerator, and donating
    one buffer twice is refused there; every leaf of the fresh carry must
    own its buffer (the zero-filled policy and stats leaves once shared
    one)."""
    nodes, _, volume, cap, backlog = small_fleet()
    cfg = FleetConfig(control=policy, telemetry="streaming")
    code = FLEET_CONTROL_CODES["adaptbf"] if policy == "coded" else None
    svc = FleetService(cfg, nodes, volume, cap, backlog, control_code=code)
    leaves = jax.tree.leaves(svc.carry)
    assert len({x.unsafe_buffer_pointer() for x in leaves}) == len(leaves)


@pytest.mark.parametrize("telemetry_mode", ["trajectory", "streaming"])
@pytest.mark.parametrize("policy", list_policies())
def test_online_matches_offline_bitwise(policy, telemetry_mode):
    nodes, rates, volume, cap, backlog = small_fleet()
    cfg = FleetConfig(control=policy, telemetry=telemetry_mode)
    offline = simulate_fleet(cfg, nodes, rates, volume, cap, backlog)
    svc = FleetService(cfg, nodes, volume, cap, backlog)
    online = svc.run(rates)
    assert svc.window == W
    assert_results_bitwise(offline, online, telemetry_mode)


@pytest.mark.parametrize("telemetry_mode", ["trajectory", "streaming"])
@pytest.mark.parametrize("policy", list_policies())
def test_resume_from_mid_horizon_checkpoint_is_bitwise(
        policy, telemetry_mode, tmp_path):
    """save -> kill -> restore at window k continues the uninterrupted run
    exactly: the carry is the complete resume point."""
    k = 7
    nodes, rates, volume, cap, backlog = small_fleet(seed=1)
    cfg = FleetConfig(control=policy, telemetry=telemetry_mode)
    offline = simulate_fleet(cfg, nodes, rates, volume, cap, backlog)

    svc = FleetService(cfg, nodes, volume, cap, backlog,
                       checkpoint_dir=str(tmp_path / "ckpt"))
    outs = [svc.step(rates[w * WT:(w + 1) * WT]) for w in range(k)]
    svc.save()
    del svc                                            # "crash"

    svc2 = FleetService(cfg, nodes, volume, cap, backlog,
                        checkpoint_dir=str(tmp_path / "ckpt"))
    assert svc2.restore() == k
    assert svc2.window == k                            # carry.window restored
    outs += [svc2.step(rates[w * WT:(w + 1) * WT]) for w in range(k, W)]

    if telemetry_mode == "trajectory":
        for i, field in enumerate(("served", "demand", "alloc", "record")):
            got = np.stack([np.asarray(o[i]) for o in outs])
            np.testing.assert_array_equal(
                got, np.asarray(getattr(offline, field)), err_msg=field)
        np.testing.assert_array_equal(np.asarray(svc2.queue),
                                      np.asarray(offline.queue_final))
    else:
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(offline.stats)[0],
                jax.tree.leaves(svc2.stats)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=jax.tree_util.keystr(path))
        np.testing.assert_array_equal(np.asarray(svc2.queue),
                                      np.asarray(offline.queue_final))


def test_online_coded_dispatch_matches_offline():
    """The coded combinator (one compiled program, runtime policy code)
    goes through the same step -- oracle holds per member code."""
    nodes, rates, volume, cap, backlog = small_fleet(seed=2)
    cfg = FleetConfig(control="coded", telemetry="streaming")
    for name, code in FLEET_CONTROL_CODES.items():
        offline = simulate_fleet(cfg, nodes, rates, volume, cap, backlog,
                                 control_code=jnp.int32(code))
        svc = FleetService(cfg, nodes, volume, cap, backlog,
                           control_code=code)
        online = svc.run(rates)
        assert_results_bitwise(offline, online, "streaming")


def test_online_tiled_horizon_matches_offline():
    """Feeding the same periodic windows online equals the offline
    n_windows= trace-tiling path."""
    n_windows = 2 * W + 3
    nodes, rates, volume, cap, backlog = small_fleet(seed=3)
    cfg = FleetConfig(control="adaptbf", telemetry="streaming")
    offline = simulate_fleet(cfg, nodes, rates, volume, cap, backlog,
                             n_windows=n_windows)
    svc = FleetService(cfg, nodes, volume, cap, backlog)
    online = svc.run(rates, n_windows=n_windows)
    assert int(online.stats.windows) == n_windows
    assert_results_bitwise(offline, online, "streaming")


def test_budget_and_alloc_views():
    """The service exposes the controller's live decisions: window 0 is
    the policy cold start (adaptbf: everything unruled), later windows
    gate finite budgets for active jobs."""
    nodes, rates, volume, cap, backlog = small_fleet()
    cfg = FleetConfig(control="adaptbf")
    svc = FleetService(cfg, nodes, volume, cap, backlog)
    assert svc.window == 0
    assert np.isinf(np.asarray(svc.budget)).all()      # cold start: no rules
    for w in range(3):
        svc.step(rates[w * WT:(w + 1) * WT])
    budget = np.asarray(svc.budget)
    assert np.isfinite(budget).any()                   # rules installed
    assert (np.asarray(svc.queue) >= 0).all()


# ---------------------------------------------------- production ingest


def test_ingest_retries_with_backoff_then_delivers():
    nodes, rates, volume, cap, backlog = small_fleet()
    cfg = FleetConfig(control="adaptbf")
    svc = FleetService(cfg, nodes, volume, cap, backlog)
    twin = FleetService(cfg, nodes, volume, cap, backlog)

    calls, delays = [], []
    def fetch():
        calls.append(1)
        if len(calls) < 3:
            raise TimeoutError("stats RPC dropped")
        return rates[:WT]

    res = svc.ingest(fetch, backoff_s=0.05, sleep=delays.append)
    assert res.delivered and res.attempts == 3
    assert delays == [0.05, 0.1]                       # exponential backoff
    assert svc.retry_count == 2 and svc.lost_windows == 0
    ref = twin.step(rates[:WT])
    for a, b in zip(jax.tree.leaves(res.out), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ingest_failure_degrades_through_loss_mask():
    """A window whose observation never arrives still advances the
    engine: zero observed arrivals, telem_ok forced to zero -- bitwise
    the explicit lost-telemetry step, not a stalled loop."""
    from repro.storage.faults import lost_telemetry_row

    nodes, rates, volume, cap, backlog = small_fleet()
    cfg = FleetConfig(control="adaptbf", telemetry="streaming")
    svc = FleetService(cfg, nodes, volume, cap, backlog)
    twin = FleetService(cfg, nodes, volume, cap, backlog)
    svc.step(rates[:WT])                               # build a standing queue
    twin.step(rates[:WT])

    def fetch():
        return None                                    # collector timed out

    res = svc.ingest(fetch, retries=2, sleep=lambda _: None)
    assert not res.delivered and res.attempts == 3
    assert svc.lost_windows == 1 and svc.window == 2
    zeros = np.zeros((WT, O, J), np.float32)
    twin.step(zeros, faults_w=lost_telemetry_row(O))
    for a, b in zip(jax.tree.leaves(svc.carry), jax.tree.leaves(twin.carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(svc.stats.obs_lost).sum()) == O  # counted


def test_ingest_watchdog_cuts_retries_at_deadline():
    nodes, rates, volume, cap, backlog = small_fleet()
    svc = FleetService(FleetConfig(), nodes, volume, cap, backlog)
    t = iter(np.arange(0.0, 100.0, 1.0))

    res = svc.ingest(lambda: None, retries=50, deadline_s=0.5,
                     sleep=lambda _: None, clock=lambda: next(t))
    assert not res.delivered
    assert res.attempts == 1                 # deadline < first backoff: stop
    assert svc.lost_windows == 1


# ------------------------------------------- restore compatibility checks


def _saved_service(tmp_path, cfg, fleet):
    nodes, rates, volume, cap, backlog = fleet
    svc = FleetService(cfg, nodes, volume, cap, backlog,
                       checkpoint_dir=str(tmp_path))
    svc.step(rates[:WT])
    svc.save()
    return svc


def test_restore_rejects_wrong_fleet_shape(tmp_path):
    """Regression: restoring a carry saved for a different (n_ost,
    n_jobs) used to fail deep inside the leaf loader with a bare numpy
    broadcast error; it must fail fast, naming both shapes."""
    fleet = small_fleet()
    cfg = FleetConfig(control="adaptbf")
    _saved_service(tmp_path, cfg, fleet)
    nodes, rates, volume, cap, backlog = fleet
    other = FleetService(cfg, nodes, volume[: O - 1], cap[: O - 1],
                         backlog[: O - 1], checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match=rf"\({O}, {J}\).*\({O - 1}, {J}\)"):
        other.restore()


def test_restore_rejects_wrong_telemetry_mode(tmp_path):
    fleet = small_fleet()
    _saved_service(tmp_path, FleetConfig(control="adaptbf",
                                         telemetry="streaming"), fleet)
    nodes, rates, volume, cap, backlog = fleet
    other = FleetService(FleetConfig(control="adaptbf"), nodes, volume,
                         cap, backlog, checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="telemetry='streaming'.*"
                                         "telemetry='trajectory'"):
        other.restore()


def test_restore_rejects_wrong_policy(tmp_path):
    fleet = small_fleet()
    _saved_service(tmp_path, FleetConfig(control="adaptbf"), fleet)
    nodes, rates, volume, cap, backlog = fleet
    other = FleetService(FleetConfig(control="aimd"), nodes, volume,
                         cap, backlog, checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="different control policy"):
        other.restore()


# ------------------------------------------------- checkpoint path contract


#: The carry's leaf paths ARE the on-disk checkpoint naming: renaming a
#: WindowCarry/StreamStats field orphans every existing checkpoint.  Append
#: new fields; never rename (see telemetry.stream_stats_leaf_paths).
EXPECTED_STATS_PATHS = (
    ".windows",
    ".served_sum", ".served_sumsq",
    ".demand_sum", ".demand_sumsq",
    ".alloc_sum", ".alloc_sumsq",
    ".alloc_windows",
    ".util_sum",
    ".busy_windows",
    ".lag_sum", ".lag_sumsq", ".lag_max",
    ".lag_hist",
    ".last_served",
    ".comp.served_sum", ".comp.served_sumsq",
    ".comp.demand_sum", ".comp.demand_sumsq",
    ".comp.alloc_sum", ".comp.alloc_sumsq",
    ".comp.util_sum", ".comp.lag_sum", ".comp.lag_sumsq", ".comp.lag_hist",
    # fault counters (PR 7) -- appended, per the naming contract
    ".down_windows", ".droop_windows", ".obs_lost",
)


def test_stream_stats_leaf_paths_are_stable():
    assert telemetry.stream_stats_leaf_paths() == EXPECTED_STATS_PATHS


def test_carry_checkpoint_paths_are_stable():
    nodes, rates, volume, cap, backlog = small_fleet()
    cfg = FleetConfig(control="adaptbf", telemetry="streaming")
    svc = FleetService(cfg, nodes, volume, cap, backlog)
    flat, _ = jax.tree_util.tree_flatten_with_path(svc.carry)
    paths = tuple(jax.tree_util.keystr(p) for p, _ in flat)
    prefix = (".window", ".queue", ".vol_left",
              ".policy_state.record", ".policy_state.remainder",
              ".policy_state.alloc_prev", ".alloc")
    # the last-observation-hold state (PR 7) -- appended after .stats,
    # per the extend-by-appending contract
    suffix = (".held.served", ".held.demand", ".held.alloc")
    assert paths[:len(prefix)] == prefix
    assert paths[len(prefix):] == tuple(
        ".stats" + p for p in EXPECTED_STATS_PATHS) + suffix
    assert len(set(paths)) == len(paths)               # paths are unique


def test_checkpoint_roundtrip_preserves_inf_and_int_leaves(tmp_path):
    """Unruled allocations are inf and counters are int32; both must
    survive the npy round-trip exactly."""
    nodes, rates, volume, cap, backlog = small_fleet()
    cfg = FleetConfig(control="adaptbf", telemetry="streaming")
    svc = FleetService(cfg, nodes, volume, cap, backlog,
                       checkpoint_dir=str(tmp_path))
    svc.step(rates[:WT])
    before = jax.tree.map(np.asarray, svc.carry)
    svc.save()
    svc2 = FleetService(cfg, nodes, volume, cap, backlog,
                        checkpoint_dir=str(tmp_path))
    svc2.restore()
    after = jax.tree.map(np.asarray, svc2.carry)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    # the round-trip really exercised both: unbounded jobs are inf in
    # vol_left, and window/alloc_windows/last_served are int32
    assert np.isinf(np.asarray(svc2.carry.vol_left)).any()
    assert np.asarray(svc2.carry.window).dtype == np.int32


# ------------------------------------------------------------- guard rails


def test_service_rejects_sharded_partition():
    nodes, rates, volume, cap, backlog = small_fleet()
    with pytest.raises(ValueError, match="partition"):
        FleetService(FleetConfig(partition="ost_shard"), nodes, volume,
                     cap, backlog)


def test_service_rejects_bad_window_shape():
    nodes, rates, volume, cap, backlog = small_fleet()
    svc = FleetService(FleetConfig(), nodes, volume, cap, backlog)
    with pytest.raises(ValueError, match="window_ticks"):
        svc.step(rates[: WT - 1])


def test_checkpoint_requires_directory():
    nodes, rates, volume, cap, backlog = small_fleet()
    svc = FleetService(FleetConfig(), nodes, volume, cap, backlog)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        svc.save()
    with pytest.raises(ValueError, match="checkpoint_dir"):
        svc.restore()

"""Streaming telemetry vs trajectory mode: the carry-resident accumulators
must (a) hold no horizon-shaped arrays and (b) finalize to the same metrics
the post-hoc numpy functions compute from full trajectories -- on every
registered scenario and every registered policy -- plus the periodic
``n_windows`` horizon override that makes long streaming runs affordable."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.storage import (
    FleetConfig,
    SimConfig,
    StreamResult,
    get_scenario,
    list_fleet_scenarios,
    list_scenarios,
    metrics,
    simulate,
    simulate_fleet,
)

SINGLE_SCENARIOS = sorted(set(list_scenarios()) - set(list_fleet_scenarios()))


def _fleet_args(scn):
    return (jnp.asarray(scn.nodes), jnp.asarray(scn.issue_rate),
            jnp.asarray(scn.volume), jnp.asarray(scn.capacity_per_tick),
            jnp.asarray(scn.max_backlog))


def _assert_stream_matches_trajectory(stats, served, demand, nodes, cap_w,
                                      tag=""):
    """Core agreement contract: streaming finalizers == post-hoc metrics."""
    np.testing.assert_allclose(
        metrics.streaming_aggregate_mb(stats), metrics.aggregate_mb(served),
        rtol=1e-5, err_msg=f"{tag}: aggregate")
    np.testing.assert_allclose(
        metrics.streaming_mean_utilization(stats),
        metrics.mean_utilization(served, cap_w),
        rtol=1e-5, err_msg=f"{tag}: utilization")
    s_j = served.sum(axis=1) if served.ndim == 3 else served
    d_j = demand.sum(axis=1) if demand.ndim == 3 else demand
    np.testing.assert_allclose(
        metrics.streaming_fairness(stats, nodes),
        metrics.fairness(s_j, nodes, d_j),
        rtol=1e-5, atol=1e-7, err_msg=f"{tag}: fairness")
    np.testing.assert_allclose(
        metrics.streaming_job_slowdown(stats, cap_w),
        metrics.job_slowdown(served, cap_w),
        rtol=1e-5, equal_nan=True, err_msg=f"{tag}: slowdown")
    # the histogram p99 reports the upper edge of the percentile's bin:
    # exact within one log-spaced bin (~16%/bin), not to the ulp
    exact = metrics.p99_queue(demand, served)
    approx = metrics.streaming_p99_queue(stats)
    assert approx <= exact * 1.3 + 0.05, f"{tag}: p99 {approx} vs {exact}"
    assert approx >= exact * 0.77 - 0.05, f"{tag}: p99 {approx} vs {exact}"


@pytest.mark.parametrize("name", list_fleet_scenarios())
def test_fleet_streaming_matches_trajectory_every_scenario(name):
    scn = get_scenario(name, duration_s=8.0)
    args = _fleet_args(scn)
    cfg = FleetConfig(control="adaptbf")
    traj = simulate_fleet(cfg, *args)
    stream = simulate_fleet(cfg._replace(telemetry="streaming"), *args)
    cap_w = scn.capacity_per_tick * cfg.window_ticks
    served, demand = np.asarray(traj.served), np.asarray(traj.demand)
    assert int(stream.stats.windows) == served.shape[0]
    _assert_stream_matches_trajectory(
        stream.stats, served, demand, scn.nodes, cap_w, tag=name)
    np.testing.assert_array_equal(np.asarray(stream.queue_final),
                                  np.asarray(traj.queue_final))


@pytest.mark.parametrize("name", SINGLE_SCENARIOS)
def test_single_target_streaming_matches_trajectory_every_scenario(name):
    scn = get_scenario(name, duration_s=8.0)
    args = (jnp.asarray(scn.nodes), jnp.asarray(scn.issue_rate),
            jnp.asarray(scn.volume), jnp.asarray(scn.max_backlog))
    cfg = SimConfig(control="adaptbf")
    traj = simulate(cfg, *args)
    stream = simulate(cfg._replace(telemetry="streaming"), *args)
    cap_w = cfg.capacity_per_tick * cfg.window_ticks
    served, demand = np.asarray(traj.served), np.asarray(traj.demand)
    # single-target stats arrive squeezed to [J]
    assert np.asarray(stream.stats.served_sum).ndim == 1
    _assert_stream_matches_trajectory(
        stream.stats, served, demand, scn.nodes, cap_w, tag=name)


@pytest.mark.parametrize("control",
                         ["adaptbf", "static", "nobw", "static_wc", "aimd"])
def test_streaming_agrees_for_every_registered_policy(control):
    """The accumulators are policy-agnostic -- including the all-infinite
    allocation trajectory of nobw (masked out of the alloc moments)."""
    scn = get_scenario("fleet_churn", duration_s=6.0)
    args = _fleet_args(scn)
    cfg = FleetConfig(control=control)
    traj = simulate_fleet(cfg, *args)
    stream = simulate_fleet(cfg._replace(telemetry="streaming"), *args)
    cap_w = scn.capacity_per_tick * cfg.window_ticks
    served, demand = np.asarray(traj.served), np.asarray(traj.demand)
    _assert_stream_matches_trajectory(
        stream.stats, served, demand, scn.nodes, cap_w, tag=control)
    # alloc moments: finite windows only; nobw never has a finite alloc
    alloc_windows = np.asarray(stream.stats.alloc_windows)
    if control == "nobw":
        assert (alloc_windows == 0).all()
    else:
        assert alloc_windows.sum() > 0
        alloc = np.asarray(traj.alloc, np.float64)
        finite = np.isfinite(alloc)
        np.testing.assert_allclose(
            np.asarray(stream.stats.alloc_sum),
            np.where(finite, alloc, 0.0).sum(axis=0), rtol=1e-5, atol=1e-3)


def test_streaming_carry_is_horizon_independent():
    """No output array may scale with the horizon: doubling n_windows must
    leave every stats shape unchanged (that is the whole point)."""
    import jax
    scn = get_scenario("fleet_ost_imbalance", duration_s=4.0)
    args = _fleet_args(scn)
    cfg = FleetConfig(control="adaptbf", telemetry="streaming")
    short = simulate_fleet(cfg, *args)
    long = simulate_fleet(cfg, *args, n_windows=160)
    assert isinstance(short, StreamResult)
    shapes_s = [np.asarray(x).shape for x in jax.tree.leaves(short.stats)]
    shapes_l = [np.asarray(x).shape for x in jax.tree.leaves(long.stats)]
    assert shapes_s == shapes_l
    assert int(long.stats.windows) == 160
    from repro.storage.telemetry import NBINS
    o, j = scn.issue_rate.shape[1], scn.nodes.shape[0]
    assert max(np.asarray(x).size
               for x in jax.tree.leaves(short.stats)) == max(o * j, o * NBINS)


def test_n_windows_tiles_the_trace_periodically():
    """The horizon override must reproduce, bitwise, a run on the explicitly
    np.tile-d trace -- trajectory mode makes the comparison exact."""
    rng = np.random.default_rng(11)
    t, o, j = 100, 3, 5
    rates = (rng.integers(0, 25, (t, o, j))
             * (rng.random((t, o, j)) < 0.5)).astype(np.float32)
    nodes = rng.integers(1, 32, (j,)).astype(np.float32)
    volume = np.full((o, j), np.inf, np.float32)
    caps = np.array([20.0, 12.0, 8.0], np.float32)
    cfg = FleetConfig(control="adaptbf")
    tiled = simulate_fleet(cfg, jnp.asarray(nodes), jnp.asarray(rates),
                           jnp.asarray(volume), jnp.asarray(caps),
                           n_windows=30)
    explicit = simulate_fleet(cfg, jnp.asarray(nodes),
                              jnp.asarray(np.tile(rates, (3, 1, 1))),
                              jnp.asarray(volume), jnp.asarray(caps))
    for field in ("served", "demand", "alloc", "record", "queue_final"):
        np.testing.assert_array_equal(
            np.asarray(getattr(tiled, field)),
            np.asarray(getattr(explicit, field)), err_msg=field)


def test_unknown_telemetry_mode_rejected():
    cfg = FleetConfig(telemetry="psychic")
    with pytest.raises(ValueError, match="telemetry"):
        simulate_fleet(cfg, jnp.ones(4), jnp.ones((10, 2, 4)),
                       jnp.full((2, 4), jnp.inf))


def test_kahan_sums_survive_past_f32_precision_cliff():
    """At long horizons a plain f32 running sum stalls (adding 1.0 to 2^24
    rounds back to 2^24 forever); the compensated accumulators must not.
    Pre-load the carry at the cliff and fold 20k more unit-served windows."""
    import jax
    from repro.storage import telemetry

    stats0 = telemetry.init_stats(1, 1)
    cliff = jnp.float32(2.0 ** 24)
    stats0 = stats0._replace(
        served_sum=jnp.full((1, 1), cliff),
        util_sum=jnp.full((1,), cliff), windows=jnp.int32(2 ** 24))
    one = jnp.ones((1, 1), jnp.float32)
    cap = jnp.ones((1,), jnp.float32)

    def fold(stats, _):
        return telemetry.update_stats(stats, one, one, one, cap), None

    stats, _ = jax.jit(lambda s: jax.lax.scan(fold, s, None, length=20_000))(
        stats0)
    # naive f32 would still read 2^24 exactly; compensated sums advance
    assert float(stats.served_sum[0, 0]) + float(
        stats.comp.served_sum[0, 0]) == 2.0 ** 24 + 20_000
    assert float(stats.util_sum[0]) + float(
        stats.comp.util_sum[0]) == 2.0 ** 24 + 20_000
    assert int(stats.windows) == 2 ** 24 + 20_000   # int32 counter is exact


# --------------------------------------------------- metric units (numpy)


def test_job_slowdown_hand_case_single_target():
    # cap 10/window; job0 moves 20 RPCs finishing in window 1 (2 windows,
    # ideal 2) -> 1.0; job1 moves 10 RPCs but finishes only in window 3
    # (4 windows, ideal 1) -> 4.0; job2 never served -> NaN
    served = np.array([
        [10.0, 0.0, 0.0],
        [10.0, 5.0, 0.0],
        [0.0, 0.0, 0.0],
        [0.0, 5.0, 0.0],
    ])
    slow = metrics.job_slowdown(served, 10.0)
    np.testing.assert_allclose(slow[:2], [1.0, 4.0])
    assert np.isnan(slow[2])


def test_job_slowdown_fleet_uses_stripe_set_capacity():
    # job0 stripes over both OSTs (cap 10+10), job1 only OST 1 (cap 10)
    served = np.zeros((2, 2, 2))
    served[0, :, 0] = [10.0, 10.0]   # 20 RPCs in window 0 -> ideal 1 -> 1.0
    served[1, 1, 1] = 10.0           # 10 RPCs, done window 1 -> ideal 1 -> 2.0
    slow = metrics.job_slowdown(served, np.array([10.0, 10.0]))
    np.testing.assert_allclose(slow, [1.0, 2.0])


def test_utilization_single_definition_and_reexport():
    """Satellite: ``simulator.utilization`` is a thin re-export of the
    single definition in ``storage/metrics.py``."""
    from repro.storage import simulator, utilization
    scn = get_scenario("allocation_ivd", duration_s=5.0)
    cfg = SimConfig(control="adaptbf")
    res = simulate(cfg, jnp.asarray(scn.nodes), jnp.asarray(scn.issue_rate),
                   jnp.asarray(scn.volume), jnp.asarray(scn.max_backlog))
    a = np.asarray(utilization(res, cfg))
    b = np.asarray(metrics.utilization(res, cfg))
    c = np.asarray(simulator.utilization(res, cfg))
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)
    assert a.shape == (np.asarray(res.served).shape[0],)


@pytest.mark.parametrize("n", [1, 2, 3, 128, 1000, 4096])
def test_row_sum_is_a_fixed_pairwise_tree(n):
    """The per-OST sums of the streaming fold (utilization, backlog growth)
    use one summation order whatever fusion XLA puts them in: a halving
    tree over the zero-padded row, reproduced here in numpy bit for bit."""
    from repro.storage.telemetry import row_sum
    x = np.random.default_rng(n).random((5, n)).astype(np.float32) * 37.0
    width = 1 << (n - 1).bit_length()
    tree = np.pad(x, [(0, 0), (0, width - n)])
    while tree.shape[-1] > 1:
        half = tree.shape[-1] // 2
        tree = tree[:, :half] + tree[:, half:]
    got = np.asarray(row_sum(jnp.asarray(x)))
    assert got.tobytes() == tree[:, 0].tobytes()
    np.testing.assert_allclose(got, x.sum(-1, dtype=np.float64), rtol=1e-5)


def _lags(case):
    """Backlog values for the histogram cases, [..., O, J] float32."""
    rng = np.random.default_rng(14)
    if case == "random":
        return rng.lognormal(2.0, 3.0, (8, 4096)).astype(np.float32)
    if case == "edges":
        # every bin edge 10^(-2 + 8k/128), zeros, negatives, past 10^6
        edges = 10.0 ** (-2.0 + np.arange(129) * 8.0 / 128.0)
        row = np.concatenate([edges, np.zeros(40), -edges[:40],
                              [1e6, 2e6, 1e9, 3.4e38, -3.4e38]])
        return np.stack([row, row[::-1], np.roll(row, 7)]).astype(np.float32)
    if case == "one_ost":
        return rng.lognormal(2.0, 3.0, (1, 4096)).astype(np.float32)
    assert case == "tenants"
    return rng.lognormal(2.0, 3.0, (5, 8, 512)).astype(np.float32)


@pytest.mark.parametrize("case", ["random", "edges", "one_ost", "tenants"])
def test_histogram_counts_agree_bitwise_across_formulations(case):
    """The TPU's one-hot contraction and the scatter-add count the same
    histogram as ``np.add.at``, bit for bit, at the fleet's shapes, at
    exact bin edges and out-of-range backlogs, at O = 1 and under a vmap
    over a tenant axis."""
    import jax
    from repro.storage import telemetry

    lag = _lags(case)
    bins = telemetry.lag_bin(jnp.asarray(lag))
    want = np.zeros(lag.shape[:-1] + (telemetry.NBINS,), np.float32)
    lead = np.indices(lag.shape[:-1])
    np.add.at(want, (*[i[..., None] for i in lead], np.asarray(bins)), 1.0)
    assert want.sum() == lag.size
    for count in (telemetry._count_bins_dot, telemetry._count_bins_scatter,
                  telemetry.count_bins):
        f = jax.jit(jax.vmap(count) if lag.ndim == 3 else count)
        got = np.asarray(f(bins))
        assert got.dtype == np.float32
        assert got.tobytes() == want.tobytes(), count.__name__


def test_cpu_fold_keeps_the_scatter():
    """Off the TPU the streaming fold counts its histogram by scatter-add,
    and only the TPU's branch holds the one-hot contraction."""
    import jax
    from repro.storage import telemetry

    stats = telemetry.init_stats(8, 256)
    x = jnp.ones((8, 256), jnp.float32)
    cap = jnp.full((8,), 10.0, jnp.float32)
    text = jax.jit(
        lambda s, a: telemetry.update_stats(s, a, 2 * a, a, cap)
    ).lower(stats, x).compile().as_text()
    assert "scatter" in text
    assert " dot(" not in text

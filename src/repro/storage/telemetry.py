"""Streaming per-window metric accumulators for long-horizon runs.

Trajectory telemetry materializes ``[n_windows, O, J]`` arrays -- fine for
paper-length horizons, impossible for the long bursty traces the paper's
evaluation sweeps (2000+ windows at fleet scale would be gigabytes).  With
``FleetConfig(telemetry="streaming")`` the engine instead folds each
window's observation into the ``StreamStats`` carry below *inside* the
``lax.scan``, so peak memory is independent of horizon length: a handful of
``[O, J]`` sufficient statistics, per-OST utilization/backlog sums, and a
fixed-width log-spaced backlog histogram per OST.

Row decomposition (the sharding contract, DESIGN.md section 8): every
accumulator keeps a leading OST axis and is updated from that OST's row
alone, so under ``FleetConfig(partition="ost_shard")`` each device folds
stats for its local OST rows and the concatenation of the shards is bitwise
identical to the single-device carry.  Cross-OST reductions (fleet means,
global histograms, global maxima) happen only in the numpy finalizers in
``storage/metrics.py`` -- identically in both modes, after the run.  The one
exception is the fleet-busy flag (a window is *busy* when any OST served
anything): that is a per-window OR across the whole fleet, kept exact under
sharding by summing int32 busy-OST counts with ``lax.psum`` -- integer
addition is associative, so the flag (and the int32 ``busy_windows``
counter) cannot drift with device count.

The backlog histogram is counted per platform (``count_bins``): a
scatter-add where that is fast (CPU, GPU), and on a TPU, which serialises
a scatter's colliding updates, a one-hot contraction over the job axis on
the MXU.  The counts are small integers, exact in f32 whatever the order
of additions, so both give the same histogram bit for bit.

Accuracy at extreme horizons: JAX runs f32 by default, and a plain f32
running sum silently drops increments once the total passes 2^24 (a job
served 200 RPCs/window stalls after ~10^5 windows).  Every floating-point
sum therefore carries a Kahan compensation term (``StreamStats.comp``) --
the accumulated error stays O(1) ulp of the total regardless of the window
count -- and pure counters (windows, busy windows, ruled-window counts) are
int32, exact to 2^31.

The numpy finalizers that turn a ``StreamStats`` into report metrics live in
``storage/metrics.py`` (``streaming_*``) next to their post-hoc trajectory
counterparts, and are tested to agree with them on every registered scenario
(``tests/test_streaming_telemetry.py``).

Carry memory budget (f32, compensation included): ``14 x [O, J] + 7 x [O]
+ 2 x [O, NBINS] + O(1)`` -- at O=64, J=1024 that is ~3.7 MB regardless of
whether the run is 20 windows or 20 million (the trajectory equivalent at
2000 windows: ~2.1 GB).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

NBINS = 128            # backlog histogram resolution
LAG_LOG10_LO = -2.0    # histogram range: 10^-2 .. 10^6 RPCs, log-spaced
LAG_LOG10_HI = 6.0


class StreamComp(NamedTuple):
    """Kahan compensation terms, one per floating-point sum field."""

    served_sum: jnp.ndarray
    served_sumsq: jnp.ndarray
    demand_sum: jnp.ndarray
    demand_sumsq: jnp.ndarray
    alloc_sum: jnp.ndarray
    alloc_sumsq: jnp.ndarray
    util_sum: jnp.ndarray
    lag_sum: jnp.ndarray
    lag_sumsq: jnp.ndarray
    lag_hist: jnp.ndarray


class StreamStats(NamedTuple):
    """Sufficient statistics folded into the window-scan carry.

    Per-job arrays are [O, J] from the fleet engine ([J] after the
    single-target squeeze); per-target arrays are [O] ([] squeezed); the
    histogram is [O, NBINS] ([NBINS] squeezed).  Only ``windows`` and
    ``busy_windows`` are fleet-global scalars -- both int32, both exact
    under OST sharding.  Float sums are Kahan-compensated (see ``comp``);
    finalizers should add the matching compensation term for the best
    estimate.
    """

    windows: jnp.ndarray        # () int32: windows accumulated
    served_sum: jnp.ndarray     # [O, J] total RPCs served per job
    served_sumsq: jnp.ndarray   # [O, J] second moment of per-window served
    demand_sum: jnp.ndarray     # [O, J] total observed demand d_x
    demand_sumsq: jnp.ndarray   # [O, J]
    alloc_sum: jnp.ndarray      # [O, J] finite (ruled) allocations only
    alloc_sumsq: jnp.ndarray    # [O, J]
    alloc_windows: jnp.ndarray  # [O, J] int32 windows with a finite alloc
    util_sum: jnp.ndarray       # [O] sum over windows of per-OST utilization
    busy_windows: jnp.ndarray   # () int32: windows where anything was served
    lag_sum: jnp.ndarray        # [O] sum of backlog growth (demand - served)
    lag_sumsq: jnp.ndarray      # [O]
    lag_max: jnp.ndarray        # [O] max per-job backlog growth seen
    lag_hist: jnp.ndarray       # [O, NBINS] log-spaced backlog histogram
    last_served: jnp.ndarray    # [O, J] int32 last window with service (-1)
    comp: StreamComp            # Kahan compensation for the float sums
    # fault counters (appended fields -- checkpoint paths must be stable;
    # all three row-local [O] int32, zero outside fault-injected runs)
    down_windows: jnp.ndarray   # [O] windows the OST spent down
    droop_windows: jnp.ndarray  # [O] windows up but capacity-degraded
    obs_lost: jnp.ndarray       # [O] windows whose observation was lost


def init_stats(n_ost: int, n_jobs: int) -> StreamStats:
    zoj = jnp.zeros((n_ost, n_jobs), jnp.float32)
    zo = jnp.zeros((n_ost,), jnp.float32)
    zh = jnp.zeros((n_ost, NBINS), jnp.float32)
    return StreamStats(
        windows=jnp.int32(0),
        served_sum=zoj, served_sumsq=zoj,
        demand_sum=zoj, demand_sumsq=zoj,
        alloc_sum=zoj, alloc_sumsq=zoj,
        alloc_windows=jnp.zeros((n_ost, n_jobs), jnp.int32),
        util_sum=zo,
        busy_windows=jnp.int32(0),
        lag_sum=zo, lag_sumsq=zo, lag_max=zo,
        lag_hist=zh,
        last_served=jnp.full((n_ost, n_jobs), -1, jnp.int32),
        comp=StreamComp(
            served_sum=zoj, served_sumsq=zoj, demand_sum=zoj,
            demand_sumsq=zoj, alloc_sum=zoj, alloc_sumsq=zoj,
            util_sum=zo, lag_sum=zo, lag_sumsq=zo, lag_hist=zh),
        down_windows=jnp.zeros((n_ost,), jnp.int32),
        droop_windows=jnp.zeros((n_ost,), jnp.int32),
        obs_lost=jnp.zeros((n_ost,), jnp.int32),
    )


def stream_stats_leaf_paths() -> Tuple[str, ...]:
    """Pytree paths of every ``StreamStats`` leaf, in flatten order.

    This is the *checkpoint naming contract*: ``repro/checkpoint`` saves
    leaves keyed by ``jax.tree_util.keystr`` path, and the online service
    (``storage/service.py``) checkpoints the whole engine carry --
    ``StreamStats`` included -- so a controller can resume after a crash.
    Renaming or reordering a field here silently orphans every checkpoint
    written before the rename (restore matches by path, so a missing path
    raises -- but a *swap* of two same-shaped fields would not).  The
    paths are pinned by ``tests/test_service.py``; extend the carry by
    *appending* fields, never by renaming.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(init_stats(1, 1))
    return tuple(jax.tree_util.keystr(path) for path, _ in flat)


def stats_pspecs(axis: str, lead: Optional[str] = None):
    """A ``StreamStats`` of ``PartitionSpec``s for ``shard_map`` out_specs:
    everything row-sharded over ``axis`` except the two scalar counters.

    ``lead`` names an optional *leading fleet axis* (the tenant batch of
    ``storage/tenants.simulate_tenants``): every leaf -- the two int32
    counters included, which are per-fleet ``[F]`` arrays in a batched
    carry -- gains that axis in front of its row layout.  This is the
    fleet extension of the row-locality contract: a batched carry is F
    independent single-fleet carries stacked, so the per-OST layout (and
    the bitwise sharded==unsharded argument that rides on it) is
    unchanged within each fleet slice.
    """
    from jax.sharding import PartitionSpec as P
    front = (lead,) if lead is not None else ()
    oj = P(*front, axis, None)
    o = P(*front, axis)
    rep = P(*front)
    return StreamStats(
        windows=rep,
        served_sum=oj, served_sumsq=oj,
        demand_sum=oj, demand_sumsq=oj,
        alloc_sum=oj, alloc_sumsq=oj,
        alloc_windows=oj,
        util_sum=o,
        busy_windows=rep,
        lag_sum=o, lag_sumsq=o, lag_max=o,
        lag_hist=oj,
        last_served=oj,
        comp=StreamComp(
            served_sum=oj, served_sumsq=oj, demand_sum=oj, demand_sumsq=oj,
            alloc_sum=oj, alloc_sumsq=oj, util_sum=o,
            lag_sum=o, lag_sumsq=o, lag_hist=oj),
        down_windows=o, droop_windows=o, obs_lost=o,
    )


def _kahan(total, comp, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One compensated-summation step: returns (total', comp')."""
    y = x - comp
    t = total + y
    return t, (t - total) - y


def lag_bin(lag: jnp.ndarray) -> jnp.ndarray:
    """Histogram bin index for a backlog value (zeros land in bin 0)."""
    f = (jnp.log10(jnp.maximum(lag, 1e-30)) - LAG_LOG10_LO) \
        / (LAG_LOG10_HI - LAG_LOG10_LO) * NBINS
    return jnp.clip(jnp.floor(f).astype(jnp.int32), 0, NBINS - 1)


_LO_BINS = 16          # a bin index splits as b = _LO_BINS * hi + lo


def _count_bins_scatter(bins: jnp.ndarray) -> jnp.ndarray:
    """[O, J] bin indices -> [O, NBINS] f32 counts by scatter-add."""
    n_ost = bins.shape[0]
    return jnp.zeros((n_ost, NBINS), jnp.float32).at[
        jnp.arange(n_ost)[:, None], bins].add(1.0)


def _count_bins_dot(bins: jnp.ndarray) -> jnp.ndarray:
    """[O, J] bin indices -> [O, NBINS] f32 counts by a factored one-hot
    contraction: ``b = 16 hi + lo``, and ``count[o, hi, lo]`` is the dot
    over J of the 0/1 one-hots ``[O, J, 8]`` and ``[O, J, 16]`` (bf16
    operands, f32 accumulation, on the MXU).  XLA fuses the one-hots into
    the dot, so no ``[O, J, 8]``/``[O, J, 16]`` buffer is written."""
    hi = jnp.arange(NBINS // _LO_BINS, dtype=bins.dtype)
    lo = jnp.arange(_LO_BINS, dtype=bins.dtype)
    hot_hi = (bins[:, :, None] // _LO_BINS == hi).astype(jnp.bfloat16)
    hot_lo = (bins[:, :, None] % _LO_BINS == lo).astype(jnp.bfloat16)
    counts = jnp.einsum("ojh,ojl->ohl", hot_hi, hot_lo,
                        preferred_element_type=jnp.float32)
    return counts.reshape(bins.shape[0], NBINS)


def count_bins(bins: jnp.ndarray) -> jnp.ndarray:
    """Histogram of each row of ``[O, J]`` bin indices in ``[0, NBINS)``:
    ``[O, NBINS]`` f32 counts, the same on every platform, bit for bit.

    The formulation follows the platform the program lowers for.  On a TPU
    a scatter's colliding updates are serialised (4096 jobs into 128 bins
    a row: ~8.9 ms a window at (248, 4096) on a v5e), so the TPU counts by
    ``_count_bins_dot``'s one-hot contraction (~0.2 ms there).  Elsewhere
    the scatter-add is the fast one, and stays: on an 8-core Xeon CPU the
    whole ``update_stats`` fold takes 12-14 ms a window at (248, 4096)
    with the scatter and 54-70 ms with the contraction, 1.2-2.0 against
    2.6 ms at (64, 1024), and 0.40 against 0.60-0.66 ms at (16, 256).
    Both are exact: a count is an integer no larger than J, far below
    2^24, so any order of f32 additions gives it; the one-hots are exact
    in bf16."""
    return jax.lax.platform_dependent(
        bins, tpu=_count_bins_dot, default=_count_bins_scatter)


def bin_upper_edge(b) -> float:
    """Upper edge (RPCs) of histogram bin ``b``."""
    import numpy as np
    return float(10.0 ** (
        LAG_LOG10_LO + (np.asarray(b) + 1) * (LAG_LOG10_HI - LAG_LOG10_LO)
        / NBINS))


def row_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over the last axis in a fixed pairwise order.

    XLA tiles a reduction to fit the fusion it lands in, and on a TPU that
    tiling sets the order of the partial sums: one ``jnp.sum`` rounds
    differently inside the offline window scan, in the online step, and on
    a sharded row slice.  A halving tree of elementwise adds (zero-padded
    to a power of two, which adds nothing) has one result per element
    whatever the fusion, so the same-program identities stay bitwise."""
    n = x.shape[-1]
    width = 1 << (n - 1).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def update_stats(stats: StreamStats, served_w, demand, alloc, cap_w,
                 axis_name: Optional[str] = None,
                 faults_w=None) -> StreamStats:
    """Fold one window's [O, J] observation into the carry.

    Mirrors the post-hoc definitions in ``storage/metrics.py`` exactly:
    per-window utilization is ``served.sum(jobs) / cap_w``, a window is
    *busy* when any OST served anything, and the allocation moments mask
    unruled (infinite) entries.  Under fault injection ``cap_w`` is the
    window's *effective* capacity (zero while down), so ``util_sum``
    accumulates utilization of what the hardware could actually serve.

    Every update touches only its own OST row, except the busy flag: with
    ``axis_name`` set (inside ``shard_map``) the int32 busy-OST count is
    ``psum``-med across the mesh so the flag matches the unsharded run bit
    for bit (integer addition cannot reorder-drift).

    The window's backlog histogram is ``count_bins(lag_bin(lag))``: a
    scatter-add off the TPU, a one-hot contraction on it (a TPU serialises
    the scatter's colliding updates).  Its counts are integers no larger
    than J, exact in f32, so the carry is bitwise the same on either path.

    ``faults_w`` (optional ``faults.FaultPlan`` row, [O] leaves) advances
    the row-local fault counters: windows down, windows up-but-degraded,
    observations lost.  ``None`` leaves them untouched -- a fault-free
    run's stats are bitwise those of the pre-fault engine.
    """
    served_o = row_sum(served_w)
    util_o = served_o / jnp.maximum(cap_w, 1e-12)
    busy_osts = jnp.sum((served_o > 0).astype(jnp.int32))
    if axis_name is not None:
        busy_osts = jax.lax.psum(busy_osts, axis_name)
    busy = busy_osts > 0
    lag = demand - served_w
    ruled = jnp.isfinite(alloc)
    alloc_f = jnp.where(ruled, alloc, 0.0)
    window_hist = count_bins(lag_bin(lag))
    c = stats.comp
    served_sum, c_served_sum = _kahan(stats.served_sum, c.served_sum, served_w)
    served_sumsq, c_served_sumsq = _kahan(
        stats.served_sumsq, c.served_sumsq, served_w * served_w)
    demand_sum, c_demand_sum = _kahan(stats.demand_sum, c.demand_sum, demand)
    demand_sumsq, c_demand_sumsq = _kahan(
        stats.demand_sumsq, c.demand_sumsq, demand * demand)
    alloc_sum, c_alloc_sum = _kahan(stats.alloc_sum, c.alloc_sum, alloc_f)
    alloc_sumsq, c_alloc_sumsq = _kahan(
        stats.alloc_sumsq, c.alloc_sumsq, alloc_f * alloc_f)
    util_sum, c_util_sum = _kahan(stats.util_sum, c.util_sum, util_o)
    lag_sum, c_lag_sum = _kahan(stats.lag_sum, c.lag_sum, row_sum(lag))
    lag_sumsq, c_lag_sumsq = _kahan(
        stats.lag_sumsq, c.lag_sumsq, row_sum(lag * lag))
    lag_hist, c_lag_hist = _kahan(stats.lag_hist, c.lag_hist, window_hist)
    down_windows, droop_windows, obs_lost = (
        stats.down_windows, stats.droop_windows, stats.obs_lost)
    if faults_w is not None:
        down = faults_w.up <= 0.0
        down_windows = down_windows + down.astype(jnp.int32)
        droop_windows = droop_windows + (
            (~down) & (faults_w.cap_scale < 1.0)).astype(jnp.int32)
        obs_lost = obs_lost + (faults_w.telem_ok <= 0.0).astype(jnp.int32)
    return StreamStats(
        windows=stats.windows + 1,
        served_sum=served_sum, served_sumsq=served_sumsq,
        demand_sum=demand_sum, demand_sumsq=demand_sumsq,
        alloc_sum=alloc_sum, alloc_sumsq=alloc_sumsq,
        alloc_windows=stats.alloc_windows + ruled.astype(jnp.int32),
        util_sum=util_sum,
        busy_windows=stats.busy_windows + busy.astype(jnp.int32),
        lag_sum=lag_sum, lag_sumsq=lag_sumsq,
        lag_max=jnp.maximum(stats.lag_max, jnp.max(lag, axis=-1)),
        lag_hist=lag_hist,
        last_served=jnp.where(served_w > 0, stats.windows,
                              stats.last_served),
        comp=StreamComp(
            served_sum=c_served_sum, served_sumsq=c_served_sumsq,
            demand_sum=c_demand_sum, demand_sumsq=c_demand_sumsq,
            alloc_sum=c_alloc_sum, alloc_sumsq=c_alloc_sumsq,
            util_sum=c_util_sum, lag_sum=c_lag_sum, lag_sumsq=c_lag_sumsq,
            lag_hist=c_lag_hist),
        down_windows=down_windows, droop_windows=droop_windows,
        obs_lost=obs_lost,
    )


def squeeze_stats(stats: StreamStats) -> StreamStats:
    """Drop the O=1 axis for the single-target view."""
    c = stats.comp
    return stats._replace(
        served_sum=stats.served_sum[0], served_sumsq=stats.served_sumsq[0],
        demand_sum=stats.demand_sum[0], demand_sumsq=stats.demand_sumsq[0],
        alloc_sum=stats.alloc_sum[0], alloc_sumsq=stats.alloc_sumsq[0],
        alloc_windows=stats.alloc_windows[0],
        util_sum=stats.util_sum[0],
        lag_sum=stats.lag_sum[0], lag_sumsq=stats.lag_sumsq[0],
        lag_max=stats.lag_max[0],
        lag_hist=stats.lag_hist[0],
        last_served=stats.last_served[0],
        comp=c._replace(
            served_sum=c.served_sum[0], served_sumsq=c.served_sumsq[0],
            demand_sum=c.demand_sum[0], demand_sumsq=c.demand_sumsq[0],
            alloc_sum=c.alloc_sum[0], alloc_sumsq=c.alloc_sumsq[0],
            util_sum=c.util_sum[0], lag_sum=c.lag_sum[0],
            lag_sumsq=c.lag_sumsq[0], lag_hist=c.lag_hist[0]),
        down_windows=stats.down_windows[0],
        droop_windows=stats.droop_windows[0],
        obs_lost=stats.obs_lost[0],
    )

"""The plain reference: the fleet controller's semantics in straightforward
numpy, one observation window at a time.

It imports nothing of the program under test.  It restates, from the
AdapTBF paper (Section III) and the simulator's documented model, what one
window does to one fleet of O storage targets (OSTs) and J jobs:

* serve: every tick, clients issue into their server-side queues (bounded by
  remaining volume and the in-flight cap), ruled jobs dequeue up to their
  token budget, scaled down together when they want more than the tick's
  capacity, and unruled jobs share whatever capacity the ruled ones left;
* observe: demand is what was served plus the standing queue;
* allocate: the control policy turns the observation into the next window's
  allocation (AdapTBF's three steps with largest-remainder integer
  distribution, or one of the comparison policies);
* fold: the streaming telemetry accumulators (Kahan-compensated sums, the
  log-spaced backlog histogram, counters).

Every operation is row-local: no OST's row reads another's, which is the
decentralization guarantee.  The one fleet-wide quantity, the busy-window
flag, is passed in by the caller.

All arithmetic runs in the dtype given (``numpy.float32`` for the
reference, ``ml_dtypes.bfloat16`` for the lower-precision control); every
constant is cast to it, so no operation silently widens.  Integer
bookkeeping (counts, ranks) uses int64.
"""
from __future__ import annotations

import numpy as np

#: streaming histogram geometry: 128 log-spaced bins over 1e-2 .. 1e6 RPCs
NBINS = 128
LAG_LOG10_LO = -2.0
LAG_LOG10_HI = 6.0

POLICIES = ("adaptbf", "aimd", "nobw", "static", "static_wc")

#: the carry leaves a streaming run accumulates, in a fixed order
STAT_SUMS = ("served_sum", "served_sumsq", "demand_sum", "demand_sumsq",
             "alloc_sum", "alloc_sumsq", "util_sum", "lag_sum", "lag_sumsq",
             "lag_hist")


class Reference:
    """One fleet's window semantics in a fixed dtype.

    Args:
      dtype: ``np.float32`` (the reference) or a lower precision (the
        control).
      window_ticks: ticks per observation window.
      u_max: AdapTBF's utilization-score cap.

    Tokens are whole: every distribution step hands out integers by largest
    remainder, as the configurations state (``integer_tokens``).
    """

    def __init__(self, dtype=np.float32, window_ticks: int = 10,
                 u_max: float = 64.0):
        self.dt = np.dtype(dtype)
        self.window_ticks = int(window_ticks)
        self.u_max = self.k(u_max)

    # -------------------------------------------------------------- dtype
    def k(self, v):
        """A constant in the working dtype."""
        return np.asarray(v, self.dt)

    def a(self, x):
        """An array in the working dtype."""
        return np.asarray(x).astype(self.dt)

    def rsum(self, x):
        """Sum over jobs (the last axis), kept as a column."""
        return np.sum(x, axis=-1, keepdims=True, dtype=self.dt)

    # -------------------------------------------------------------- serve
    def serve_window(self, queue, vol_left, budget, rates_w, backlog,
                     cap_tick):
        """All ticks of one window.  ``rates_w`` [W, O, J]; ``cap_tick`` [O].
        Returns (queue, vol_left, served over the window)."""
        k, z = self.k, self.k(0)
        cap = self.a(cap_tick)[:, None]
        served_w = np.zeros_like(queue)
        for rate in rates_w:
            headroom = np.maximum(backlog - queue, z)
            issued = np.minimum(np.minimum(self.a(rate), vol_left), headroom)
            queue = np.maximum(queue + issued, z)
            vol_left = vol_left - issued
            ruled = np.isfinite(budget)
            want1 = np.where(ruled, np.minimum(queue, np.maximum(budget, z)),
                             z)
            s1 = want1 * np.minimum(k(1), cap / np.maximum(self.rsum(want1),
                                                          k(1e-9)))
            spare = np.maximum(cap - self.rsum(s1), z)
            want2 = np.where(ruled, z, queue)
            s2 = want2 * np.minimum(k(1), spare / np.maximum(self.rsum(want2),
                                                            k(1e-9)))
            served = np.minimum(s1 + s2, queue)
            queue = queue - served
            budget = budget - served
            served_w = served_w + served
        return queue, vol_left, served_w

    # ------------------------------------------------ integer distribution
    @staticmethod
    def _top(key, k):
        """Membership of the ``k`` largest keys per row (ties: lower index
        first).  ``key`` [O, J]; ``k`` [O, 1] int."""
        key = np.where(key == 0, np.zeros_like(key), key)  # -0.0 ties +0.0
        order = np.argsort(-key.astype(np.float64), axis=-1, kind="stable")
        rank = np.empty_like(order)
        np.put_along_axis(rank, order, np.arange(key.shape[-1])[None, :],
                          axis=-1)
        return rank < k

    def distribute(self, raw, remainder, budget, mask):
        """Hand out ``budget`` whole tokens over ``mask`` by largest
        remainder (paper Eq. 21-25).  Returns (tokens, new remainder)."""
        z = self.k(0)
        raw = np.where(mask, raw, z)
        x = np.where(mask, raw + remainder, z)
        floored = np.maximum(np.floor(x), z)
        rem = np.where(mask, x - floored, z)
        delta = np.round(budget - self.rsum(floored)).astype(np.float64)
        delta = np.clip(delta, -(2.0 ** 30), 2.0 ** 30).astype(np.int64)
        n_mask = mask.sum(axis=-1, keepdims=True)
        neg = np.asarray(-np.inf, self.dt)
        # leftover: q tokens to every masked job, then one more to the
        # ``part`` largest remainders
        up = np.maximum(delta, 0)
        q = up // np.maximum(n_mask, 1)
        part = up - q * n_mask
        bump_up = q * mask + (self._top(np.where(mask, rem, neg), part)
                              & mask)
        # excess: take one token per round from every job still holding
        # one, while the whole round fits; the last, partial round takes
        # from the largest remainders among those still holding a token
        left = np.maximum(-delta, 0)
        hold = np.where(mask, floored, z).astype(np.float64)
        take = np.zeros(hold.shape, np.float64)
        while True:
            elig = mask & (hold - take >= 1)
            n_el = elig.sum(axis=-1, keepdims=True)
            full = (n_el > 0) & (left >= n_el)
            if not full.any():
                break
            take = take + (full & elig)
            left = left - np.where(full, n_el, 0)
        elig = mask & (hold - take >= 1)
        take = take + (self._top(np.where(elig, rem, neg),
                                 np.where(left > 0, left, 0)) & elig)
        applied = np.where(delta > 0, bump_up, np.where(delta < 0, -take, 0))
        applied = applied.astype(self.dt)
        return floored + applied, np.where(mask, rem - applied, remainder)

    # ------------------------------------------------------------ policies
    def static_alloc(self, nodes, cap_w):
        share = nodes / np.maximum(self.rsum(nodes), self.k(1e-12))
        return cap_w[:, None] * share

    def init_policy(self, policy, nodes, cap_w):
        """(state, window-0 allocation)."""
        inf = np.full(nodes.shape, np.inf, self.dt)
        z = np.zeros(nodes.shape, self.dt)
        if policy == "adaptbf":
            return {"record": z, "remainder": z.copy(),
                    "alloc_prev": z.copy()}, inf
        if policy == "aimd":
            return {"rate": self.static_alloc(nodes, cap_w)}, inf
        if policy == "nobw":
            return {}, inf
        if policy in ("static", "static_wc"):
            return {}, self.static_alloc(nodes, cap_w)
        raise ValueError(f"unknown policy {policy!r}")

    def gate(self, policy, alloc):
        """Window-start token budget (inf = unruled)."""
        if policy in ("adaptbf", "aimd", "static_wc"):
            return np.where(alloc > 0, alloc, self.k(np.inf))
        return alloc

    def record(self, policy, state, nodes):
        if policy == "adaptbf":
            return state["record"]
        return np.zeros(nodes.shape, self.dt)

    def step_policy(self, policy, state, served, demand, alloc, nodes,
                    cap_w):
        """One control round -> (state, next allocation)."""
        k, z = self.k, self.k(0)
        if policy == "adaptbf":
            return self._adaptbf(state, demand, nodes, cap_w)
        if policy == "nobw":
            return state, np.full(nodes.shape, np.inf, self.dt)
        if policy == "static":
            return state, self.static_alloc(nodes, cap_w)
        if policy == "static_wc":
            share = self.static_alloc(nodes, cap_w)
            active = demand > 0
            base = np.where(active, np.minimum(share, demand), z)
            spare = np.maximum(cap_w[:, None] - self.rsum(base), z)
            weight = np.where(active & (demand > share), share, z)
            extra = spare * weight / np.maximum(self.rsum(weight), k(1e-9))
            return state, np.floor(np.where(active, base + extra, z))
        if policy == "aimd":
            p = nodes / np.maximum(self.rsum(nodes), k(1e-9))
            cap = cap_w[:, None]
            congested = (self.rsum(served) >= k(0.95) * cap) & (cap > z)
            gated = np.isfinite(alloc) & (alloc > 0)
            binding = gated & (served >= k(0.95) * alloc)
            rate = state["rate"]
            rate = np.where(congested & binding, rate * k(0.7),
                            np.where(congested, rate,
                                     rate + k(0.08) * cap * p))
            rate = np.clip(rate, k(1), np.maximum(cap, k(1)))
            thr = np.floor(np.where(demand > 0, rate, z))
            return {"rate": rate}, np.where(congested, thr, k(np.inf))
        raise ValueError(f"unknown policy {policy!r}")

    def _adaptbf(self, state, demand, nodes, cap_w):
        """AdapTBF's window (paper Eq. 1-20): priority allocation, surplus
        redistribution, re-compensation of lenders."""
        k, z, eps = self.k, self.k(0), self.k(1e-12)
        dist, s = self.distribute, self.rsum
        record, rem0, alloc_prev = (state["record"], state["remainder"],
                                    state["alloc_prev"])
        active = demand > 0
        n_act = np.where(active, nodes, z)
        p = n_act / np.maximum(s(n_act), eps)
        budget1 = np.where(active.any(axis=-1, keepdims=True),
                           cap_w[:, None], z)
        alpha1, rem = dist(budget1 * p, rem0, budget1, active)
        u = np.minimum(demand / np.maximum(alloc_prev, k(1)), self.u_max)
        u = np.where(active, u, z)
        surplus = np.where(active, np.maximum(alpha1 - demand, z), z)
        t_s = s(surplus)
        df = np.where(active, np.where(u > k(1), u + u * p, u * p), z)
        share = df / np.maximum(s(df), eps)
        add_rd, rem = dist(share * t_s, rem, t_s, active)
        alpha_rd = alpha1 - surplus + add_rd
        r_rd = record + surplus - add_rd
        j_plus = active & (record > 0) & (r_rd > 0)
        j_minus = active & (record < 0) & (r_rd < 0)
        u_future = demand / np.maximum(alpha_rd, k(1))
        c = s(np.where(j_plus, p * (np.maximum(k(1), u)
                                    + np.maximum(z, k(1) - u_future)) / k(2),
                       z))
        reclaim = np.minimum(np.minimum(np.abs(record), np.abs(c * alpha_rd)),
                             alpha_rd)
        reclaim = np.where(j_minus, reclaim, z)
        owed = np.where(j_plus, r_rd, z)
        reclaim = reclaim * np.minimum(
            k(1), s(owed) / np.maximum(s(reclaim), eps))
        reclaim = np.floor(reclaim)
        t_r = s(reclaim)
        df_plus = np.where(j_plus, df, z)
        share_plus = df_plus / np.maximum(s(df_plus), eps)
        add1 = np.minimum(share_plus * t_r, owed)
        headroom = owed - add1
        add_raw = add1 + (t_r - s(add1)) * headroom / np.maximum(
            s(headroom), eps)
        add_rc, rem = dist(add_raw, rem, t_r, j_plus)
        alloc = np.where(active, alpha_rd - reclaim + add_rc, z)
        new = {"record": r_rd + reclaim - add_rc, "remainder": rem,
               "alloc_prev": alloc}
        return new, alloc

    # ----------------------------------------------------------- telemetry
    def init_stats(self, n_ost, n_jobs):
        zoj = np.zeros((n_ost, n_jobs), self.dt)
        zo = np.zeros((n_ost,), self.dt)
        st = {name: zoj.copy() for name in STAT_SUMS[:6]}
        st.update(util_sum=zo.copy(), lag_sum=zo.copy(), lag_sumsq=zo.copy(),
                  lag_hist=np.zeros((n_ost, NBINS), self.dt))
        st["comp"] = {name: np.zeros_like(st[name]) for name in STAT_SUMS}
        st.update(windows=0, busy_windows=0, lag_max=zo.copy(),
                  alloc_windows=np.zeros((n_ost, n_jobs), np.int64),
                  last_served=np.full((n_ost, n_jobs), -1, np.int64))
        return st

    def lag_bin(self, lag):
        f = ((np.log10(np.maximum(lag, self.k(1e-30))) - self.k(LAG_LOG10_LO))
             / self.k(LAG_LOG10_HI - LAG_LOG10_LO) * self.k(NBINS))
        return np.clip(np.floor(f).astype(np.float64), 0, NBINS - 1).astype(
            np.int64)

    def fold(self, stats, served, demand, alloc, cap_w, busy):
        """Fold one window into the streaming accumulators.  ``busy`` is
        the fleet-wide flag: did any OST serve anything this window."""
        z = self.k(0)
        n_ost = served.shape[0]
        lag = demand - served
        alloc_f = np.where(np.isfinite(alloc), alloc, z)
        hist = np.zeros((n_ost, NBINS), self.dt)
        np.add.at(hist, (np.arange(n_ost)[:, None], self.lag_bin(lag)),
                  self.k(1))
        x = {"served_sum": served, "served_sumsq": served * served,
             "demand_sum": demand, "demand_sumsq": demand * demand,
             "alloc_sum": alloc_f, "alloc_sumsq": alloc_f * alloc_f,
             "util_sum": (np.sum(served, axis=-1, dtype=self.dt)
                          / np.maximum(cap_w, self.k(1e-12))),
             "lag_sum": np.sum(lag, axis=-1, dtype=self.dt),
             "lag_sumsq": np.sum(lag * lag, axis=-1, dtype=self.dt),
             "lag_hist": hist}
        out = dict(stats)
        out["comp"] = dict(stats["comp"])
        for name in STAT_SUMS:   # Kahan: total' = total + (x - comp)
            y = x[name] - stats["comp"][name]
            t = stats[name] + y
            out["comp"][name] = (t - stats[name]) - y
            out[name] = t
        out["windows"] = stats["windows"] + 1
        out["busy_windows"] = stats["busy_windows"] + int(bool(busy))
        out["alloc_windows"] = stats["alloc_windows"] + np.isfinite(alloc)
        out["lag_max"] = np.maximum(stats["lag_max"], np.max(lag, axis=-1))
        out["last_served"] = np.where(served > 0, stats["windows"],
                                      stats["last_served"])
        return out

    # --------------------------------------------------------------- window
    def init_carry(self, policy, nodes, volume, cap_tick, streaming):
        nodes = self.a(nodes)
        cap_w = self.a(cap_tick) * self.k(self.window_ticks)
        state, alloc = self.init_policy(policy, nodes, cap_w)
        n_ost, n_jobs = nodes.shape
        zoj = np.zeros((n_ost, n_jobs), self.dt)
        return {"window": 0, "queue": zoj, "vol_left": self.a(volume),
                "policy": state, "alloc": alloc,
                "stats": self.init_stats(n_ost, n_jobs) if streaming else None,
                "held": {"served": zoj.copy(), "demand": zoj.copy(),
                         "alloc": alloc.copy()}}

    def window(self, policy, carry, rates_w, nodes, cap_tick, backlog,
               observed=None):
        """One observation window: gate, serve, observe, allocate, fold.

        Returns (carry', out) where ``out`` holds the window's served,
        demand, applied allocation and policy record.  ``observed``, a
        (served, demand) pair, makes the allocation and the fold start
        from that observation instead of this window's own, so each phase
        can be checked on the inputs it was given."""
        nodes, backlog = self.a(nodes), self.a(backlog)
        cap_w = self.a(cap_tick) * self.k(self.window_ticks)
        budget = self.gate(policy, carry["alloc"])
        queue, vol_left, served = self.serve_window(
            carry["queue"], carry["vol_left"], budget, rates_w, backlog,
            cap_tick)
        demand = served + queue
        seen_served, seen_demand = (
            (served, demand) if observed is None
            else (self.a(observed[0]), self.a(observed[1])))
        state, alloc_next = self.step_policy(
            policy, carry["policy"], seen_served, seen_demand, carry["alloc"],
            nodes, cap_w)
        stats = carry["stats"]
        if stats is not None:
            # the fleet-wide busy flag, from the rows given
            busy = bool((np.sum(seen_served, axis=-1) > 0).any())
            stats = self.fold(stats, seen_served, seen_demand, carry["alloc"],
                              cap_w, busy)
        out = {"served": served, "demand": demand, "alloc": carry["alloc"],
               "record": self.record(policy, state, nodes)}
        new = {"window": carry["window"] + 1, "queue": queue,
               "vol_left": vol_left, "policy": state, "alloc": alloc_next,
               "stats": stats,
               "held": {"served": served, "demand": demand,
                        "alloc": carry["alloc"]}}
        return new, out

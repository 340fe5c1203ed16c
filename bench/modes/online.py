"""Online mode: the production control loop.

One caller drives ``FleetService.ingest`` in a closed loop: each round
fetches the next window of rate observations (a view into a cycle generated
in set-up, so nothing is generated inside the window), copies it to the
device, steps the controller, and waits for the new allocation.  A round is
timed from the start of ``ingest`` until that allocation is ready.

Correctness: at rounds drawn from the seed, the carry before and after the
round is read back (outside the round's timing) and the plain reference
repeats that one window from the same carry.  Every row, every job and
every leaf is compared: the serve phase (queues, remaining volumes, served
and observed demand), the allocation (next allocation, lending record,
remainders), the streaming telemetry fold (sums, histogram, counters), and
the guarantees (capacity, volume, token conservation).
"""
from __future__ import annotations

import numpy as np

from lib import compare, program, traffic
from lib.floor import window_floor_bytes
from lib.harness import Check, median, quantile
from lib.reference import STAT_SUMS, Reference


class Mode:
    def __init__(self, cell, seed, spans):
        import jax
        from repro.storage import FleetService

        self.spans = spans
        cfg, tr = cell.config, cell.traffic
        self.limits = tr["limits"]
        self.policy = cfg["control"]
        self.wt = int(cfg["window_ticks"])
        self.u_max = float(cfg["u_max"])
        with spans("bench.setup.generate"):
            self.fleet = f = traffic.generate(
                cell.profile, cfg["n_ost"], cfg["n_jobs"],
                cfg["capacity_per_tick"], tr["cycle_ticks"], seed,
                tr["profile"])
            self.rates = traffic.expand(f)
        self.cycle = self.rates.shape[0] // self.wt
        with spans("bench.setup.build"):
            self.svc = FleetService(
                program.fleet_config(cfg, tr["telemetry"]), f.nodes,
                f.volume, capacity_per_tick=f.capacity, max_backlog=f.backlog)
        self.round = 0
        with spans("bench.setup.warmup"):
            for _ in range(int(tr["warmup_rounds"])):
                self._ingest()
                jax.block_until_ready(self.svc.alloc)
        rng = np.random.default_rng([int(seed) % 2 ** 64, 0xC4EC])
        lo, hi = tr["check_rounds_from"], tr["check_rounds_to"]
        self.sampled = set(int(r) for r in rng.choice(
            np.arange(lo, hi), int(tr["check_rounds"]), replace=False))
        self.snapshots = []
        self.floor_bytes = window_floor_bytes(
            cfg["n_ost"], cfg["n_jobs"], self.wt, (self.policy,),
            tr["telemetry"])
        self.start_window()

    def start_window(self):
        self.windows = 0
        self.round_s = []
        self.window_round0 = self.round

    def _fetch(self):
        with self.spans("bench.fetch"):
            s = (self.round % self.cycle) * self.wt
            return self.rates[s:s + self.wt]

    def _ingest(self):
        self.svc.ingest(self._fetch)
        self.round += 1

    def step(self):
        import time

        import jax
        k = self.round - self.window_round0
        snap = k in self.sampled
        if snap:    # read back outside the round's timing
            with self.spans("bench.check_copy"):
                carry_in = jax.device_get(self.svc.carry)
                rates_w = self._fetch()
        with self.spans("bench.round"):
            t0 = time.perf_counter()
            with self.spans("bench.ingest"):
                self._ingest()
            with self.spans("bench.wait"):
                jax.block_until_ready(self.svc.alloc)
            self.round_s.append(time.perf_counter() - t0)
        self.windows += 1
        if snap:
            with self.spans("bench.check_copy"):
                self.snapshots.append((carry_in,
                                       jax.device_get(self.svc.carry),
                                       np.array(rates_w)))

    @property
    def attempted(self):
        return self.windows

    @property
    def failed(self):
        return self.svc.lost_windows if self.svc is not None else self.lost

    def metrics(self, elapsed):
        ms = [1e3 * s for s in self.round_s]
        return {"round_ms_p50": median(ms),
                "round_ms_p95": quantile(ms, 0.95)}

    def check(self, dtype=None):
        """Numbers for the program's sampled rounds, or with ``dtype`` for
        the reference in that precision put in the program's place."""
        if self.svc is not None:   # the program's state is not needed now
            self.lost, self.svc = self.svc.lost_windows, None
        if len(self.snapshots) < len(self.sampled):
            return [Check("sampled_rounds_missing",
                          len(self.sampled) - len(self.snapshots), 0)]
        f = self.fleet
        ref = Reference(np.float32, self.wt, self.u_max)
        worst = {}
        nodes = np.broadcast_to(f.nodes, f.weights.shape)
        for carry_in, carry_out, rates_w in self.snapshots:
            cin = program.carry_dict(carry_in, self.policy)
            if dtype is None:
                got = program.carry_dict(carry_out, self.policy)
            else:
                got, _ = Reference(dtype, self.wt, self.u_max).window(
                    self.policy, _cast(cin, dtype), rates_w, nodes,
                    f.capacity, f.backlog)
            # serve from the same carry; allocate and fold from the same
            # observation, so each phase is judged on its own inputs
            want, _ = ref.window(
                self.policy, cin, rates_w, nodes, f.capacity, f.backlog,
                observed=(got["held"]["served"], got["held"]["demand"]))
            for name, value in self._numbers(got, want, cin).items():
                worst[name] = max(worst.get(name, 0.0), value)
        return [Check(n, v, self.limits[n]) for n, v in worst.items()]

    def _numbers(self, got, want, cin):
        f = self.fleet
        num = {}
        num["serve_gap"] = max(
            compare.rel_gap(got["queue"], want["queue"]),
            compare.rel_gap(got["vol_left"], want["vol_left"]),
            compare.rel_gap(got["held"]["served"], want["held"]["served"]),
            compare.rel_gap(got["held"]["demand"], want["held"]["demand"]))
        gs, ws = got["stats"], want["stats"]
        num["stats_gap"] = max(
            [compare.rel_gap(compare.kahan_total(gs, n),
                             compare.kahan_total(ws, n))
             for n in STAT_SUMS if n != "lag_hist"]
            + [compare.rel_gap(gs["lag_max"], ws["lag_max"])])
        num["hist_moved"] = compare.hist_moved(
            compare.kahan_total(gs, "lag_hist"),
            compare.kahan_total(ws, "lag_hist"), f.weights.size)
        # jobs whose remainders tie in exact arithmetic (copies of one
        # experiment) differ in float by an ulp of their history, so which
        # of them gets a whole token is rounding: an entry may be one token
        # off, never more
        num["token_mismatch"] = float(np.mean(
            [compare.mismatch_share(got["alloc"], want["alloc"], 1.001)]
            + [compare.mismatch_share(got["policy"][k], want["policy"][k],
                                      1.001) for k in want["policy"]]))
        num["counter_mismatch"] = float(
            (gs["windows"] != ws["windows"])
            + (gs["busy_windows"] != ws["busy_windows"])
            + np.sum(gs["alloc_windows"] != ws["alloc_windows"])
            + np.sum(gs["last_served"] != ws["last_served"]))
        cap_w = f.capacity * np.float32(self.wt)
        num["token_conservation"] = (compare.token_conservation(
            got["alloc"], got["policy"]["record"], cin["policy"]["record"],
            got["held"]["demand"], cap_w) if self.policy == "adaptbf"
            else 0.0)
        num["capacity_excess"] = compare.capacity_excess(
            np.asarray(got["held"]["served"], np.float64).sum(axis=-1), cap_w)
        num["volume_excess"] = float(max(
            -np.min(got["vol_left"]), -np.min(got["queue"]), 0.0))
        return num


def _cast(tree, dtype):
    if isinstance(tree, dict):
        return {k: _cast(v, dtype) for k, v in tree.items()}
    if isinstance(tree, np.ndarray) and tree.dtype.kind == "f":
        return tree.astype(dtype)
    return tree

"""Replay mode: offline ``simulate_fleet`` over a generated cycle.

The cycle's per-target rates are built on the device in set-up; each step
of the window is one ``simulate_fleet`` call over the whole cycle, from a
cold start, with trajectory telemetry (every window's served, demand,
allocation and record come back as outputs).  Nothing crosses to the host
inside the window.

Correctness: the last call's trajectories on a sample of targets drawn from
the seed are compared with the plain reference run over the same cycle from
the same cold start.  The closed loop forks where a token's rounding falls
one ulp apart, so the windows after such a fork are compared by what holds
through it: each target's totals over the cycle (served, allocated), the
first window exactly, and the guarantees on every window (capacity,
volume, token conservation).
"""
from __future__ import annotations

import numpy as np

from lib import compare, program, traffic
from lib.floor import window_floor_bytes
from lib.harness import Check
from lib.reference import Reference


OUTPUTS = ("served", "demand", "alloc", "record")


class Mode:
    def __init__(self, cell, seed, spans):
        import jax
        import jax.numpy as jnp
        from repro.storage import simulate_fleet

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
        self.cycle = f.trace.shape[0] // self.wt
        with spans("bench.setup.build"):
            rates = jax.jit(lambda t, w: t[:, None, :] * w[None, :, :])(
                f.trace, f.weights)
            self.args = jax.block_until_ready((
                jnp.asarray(f.nodes), rates, jnp.asarray(f.volume),
                jnp.asarray(f.capacity), jnp.asarray(f.backlog)))
        self.cfg = program.fleet_config(cfg, tr["telemetry"])
        self.run = simulate_fleet
        with spans("bench.setup.warmup"):
            self.res = jax.block_until_ready(self.run(self.cfg, *self.args))
        rng = np.random.default_rng([int(seed) % 2 ** 64, 0x5E1])
        self.rows = np.sort(rng.choice(cfg["n_ost"], int(tr["check_rows"]),
                                       replace=False))
        self.floor_bytes = window_floor_bytes(
            cfg["n_ost"], cfg["n_jobs"], self.wt, (self.policy,),
            tr["telemetry"])
        self.start_window()

    def start_window(self):
        self.windows = 0

    def step(self):
        import jax
        self.res = None      # one cycle's trajectories (1.1 GB) live at once
        with self.spans("bench.call"):
            res = self.run(self.cfg, *self.args)
        with self.spans("bench.wait"):
            self.res = jax.block_until_ready(res)
        self.windows += self.cycle

    attempted = property(lambda self: self.windows)
    failed = 0

    def metrics(self, elapsed):
        return {"windows_per_s": self.windows / elapsed}

    def _reference(self, dtype):
        f, rows = self.fleet, self.rows
        ref = Reference(dtype, self.wt, self.u_max)
        nodes = np.broadcast_to(f.nodes, (rows.size, f.nodes.size))
        rates = traffic.expand(f, rows)
        carry = ref.init_carry(self.policy, nodes, f.volume[rows],
                               f.capacity[rows], streaming=False)
        outs = {k: [] for k in OUTPUTS}
        for w in range(self.cycle):
            carry, out = ref.window(self.policy, carry,
                                    rates[w * self.wt:(w + 1) * self.wt],
                                    nodes, f.capacity[rows],
                                    f.backlog[rows])
            for k in OUTPUTS:
                outs[k].append(out[k])
        traj = {k: np.stack(v).astype(np.float64) for k, v in outs.items()}
        traj["queue_final"] = carry["queue"].astype(np.float64)
        return traj

    def check(self, dtype=None):
        """Numbers for the program's last call, or with ``dtype`` for the
        reference in that precision put in the program's place."""
        if self.res is not None:   # read back the sample, free the rest
            rows = self.rows
            self.got = {k: np.asarray(getattr(self.res, k)[:, rows],
                                      np.float64) for k in OUTPUTS}
            self.got["queue_final"] = np.asarray(
                self.res.queue_final[rows], np.float64)
            self.res = self.args = None
        want = self._reference(np.float32)
        got = self.got if dtype is None else self._reference(dtype)
        return [Check(n, v, self.limits[n])
                for n, v in self._numbers(got, want).items()]

    def _numbers(self, got, want):
        f, rows = self.fleet, self.rows
        cap_w = f.capacity[rows] * np.float32(self.wt)

        def per_target(x):
            return np.where(np.isfinite(x), x, 0.0).sum(axis=(0, 2))

        num = {"first_window_gap": max(
            compare.rel_gap(got["served"][0], want["served"][0]),
            compare.rel_gap(got["demand"][0], want["demand"][0]))}
        num["served_target_gap"] = compare.rel_gap(
            per_target(got["served"]), per_target(want["served"]))
        num["alloc_target_gap"] = compare.rel_gap(
            per_target(got["alloc"]), per_target(want["alloc"]))
        if self.policy == "adaptbf":
            rec = got["record"]
            before = np.concatenate([np.zeros_like(rec[:1]), rec[:-1]])
            num["token_conservation"] = compare.token_conservation(
                got["alloc"][1:], rec[:-1], before[:-1], got["demand"][:-1],
                cap_w)
        else:
            num["token_conservation"] = 0.0
        num["capacity_excess"] = compare.capacity_excess(
            got["served"].sum(axis=-1), cap_w)
        num["volume_excess"] = compare.volume_excess(
            got["served"].sum(axis=0) + got["queue_final"], f.volume[rows])
        return num

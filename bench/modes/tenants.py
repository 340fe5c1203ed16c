"""Tenants mode: one ``simulate_tenants`` call runs every policy of a coded
sweep as a tenant of one compiled program, over one scenario.

Set-up draws the scenario from the seed and builds its per-target rates on
the device, shared by the tenants; each step of the window is one call over
the whole cycle, from a cold start, with streaming telemetry (the final
accumulators come back, no trajectories).  Every tenant's window counts.

Correctness: for the last call, on a sample of targets drawn from the seed,
each tenant's final accumulators are compared with the plain reference
running that tenant's policy directly over the same horizon.  Forks of the
closed loop where a rounding falls one ulp apart are absorbed by comparing
each target's totals (served, backlog, allocated; AdapTBF's allocations
apart from the other policies', which do not fork), the histogram's mass,
the counters, and the guarantees (capacity, volume).
"""
from __future__ import annotations

import numpy as np

from lib import compare, program, traffic
from lib.floor import window_floor_bytes
from lib.harness import Check
from lib.reference import Reference


class Mode:
    def __init__(self, cell, seed, spans):
        import jax
        import jax.numpy as jnp
        from repro.storage import simulate_tenants

        self.spans = spans
        cfg, tr = cell.config, cell.traffic
        self.limits = tr["limits"]
        self.policies = tuple(tr["tenants"])
        self.wt = int(cfg["window_ticks"])
        self.u_max = float(cfg["u_max"])
        with spans("bench.setup.generate"):
            self.fleet = f = traffic.generate(
                cell.profile, cfg["n_ost"], cfg["n_jobs"],
                cfg["capacity_per_tick"], tr["cycle_ticks"], seed,
                tr["profile"])
        with spans("bench.setup.build"):
            rates = jax.jit(lambda t, w: t[:, None, :] * w[None, :, :])(
                f.trace, f.weights)
            self.args = jax.block_until_ready((
                jnp.asarray(f.nodes), rates, jnp.asarray(f.volume),
                jnp.asarray(f.capacity), jnp.asarray(f.backlog)))
        self.codes = jnp.arange(len(self.policies), dtype=jnp.int32)
        self.cycle = f.trace.shape[0] // self.wt
        self.cfg = program.fleet_config(cfg, tr["telemetry"], self.policies)
        self.run = simulate_tenants
        with spans("bench.setup.warmup"):
            self.res = jax.block_until_ready(self._call())
        rng = np.random.default_rng([int(seed) % 2 ** 64, 0x7E4])
        self.rows = np.sort(rng.choice(cfg["n_ost"], int(tr["check_rows"]),
                                       replace=False))
        # windows are counted per tenant, so the floor is a step's over F
        self.floor_bytes = window_floor_bytes(
            cfg["n_ost"], cfg["n_jobs"], self.wt, self.policies,
            tr["telemetry"], n_fleets=len(self.policies)) / len(self.policies)
        self.start_window()

    def _call(self):
        return self.run(self.cfg, *self.args, control_code=self.codes)

    def start_window(self):
        self.windows = 0

    def step(self):
        import jax
        self.res = None      # one call's results live at once
        with self.spans("bench.call"):
            res = self._call()
        with self.spans("bench.wait"):
            self.res = jax.block_until_ready(res)
        self.windows += self.cycle * len(self.policies)

    attempted = property(lambda self: self.windows)
    failed = 0

    def metrics(self, elapsed):
        return {"tenant_windows_per_s": self.windows / elapsed}

    def _reference(self, dtype, policy):
        f, rows = self.fleet, self.rows
        ref = Reference(dtype, self.wt, self.u_max)
        nodes = np.broadcast_to(f.nodes, (rows.size, f.nodes.size))
        rates = traffic.expand(f, rows)
        carry = ref.init_carry(policy, nodes, f.volume[rows],
                               f.capacity[rows], streaming=True)
        for w in range(self.cycle):
            carry, _ = ref.window(policy, carry,
                                  rates[w * self.wt:(w + 1) * self.wt],
                                  nodes, f.capacity[rows], f.backlog[rows])
        return {"stats": carry["stats"], "queue": carry["queue"]}

    def check(self, dtype=None):
        """Numbers for the program's last call, or with ``dtype`` for the
        reference in that precision put in the program's place."""
        if self.res is not None:   # read back the sample, free the rest
            import jax
            res = jax.device_get(self.res)
            self.got = [{"stats": _rows(program.stats_dict(res.stats, i),
                                        self.rows),
                         "queue": np.asarray(res.queue_final[i])[self.rows]}
                        for i in range(len(self.policies))]
            self.res = self.args = None
        worst = {}
        for i, policy in enumerate(self.policies):
            want = self._reference(np.float32, policy)
            got = self.got[i] if dtype is None else self._reference(
                dtype, policy)
            for name, value in self._numbers(got, want, policy).items():
                worst[name] = max(worst.get(name, 0.0), value)
        return [Check(n, v, self.limits[n]) for n, v in worst.items()]

    def _numbers(self, got, want, policy):
        f, rows = self.fleet, self.rows
        gs, ws = got["stats"], want["stats"]

        def per_target(stats, name):
            x = compare.kahan_total(stats, name)
            return x.sum(axis=-1) if x.ndim == 2 else x

        num = {}
        num["served_target_gap"] = compare.rel_gap(
            per_target(gs, "served_sum"), per_target(ws, "served_sum"))
        num["backlog_target_gap"] = compare.rel_gap(
            per_target(gs, "lag_sum"), per_target(ws, "lag_sum"))
        # AdapTBF carries each job's fractional token across windows, so an
        # ulp apart its closed loop forks; the other policies' allocations
        # stay within rounding of the reference all through the horizon
        alloc = ("alloc_target_gap.adaptbf" if policy == "adaptbf"
                 else "alloc_target_gap.others")
        num[alloc] = compare.rel_gap(
            per_target(gs, "alloc_sum"), per_target(ws, "alloc_sum"))
        mass = float(ws["windows"]) * ws["served_sum"].size
        num["hist_moved"] = compare.hist_moved(
            compare.kahan_total(gs, "lag_hist"),
            compare.kahan_total(ws, "lag_hist"), mass)
        num["counter_mismatch"] = float(
            (gs["windows"] != ws["windows"])
            + (gs["busy_windows"] != ws["busy_windows"])
            + np.sum(gs["alloc_windows"] != ws["alloc_windows"]))
        cap_w = f.capacity[rows] * np.float32(self.wt)
        num["capacity_excess"] = compare.capacity_excess(
            per_target(gs, "served_sum") / max(gs["windows"], 1), cap_w)
        num["volume_excess"] = compare.volume_excess(
            compare.kahan_total(gs, "served_sum") + np.asarray(
                got["queue"], np.float64), f.volume[rows])
        return num


def _rows(stats: dict, rows) -> dict:
    """The sampled targets' rows of a stats dictionary."""
    out = {}
    for k, v in stats.items():
        if k == "comp":
            out[k] = _rows(v, rows)
        elif isinstance(v, np.ndarray) and v.ndim >= 1:
            out[k] = v[rows]
        else:
            out[k] = v
    return out

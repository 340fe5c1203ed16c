"""What the benchmark takes from the program under test, in one place: its
configuration type and entry points, and the reading of its carry into the
plain dictionaries the reference works on.  Fields are read by name."""
from __future__ import annotations

import numpy as np

from lib.reference import STAT_SUMS


def fleet_config(config: dict, telemetry: str, tenants=None):
    """The program's ``FleetConfig`` for a configuration file, or, given
    ``tenants``, for a coded program whose members are those policies;
    engine backends are left at the program's defaults."""
    from repro.storage import FleetConfig
    if not config["integer_tokens"]:
        raise ValueError("the reference distributes whole tokens only")
    kw = dict(capacity_per_tick=float(config["capacity_per_tick"]),
              window_ticks=int(config["window_ticks"]),
              tick_seconds=float(config["tick_seconds"]),
              control="coded" if tenants else config["control"],
              u_max=float(config["u_max"]),
              integer_tokens=bool(config["integer_tokens"]),
              max_backlog=float(config["max_backlog"]),
              telemetry=telemetry)
    if tenants:
        kw["coded_policies"] = tuple(tenants)
    return FleetConfig(**kw)


def _np(x):
    return np.asarray(x)


def stats_dict(st, index=None) -> dict:
    """A ``StreamStats`` (optionally one fleet of a batched one) as the
    reference's stats dictionary."""
    pick = (lambda x: _np(x)) if index is None else (lambda x: _np(x)[index])
    out = {n: pick(getattr(st, n)) for n in STAT_SUMS}
    out["comp"] = {n: pick(getattr(st.comp, n)) for n in STAT_SUMS}
    out["windows"] = int(pick(st.windows))
    out["busy_windows"] = int(pick(st.busy_windows))
    out["lag_max"] = pick(st.lag_max)
    out["alloc_windows"] = pick(st.alloc_windows).astype(np.int64)
    out["last_served"] = pick(st.last_served).astype(np.int64)
    return out


def carry_dict(carry, policy: str) -> dict:
    """The engine's ``WindowCarry`` (on the host) as the reference's carry
    for a single-policy fleet."""
    ps = carry.policy_state
    if policy == "adaptbf":
        state = {"record": _np(ps.record), "remainder": _np(ps.remainder),
                 "alloc_prev": _np(ps.alloc_prev)}
    elif policy == "aimd":
        state = {"rate": _np(ps)}
    else:
        state = {}
    return {"window": int(carry.window), "queue": _np(carry.queue),
            "vol_left": _np(carry.vol_left), "policy": state,
            "alloc": _np(carry.alloc),
            "stats": (stats_dict(carry.stats)
                      if hasattr(carry.stats, "served_sum") else None),
            "held": {"served": _np(carry.held.served),
                     "demand": _np(carry.held.demand),
                     "alloc": _np(carry.held.alloc)}}

"""The comparison that decides ``correct``: gaps between what the program
produced and what the plain reference produces, and the guarantees the
configurations state.  Every number here reads 0 for a perfect match and
grows with the disagreement; a mode compares each with its limit."""
from __future__ import annotations

import numpy as np


def _f64(x):
    return np.asarray(x, np.float64)


def rel_gap(p, r, floor: float = 1.0) -> float:
    """Widest ``|p - r| / max(|r|, floor)``; equal infinities agree, a NaN
    or a lone infinity reads as infinite."""
    p, r = _f64(p), _f64(r)
    same_inf = np.isinf(p) & np.isinf(r) & (np.sign(p) == np.sign(r))
    scale = np.maximum(np.abs(np.where(np.isinf(r), 0.0, r)), floor)
    with np.errstate(invalid="ignore"):
        g = np.where(same_inf, 0.0, np.abs(p - r) / scale)
    g = np.nan_to_num(g, nan=np.inf)
    return float(g.max()) if g.size else 0.0


def mismatch_share(p, r, tol: float = 1e-3) -> float:
    """Share of entries that differ by more than ``tol`` tokens."""
    p, r = _f64(p), _f64(r)
    same_inf = np.isinf(p) & np.isinf(r) & (np.sign(p) == np.sign(r))
    with np.errstate(invalid="ignore"):
        off = ~same_inf & ~(np.abs(p - r) <= tol)
    return float(off.mean()) if off.size else 0.0


def kahan_total(stats: dict, name: str):
    """A Kahan-compensated accumulator's best value, in float64."""
    return _f64(stats[name]) + _f64(stats["comp"][name])


def hist_moved(p_hist, r_hist, mass: float) -> float:
    """Share of ``mass`` histogram counts that sit in another bin."""
    return float(np.abs(_f64(p_hist) - _f64(r_hist)).sum() / (2.0 * mass))


def token_conservation(alloc_next, record_out, record_in, demand,
                       cap_w) -> float:
    """AdapTBF's first step hands out exactly ``round(capacity)`` whole
    tokens on every target with an active job, and redistribution and
    re-compensation only move tokens between allocation and the lending
    record.  So per target ``sum(alloc' + record' - record)`` is that
    integer (zero with no active job).  Returns the widest miss, in
    tokens."""
    given = (_f64(np.where(np.isfinite(alloc_next), alloc_next, 0.0))
             + _f64(record_out) - _f64(record_in)).sum(axis=-1)
    active = (_f64(demand) > 0).any(axis=-1)
    due = np.where(active, np.round(_f64(np.float32(cap_w))), 0.0)
    return float(np.abs(given - due).max())


def capacity_excess(served_per_target, cap) -> float:
    """Widest share by which a target served more than its capacity."""
    return float((_f64(served_per_target) / _f64(cap) - 1.0).max())


def volume_excess(issued, volume) -> float:
    """Widest amount (RPCs) by which a job issued past its volume."""
    v = _f64(volume)
    bounded = np.isfinite(v)
    if not bounded.any():
        return 0.0
    return float(max((_f64(issued) - v)[bounded].max(), 0.0))

"""Dispatching wrapper for the fused window-service kernel: pads (O, J) to
hardware-friendly multiples, picks a VMEM-safe OST block, and routes to the
Pallas kernel (TPU, or interpret mode when forced) or the identical fused
XLA trace (CPU/GPU -- same math, none of the Pallas interpreter's per-block
emulation cost)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import block_rows as _block_rows
from repro.kernels.dispatch import on_tpu as _on_tpu
from repro.kernels.dispatch import pad_lanes as _pad_lanes
from repro.kernels.dispatch import pad_to as _pad_to
from repro.kernels.dispatch import vmem_limit_bytes as _vmem_limit_bytes
from repro.kernels.fleet_window import ref
from repro.kernels.fleet_window.kernel import (
    fleet_window_pallas,
    serve_tick_block,
)


def _serve_window_xla(queue, vol_left, budget, rates, backlog_cap, cap):
    """Fused window service as plain XLA: the kernel's per-tick math under a
    no-stack ``lax.scan`` (faster than fori+gather on XLA:CPU, bitwise-equal
    output)."""
    def tick(carry, rate_t):
        q, v, b, acc = carry
        q, v, b, served = serve_tick_block(q, v, b, rate_t, backlog_cap, cap)
        return (q, v, b, acc + served), None

    (q, v, _, served), _ = jax.lax.scan(
        tick, (queue, vol_left, budget, jnp.zeros_like(queue)), rates)
    return q, v, served


def fleet_window_serve(queue, vol_left, budget, rates, backlog_cap, cap_tick,
                       *, interpret: bool = None):
    """One observation window of two-phase NRS-TBF service, fused.

    queue/vol_left/budget/backlog_cap: [O, J]; rates: [W, O, J];
    cap_tick: [O].  Returns (queue, vol_left, served_window).

    ``interpret=None`` auto-routes: the compiled Pallas kernel on TPU, the
    bit-identical fused XLA trace elsewhere.  Pass ``interpret=True`` to
    force the kernel through the Pallas interpreter (kernel-fidelity tests).
    """
    if interpret is None:
        if not _on_tpu():
            return _serve_window_xla(
                queue, vol_left, budget, rates, backlog_cap,
                cap_tick.reshape(-1, 1).astype(jnp.float32))
        interpret = False
    o, j = queue.shape
    w = rates.shape[0]
    jp = _pad_lanes(j)
    # the [W, block_o, J] rate-trace block dominates VMEM alongside ~10
    # [block_o, J] state/temp arrays
    bo = _block_rows(o)
    args = [_pad_to(_pad_to(x, jp, 1), bo, 0)
            for x in (queue, vol_left, budget, backlog_cap)]
    rates_p = _pad_to(_pad_to(rates, jp, 2), bo, 1)
    cap = _pad_to(cap_tick.reshape(-1), bo, 0)
    q, v, s = fleet_window_pallas(*args, rates_p, cap,
                                  block_o=bo,
                                  vmem_limit_bytes=_vmem_limit_bytes(
                                      bo, jp, w + 10),
                                  interpret=interpret)
    return q[:o, :j], v[:o, :j], s[:o, :j]


fleet_window_ref = ref.fleet_window_ref

"""Pallas TPU kernel: one full observation window of two-phase NRS-TBF
service, fused across ticks.

The simulator's inner loop used to be a ``lax.scan`` over ticks, each
iteration a handful of small element-wise XLA ops over the whole fleet plus
the stacking of per-tick outputs.  Here the entire window (``window_ticks``
ticks) runs for a block of OSTs inside ONE kernel invocation: state
(queue / volume / budget) stays resident in VMEM across the ``fori_loop``
and only the window-summed service leaves the chip.  One grid step serves a
[BLOCK_O, J] block; every op is row-independent, so the paper's
decentralization property is preserved structurally: the tick math IS
``storage.simulator._serve_tick`` (shape-generic, imported here -- the
backends cannot drift; asserted in ``tests/test_kernel_fleet_window.py``).
Since the engine unification (DESIGN.md section 7) this is the serve path
of BOTH entry points: ``simulate`` (O=1 view) and ``simulate_fleet`` under
any registered control policy route through the same ``serve_window``
dispatch, so kernel parity automatically covers every policy.

VMEM footprint ~ (window_ticks + 10) x BLOCK_O x J f32 arrays: the rate
trace block dominates.  BLOCK_O is one row tile (or the local row count);
a wide job axis raises the scoped-VMEM limit instead (see
dispatch.block_rows and dispatch.vmem_limit_bytes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.storage.simulator import _serve_tick


def serve_tick_block(queue, vol_left, budget, rate_t, backlog_cap, cap):
    """One tick on a [O, J] block of OSTs; ``cap``: [O, 1] per-tick capacity.
    The simulator's own tick function on 2-D rows, minus the per-tick issued
    output the window sum never consumes."""
    queue, vol_left, budget, served, _ = _serve_tick(
        queue, vol_left, budget, rate_t, backlog_cap, cap)
    return queue, vol_left, budget, served


def serve_window_block(queue, vol_left, budget, rates_ref, backlog_cap, cap):
    """All ticks of one window, fused: ``rates_ref`` the [W, O, J] rate
    block as a Pallas ref, state [O, J], ``cap`` [O, 1].  Returns (queue,
    vol_left, served_window).

    ``fori_loop`` loading one tick's [O, J] slice from the ref per
    iteration: Mosaic lowers a dynamic index on a ref's leading axis, but
    not a ``dynamic_slice`` of a loaded value.  The XLA fallback
    (ops._serve_window_xla) runs the same per-tick math under a no-stack
    ``lax.scan``, which XLA:CPU executes ~1.7x faster.  The window-start
    budget is consumed and discarded; every window re-gates from the fresh
    allocation.
    """
    def tick(t, carry):
        queue, vol_left, budget, acc = carry
        rate_t = rates_ref[t]
        queue, vol_left, budget, served = serve_tick_block(
            queue, vol_left, budget, rate_t, backlog_cap, cap)
        return queue, vol_left, budget, acc + served

    queue, vol_left, _, served = jax.lax.fori_loop(
        0, rates_ref.shape[0], tick,
        (queue, vol_left, budget, jnp.zeros_like(queue)))
    return queue, vol_left, served


def _kernel(queue_ref, vol_ref, budget_ref, backlog_ref, cap_ref, rates_ref,
            queue_out, vol_out, served_out):
    queue, vol_left, served = serve_window_block(
        queue_ref[...], vol_ref[...], budget_ref[...], rates_ref,
        backlog_ref[...], cap_ref[...])
    queue_out[...] = queue
    vol_out[...] = vol_left
    served_out[...] = served


@functools.partial(jax.jit, static_argnames=("block_o", "vmem_limit_bytes",
                                             "interpret"))
def fleet_window_pallas(queue, vol_left, budget, backlog_cap, rates,
                        cap_tick, *, block_o: int = 8,
                        vmem_limit_bytes: int = None,
                        interpret: bool = False):
    """[O, J] window service.  rates: [W, O, J]; cap_tick: [O].  J should be
    a multiple of 128 and O a multiple of block_o (ops.py pads).  Returns
    (queue, vol_left, served_window)."""
    o, j = queue.shape
    w = rates.shape[0]
    cap2 = cap_tick.reshape(o, 1).astype(jnp.float32)
    grid = (o // block_o,)
    row_spec = pl.BlockSpec((block_o, j), lambda i: (i, 0))
    cap_spec = pl.BlockSpec((block_o, 1), lambda i: (i, 0))
    rates_spec = pl.BlockSpec((w, block_o, j), lambda i: (0, i, 0))
    out_shape = [jax.ShapeDtypeStruct((o, j), jnp.float32)] * 3
    fn = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[row_spec] * 4 + [cap_spec, rates_spec],
        out_specs=[row_spec] * 3,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )
    args = [x.astype(jnp.float32)
            for x in (queue, vol_left, budget, backlog_cap)]
    return tuple(fn(*args, cap2, rates.astype(jnp.float32)))

"""Dispatching wrapper for the window megakernel: pads (O, J) to
hardware-friendly multiples, picks a VMEM-safe OST block, and routes to the
Pallas megakernel (TPU, or interpret mode when forced) or a row-blocked XLA
fallback that traces the identical round with the runtime-specialized serve
loop (``kernel._serve_window_lean``) and conditional integerizer branches
(``alloc_backend="block_cond"``) -- each [block, J] slice of engine state
stays cache-resident across gate -> ticks -> allocation."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import block_rows as _block_rows
from repro.kernels.dispatch import on_tpu as _on_tpu
from repro.kernels.dispatch import pad_lanes as _pad_lanes
from repro.kernels.dispatch import pad_to as _pad_to
from repro.kernels.dispatch import vmem_limit_bytes as _vmem_limit_bytes
from repro.kernels.window_mega.kernel import (
    mega_round_block,
    mega_window_pallas,
)

# live [block, J] f32 arrays per block beyond the rate trace: engine state
# in+out (queue, volume, allocation, held/obs triple, served, demand),
# serve-loop temporaries, the allocator's integerize temporaries, and two
# generations of every policy-state leaf (DESIGN.md section 12)
_LIVE_ROWS_BASE = 26


def _live_rows(n_state_leaves: int, w: int) -> int:
    return w + _LIVE_ROWS_BASE + 2 * max(n_state_leaves, 3)


def _flatten_state(pstate, o: int):
    leaves, treedef = jax.tree.flatten(pstate)
    for leaf in leaves:
        if leaf.ndim < 1 or leaf.shape[0] != o:
            raise ValueError(
                "serve_backend=\"mega\" needs every policy-state leaf to "
                f"carry a leading OST axis (shape[0] == {o}); got a leaf "
                f"of shape {leaf.shape}.  Row-less state cannot be blocked "
                "over OST rows.")
    return leaves, treedef


def _mega_round_xla(policy, ctx, cap_tick, backlog_cap, queue, vol_left,
                    alloc, held, pstate, rates_w, telem_ok, up):
    """Row-blocked fused round as plain XLA: a no-stack ``lax.scan`` over
    [block, J] row blocks, each block running the whole gate -> serve ->
    observe -> step round with the specialized serve loop."""
    o, j = queue.shape
    w = rates_w.shape[0]
    leaves, treedef = _flatten_state(pstate, o)
    bo = _block_rows(o)
    has_faults = telem_ok is not None

    row_arrays = [queue, vol_left, alloc, *held, *leaves,
                  ctx.nodes, backlog_cap]
    col_arrays = [jnp.reshape(cap_tick, (o, 1)),
                  jnp.reshape(ctx.cap_w, (o, 1))]
    if has_faults:
        col_arrays += [jnp.reshape(telem_ok, (o, 1)),
                       jnp.reshape(up, (o, 1))]
    if o % bo:
        # padded rows run a harmless round (zero demand/capacity/queue --
        # safe under every registered policy's degraded-mode contract) and
        # are sliced away below; block-level branch predicates may differ
        # but every branch is bitwise-identical per row
        row_arrays = [_pad_to(a, bo, 0) for a in row_arrays]
        col_arrays = [_pad_to(a, bo, 0) for a in col_arrays]
        rates_w = _pad_to(rates_w, bo, 1)
    op = row_arrays[0].shape[0]
    nb = op // bo

    def blocked(a):
        return a.reshape(nb, bo, *a.shape[1:])

    xs = ([blocked(a) for a in row_arrays],
          [blocked(a) for a in col_arrays],
          jnp.arange(nb))

    def body(carry, xs_b):
        rows, cols, ib = xs_b
        # slice the rate trace in-body rather than pre-transposing it to a
        # block-major [nb, W, bo, J] copy -- at (O=256, J=4096, W=10) that
        # transpose alone costs ~15% of a window
        rates_b = jax.lax.dynamic_slice_in_dim(rates_w, ib * bo, bo, axis=1)
        pstate_b = jax.tree.unflatten(treedef, rows[6:6 + len(leaves)])
        nodes_b, backlog_b = rows[6 + len(leaves):]
        telem_b = cols[2] if has_faults else None
        up_b = cols[3] if has_faults else None
        ctx_blk = ctx._replace(nodes=nodes_b, cap_w=cols[1][:, 0],
                               alloc_backend="block_cond")
        out = mega_round_block(
            policy, ctx_blk, rows[0], rows[1], rows[2], tuple(rows[3:6]),
            pstate_b, rates_b, backlog_b, cols[0],
            telem_col=telem_b, up_col=up_b, lean=True)
        return carry, tuple(
            list(out[:7]) + jax.tree.leaves(out[7]) + [out[8]])

    if nb == 1:
        _, ys = body(None, jax.tree.map(lambda a: a[0], xs))
        outs = [y[:o] for y in ys]
    else:
        _, ys = jax.lax.scan(body, None, xs)
        outs = [y.reshape(op, j)[:o] for y in ys]
    pstate = jax.tree.unflatten(treedef, outs[7:7 + len(leaves)])
    return (*outs[:7], pstate, outs[-1])


def mega_window_round(policy, ctx, cap_tick, backlog_cap, queue, vol_left,
                      alloc, held, pstate, rates_w, telem_ok=None, up=None,
                      *, interpret: bool = None):
    """One fused control round: gate -> serve all ticks -> observation
    select -> policy step, in a single megakernel invocation.

    queue/vol_left/alloc/backlog_cap: [O, J]; held: (served, demand, alloc)
    last-delivered rows; pstate: the policy-state pytree (every leaf
    [O, ...]); rates_w: [W, O, J] fault-scaled issue attempts; cap_tick:
    [O] effective per-tick rate (``ctx.cap_w`` must be its window total);
    telem_ok/up: optional [O] fault columns.

    Returns (queue, vol_left, served_w, demand, obs_served, obs_demand,
    obs_alloc, pstate, alloc_next) -- the obs triple is the next held
    state; trajectory record/telemetry stay with the caller
    (``storage.simulator.window_step``).

    ``interpret=None`` auto-routes: the Pallas megakernel on TPU, the
    blocked specialized XLA trace elsewhere.  Pass ``interpret=True`` to
    force the kernel through the Pallas interpreter (kernel-fidelity
    tests).
    """
    if interpret is None:
        if not _on_tpu():
            return _mega_round_xla(policy, ctx, cap_tick, backlog_cap,
                                   queue, vol_left, alloc, held, pstate,
                                   rates_w, telem_ok, up)
        interpret = False
    o, j = queue.shape
    w = rates_w.shape[0]
    leaves, treedef = _flatten_state(pstate, o)
    for leaf in leaves:
        if leaf.shape != (o, j):
            raise ValueError(
                "the Pallas megakernel blocks policy-state leaves as "
                f"[O, J] rows; got a leaf of shape {leaf.shape} "
                f"(expected {(o, j)})")
    jp = _pad_lanes(j)
    bo = _block_rows(o)
    vmem = _vmem_limit_bytes(bo, jp, _live_rows(len(leaves), w))

    def pad(a):
        return _pad_to(_pad_to(a, jp, 1), bo, 0)

    def pad_col(a):
        return _pad_to(jnp.reshape(a, (o, 1)), bo, 0)

    ctx_p = ctx._replace(nodes=pad(ctx.nodes),
                         cap_w=_pad_to(jnp.reshape(ctx.cap_w, (o,)), bo, 0))
    out = mega_window_pallas(
        policy, ctx_p, pad(queue), pad(vol_left), pad(alloc),
        tuple(pad(h) for h in held), [pad(x) for x in leaves], treedef,
        _pad_to(_pad_to(rates_w, jp, 2), bo, 1), pad(backlog_cap),
        _pad_to(jnp.reshape(cap_tick, (o,)), bo, 0),
        telem_ok=None if telem_ok is None else pad_col(telem_ok),
        up=None if up is None else pad_col(up),
        block_o=bo, vmem_limit_bytes=vmem, interpret=interpret)
    unpad = lambda a: a[:o, :j]
    pstate = jax.tree.unflatten(treedef, [unpad(x) for x in out[7]])
    return (*(unpad(x) for x in out[:7]), pstate, unpad(out[8]))

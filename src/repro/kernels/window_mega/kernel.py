"""Pallas TPU megakernel: the whole per-window control round, fused.

One grid step runs, for a [BLOCK_O, J] block of OSTs, everything the engine
does between two windows: ``policy.gate`` on the standing allocation, every
service tick of the window (``fleet_window.serve_window_block`` -- the same
tick math as the scan backend), the lost-telemetry observation select, and
``policy.step`` -- the full AdapTBF three-step allocation
(``adaptbf_alloc._alloc_block``) for the adaptbf discipline.  Queues, token
buckets, volumes, held observations, and allocator state stay resident in
VMEM across the phase boundary that previously cost an HBM round-trip
between ``kernels/adaptbf_alloc`` and ``kernels/fleet_window``, and
``input_output_aliases`` donates every state buffer in place (the carry
leaves are fresh per ``init_carry``, so in-place reuse cannot alias another
leaf -- the simulator's "fresh buffer per leaf" rule).

Every op is row-local (the policy contract), so the kernel blocks freely
over OST rows and a sharded engine (``partition="ost_shard"``) hands each
device the same program on its local rows -- block boundaries never change
any row's result, which is what keeps sharded == unsharded bitwise.

The off-TPU fallback (``ops._mega_round_xla``) traces the identical round
per row block but swaps the straight-line serve loop for
``_serve_window_lean``: a runtime-specialized tick loop that picks, per
window per block, one of six ``lax.switch`` branches -- {all-ruled,
all-unruled, mixed} x {volume-tracked, all-infinite-volume} -- each a
provably output-identical reduction of ``storage.simulator._serve_tick``
(the derivations are inline below; parity is pinned per window against the
scan oracle in ``tests/test_kernel_window_mega.py``).  Branch predicates
reduce over the whole block, but every branch is bitwise-identical per row,
so blocking/sharding differences in predicate scope cannot fork results.

VMEM footprint ~ (window_ticks + ~26 + 2 x state leaves) live [BLOCK_O, J]
f32 arrays (rate trace + engine state + allocator temporaries); see
DESIGN.md section 12 for the budget table.  ``dispatch.block_rows`` stays
the single sizing authority.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policies import PolicyContext, WindowObs
from repro.kernels.fleet_window.kernel import serve_window_block
from repro.storage.simulator import _EPS


def _serve_window_lean(queue, vol_left, budget0, rates, backlog_cap, cap):
    """All ticks of one window with runtime branch specialization (XLA
    fallback only; the Pallas kernel keeps the straight-line loop).

    queue/vol_left/budget0/backlog_cap: [O, J]; rates: [W, O, J];
    cap: [O, 1].  Returns (queue, vol_left, served_window), bitwise equal
    to the scan backend's ``vmap(_serve_tick)`` loop.

    Specializations (each an IEEE identity, not an approximation):

    * ruledness is window-invariant (a finite budget only decreases, an
      infinite one stays infinite), so ``isfinite`` is hoisted out of the
      tick loop and ``b = where(ruled, max(budget0, 0), 0)`` makes
      ``want1 = min(q, b)`` exact for both classes (unruled rows see
      b == +0.0, exactly the ``where(ruled, ..., 0.0)`` the oracle
      computes; a ruled budget never goes negative because s1 <= b).
    * ``served = min(s1 + s2, q)`` drops: s1 and s2 have disjoint row
      support and each is (want * scale<=1) <= want <= q under
      round-to-nearest, so the clamp is an identity.
    * all-ruled blocks skip phase 2 entirely (want2 == 0 -> s2 == +0.0
      and the spare reduction is never consumed).
    * all-unruled blocks skip phase 1 (s1 == +0.0) and use
      spare = max(cap, 0) directly (== max(cap - sum(+0), 0)).
    * blocks whose volumes are all infinite skip the volume bound and
      update (min(rate, inf) == rate; inf - issued == inf).
    """
    w = rates.shape[0]
    ruled = jnp.isfinite(budget0)
    b0 = jnp.where(ruled, jnp.maximum(budget0, 0.0), 0.0)
    any_ruled = jnp.any(ruled)
    any_unruled = jnp.any(~ruled)
    vol_live = jnp.any(jnp.isfinite(vol_left))
    # 0 = all ruled, 1 = all unruled, 2 = mixed; x2 for volume tracking
    mode = jnp.where(any_ruled & any_unruled, 2,
                     jnp.where(any_ruled, 0, 1))
    branch = mode * 2 + vol_live.astype(jnp.int32)

    def make(phases, track_vol):
        def run(args):
            queue, vol = args

            def tick(t, carry):
                q, v, b, acc = carry
                rate_t = jax.lax.dynamic_index_in_dim(
                    rates, t, 0, keepdims=False)
                h = jnp.maximum(backlog_cap - q, 0.0)
                if track_vol:
                    iss = jnp.minimum(jnp.minimum(rate_t, v), h)
                    v = v - iss
                else:
                    iss = jnp.minimum(rate_t, h)
                q = jnp.maximum(q + iss, 0.0)
                if phases == 0:      # all ruled: phase 1 only
                    want1 = jnp.minimum(q, b)
                    s1 = want1 * jnp.minimum(1.0, cap / jnp.maximum(
                        jnp.sum(want1, axis=-1, keepdims=True), _EPS))
                    return q - s1, v, b - s1, acc + s1
                if phases == 1:      # all unruled: phase 2 only
                    spare = jnp.maximum(cap, 0.0)
                    s2 = q * jnp.minimum(1.0, spare / jnp.maximum(
                        jnp.sum(q, axis=-1, keepdims=True), _EPS))
                    return q - s2, v, b, acc + s2
                want1 = jnp.minimum(q, b)
                s1 = want1 * jnp.minimum(1.0, cap / jnp.maximum(
                    jnp.sum(want1, axis=-1, keepdims=True), _EPS))
                spare = jnp.maximum(
                    cap - jnp.sum(s1, axis=-1, keepdims=True), 0.0)
                want2 = jnp.where(ruled, 0.0, q)
                s2 = want2 * jnp.minimum(1.0, spare / jnp.maximum(
                    jnp.sum(want2, axis=-1, keepdims=True), _EPS))
                served = s1 + s2
                return q - served, v, b - s1, acc + served

            q, v, _, acc = jax.lax.fori_loop(
                0, w, tick, (queue, vol, b0, jnp.zeros_like(queue)))
            return q, v, acc

        return run

    return jax.lax.switch(
        branch, [make(ph, tv) for ph in (0, 1, 2) for tv in (False, True)],
        (queue, vol_left))


def mega_round_block(policy, ctx_blk: PolicyContext, queue, vol_left, alloc,
                     held, pstate, rates, backlog_cap, cap2,
                     telem_col=None, up_col=None, *, lean: bool):
    """One full control round on a [O, J] block of OSTs.

    held: (served, demand, alloc) last-delivered observation rows;
    pstate: the policy-state pytree sliced to the block's rows;
    rates: [W, O, J] (fault-scaled) -- the Pallas ref in the kernel body,
    an array in the XLA fallback; cap2: [O, 1] effective per-tick rate;
    telem_col/up_col: optional [O, 1] fault columns.  ``ctx_blk`` must
    already carry the block's nodes/cap_w and ``alloc_backend="block"``
    (straight-line, Pallas-safe) or ``"block_cond"`` (runtime-specialized,
    XLA fallback).  Returns (queue, vol_left, served_w, demand, obs_served,
    obs_demand, obs_alloc, pstate, alloc_next) -- the obs triple is the new
    held state; telemetry/record stay with the caller.
    """
    budget0 = policy.gate(alloc, ctx_blk)
    serve = _serve_window_lean if lean else serve_window_block
    queue, vol_left, served_w = serve(
        queue, vol_left, budget0, rates, backlog_cap, cap2)
    demand = served_w + queue
    if telem_col is None:
        obs_served, obs_demand, obs_alloc = served_w, demand, alloc
    else:
        delivered = telem_col > 0
        obs_served = jnp.where(delivered, served_w, held[0])
        obs_demand = jnp.where(delivered, demand, held[1])
        obs_alloc = jnp.where(delivered, alloc, held[2])
    pstate, alloc_next = policy.step(
        pstate,
        WindowObs(served=obs_served, demand=obs_demand, alloc=obs_alloc,
                  up=up_col),
        ctx_blk)
    return (queue, vol_left, served_w, demand, obs_served, obs_demand,
            obs_alloc, pstate, alloc_next)


def mega_window_pallas(policy, ctx: PolicyContext, queue, vol_left, alloc,
                       held, state_leaves, state_treedef, rates, backlog_cap,
                       cap_tick, telem_ok=None, up=None, *, block_o: int = 8,
                       vmem_limit_bytes: int = None,
                       interpret: bool = False):
    """[O, J] fused control round.  rates: [W, O, J]; cap_tick: [O] (the
    effective, fault-scaled per-tick rate; ``ctx.cap_w`` must be its window
    total).  J should be a lane multiple and O a block multiple (ops.py
    pads).  Returns (queue, vol_left, served_w, demand, obs_served,
    obs_demand, obs_alloc, state_leaves, alloc_next).

    State buffers (queue, volume, held observations, policy-state leaves)
    are donated in place via ``input_output_aliases``; the standing
    allocation is NOT donated because the caller still reads it for
    telemetry after the round.
    """
    o, j = queue.shape
    w = rates.shape[0]
    n_state = len(state_leaves)
    cap2 = cap_tick.reshape(o, 1).astype(jnp.float32)
    capw2 = ctx.cap_w.reshape(o, 1).astype(jnp.float32)
    has_faults = telem_ok is not None
    has_code = ctx.control_code is not None

    row_spec = pl.BlockSpec((block_o, j), lambda i: (i, 0))
    col_spec = pl.BlockSpec((block_o, 1), lambda i: (i, 0))
    rates_spec = pl.BlockSpec((w, block_o, j), lambda i: (0, i, 0))
    one_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    oj = jax.ShapeDtypeStruct((o, j), jnp.float32)

    def kernel(*refs):
        it = iter(refs)
        queue_b, vol_b, alloc_b = (next(it)[...] for _ in range(3))
        held_b = tuple(next(it)[...] for _ in range(3))
        pstate_b = jax.tree.unflatten(
            state_treedef, [next(it)[...] for _ in range(n_state)])
        nodes_b = next(it)[...]
        backlog_b = next(it)[...]
        cap_b = next(it)[...]
        capw_b = next(it)[...]
        telem_b = next(it)[...] if has_faults else None
        up_b = next(it)[...] if has_faults else None
        rates_b = next(it)      # a ref: the serve loop loads one tick
        code = next(it)[0, 0] if has_code else None
        ctx_blk = ctx._replace(nodes=nodes_b, cap_w=capw_b[:, 0],
                               alloc_backend="block", control_code=code)
        out = mega_round_block(
            policy, ctx_blk, queue_b, vol_b, alloc_b, held_b, pstate_b,
            rates_b, backlog_b, cap_b, telem_col=telem_b, up_col=up_b,
            lean=False)
        outs = list(out[:7]) + jax.tree.leaves(out[7]) + [out[8]]
        for ref, val in zip(refs[len(refs) - len(outs):], outs):
            ref[...] = val

    in_specs = ([row_spec] * (6 + n_state) + [row_spec, row_spec]
                + [col_spec, col_spec]
                + ([col_spec, col_spec] if has_faults else [])
                + [rates_spec] + ([one_spec] if has_code else []))
    out_specs = [row_spec] * (8 + n_state)
    out_shape = [oj] * (8 + n_state)
    # donate the state buffers in place: queue->queue', vol->vol',
    # held->obs (the obs triple IS the next held state), state leaves
    aliases = {0: 0, 1: 1, 3: 4, 4: 5, 5: 6}
    aliases.update({6 + i: 7 + i for i in range(n_state)})
    args = [x.astype(jnp.float32) for x in (queue, vol_left, alloc, *held)]
    args += [x.astype(jnp.float32) for x in state_leaves]
    args += [ctx.nodes.astype(jnp.float32),
             backlog_cap.astype(jnp.float32), cap2, capw2]
    if has_faults:
        args += [telem_ok.reshape(o, 1).astype(jnp.float32),
                 up.reshape(o, 1).astype(jnp.float32)]
    args.append(rates.astype(jnp.float32))
    if has_code:
        args.append(ctx.control_code.reshape(1, 1).astype(jnp.int32))

    out = pl.pallas_call(
        kernel,
        grid=(o // block_o,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(*args)
    queue, vol_left, served, demand, obs_s, obs_d, obs_a = out[:7]
    return (queue, vol_left, served, demand, obs_s, obs_d, obs_a,
            list(out[7:7 + n_state]), out[7 + n_state])

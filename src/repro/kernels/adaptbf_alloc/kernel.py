"""Pallas TPU kernel: fleet-scale AdapTBF token allocation.

One grid step allocates for a block of OSTs (rows) x all jobs (lanes), the
whole three-step algorithm (priority -> redistribution -> re-compensation,
paper Section III-C) running in VMEM on the VPU.  The decentralization
property is structural: every op is row-independent.

The largest-remainder correction reuses ``core/remainder.integerize``
verbatim -- its ``topk_mask`` selection (fixed-probe binary search on the
remainder threshold, index tie-break at the boundary) is sort-free,
vector-unit friendly, exact, and O(J) in VMEM, so the kernel and the core
allocator literally cannot drift apart.

Block sizing: BLOCK_O x J with J padded to a lane multiple (128).  VMEM
footprint ~ 16 live [BLOCK_O, J] f32 arrays (see dispatch.block_rows); BLOCK_O=8
holds out to J=16384 under the raised scoped-VMEM limit, where the old [BLOCK_O, J, J] rank matrix forced
BLOCK_O=1 by J~1448 and made J=4096 (64 MB) impossible at any block size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.remainder import integerize as _integerize

_EPS = 1e-12


def _alloc_block(demand, nodes, record, remainder, alloc_prev, capacity,
                 u_max: float, *, dist=None, integer_tokens: bool = True,
                 specialize: bool = False):
    """The full three-step window allocation on a [O, J] block.

    ``dist`` is the distribution primitive (default
    ``core/remainder.integerize``; the window megakernel's XLA fallback
    passes the runtime-specialized variant, float-token callers pass
    ``passthrough``); ``integer_tokens`` controls the reclaim floor,
    matching ``core/adaptbf.allocate``.

    ``specialize=True`` wraps the surplus-redistribution and
    re-compensation distribution calls in ``lax.cond`` on their runtime
    totals.  Distributing a zero total is an exact identity (raw == 0,
    floor == 0, delta == 0, so applied == 0 and the remainder carry is
    returned unchanged), so the skip is bitwise-equal to the full trace --
    it only drops work the numbers prove dead.  Saturated fleets (demand
    everywhere above allocation, empty borrowing ledger) take both skips
    every window, paying for one distribution instead of three.  Only
    valid off-vmap and outside Pallas (``lax.cond`` under vmap degrades
    to running both branches).
    """
    dist = _integerize if dist is None else dist
    active = demand > 0
    any_active = jnp.any(active, axis=-1, keepdims=True)

    # step 1: priority-based initial allocation (Eq. 1-2)
    n_act = jnp.where(active, nodes, 0.0)
    p = n_act / jnp.maximum(jnp.sum(n_act, axis=-1, keepdims=True), _EPS)
    budget1 = jnp.where(any_active, capacity, 0.0)
    alpha1, rem = dist(budget1 * p, remainder, budget1, active)

    # step 2: surplus redistribution (Eq. 3-8)
    u = jnp.minimum(demand / jnp.maximum(alloc_prev, 1.0), u_max)
    u = jnp.where(active, u, 0.0)
    surplus = jnp.where(active, jnp.maximum(alpha1 - demand, 0.0), 0.0)
    t_s = jnp.sum(surplus, axis=-1, keepdims=True)
    df = jnp.where(u > 1.0, u + u * p, u * p)
    df = jnp.where(active, df, 0.0)
    share = df / jnp.maximum(jnp.sum(df, axis=-1, keepdims=True), _EPS)
    if specialize:
        add_rd, rem = jax.lax.cond(
            jnp.any(t_s > 0),
            lambda _: dist(share * t_s, rem, t_s, active),
            lambda _: (jnp.zeros_like(share), rem),
            operand=None)
    else:
        add_rd, rem = dist(share * t_s, rem, t_s, active)
    alpha_rd = alpha1 - surplus + add_rd
    r_rd = record + surplus - add_rd

    # step 3: re-compensation (Eq. 9-20)
    j_plus = active & (record > 0) & (r_rd > 0)
    j_minus = active & (record < 0) & (r_rd < 0)
    u_future = demand / jnp.maximum(alpha_rd, 1.0)
    c_terms = p * (jnp.maximum(1.0, u) + jnp.maximum(0.0, 1.0 - u_future)) / 2.0
    c = jnp.sum(jnp.where(j_plus, c_terms, 0.0), axis=-1, keepdims=True)
    reclaim = jnp.minimum(jnp.abs(record), jnp.abs(c * alpha_rd))
    reclaim = jnp.minimum(reclaim, alpha_rd)
    reclaim = jnp.where(j_minus, reclaim, 0.0)
    # total reclaim capped at what active lenders are owed; per-lender
    # compensation capped at its record (DESIGN.md deviation 3)
    owed = jnp.where(j_plus, r_rd, 0.0)
    t_owed = jnp.sum(owed, axis=-1, keepdims=True)
    reclaim = reclaim * jnp.minimum(
        1.0, t_owed / jnp.maximum(jnp.sum(reclaim, axis=-1, keepdims=True), _EPS))
    if integer_tokens:
        reclaim = jnp.floor(reclaim)
    t_r = jnp.sum(reclaim, axis=-1, keepdims=True)
    df_plus = jnp.where(j_plus, df, 0.0)
    share_p = df_plus / jnp.maximum(jnp.sum(df_plus, axis=-1, keepdims=True), _EPS)
    add1 = jnp.minimum(share_p * t_r, owed)
    headroom = owed - add1
    leftover = t_r - jnp.sum(add1, axis=-1, keepdims=True)
    add_raw = add1 + leftover * headroom / jnp.maximum(
        jnp.sum(headroom, axis=-1, keepdims=True), _EPS)
    if specialize:
        add_rc, rem = jax.lax.cond(
            jnp.any(t_r > 0),
            lambda _: dist(add_raw, rem, t_r, j_plus),
            lambda _: (jnp.zeros_like(add_raw), rem),
            operand=None)
    else:
        add_rc, rem = dist(add_raw, rem, t_r, j_plus)
    alpha_rc = alpha_rd - reclaim + add_rc
    r_rc = r_rd + reclaim - add_rc

    alloc = jnp.where(active, alpha_rc, 0.0)
    return alloc, r_rc, rem


def _kernel(demand_ref, nodes_ref, record_ref, rem_ref, prev_ref, cap_ref,
            alloc_ref, new_rec_ref, new_rem_ref, *, u_max: float):
    alloc, rec, rem = _alloc_block(
        demand_ref[...], nodes_ref[...], record_ref[...], rem_ref[...],
        prev_ref[...], cap_ref[...], u_max)
    alloc_ref[...] = alloc
    new_rec_ref[...] = rec
    new_rem_ref[...] = rem


@functools.partial(jax.jit,
                   static_argnames=("u_max", "block_o", "vmem_limit_bytes",
                                    "interpret"))
def fleet_alloc_pallas(demand, nodes, record, remainder, alloc_prev,
                       capacity, *, u_max: float = 64.0, block_o: int = 8,
                       vmem_limit_bytes: int = None,
                       interpret: bool = False):
    """[O, J] fleet allocation.  capacity: [O].  J should be a multiple of
    128 and O a multiple of block_o (ops.py pads).  Returns
    (alloc, new_record, new_remainder)."""
    o, j = demand.shape
    cap2 = capacity.reshape(o, 1).astype(jnp.float32)
    grid = (o // block_o,)
    row_spec = pl.BlockSpec((block_o, j), lambda i: (i, 0))
    cap_spec = pl.BlockSpec((block_o, 1), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((o, j), jnp.float32)] * 3
    fn = pl.pallas_call(
        functools.partial(_kernel, u_max=u_max),
        grid=grid,
        in_specs=[row_spec] * 5 + [cap_spec],
        out_specs=[row_spec] * 3,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )
    args = [x.astype(jnp.float32) for x in
            (demand, nodes, record, remainder, alloc_prev)] + [cap2]
    return tuple(fn(*args))

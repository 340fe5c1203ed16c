"""Pallas TPU kernel: Mamba-2 SSD chunked scan [arXiv:2405.21060].

Grid (B, H, n_chunks) with the chunk dim innermost and sequential; the
recurrent state h [N, P] lives in VMEM scratch and is carried across chunk
steps, so HBM traffic per chunk is exactly (x, dt, B, C in; y out) -- the
quadratic intra-chunk work happens on the MXU against VMEM-resident blocks.

TPU adaptation of the paper's (GPU) layout: the chunk-parallel/warp split of
the Triton kernel becomes grid parallelism over (batch x heads) with a
sequential chunk walk per core; the within-chunk masked quadratic form is
shaped [Q, Q] to feed the 128x128 MXU, and the cumulative decay is built with
a lower-triangular ones matmul rather than a warp-level prefix scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref,
                y_ref, state_ref, h_scr, *, chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0, 0]                  # [Q, P]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # [Q, 1]
    a = a_ref[0, 0]                     # scalar
    bb = b_ref[0, 0]                    # [Q, N]
    cc = c_ref[0, 0]                    # [Q, N]

    da = dt * a                         # [Q, 1], <= 0
    # cumulative within-chunk decay via lower-triangular ones matmul
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    cum = jax.lax.dot_general(tri, da, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [Q,1]
    xw = x * dt.astype(x.dtype)         # dt-weighted input

    # intra-chunk quadratic form
    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q,Q]
    decay = jnp.exp(jnp.minimum(cum - cum.reshape(1, chunk), 0.0))
    w = jnp.where(tri > 0, scores * decay, 0.0)
    y = jax.lax.dot_general(w.astype(x.dtype), xw, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)      # [Q,P]

    # inter-chunk contribution from carried state h [N, P]
    c_in = cc * jnp.exp(cum).astype(cc.dtype)
    y = y + jax.lax.dot_general(c_in, h_scr[...].astype(cc.dtype),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: h = h * gamma + B^T (state_decay * xw)
    seg = cum[chunk - 1, 0]
    state_decay = jnp.exp(seg - cum)    # [Q,1]
    b_w = bb * state_decay.astype(bb.dtype)
    h_scr[...] = h_scr[...] * jnp.exp(seg) + jax.lax.dot_general(
        b_w, xw, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y = y + x.astype(jnp.float32) * dskip_ref[0, 0]
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        state_ref[0, 0] = h_scr[...].astype(state_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas_bhcqp(x, dt, a, b, c, d_skip, *, chunk=128, interpret=False):
    """x [B,H,NC,Q,P]; dt [B,H,NC,Q,1]; a [H,1]; b/c [B,NC,Q,N];
    d_skip [H,1].  Returns (y [B,H,NC,Q,P], state [B,H,N,P])."""
    bsz, h, nc, q, p_ = x.shape
    n = b.shape[-1]
    kernel = functools.partial(_ssd_kernel, chunk=q, n_chunks=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p_), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1), lambda ib, ih, ic: (ih, 0)),
            pl.BlockSpec((1, 1, q, n), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1), lambda ib, ih, ic: (ih, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p_), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, n, p_), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, nc, q, p_), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, n, p_), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a.reshape(h, 1), b, c, d_skip.reshape(h, 1))
    return y, state


def ssd_pallas(x, dt, a, B, C, d_skip=None, initial_state=None,
               chunk: int = 128, interpret: bool = False):
    """Model-layout wrapper matching ref.ssd_chunked:
    x [B,S,H,P], dt [B,S,H], a [H], B/C [B,S,N] -> (y [B,S,H,P],
    state [B,H,P,N])."""
    if initial_state is not None:
        # warm-started prefill continuation falls back to the oracle path
        from repro.kernels.ssd import ref
        return ref.ssd_chunked(x, dt, a, B, C, d_skip=d_skip,
                               initial_state=initial_state, chunk=chunk)
    bsz, s, h, p_ = x.shape
    n = B.shape[-1]
    orig_s = s
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        s += pad
    nc = s // chunk
    xr = x.reshape(bsz, nc, chunk, h, p_).transpose(0, 3, 1, 2, 4)
    dtr = dt.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)[..., None]
    br = B.reshape(bsz, nc, chunk, n)
    cr = C.reshape(bsz, nc, chunk, n)
    if d_skip is None:
        d_skip = jnp.zeros((h,), jnp.float32)
    y, state = ssd_pallas_bhcqp(xr, dtr, a.astype(jnp.float32), br, cr,
                                d_skip.astype(jnp.float32), chunk=chunk,
                                interpret=interpret)
    y = y.transpose(0, 2, 3, 1, 4).reshape(bsz, s, h, p_)[:, :orig_s]
    return y, state.transpose(0, 1, 3, 2)  # [B,H,P,N]

"""Pallas TPU flash attention (forward + single-token decode).

Layout: ops.py feeds [B, H, S, D] (heads-major so the TP-sharded head dim is
a pure grid dimension).  Grid (B, Hq, nQ, nKV) with the KV dim innermost and
sequential; online-softmax state (m, l, acc) lives in VMEM scratch and the
normalized output block is written on the last KV step.  GQA is an index-map
(kv head = q head // group): KV blocks are NOT materialized per q-head, which
is the bandwidth advantage over the broadcast XLA path.

Causal blocks strictly above the diagonal are skipped with pl.when (no MXU
work), matching the ~2x causal FLOP saving.  Block sizes default to 512x512;
VMEM per step ~ (q + k + v + p + acc) ~= 2.5 MB at D=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, scale, block_q, block_kv, n_kv, t_actual):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = ik * block_kv <= iq * block_q + block_q - 1

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                              # [bq, D]
        k = k_ref[0, 0]                              # [bk, D]
        v = v_ref[0, 0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kv_pos = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = kv_pos < t_actual
        if causal:
            mask = mask & (kv_pos <= q_pos)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l_safe)).astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, block_q=512, block_kv=512,
                         interpret=False):
    """q [B,Hq,S,D]; k/v [B,Hkv,T,D] with Hq % Hkv == 0.
    Returns (o [B,Hq,S,D], lse [B,Hq,S,1])."""
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    group = hq // hkv
    block_q = min(block_q, s)
    block_kv = min(block_kv, t)
    # pad S/T to block multiples (masked out via t_actual / output slice)
    sp = s + (-s) % block_q
    tp = t + (-t) % block_kv
    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    if tp != t:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    n_q, n_kv = sp // block_q, tp // block_kv

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=d ** -0.5, block_q=block_q,
        block_kv=block_kv, n_kv=n_kv, t_actual=t)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sp, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o[:, :, :s], lse[:, :, :s]


def flash_attention(q, k, v, *, causal=True, interpret=False):
    """Model-layout wrapper: q [B,S,H,D], k/v [B,T,H,D] -> [B,S,H,D]."""
    o, _ = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, interpret=interpret)
    return o.transpose(0, 2, 1, 3)


# ------------------------------------------------------------------ decode


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale, block_kv, n_kv):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]
    run = ik * block_kv < length

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                               # [1, D]
        k = k_ref[0, 0]                               # [bk, D]
        v = v_ref[0, 0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [1, bk]
        kv_pos = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        logits = jnp.where(kv_pos < length, logits, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def flash_decode(q, k_cache, v_cache, length, *, block_kv=512,
                 interpret=False):
    """q [B,1,Hq,D]; caches [B,T,Hkv,D]; length [B] -> [B,1,Hq,D]."""
    b, _, hq, d = q.shape
    t, hkv = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    qt = q.transpose(0, 2, 1, 3)                      # [B,Hq,1,D]
    kt = k_cache.transpose(0, 2, 1, 3)                # [B,Hkv,T,D]
    vt = v_cache.transpose(0, 2, 1, 3)
    block_kv = min(block_kv, t)
    tp = t + (-t) % block_kv
    if tp != t:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    n_kv = tp // block_kv

    kernel = functools.partial(_decode_kernel, scale=d ** -0.5,
                               block_kv=block_kv, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda b_, h, ik, lens: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, ik, lens: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, ik, lens: (b_, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda b_, h, ik, lens: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), q.dtype),
        interpret=interpret,
    )(length.astype(jnp.int32), qt, kt, vt)
    return o.transpose(0, 2, 1, 3)

"""Blocked GQA flash attention — Pallas TPU kernel.

TPU-native design (not a CUDA port): the (batch × kv_head) pairs and the
query blocks form the parallel grid dims; the KV axis is the innermost
``arbitrary`` (sequential) dim, with the online-softmax running max /
normalizer / accumulator carried across KV steps in VMEM scratch. All
matmuls are MXU-shaped (block_q × head_dim × block_k, 128-aligned), and
each grid step touches only VMEM-resident blocks declared by BlockSpecs.

Supports causal and sliding-window masking plus gemma-style logit
softcap; grouped queries (G = H/KV) ride along in the q block so MQA
archs (recurrentgemma, kv=1) keep full MXU occupancy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, softcap: float, sm_scale: float,
            block_q: int, block_k: int, q_offset: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                       # (bq, G, hd)
    k = k_ref[0]                       # (bk, hd)
    v = v_ref[0]
    bq, G, hd = q.shape

    s = jax.lax.dot_general(
        q.reshape(bq * G, hd), k,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (bq*G, bk)
    s = s * sm_scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    qi = pl.program_id(1)
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (bq, G), 0) + q_offset              # (bq, G)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)                    # (1, bk)
    qpos_f = qpos.reshape(bq * G, 1)
    mask = jnp.ones((bq * G, block_k), bool)
    if causal:
        mask &= kpos <= qpos_f
    if window > 0:
        mask &= kpos > qpos_f - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...].reshape(bq * G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # (bq*G, bk)
    l_new = l_scr[...].reshape(bq * G, 1) * alpha + \
        jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (bq*G, hd)
    acc = acc_scr[...].reshape(bq * G, hd) * alpha + pv

    m_scr[...] = m_new.reshape(bq, G)
    l_scr[...] = l_new.reshape(bq, G)
    acc_scr[...] = acc.reshape(bq, G, hd)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...].reshape(bq * G, 1)
        out = acc_scr[...].reshape(bq * G, hd) / jnp.maximum(l, 1e-30)
        o_ref[0] = out.reshape(bq, G, hd).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False,
                    q_offset: int = None) -> jax.Array:
    """q (B, Sq, H, hd); k/v (B, Skv, KV, hd) -> (B, Sq, H, hd).

    Query i is at absolute position (q_offset + i); by default
    q_offset = Skv - Sq (queries are the LAST Sq positions), matching
    ref.py. ops.py overrides it to preserve alignment under padding.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    if q_offset is None:
        q_offset = Skv - Sq

    # (B, Sq, KV, G, hd) -> (B*KV, Sq, G, hd)
    qz = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B * KV, Sq, G, hd)
    kz = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    vz = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)

    grid = (B * KV, Sq // bq, Skv // bk)
    kernel = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap,
        sm_scale=hd ** -0.5, block_q=bq, block_k=bk, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, G, hd), lambda z, qi, ki: (z, qi, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda z, qi, ki: (z, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda z, qi, ki: (z, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, G, hd), lambda z, qi, ki: (z, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, Sq, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, G), jnp.float32),
            pltpu.VMEM((bq, G), jnp.float32),
            pltpu.VMEM((bq, G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qz, kz, vz)

    return out.reshape(B, KV, Sq, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Sq, H, hd)

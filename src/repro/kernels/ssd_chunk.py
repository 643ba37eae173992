"""Mamba-2 SSD intra-chunk quadratic dual form — Pallas TPU kernel.

Computes, per (batch·head, chunk) grid cell, the zero-initial-state
chunk output

    y = (C Bᵀ ∘ L) x,   L[i,j] = exp(cumsum(loga)_i - cumsum(loga)_j)·[j<=i]

with the (Q × Q) decay-masked score matrix living entirely in VMEM and
both contractions on the MXU. This is the compute hot spot of the SSD
scan (models/ssm.py ``ssd_scan`` y_diag term, which is its oracle via
``kernels/ref.py::ssd_chunk_ref``); the inter-chunk recurrence stays in
XLA (tiny, bandwidth-bound).

Grid: (B*H, L/Q) — fully parallel; chunk length Q is the block size
(Mamba-2 uses 256, MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _kernel(x_ref, loga_ref, b_ref, c_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    loga = loga_ref[0].astype(jnp.float32)    # (Q, 1)
    bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0].astype(jnp.float32)         # (Q, N)
    Q = x.shape[0]

    z = jnp.cumsum(loga[:, 0])                # (Q,)
    t = z[:, None] - z[None, :]               # (Q, Q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.where(kj <= qi, jnp.exp(t), 0.0)

    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * decay, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)


def ssd_chunk(xdt: jax.Array, loga: jax.Array, Bm: jax.Array,
              Cm: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Intra-chunk SSD, zero initial state.

    xdt (B, L, H, P); loga (B, L, H); Bm/Cm (B, L, H, N) — groups
    pre-broadcast to heads. L must be a multiple of the chunk length Q
    implied by the caller's reshape; here each grid step handles one
    (b·h, chunk) pair with Q = block over L. Returns y (B, L, H, P).
    """
    B, L, H, P = xdt.shape
    N = Bm.shape[-1]
    Q = min(256, L)
    assert L % Q == 0, (L, Q)

    # (B, L, H, *) -> (B*H, L, *)
    xz = xdt.transpose(0, 2, 1, 3).reshape(B * H, L, P)
    lz = loga.transpose(0, 2, 1).reshape(B * H, L, 1)
    bz = Bm.transpose(0, 2, 1, 3).reshape(B * H, L, N)
    cz = Cm.transpose(0, 2, 1, 3).reshape(B * H, L, N)

    out = pl.pallas_call(
        _kernel,
        grid=(B * H, L // Q),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda z, ci: (z, ci, 0)),
            pl.BlockSpec((1, Q, 1), lambda z, ci: (z, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda z, ci: (z, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda z, ci: (z, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda z, ci: (z, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, L, P), xdt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xz, lz, bz, cz)
    return out.reshape(B, H, L, P).transpose(0, 2, 1, 3)

"""Blocked diagonal linear recurrence h_t = a_t * h_{t-1} + b_t — Pallas TPU.

Backs RG-LRU (recurrentgemma) and any diagonal SSM update. TPU layout:
channels (R) ride the vector lanes (parallel grid dim), time is the
innermost ``arbitrary`` grid dim with the carry h held in VMEM scratch
across time blocks; within a block a fori_loop steps the recurrence with
full lane parallelism. (A two-level blocked associative scan is the
§Perf follow-up; this layout already keeps HBM traffic at exactly
read-a,b + write-h.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_R = 512


def _kernel(a_ref, b_ref, h0_ref, o_ref, carry, *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        carry[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)          # (bt, br)
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, pl.ds(t, 1), :] = h[None].astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, carry[0])
    carry[...] = h[None]


def lru_scan(a: jax.Array, b: jax.Array, h0: jax.Array = None, *,
             block_t: int = DEFAULT_BLOCK_T,
             block_r: int = DEFAULT_BLOCK_R,
             interpret: bool = False) -> jax.Array:
    """a, b (B, L, R); h0 (B, R) or None -> h (B, L, R)."""
    B, L, R = a.shape
    bt = min(block_t, L)
    br = min(block_r, R)
    assert L % bt == 0 and R % br == 0, (L, bt, R, br)
    if h0 is None:
        h0 = jnp.zeros((B, R), a.dtype)

    grid = (B, R // br, L // bt)
    out = pl.pallas_call(
        functools.partial(_kernel, block_t=bt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, br), lambda bi, ri, ti: (bi, ti, ri)),
            pl.BlockSpec((1, bt, br), lambda bi, ri, ti: (bi, ti, ri)),
            pl.BlockSpec((1, br), lambda bi, ri, ti: (bi, ri)),
        ],
        out_specs=pl.BlockSpec((1, bt, br), lambda bi, ri, ti: (bi, ti, ri)),
        out_shape=jax.ShapeDtypeStruct((B, L, R), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, br), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
    return out

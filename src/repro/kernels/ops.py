"""Jit'd dispatch wrappers for the Pallas kernels.

Handles padding to block multiples, dtype plumbing, and backend
selection: on the CPU backend the kernels execute in interpret mode
(the kernel body runs as traced Python — bit-accurate semantics, no
Mosaic); on TPU they always compile natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import PAPER_S
from repro.kernels import flash_attention as _fa
from repro.kernels import lru_scan as _ls
from repro.kernels import schedule_step as _ss
from repro.kernels import ssd_chunk as _sc


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K):
    """GQA flash attention; pads Sq/Skv to block multiples.

    Query i sits at absolute position Skv - Sq + i (see kernel docs).
    KV padding is appended AFTER the queries, so causal masking makes the
    padded keys unreachable; padded query rows are sliced off.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    qp, _ = _pad_to(q, 1, block_q)
    kp, _ = _pad_to(k, 1, block_k)
    vp, _ = _pad_to(v, 1, block_k)
    padded = qp.shape[1] != Sq or kp.shape[1] != Skv
    if padded and not causal:
        raise ValueError("non-causal attention requires block-aligned "
                         "Sq and Skv (padded keys would be attended)")
    # Keep the ORIGINAL query/key alignment: padded keys land at positions
    # beyond every real query and are causally masked; padded query rows
    # are sliced off below.
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              softcap=softcap, block_q=block_q,
                              block_k=block_k, interpret=_interpret(),
                              q_offset=Skv - Sq)
    return out[:, :Sq]


@functools.partial(jax.jit, static_argnames=("block_t", "block_r"))
def lru_scan(a, b, h0=None, *, block_t: int = _ls.DEFAULT_BLOCK_T,
             block_r: int = _ls.DEFAULT_BLOCK_R):
    """Diagonal linear recurrence; pads L (with a=1, b=0) and R."""
    B, L, R = a.shape
    ap, _ = _pad_to(a, 1, block_t, value=1.0)
    bp, _ = _pad_to(b, 1, block_t, value=0.0)
    ap, _ = _pad_to(ap, 2, block_r, value=1.0)
    bp, _ = _pad_to(bp, 2, block_r, value=0.0)
    if h0 is not None:
        h0p, _ = _pad_to(h0, 1, block_r)
    else:
        h0p = None
    out = _ls.lru_scan(ap, bp, h0p, block_t=min(block_t, ap.shape[1]),
                       block_r=min(block_r, ap.shape[2]),
                       interpret=_interpret())
    return out[:, :L, :R]


@functools.partial(jax.jit, static_argnames=("block_j",))
def schedule_step(demand, gp, width, queue_key, assign, free,
                  pending_free, cand, under, be_q, te_demand, node_cap,
                  *, s: float = PAPER_S,
                  block_j: int = _ss.DEFAULT_BLOCK_J):
    """One fused schedule pass over the (jobs, nodes) tile — Eq. 3
    scoring, Eq. 2 best-victim-node reduction, Eq. 4 masked argmin,
    all-or-nothing gang-fit counts (now and promised), and the BE
    head / first-fit / skip-count scan, in one kernel invocation.

    ``demand`` (J, 3); ``assign`` (J, M); ``free``/``pending_free``
    (M, 3); ``gp``/``queue_key`` (J,) f32; ``width`` (J,) i32;
    ``cand``/``under``/``be_q`` (J,) bool; ``s`` may be traced. Pads
    J to the block multiple (padded rows never fit, never selected).
    Returns a
    ``SchedulePass``; see kernels/schedule_step for the field
    contract."""
    J = demand.shape[0]
    M = free.shape[0]
    sz = _ss.eq1_size(demand.astype(jnp.float32),
                      node_cap.astype(jnp.float32))
    max_sz = jnp.maximum(jnp.max(jnp.where(cand, sz, 0.0)), 1e-12)
    max_gp = jnp.maximum(
        jnp.max(jnp.where(cand, gp.astype(jnp.float32), 0.0)), 1e-12)

    dp, _ = _pad_to(demand, 0, block_j)
    gpp, _ = _pad_to(gp.astype(jnp.float32), 0, block_j)
    wp, _ = _pad_to(width, 0, block_j, value=M + 1)  # pad rows never fit
    kp, _ = _pad_to(queue_key, 0, block_j, value=jnp.inf)
    ap, _ = _pad_to(assign, 0, block_j, value=False)  # no nodes: ineligible
    cp, _ = _pad_to(cand, 0, block_j, value=False)
    up, _ = _pad_to(under, 0, block_j, value=False)
    bp, _ = _pad_to(be_q, 0, block_j, value=False)
    ps = _ss.schedule_step_pallas(
        dp, gpp, wp, kp, ap, free, pending_free, cp, up, bp,
        te_demand, node_cap, max_sz, max_gp, s,
        block_j=min(block_j, dp.shape[0]), interpret=_interpret())
    return _ss.SchedulePass(ps.scores[:J], ps.fits[:J], ps.fit_now[:J],
                            ps.fit_pend[:J], ps.victim, ps.be_head,
                            ps.be_pick, ps.nskip)


def fitgpp_select(*args, **kwargs):
    """Removed: the standalone Eq. 1-4 victim-selection kernel was
    subsumed by the fused :func:`schedule_step` pass."""
    raise RuntimeError(
        "kernels.ops.fitgpp_select was removed: the standalone fitgpp "
        "victim-selection kernel is subsumed by the fused schedule-pass "
        "kernel. Call kernels.ops.schedule_step (and read .victim / "
        ".scores from the returned SchedulePass); "
        "SimConfig.score_backend='pallas' keeps working and now routes "
        "through the fused kernel.")


@jax.jit
def ssd_chunk(xdt, loga, Bm, Cm):
    """Mamba-2 intra-chunk SSD (zero initial state); see kernels/ssd_chunk."""
    return _sc.ssd_chunk(xdt, loga, Bm, Cm, interpret=_interpret())

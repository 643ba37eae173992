"""Fused schedule-pass kernel — one invocation per scheduler pass.

The JAX engine's per-event hot loop used to issue a chain of small
kernels per pass (Eq. 3 score, Eq. 2 best-victim-node reduction,
masked argmin, per-job gang fit, BE head/backfill scan).  This module
fuses the whole pass over the ``(jobs, nodes)`` tile into ONE
invocation that returns everything a pass consumes:

* ``scores``   (J,)  f32 — Eq. 3 score (Size/maxSize + s*GP/maxGP,
  normalizers over the running-BE candidates, computed outside and
  passed in as scalars like the te demand).
* ``fits``     (J,M) i32 — per (job, node) fit of ``free`` vs the
  job's per-node demand (the all-or-nothing gang-fit tile; a job fits
  iff its row sums to >= width).
* ``fit_now``  (J,)  i32 — row sums of ``fits``.
* ``fit_pend`` (J,)  i32 — same counts against ``free +
  pending_free`` (the promised-resource gate of the preemption
  trigger).
* ``victim``   ()    i32 — Eq. 4 masked argmin over running-BE &
  under-P-cap & Eq. 2-eligible candidates (eligibility against each
  candidate's BEST assigned node), -1 when nothing passes.
* ``be_head``  ()    i32 — min-queue-key queued BE job, -1 when the
  BE queue is empty.
* ``be_pick``  ()    i32 — min-queue-key queued BE job whose gang
  fits ``free`` right now, -1 when none fits.
* ``nskip``    ()    i32 — how many queued BE jobs ahead of
  ``be_pick`` do NOT fit (the bounded-backfill scan depth consumed
  before the pick; ``be_pick`` is placeable iff ``nskip`` is below
  the remaining depth budget; equals the queued count when
  ``be_pick`` is -1).

Three interchangeable backends share this contract bit-for-bit:
:func:`schedule_step_jnp` (portable jnp twin — the engine's default),
:func:`schedule_step_pallas` (TPU Pallas over (nodes, jobs) tiles,
jobs on the vector lanes, two grid phases: reduce then finalize), and
the jnp oracle ``kernels.ref.schedule_step_ref``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.engine.placement import FIT_EPS

DEFAULT_BLOCK_J = 512
_INF = jnp.inf


class SchedulePass(NamedTuple):
    """Outputs of one fused schedule pass (see module docstring)."""
    scores: jax.Array       # (J,)  f32
    fits: jax.Array         # (J, M) i32
    fit_now: jax.Array      # (J,)  i32
    fit_pend: jax.Array     # (J,)  i32
    victim: jax.Array       # ()    i32, -1 sentinel
    be_head: jax.Array      # ()    i32, -1 sentinel
    be_pick: jax.Array      # ()    i32, -1 sentinel
    nskip: jax.Array        # ()    i32


def eq1_size(demand, node_cap):
    """Eq. 1 size in jnp: the norm of the capacity-normalized demand,
    summed in the fixed order (cpu, mem, gpu) so that XLA on any
    backend, the Pallas path and numpy round it alike."""
    q = jnp.square(demand / node_cap)
    return jnp.sqrt(q[..., 0] + q[..., 1] + q[..., 2])


def eq3_scores(demand, gp, node_cap, max_sz, max_gp, s):
    """Eq. 3 score over the whole job axis (masking happens at the
    argmin); shared by both backends so their scores are bitwise equal."""
    return eq1_size(demand, node_cap) / max_sz + s * (gp / max_gp)


def schedule_step_jnp(demand, gp, width, queue_key, assign, free,
                      pending_free, cand, under, be_q, te_demand,
                      node_cap, max_sz, max_gp, s) -> SchedulePass:
    """Portable jnp twin — the op-order reference for both the Pallas
    kernel (bit-parity on CPU and TPU) and the engine's default path.

    demand (J,3) f32; gp/queue_key (J,) f32; width (J,) i32;
    assign (J,M) bool; free/pending_free (M,3) f32; cand/under/be_q
    (J,) bool; te_demand/node_cap (3,) f32; max_sz/max_gp/s scalars
    (normalizers pre-clamped by the caller).
    """
    demand = demand.astype(jnp.float32)
    free = free.astype(jnp.float32)
    scores = eq3_scores(demand, gp, node_cap, max_sz, max_gp, s)
    # per-(job, node) fit tiles, now and promised
    fits_b = jnp.all(free[None, :, :] >= demand[:, None, :] - FIT_EPS,
                     axis=2)                                   # (J, M)
    fit_now = jnp.sum(fits_b, axis=1).astype(jnp.int32)
    fit_pend = jnp.sum(jnp.all(
        (free + pending_free)[None, :, :] >= demand[:, None, :] - FIT_EPS,
        axis=2), axis=1).astype(jnp.int32)
    # Eq. 2 eligibility against each candidate's BEST assigned node
    slack = jnp.min(free[None, :, :] + demand[:, None, :]
                    - te_demand[None, None, :], axis=2)        # (J, M)
    best = jnp.max(jnp.where(assign, slack, -_INF), axis=1)
    allowed = cand & under & (best >= -FIT_EPS)
    victim = jnp.where(allowed.any(),
                       jnp.argmin(jnp.where(allowed, scores, _INF)),
                       -1).astype(jnp.int32)
    # BE queue scan: head, first fit in key order, skips ahead of it
    key_q = jnp.where(be_q, queue_key, _INF)
    be_head = jnp.where(be_q.any(), jnp.argmin(key_q), -1).astype(jnp.int32)
    ok = fit_now >= width
    key_ok = jnp.where(be_q & ok, queue_key, _INF)
    has_pick = (be_q & ok).any()
    be_pick = jnp.where(has_pick, jnp.argmin(key_ok), -1).astype(jnp.int32)
    pick_key = jnp.where(has_pick, queue_key[be_pick], _INF)
    nskip = jnp.sum(be_q & ~ok & (queue_key < pick_key)).astype(jnp.int32)
    return SchedulePass(scores, fits_b.astype(jnp.int32), fit_now,
                        fit_pend, victim, be_head, be_pick, nskip)


def _fit_tile(node_ref, dem):
    """(M, bj) bool: node m covers job j's per-node demand on all three
    resources. ``node_ref`` is (3, M, 1), ``dem`` (3, bj)."""
    fits = node_ref[0] >= dem[0:1, :] - FIT_EPS
    for c in (1, 2):
        fits = fits & (node_ref[c] >= dem[c:c + 1, :] - FIT_EPS)
    return fits


def _fold_argmin(red, i: int, val, base):
    """Fold this block's first-minimum of ``val`` (1, bj) into the
    running (value, index) pair at ``red[i]``, ``red[i + 1]``; a strict
    ``<`` keeps the earliest block on ties, as ``jnp.argmin`` does."""
    lmin = jnp.min(val)
    lane = jax.lax.broadcasted_iota(jnp.int32, val.shape, 1)
    larg = jnp.min(jnp.where(val == lmin, lane.astype(jnp.float32), _INF))
    better = lmin < red[i]
    red[i + 1] = jnp.where(better, larg + base, red[i + 1])
    red[i] = jnp.where(better, lmin, red[i])


def _kernel(te_ref, dem_ref, score_ref, wid_ref, key_ref, asg_ref,
            free_ref, prom_ref, cand_ref, under_ref, beq_ref,
            fits_ref, fnow_ref, fpend_ref, out_ref, red, *,
            block_j: int, n_blocks: int):
    """Two grid phases over the job blocks, jobs on the lanes and nodes
    on the sublanes of every (M, bj) tile. Phase 0 writes every
    blockwise output and folds the three global argmins (victim, BE
    head, BE pick) into the SMEM scratch ``red``; phase 1 recomputes
    the fit counts to count the skips ahead of the (now known) pick
    key, and its last step writes the four scalars to SMEM."""
    ph = pl.program_id(0)
    ji = pl.program_id(1)

    dem = dem_ref[...]                        # (3, bj)
    fit_now = jnp.sum(_fit_tile(free_ref, dem).astype(jnp.float32),
                      axis=0, keepdims=True)  # (1, bj)
    key = key_ref[...]
    be_q = beq_ref[...] > 0
    ok = be_q & (fit_now >= wid_ref[...])

    @pl.when(ph == 0)
    def _reduce():
        @pl.when(ji == 0)
        def _init():
            for i in range(0, 6, 2):
                red[i] = _INF           # victim score / head key / pick key
                red[i + 1] = -1.0       # ... and its job index
            red[6] = 0.0                # nskip accumulator

        slack = (free_ref[0] + dem[0:1, :]) - te_ref[0, 0]
        for c in (1, 2):
            slack = jnp.minimum(
                slack, (free_ref[c] + dem[c:c + 1, :]) - te_ref[0, c])
        best = jnp.max(jnp.where(asg_ref[...] > 0, slack, -_INF),
                       axis=0, keepdims=True)             # (1, bj)
        allowed = ((cand_ref[...] > 0) & (under_ref[...] > 0)
                   & (best >= -FIT_EPS))

        fits_ref[...] = _fit_tile(free_ref, dem).astype(fits_ref.dtype)
        fnow_ref[...] = fit_now.astype(fnow_ref.dtype)
        fpend_ref[...] = jnp.sum(
            _fit_tile(prom_ref, dem).astype(jnp.float32), axis=0,
            keepdims=True).astype(fpend_ref.dtype)

        base = (ji * block_j).astype(jnp.float32)
        _fold_argmin(red, 0, jnp.where(allowed, score_ref[...], _INF), base)
        _fold_argmin(red, 2, jnp.where(be_q, key, _INF), base)
        _fold_argmin(red, 4, jnp.where(ok, key, _INF), base)

    @pl.when(ph == 1)
    def _finalize():
        pick_key = red[4]
        red[6] += jnp.sum((be_q & ~ok & (key < pick_key))
                          .astype(jnp.float32))

        @pl.when(ji == n_blocks - 1)
        def _emit():
            for k in range(3):
                out_ref[0, k] = jnp.where(red[2 * k] < _INF, red[2 * k + 1],
                                       -1.0).astype(jnp.int32)
            out_ref[0, 3] = red[6].astype(jnp.int32)


def schedule_step_pallas(demand, gp, width, queue_key, assign, free,
                         pending_free, cand, under, be_q, te_demand,
                         node_cap, max_sz, max_gp, s, *,
                         block_j: int = DEFAULT_BLOCK_J,
                         interpret: bool = False) -> SchedulePass:
    """Pallas TPU backend of the fused pass (same contract as
    :func:`schedule_step_jnp`; grid = (2 phases, J/block_j job blocks)).

    The kernel works on (nodes, jobs) tiles, so the (J, M) ``assign``
    goes in transposed and ``fits`` comes out transposed (the engine
    loop holds ``assign`` node-major and passes its transpose, so the
    two cancel in its compiled program); the Eq. 3
    scores are computed in XLA by the twin's own :func:`eq3_scores`.
    Scalars live in SMEM: the TE demand, the reduction state and the
    four scalar outputs."""
    J = demand.shape[0]
    M = free.shape[0]
    bj = min(block_j, J)
    assert J % bj == 0, (J, bj)
    n_blocks = J // bj
    f32 = jnp.float32
    demand = demand.astype(f32)
    free = free.astype(f32)
    scores = eq3_scores(demand, gp.astype(f32), node_cap.astype(f32),
                        max_sz, max_gp, s)

    def row(x):                         # (J,) -> (1, J), jobs on lanes
        return x.astype(f32)[None, :]

    def node_cols(x):                   # (M, 3) -> (3, M, 1)
        return x.T[:, :, None]

    def phase0(ph, ji):
        # blocks only phase 0 touches stay on the last block through
        # phase 1: nothing is fetched again, and no output buffer is
        # written back over what phase 0 stored
        return 0, ji + ph * (n_blocks - 1 - ji)

    job_vec = pl.BlockSpec((1, bj), lambda ph, ji: (0, ji))
    job_vec0 = pl.BlockSpec((1, bj), phase0)
    tile0 = pl.BlockSpec((M, bj), phase0)
    nodes = pl.BlockSpec((3, M, 1), lambda ph, ji: (0, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    fits, fit_now, fit_pend, out = pl.pallas_call(
        functools.partial(_kernel, block_j=bj, n_blocks=n_blocks),
        grid=(2, n_blocks),
        in_specs=[
            smem,                                          # te (1, 3)
            pl.BlockSpec((3, bj), lambda ph, ji: (0, ji)),  # demand^T
            job_vec0, job_vec, job_vec,                    # score wid key
            tile0,                                         # assign^T
            nodes, nodes,                                  # free, promised
            job_vec0, job_vec0, job_vec,                   # cand under be_q
        ],
        out_specs=[tile0, job_vec0, job_vec0, smem],
        out_shape=[
            jax.ShapeDtypeStruct((M, J), jnp.int32),
            jax.ShapeDtypeStruct((1, J), jnp.int32),
            jax.ShapeDtypeStruct((1, J), jnp.int32),
            jax.ShapeDtypeStruct((1, 4), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((8,), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(te_demand.astype(f32)[None, :], demand.T, row(scores), row(width),
      row(queue_key), assign.T.astype(f32), node_cols(free),
      node_cols(free + pending_free.astype(f32)), row(cand), row(under),
      row(be_q))
    return SchedulePass(scores, fits.T, fit_now[0], fit_pend[0],
                        out[0, 0], out[0, 1], out[0, 2], out[0, 3])

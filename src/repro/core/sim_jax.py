"""The FitGpp scheduler as a pure-JAX module.

Fixed-capacity struct-of-arrays state, ``lax.while_loop`` tick loop,
bounded inner while-loops for the schedule-until-blocked phases, and
vectorized Eq. 1-4 victim selection (masked argmin). ``jit``-able and
``vmap``-able over trials, which is what lets the sensitivity sweeps
(Figs. 4-7) distribute over the production mesh with ``shard_map``
(see core/sweep.py).

Parity: semantics mirror ``core/simulator.py`` tick-for-tick for the
deterministic policies (fifo / lrtp / srtp / the score policies'
main path); the random fallback and RAND use a jax PRNG and are
excluded from exact parity (property-tested statistically instead).

Gang (multi-node) jobs: placement state is an ``(n_jobs, n_nodes)``
boolean assignment mask (``State.assign``) instead of a scalar node
index, and every job carries its gang width (``Jobs.width``).
Placement is all-or-nothing first-fit — the first ``width`` nodes
whose free vector covers the PER-NODE demand — the vectorized mirror
of ``engine/placement.ClusterState.fits_job``. Victims vacate and
requeue all their nodes at once, Eq. 2 is evaluated against a
multi-node victim's BEST node (the ``engine/preemption.
best_victim_node`` reduction), and a blocked gang TE selects victims
with the ``engine/preemption.gang_select`` strategy: the min-score
single victim whose eviction alone frees enough nodes, falling back
to accumulation in policy order (and signalling nothing when even
preempting everyone would not suffice).

Victim selection is registry-dispatched (``core/policy_registry.py``,
DESIGN.md §6): ``make_tick`` builds its preemption trigger from the
registered policy's JAX declaration — ``jax_kind == "rank"`` policies
feed :func:`_until_fits_select`, ``"score"`` policies feed
:func:`_score_select` (Eq. 4 masked argmin + the paper's random
fallback), and score policies may route the pass through an
accelerated kernel via ``SimConfig.score_backend`` (``"pallas"`` is
the fused ``kernels/schedule_step`` pass: Eq. 3 scoring, best-node
Eq. 2 reduction, Eq. 4 argmin, gang-fit tiles and the BE backfill
scan in ONE kernel over the (jobs, nodes) tile; parity-tested vs
jnp). Gang TEs dispatch to :func:`_gang_select` on either contract.

The schedule pass itself is computed once per acting tick as a shared
:class:`_Pass` (``_make_queue_pass`` — the jnp twin of the fused
kernel's per-pass outputs) and threaded through the TE lane, the BE
lane and the post-pass trigger gate (``_make_gate``), so the
``while_loop`` body issues one fused tile computation instead of a
kernel-per-op chain; non-acting ticks are gated by the cheap cached
:func:`_make_would_act_cached` check and skip the pass entirely.

The BE queue is strict FIFO (head-of-line blocking) by default;
``SimConfig.backfill`` enables the same bounded first-fit backfill
scan as the reference ``SchedulerCore.schedule`` (skip up to
``backfill_depth`` blocked jobs per pass, FIFO order otherwise).

Time advancement (``SimConfig.time_mode``, DESIGN.md §7): the default
``"event"`` mode compresses runs of provably no-op ticks inside the
jitted ``while_loop`` — after a tick whose schedule pass could not act,
the body jumps ``dt`` quanta straight to the next event (the masked
minimum over the next valid arrival, ``t + remaining`` of running
jobs and ``t + grace_left`` of GRACE jobs), bulk-decrementing
``remaining``/``grace_left`` by the same ``dt``. The jump is gated by
:func:`_make_would_act_cached` — the vectorized mirror of the
reference engine's ``SchedulerCore.schedule_would_act``, gang fits
and the backfill scan included (on acting ticks the gate value is the
exit evaluation of the shared pass, not a recomputation) — so any
tick on which the policy would be (re-)invoked still executes and the
rng stream, every metric timestamp and the full State agree
bit-for-bit with ``"tick"`` mode at every event boundary. When the
queue is empty (``_Cache.n_queued == 0``) no finisher can trigger a
pass, so one iteration drain-jumps straight to the next arrival or
vacate and bulk-retires every job finishing in between — k
consecutive events per ``while_loop`` iteration. All of it is plain array math, so under
``vmap`` the jump ``dt`` is per-lane: ragged sentinel-padded batches
and heterogeneous per-trial horizons each fast-forward at their own
pace.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.cluster import SimConfig
from repro.core import policy_registry
from repro.core.engine.placement import FIT_EPS
from repro.core.types import JobSet
from repro.obs import ring as obs_ring
from repro.obs import schema as obs_schema

NOT_ARRIVED, QUEUED, RUNNING, GRACE, DONE = 0, 1, 2, 3, 4
_INF = jnp.inf
_EPS = FIT_EPS    # one epsilon for every fit check, engine-wide


class Jobs(NamedTuple):
    """Static workload arrays (device-resident).

    ``demand`` is PER NODE; ``width`` is the gang width (1 for the
    paper's single-task jobs) and the job needs ``width`` nodes
    simultaneously (all-or-nothing gang placement).

    ``valid`` marks real jobs; False rows are sentinel padding added by
    ``sweep.stack_jobsets`` so jobsets of unequal ``n`` can share one
    vmapped batch. Sentinels are born DONE (``init_state``) — they never
    arrive, queue, run or get preempted — and are masked out of every
    percentile/mean in ``sweep`` and ``result_summary``, so a padded
    trial is bit-identical to its unpadded run (DESIGN.md §5).
    Sentinels keep ``width == 1``.
    """
    submit: jax.Array        # (N,) i32
    exec_total: jax.Array    # (N,) i32
    demand: jax.Array        # (N, 3) f32, per node
    is_te: jax.Array         # (N,) bool
    gp: jax.Array            # (N,) i32
    width: jax.Array         # (N,) i32 gang width (>= 1)
    valid: jax.Array         # (N,) bool
    # (N,) f32 GLOBAL arrival-order key, or None (the default). None
    # means row index == arrival order (every monolithic jobset: rows
    # are submit-sorted) and the engine keys queues by ``arange(N)``.
    # The streaming engine (core/stream/) recycles slots, so slot
    # index no longer encodes arrival order; it stamps each packed
    # job's global sequence number here and every order-sensitive
    # site — arrival queue keys, vacate requeue ranks, victim-pick
    # tie-breaks — keys on ``akey`` instead, which is what makes a
    # slot-recycled run bit-identical to the monolithic one
    # (DESIGN.md §10). f32 is exact for sequence numbers < 2^24.
    akey: jax.Array = None


class State(NamedTuple):
    t: jax.Array
    state: jax.Array         # (N,) i32
    remaining: jax.Array     # (N,) i32
    assign: jax.Array        # (N, n_nodes) bool placement mask
    preempt_count: jax.Array
    grace_left: jax.Array
    queue_key: jax.Array     # (N,) f32, +inf when not queued
    top_key: jax.Array       # () f32
    finish: jax.Array
    te_pending: jax.Array
    victim_of: jax.Array
    free: jax.Array          # (nodes, 3) f32
    pending_free: jax.Array
    last_signal: jax.Array   # (N,) i32 metrics
    last_vacate: jax.Array
    last_resume: jax.Array
    awaiting_resume: jax.Array   # (N,) bool
    n_done: jax.Array
    rng: jax.Array
    # () i32: victim selections that fell back past the main masked
    # path (score policies' random fallback, rank/gang selections'
    # over-P-cap last resort). Observability for the invariant suite:
    # when 0, the paper's P cap is exact — sum(max(preempt_count - P,
    # 0)) never exceeds this counter.
    fallback_count: jax.Array
    # () i32: ``while_loop`` iterations of ``_run_loop`` so far (one per
    # executed step: a tick, or a tick plus its event jump), carried
    # across streamed rounds like ``fallback_count``. A performance
    # counter, not simulation state: tick and event mode reach the same
    # State in different numbers of iterations, so the parity checks
    # leave it out (``state_diff_fields``).
    n_iter: jax.Array
    # In-jit event ring buffer (obs/ring.py layout): (capacity + 1,
    # 4 + n_words) i32 rows [t, code, job, aux, node words...]; the
    # extra row is the dump slot for masked/overflowing writes,
    # re-zeroed after every append. ``ev_n`` counts rows EMITTED
    # (monotonic; overflow = max(0, ev_n - capacity)). With tracing
    # off both are zero-size/zero and every append site is compiled
    # out (the ``trace`` flag is Python-static).
    ev_buf: jax.Array        # (cap+1, 4+W) i32
    ev_n: jax.Array          # () i32


def jobs_from_jobset(js: JobSet) -> Jobs:
    return Jobs(
        submit=jnp.asarray(js.submit, jnp.int32),
        exec_total=jnp.asarray(js.exec_total, jnp.int32),
        demand=jnp.asarray(js.demand, jnp.float32),
        is_te=jnp.asarray(js.is_te, bool),
        gp=jnp.asarray(js.gp, jnp.int32),
        width=jnp.asarray(js.n_nodes, jnp.int32),
        valid=jnp.ones(len(js.submit), bool),
    )


def init_state(jobs: Jobs, n_nodes: int, node_cap, seed,
               trace_capacity: int = 0) -> State:
    N = jobs.submit.shape[0]
    cap = jnp.asarray(node_cap, jnp.float32)
    tcap = int(trace_capacity)
    ev_shape = ((tcap + 1, obs_ring.HEADER_WORDS
                 + obs_ring.n_node_words(n_nodes))
                if tcap > 0 else (0, 0))
    return State(
        t=jnp.zeros((), jnp.int32),
        # sentinel (padding) jobs are born DONE: never arrive, never run
        state=jnp.where(jobs.valid, NOT_ARRIVED, DONE).astype(jnp.int32),
        # forced copy: a no-op astype would ALIAS jobs.exec_total, so
        # any caller that donates (or mutates) State buffers would
        # corrupt the workload array under everyone else
        remaining=jnp.array(jobs.exec_total, jnp.int32),
        assign=jnp.zeros((N, n_nodes), bool),
        preempt_count=jnp.zeros((N,), jnp.int32),
        grace_left=jnp.zeros((N,), jnp.int32),
        queue_key=jnp.full((N,), _INF, jnp.float32),
        top_key=jnp.asarray(-1.0, jnp.float32),
        finish=jnp.full((N,), -1, jnp.int32),
        te_pending=jnp.zeros((N,), jnp.int32),
        victim_of=jnp.full((N,), -1, jnp.int32),
        free=jnp.tile(cap[None, :], (n_nodes, 1)),
        pending_free=jnp.zeros((n_nodes, 3), jnp.float32),
        last_signal=jnp.full((N,), -1, jnp.int32),
        last_vacate=jnp.full((N,), -1, jnp.int32),
        last_resume=jnp.full((N,), -1, jnp.int32),
        awaiting_resume=jnp.zeros((N,), bool),
        n_done=jnp.sum(~jobs.valid).astype(jnp.int32),
        rng=seed if (isinstance(seed, jax.Array)
                     and jnp.issubdtype(seed.dtype, jax.dtypes.prng_key))
        else jax.random.key(seed),
        fallback_count=jnp.zeros((), jnp.int32),
        n_iter=jnp.zeros((), jnp.int32),
        ev_buf=jnp.zeros(ev_shape, jnp.int32),
        ev_n=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# event cache — exact scalars derived from State, threaded as a loop
# carry so the hot path can gate whole phases on O(1) comparisons
# ---------------------------------------------------------------------------

_BIG = 1 << 30   # "no event pending" sentinel (i32-safe)


class _Cache(NamedTuple):
    """Exact next-event scalars, a pure function of ``(jobs, State)``
    (``_cache_from_state``) threaded alongside State through the tick
    loop so maintaining it costs nothing on no-op ticks:

      * ``next_arrival`` — absolute tick of the earliest NOT_ARRIVED
        submit (``_BIG`` when none); recomputed only when an arrival
        fires.
      * ``next_vacate`` — absolute tick of the earliest grace expiry
        (``_BIG`` when none — i.e. exactly when no job is in GRACE,
        since GRACE jobs leave only by vacating); recomputed after
        vacates and after every acting schedule pass.
      * ``n_q_te`` — queued-TE count; TEs enter the queue only at
        arrival (victims are always BE) and leave it only in the
        schedule pass, so those two sites keep it exact.
      * ``n_queued`` — total queued count (BE + TE); jobs queue at
        arrival and at vacate, and leave the queue only in the
        schedule pass. ``n_queued == 0`` means ``would_act`` is False
        no matter what finishes — the gate for the bulk finish drain
        in the event jump.

    Because every field is derivable from State, the cache is purely an
    optimization: ``make_tick`` rebuilds it per call and parity is
    untouched."""
    next_arrival: jax.Array   # () i32
    next_vacate: jax.Array    # () i32
    n_q_te: jax.Array         # () i32
    n_queued: jax.Array       # () i32


def _cache_from_state(jobs: Jobs, st: State,
                      ext_arrival=None) -> _Cache:
    """``ext_arrival`` (absolute tick or None) is the submit time of
    the earliest job NOT in this pool — the streaming engine's round
    boundary. Folding it into ``next_arrival`` at every recompute site
    is what keeps the event jump (the empty-queue drain branch
    especially) from overshooting the boundary: the jump lands ON the
    external arrival's tick exactly as the monolithic engine would."""
    in_grace = st.state == GRACE
    queued = st.state == QUEUED
    nxt = jnp.min(jnp.where(st.state == NOT_ARRIVED,
                            jobs.submit, _BIG)).astype(jnp.int32)
    if ext_arrival is not None:
        nxt = jnp.minimum(nxt, jnp.asarray(ext_arrival, jnp.int32))
    return _Cache(
        next_arrival=nxt,
        next_vacate=jnp.where(
            in_grace.any(),
            st.t + jnp.min(jnp.where(in_grace, st.grace_left, _BIG)),
            _BIG).astype(jnp.int32),
        n_q_te=jnp.sum(queued & jobs.is_te).astype(jnp.int32),
        n_queued=jnp.sum(queued).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def _gang_fit(free: jax.Array, d: jax.Array, w: jax.Array):
    """All-or-nothing first fit: (ok, node mask of the FIRST ``w``
    nodes whose free vector covers the per-node demand ``d``). The
    vectorized mirror of ``ClusterState.fits_job``; ``w == 1`` is
    plain first-fit. The mask is all-False when the gang does not fit."""
    fits = jnp.all(free >= d[None, :] - _EPS, axis=1)
    ok = jnp.sum(fits) >= w
    mask = fits & (jnp.cumsum(fits) <= w) & ok
    return ok, mask


def _gang_fits(free: jax.Array, demand: jax.Array,
               width: jax.Array) -> jax.Array:
    """Per-job gang feasibility: (N,) bool, True where at least
    ``width[j]`` nodes of ``free`` each cover ``demand[j]`` (the
    vectorized form of ``_gang_fit(...)[0]`` over every job at once)."""
    return _fit_counts(free, demand) >= width


def _fit_counts(free: jax.Array, demand: jax.Array) -> jax.Array:
    """Per-job count of nodes whose free vector covers the per-node
    demand: (N,) i32. ``_gang_fits`` is ``counts >= width``; the fused
    schedule_step kernel computes the same reduction in-tile."""
    fits = jnp.all(free[None, :, :] >= demand[:, None, :] - _EPS, axis=2)
    return jnp.sum(fits, axis=1).astype(jnp.int32)


def _best_victim_node(free: jax.Array, assign: jax.Array,
                      demand: jax.Array, te_d: jax.Array):
    """Eq. 2 glue (``engine/preemption.best_victim_node``): for every
    job, the min-slack of ``free + own demand - te_demand`` per node
    masked to the job's assigned nodes, and the argmax node — the node
    a multi-node victim is evaluated (and accounted) against. Rows
    with no assignment get ``-inf`` slack (never eligible)."""
    slack = jnp.min(free[None, :, :] + demand[:, None, :]
                    - te_d[None, None, :], axis=2)          # (N, nodes)
    slack = jnp.where(assign, slack, -_INF)
    return jnp.max(slack, axis=1), jnp.argmax(slack, axis=1)


def _onehot(N: int, j: jax.Array) -> jax.Array:
    return jnp.arange(N) == j


def _argmin_key(mask: jax.Array, val, akey) -> jax.Array:
    """Masked argmin with GLOBAL-ORDER tie-breaking: among tied
    minima, the smallest ``akey`` (arrival order) wins. With ``akey``
    None — every monolithic jobset, where row index IS arrival order —
    this is plain ``jnp.argmin`` (first minimum), byte-identical to
    the engine's historical behavior. The streaming engine's recycled
    pools set ``akey``, where first-slot ties would otherwise depend
    on which slot a job happened to land in."""
    if akey is None:
        return jnp.argmin(jnp.where(mask, val, _INF)).astype(jnp.int32)
    best = jnp.min(jnp.where(mask, val, _INF))
    tied = mask & (val == best)
    return jnp.argmin(jnp.where(tied, akey, _INF)).astype(jnp.int32)


def _argmax_key(mask: jax.Array, val, akey) -> jax.Array:
    """Masked argmax twin of :func:`_argmin_key` (ties -> min akey)."""
    if akey is None:
        return jnp.argmax(jnp.where(mask, val, -_INF)).astype(jnp.int32)
    best = jnp.max(jnp.where(mask, val, -_INF))
    tied = mask & (val == best)
    return jnp.argmin(jnp.where(tied, akey, _INF)).astype(jnp.int32)


def _gang_release(assign: jax.Array, demand: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """Summed per-node demand of the ``mask``-selected jobs over their
    assigned nodes: (nodes, 3). One matmul replaces the scalar-node
    scatter-add (exact for the integer/quantized demands)."""
    sel = (assign & mask[:, None]).astype(demand.dtype)
    return sel.T @ demand


# -- in-jit event tracing (obs/ring.py layout; DESIGN.md §8) ----------------

class _TraceCtx(NamedTuple):
    """Static per-build trace context: the node-mask packing weights
    (``obs.ring.node_mask_weights``) as a device constant. ``None``
    everywhere a trace context is accepted means tracing is off and
    the emission code is not built at all."""
    weights: jax.Array       # (n_words, n_nodes) uint32


def _trace_ctx(n_nodes: int) -> _TraceCtx:
    return _TraceCtx(
        weights=jnp.asarray(obs_ring.node_mask_weights(n_nodes)))


def _ev_rows(tc: _TraceCtx, t, code, job, aux=None,
             nodes=None) -> jax.Array:
    """Build (K, 4+W) i32 event rows from broadcastable parts. ``job``
    fixes K; ``nodes`` is an optional (K, n_nodes) bool placement mask
    packed 32 nodes per little-endian word."""
    job = jnp.asarray(job, jnp.int32)
    K = job.shape[0]
    t = jnp.broadcast_to(jnp.asarray(t).astype(jnp.int32), (K,))
    code = jnp.broadcast_to(jnp.asarray(code, jnp.int32), (K,))
    aux = (jnp.full((K,), -1, jnp.int32) if aux is None
           else jnp.broadcast_to(jnp.asarray(aux).astype(jnp.int32), (K,)))
    if nodes is None:
        words = jnp.zeros((K, tc.weights.shape[0]), jnp.int32)
    else:
        packed = jnp.sum(jnp.where(nodes[:, None, :],
                                   tc.weights[None, :, :],
                                   jnp.uint32(0)), axis=2)
        words = jax.lax.bitcast_convert_type(packed, jnp.int32)
    return jnp.concatenate(
        [jnp.stack([t, code, job, aux], axis=1), words], axis=1)


def _ev_append(st: State, rows: jax.Array, mask: jax.Array) -> State:
    """Append ``rows[i]`` where ``mask[i]``, preserving row order.
    Masked-out and past-capacity rows scatter into the dump row (index
    ``capacity``), which is re-zeroed afterwards so the buffer stays a
    pure function of the emitted stream (bitwise tick/event parity
    covers the trace). ``ev_n`` counts every emitted row, dropped or
    not — the overflow signal."""
    dump = st.ev_buf.shape[0] - 1
    m = mask.astype(jnp.int32)
    idx = jnp.where(mask, st.ev_n + jnp.cumsum(m) - 1, dump)
    buf = st.ev_buf.at[jnp.minimum(idx, dump)].set(rows)
    buf = buf.at[dump].set(jnp.zeros((buf.shape[1],), jnp.int32))
    return st._replace(ev_buf=buf, ev_n=st.ev_n + jnp.sum(m))


def _ev1(st: State, tc: _TraceCtx, t, code, job, aux=None, nodes=None,
         cond=None) -> State:
    """Append one event row (optionally gated by the traced ``cond``).
    The unconditional case — every row the emission loops produce —
    skips ``_ev_append``'s masked-compaction machinery: one clamped
    scatter, with the row zeroed at capacity so the dump row needs no
    re-zeroing pass (same pure-function-of-the-stream buffer)."""
    row = _ev_rows(tc, t, code, jnp.reshape(job, (1,)), aux=aux,
                   nodes=None if nodes is None
                   else jnp.reshape(nodes, (1, -1)))
    if cond is not None:
        return _ev_append(st, row, jnp.reshape(cond, (1,)))
    dump = st.ev_buf.shape[0] - 1
    keep = st.ev_n < dump
    buf = st.ev_buf.at[jnp.minimum(st.ev_n, dump)].set(
        jnp.where(keep, row[0], 0))
    return st._replace(ev_buf=buf, ev_n=st.ev_n + 1)


def _ev_scan(st: State, tc: _TraceCtx, t, code, mask) -> State:
    """Append one ``code`` row per set ``mask`` bit, ascending job
    index. A bounded loop of single-row appends: a firing tick pays
    O(k) emitted rows, not an N-row scatter — the batch scatters
    otherwise dominate traced-run cost on arrival-heavy workloads
    (their cost is O(N) per firing tick, O(N^2) over a run whose
    firing ticks scale with N)."""
    def body(carry):
        st, m = carry
        j = jnp.argmax(m).astype(jnp.int32)
        return _ev1(st, tc, t, code, j), m.at[j].set(False)

    st, _ = jax.lax.while_loop(lambda c: c[1].any(), body, (st, mask))
    return st


def _place(st: State, jobs: Jobs, j: jax.Array, nodes: jax.Array,
           tc: _TraceCtx = None) -> State:
    """Start job j on the ``nodes`` mask (assumes the gang fits).
    Scatter (row-indexed) updates, not full-array wheres — this runs
    once per placement inside the schedule while-loops, so it must not
    pay O(N) per job started."""
    resumed = st.awaiting_resume[j]
    if tc is not None:
        st = _ev1(st, tc, st.t,
                  jnp.where(resumed, obs_schema.RESUME, obs_schema.START),
                  j, nodes=nodes)
    return st._replace(
        state=st.state.at[j].set(RUNNING),
        assign=st.assign.at[j].set(nodes),
        queue_key=st.queue_key.at[j].set(_INF),
        free=st.free - jobs.demand[j][None, :]
        * nodes[:, None].astype(jnp.float32),
        last_resume=st.last_resume.at[j].set(
            jnp.where(resumed, st.t, st.last_resume[j])),
        awaiting_resume=st.awaiting_resume.at[j].set(False),
    )


def _signal_one(st: State, jobs: Jobs, v: jax.Array, te: jax.Array,
                tc: _TraceCtx = None) -> State:
    """Signal preemption of running BE job v for TE job te (scalars).
    Gang victims promise / vacate ALL their nodes at once.

    GP == 0 vacates inline (same tick); GP > 0 enters grace and the
    victim's resources become "pending". Both branches are expressed as
    per-victim scatters selected by the scalar ``gp0`` — one row write
    per field instead of the old two-full-State ``tree.map`` select, so
    a signal costs O(nodes), not O(N)."""
    row = st.assign[v]
    gp0 = jobs.gp[v] == 0
    if tc is not None:
        # SIGNAL always; a GP=0 victim vacates and requeues inline
        # (no GRACE_EXPIRE — it never entered grace)
        v3 = jnp.stack([v, v, v])
        codes = jnp.asarray([obs_schema.PREEMPT_SIGNAL, obs_schema.VACATE,
                             obs_schema.REQUEUE], jnp.int32)
        aux3 = jnp.stack([te, te, jnp.int32(-1)])
        st = _ev_append(
            st, _ev_rows(tc, st.t, codes, v3, aux=aux3),
            jnp.stack([jnp.asarray(True), gp0, gp0]))
    d = jobs.demand[v][None, :] * row[:, None].astype(jnp.float32)
    zero = jnp.zeros_like(d)
    return st._replace(
        preempt_count=st.preempt_count.at[v].add(1),
        last_signal=st.last_signal.at[v].set(st.t),
        awaiting_resume=st.awaiting_resume.at[v].set(True),
        state=st.state.at[v].set(jnp.where(gp0, QUEUED, GRACE)),
        assign=st.assign.at[v].set(row & ~gp0),
        queue_key=st.queue_key.at[v].set(
            jnp.where(gp0, st.top_key, st.queue_key[v])),
        top_key=jnp.where(gp0, st.top_key - 1.0, st.top_key),
        free=st.free + jnp.where(gp0, d, zero),
        pending_free=st.pending_free + jnp.where(gp0, zero, d),
        last_vacate=st.last_vacate.at[v].set(
            jnp.where(gp0, st.t, st.last_vacate[v])),
        grace_left=st.grace_left.at[v].set(
            jnp.where(gp0, st.grace_left[v], jobs.gp[v])),
        victim_of=st.victim_of.at[v].set(
            jnp.where(gp0, st.victim_of[v], te)),
        te_pending=st.te_pending.at[te].add(
            jnp.where(gp0, 0, 1)),
    )


# ---------------------------------------------------------------------------
# victim selection (registry-dispatched; policies declare jax_rank/jax_score)
# ---------------------------------------------------------------------------

def _schedule_scope():
    """``jax.named_scope`` of the schedule pass: the queue pass, the
    score selection and the fused Pallas kernel inside it. It changes
    only the ops' ``op_name`` metadata, so a profiler can group the
    pass's device ops; the compiled program is the same."""
    return jax.named_scope("schedule_pass")


def _in_schedule_scope(fn):
    """Trace ``fn`` inside :func:`_schedule_scope`."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with _schedule_scope():
            return fn(*args, **kwargs)
    return scoped


@_in_schedule_scope
def _score_select(st: State, jobs: Jobs, te: jax.Array, pol, node_cap, s,
                  P, backend: str):
    """Generic score-policy selection -> (state with advanced rng, victim).

    The policy's ``jax_score`` gives per-job scores (lower = better
    victim); this applies Eq. 2 eligibility — evaluated against each
    victim's BEST node (``_best_victim_node``), so gang victims are
    judged where they have the most slack — the P cap and the Eq. 4
    masked argmin, with the paper's random-candidate fallback when no
    job passes the masks. ``backend != "jnp"`` fuses score, best-node
    reduction and masked argmin on the policy's registered accelerated
    kernel (``jax_score_accel``; returns -1 when nothing passes).
    """
    cand = (st.state == RUNNING) & ~jobs.is_te
    under = st.preempt_count < P
    if backend != "jnp":
        be_q = (st.state == QUEUED) & ~jobs.is_te
        main = pol.jax_score_accel(backend, jobs, te, st.free, st.assign,
                                   cand, under, node_cap, s,
                                   pending_free=st.pending_free,
                                   queue_key=st.queue_key, be_q=be_q)
        mask_any = main >= 0
    else:
        score = pol.jax_score(jobs, cand, node_cap, s)
        best_slack, _ = _best_victim_node(st.free, st.assign, jobs.demand,
                                          jobs.demand[te])
        elig = best_slack >= -_EPS
        mask = cand & elig & under
        main = _argmin_key(mask, score, jobs.akey)
        mask_any = mask.any()

    rng, sub = jax.random.split(st.rng)
    p = cand.astype(jnp.float32)
    p = p / jnp.maximum(p.sum(), 1.0)
    rnd = jax.random.choice(sub, jobs.submit.shape[0], p=p).astype(jnp.int32)
    st = st._replace(
        rng=rng,
        fallback_count=st.fallback_count + (~mask_any).astype(jnp.int32))
    return st, jnp.where(mask_any, main, rnd)


def _resolve_score_backend(cfg: SimConfig, spec) -> str:
    """Effective score backend: ``cfg.score_backend``, for a static or
    a traced ``s`` alike (the kernel takes ``s`` as data). The option
    is name-level only: a policy that registers no such backend runs
    its jnp path."""
    if os.environ.get("REPRO_SIM_KERNEL") is not None:
        raise RuntimeError(
            "the REPRO_SIM_KERNEL env override was removed; select the "
            "accelerated score path with SimConfig(score_backend='pallas') "
            "(or --score-backend on the scenarios CLI) instead")
    backend = cfg.score_backend
    return backend if backend in spec.score_backends else "jnp"


def _until_fits_select(st: State, jobs: Jobs, te: jax.Array, rank_val,
                       P, tc: _TraceCtx = None) -> State:
    """LRTP/RAND: keep signalling victims (best ``rank_val`` first,
    under-P-cap first) until the TE fits on the last victim's BEST
    node, counting the demand signalled there so far. Mirrors
    ``policies._preempt_until_fits`` over the invocation snapshot:
    victims are accounted at the node ``engine/preemption.
    best_victim_node`` would pick (their only node when single-node),
    chosen once from the free vectors at trigger time."""
    N = jobs.submit.shape[0]
    te_d = jobs.demand[te]
    n_nodes = st.free.shape[0]
    free0 = st.free                                # invocation snapshot
    _, best_node = _best_victim_node(free0, st.assign, jobs.demand, te_d)

    def cond(carry):
        st, taken, pending, satisfied = carry
        cand = (st.state == RUNNING) & ~jobs.is_te & ~taken
        return (~satisfied) & cand.any()

    def body(carry):
        st, taken, pending, _ = carry
        cand = (st.state == RUNNING) & ~jobs.is_te & ~taken
        under = st.preempt_count < P
        # under-cap candidates first, then by rank_val descending
        # (two-level pick, NOT an additive offset — a +1e12 offset in f32
        # would swallow rank_val and break the ordering)
        m1 = cand & under
        pick_from = jnp.where(m1.any(), m1, cand)
        v = _argmax_key(pick_from, rank_val, jobs.akey)
        node = best_node[v]
        st = st._replace(
            fallback_count=st.fallback_count + (~m1.any()).astype(jnp.int32))
        st = _signal_one(st, jobs, v, te, tc)
        # Accumulate each selection's demand at its best node and test
        # the TE there against the snapshot — mirrors
        # policies._preempt_until_fits (pending starts at free, adds
        # every victim regardless of GP; GP=0 inline vacates are part
        # of that same accounting).
        pending = pending.at[node].add(jobs.demand[v])
        satisfied = jnp.all(te_d <= free0[node] + pending[node] + _EPS)
        return st, taken | _onehot(N, v), pending, satisfied

    st, _, _, _ = jax.lax.while_loop(
        cond, body, (st, jnp.zeros((N,), bool),
                     jnp.zeros((n_nodes, 3), jnp.float32),
                     jnp.asarray(False)))
    return st


def _gang_select(st: State, jobs: Jobs, te: jax.Array, rank_val, P,
                 score=None, tc: _TraceCtx = None) -> State:
    """Multi-node TE: the vectorized mirror of
    ``engine/preemption.gang_select``. With ``score`` (Eq. 4-style
    argmin policies; LOWER = better victim, computed over TOTAL gang
    demand), prefer the min-score SINGLE victim whose eviction alone
    yields >= width satisfying nodes — restricted to under-P-cap
    candidates when any exist; otherwise accumulate victims in policy
    order (``rank_val`` HIGHER = preempt first, under-cap first) until
    the gang fits, and signal NOTHING when even preempting every
    candidate would not suffice (signalling then would burn preemption
    budget for no gain). Over-P-cap signals count into
    ``fallback_count`` (the P-cap invariant's allowance)."""
    N = jobs.submit.shape[0]
    te_d = jobs.demand[te]
    w = jobs.width[te]
    free0 = st.free
    cand0 = (st.state == RUNNING) & ~jobs.is_te
    under0 = st.preempt_count < P

    def n_fit(fr):
        return jnp.sum(jnp.all(fr >= te_d[None, :] - _EPS, axis=1))

    if score is not None:
        # single-eviction sufficiency: free + the victim's demand on
        # each of its nodes must yield >= width fitting nodes
        trial = free0[None, :, :] + jobs.demand[:, None, :] \
            * st.assign[:, :, None].astype(jnp.float32)
        nfit1 = jnp.sum(jnp.all(trial >= te_d[None, None, :] - _EPS,
                                axis=2), axis=1)
        pool = cand0 & jnp.where((cand0 & under0).any(), under0, True)
        single = pool & (nfit1 >= w)
        v1 = _argmin_key(single, score, jobs.akey)
        have_single = single.any()
    else:
        v1 = jnp.int32(0)
        have_single = jnp.asarray(False)

    # accumulation (pure — no signals until the whole set is known to
    # suffice): walk candidates in policy order, recording selection
    # sequence numbers, until >= width nodes fit the TE
    def acc_cond(carry):
        taken, pending, satisfied, nsel, seq = carry
        return (~satisfied) & (cand0 & ~taken).any()

    def acc_body(carry):
        taken, pending, satisfied, nsel, seq = carry
        c = cand0 & ~taken
        m1 = c & under0
        pick = jnp.where(m1.any(), m1, c)
        v = _argmax_key(pick, rank_val, jobs.akey)
        pending = pending + jobs.demand[v][None, :] \
            * st.assign[v][:, None].astype(jnp.float32)
        return (taken | _onehot(N, v), pending, n_fit(pending) >= w,
                nsel + 1, seq.at[v].set(nsel))

    taken, pending, satisfied, nsel, seq = jax.lax.while_loop(
        acc_cond, acc_body,
        (jnp.zeros((N,), bool), free0, n_fit(free0) >= w,
         jnp.int32(0), jnp.full((N,), -1, jnp.int32)))

    def signal_single(st):
        st = st._replace(fallback_count=st.fallback_count
                         + (~under0[v1]).astype(jnp.int32))
        return _signal_one(st, jobs, v1, te, tc)

    def signal_accum(st):
        n_sig = jnp.where(satisfied, nsel, 0)   # insufficient -> nothing

        def sig_cond(carry):
            return carry[1] < n_sig

        def sig_body(carry):
            st, k = carry
            v = jnp.argmax(seq == k).astype(jnp.int32)
            st = st._replace(fallback_count=st.fallback_count
                             + (~under0[v]).astype(jnp.int32))
            return _signal_one(st, jobs, v, te, tc), k + 1

        st, _ = jax.lax.while_loop(sig_cond, sig_body, (st, jnp.int32(0)))
        return st

    return jax.lax.cond(have_single, signal_single, signal_accum, st)


# ---------------------------------------------------------------------------
# event-compressed time advancement (SimConfig.time_mode, DESIGN.md §7)
# ---------------------------------------------------------------------------

class _Pass(NamedTuple):
    """One fused schedule-pass evaluation over the current State — the
    engine-side (TE-independent) half of the ``kernels/schedule_step``
    contract, computed ONCE per state version and shared by the
    would-act gate, the TE lane and the BE lane inside a single
    while-loop iteration (the TE-dependent half — Eq. 3 score, Eq. 2
    best-node reduction, Eq. 4 argmin — is per-trigger and lives in
    ``_score_select`` / the fused kernel)."""
    fits: jax.Array      # (N, M) bool : free covers demand, per node
    fit_now: jax.Array   # (N,)  i32  : row sums of ``fits``
    fit_pend: jax.Array  # (N,)  i32  : counts vs free + pending_free
    be_pick: jax.Array   # ()    i32  : BE job the lane would try next
    be_can: jax.Array    # ()    bool : the pick exists and fits
    nskip: jax.Array     # ()    i32  : non-fitting queued BE ahead of
    #                                   the pick (backfill scan budget)


def _make_queue_pass(jobs: Jobs, backfill: bool):
    """Build ``queue_pass(st, be_mask) -> _Pass``: the per-job fit
    tile against ``free`` (and, bitwise-gated on any pending residue,
    against ``free + pending_free`` — residue-exact mirror of the full
    promised-capacity evaluation), plus the BE queue scan over
    ``be_mask``. Without backfill the pick is the queue head
    (head-of-line blocking: ``be_can`` is False when the head does not
    fit); with backfill it is the first FITTING job in key order and
    ``nskip`` counts the non-fitting jobs ahead of it (the bounded
    scan depth the reference consumes before placing it)."""
    @_in_schedule_scope
    def queue_pass(st: State, be_mask: jax.Array) -> _Pass:
        fits_b = jnp.all(st.free[None, :, :]
                         >= jobs.demand[:, None, :] - _EPS, axis=2)
        fit_now = jnp.sum(fits_b, axis=1).astype(jnp.int32)
        fit_pend = jax.lax.cond(
            (st.pending_free != 0).any(),
            lambda: _fit_counts(st.free + st.pending_free, jobs.demand),
            lambda: fit_now)
        okj = fit_now >= jobs.width
        if not backfill:
            pick = jnp.argmin(jnp.where(be_mask, st.queue_key, _INF)) \
                .astype(jnp.int32)
            be_can = be_mask.any() & okj[pick]
            nskip = jnp.int32(0)
        else:
            mq = be_mask & okj
            be_can = mq.any()
            pick = jnp.argmin(jnp.where(mq, st.queue_key, _INF)) \
                .astype(jnp.int32)
            pick_key = jnp.where(be_can, st.queue_key[pick], _INF)
            nskip = jnp.sum(be_mask & ~okj
                            & (st.queue_key < pick_key)).astype(jnp.int32)
        return _Pass(fits_b, fit_now, fit_pend, pick, be_can, nskip)

    return queue_pass


def _make_gate(jobs: Jobs, preemptive: bool, backfill: bool = False,
               backfill_depth: int = 64):
    """Gate glue over a precomputed :class:`_Pass` — the same verdict
    as :func:`_make_would_act_cached`, for call sites that already
    hold a fresh pass (the schedule lanes' exit evaluation)."""
    N = jobs.submit.shape[0]
    depth = min(int(backfill_depth), N)

    def gate(st: State, ps: _Pass) -> jax.Array:
        act = ps.be_can if not backfill else ps.be_can & (ps.nskip < depth)
        if preemptive:
            te_q = (st.state == QUEUED) & jobs.is_te
            has_cand = ((st.state == RUNNING) & ~jobs.is_te).any()
            trigger = (st.te_pending == 0) & ~(ps.fit_pend >= jobs.width) \
                & has_cand
            act = act | (te_q & ((ps.fit_now >= jobs.width)
                                 | trigger)).any()
        return act

    return gate


def _make_would_act_cached(jobs: Jobs, preemptive: bool,
                           backfill: bool = False,
                           backfill_depth: int = 64):
    """Vectorized mirror of ``SchedulerCore.schedule_would_act``,
    taking the threaded ``_Cache`` so the common no-op evaluation is
    cheap —

      * the BE head check gathers ONE demand row and fits it against
        the free vectors (O(nodes)), instead of the full (jobs, nodes)
        feasibility tile;
      * the whole TE part (fit counts, trigger arming) sits behind an
        O(1) ``n_q_te > 0`` gate, and the pending-capacity recount
        behind a ``pending_free != 0`` gate (bitwise — residue-exact
        mirror of the full ``free + pending_free`` evaluation).

    True whenever a schedule pass on this State could start a job or
    (re-)invoke victim selection: a queued TE's gang fits, a queued
    TE's preemption trigger is armed (``te_pending == 0``, does not fit
    even counting ``pending_free``, running BE candidates exist), the
    BE head fits — or, under backfill, any of the first
    ``backfill_depth`` queued BE jobs (queue order) fits. Deliberately
    conservative in the same way as the reference: a fruitless policy
    invocation still counts, because RAND and the score policies'
    random fallback consume rng on every invocation — this is what
    keeps the event jump bit-exact for the stochastic paths too
    (DESIGN.md §4/§7).
    """
    N = jobs.submit.shape[0]
    depth = min(int(backfill_depth), N)

    def would_act(st: State, cache: _Cache) -> jax.Array:
        queued = st.state == QUEUED
        be_q = queued & ~jobs.is_te if preemptive else queued
        if not backfill:
            head = jnp.argmin(jnp.where(be_q, st.queue_key, _INF))
            ok_head = jnp.sum(jnp.all(
                st.free >= jobs.demand[head][None, :] - _EPS,
                axis=1)) >= jobs.width[head]
            act = be_q.any() & ok_head
        else:
            # the reference scan examines the first `depth` jobs in
            # queue order and acts iff any of them fits
            fits_all = _gang_fits(st.free, jobs.demand, jobs.width)
            order = jnp.argsort(jnp.where(be_q, st.queue_key, _INF))
            scan = order[:depth]
            act = (be_q[scan] & fits_all[scan]).any()
        if preemptive:
            def te_part():
                te_q = queued & jobs.is_te
                fits_now = _fit_counts(st.free, jobs.demand) >= jobs.width
                fits_pend = jax.lax.cond(
                    (st.pending_free != 0).any(),
                    lambda: _fit_counts(st.free + st.pending_free,
                                        jobs.demand) >= jobs.width,
                    lambda: fits_now)
                has_cand = ((st.state == RUNNING) & ~jobs.is_te).any()
                trigger = (st.te_pending == 0) & ~fits_pend & has_cand
                return (te_q & (fits_now | trigger)).any()

            act = act | jax.lax.cond(cache.n_q_te > 0, te_part,
                                     lambda: jnp.asarray(False))
        return act

    return would_act


def _make_step(cfg: SimConfig, jobs: Jobs, n_nodes: int,
               s=None, P=None, time_mode: str = None,
               max_ticks: int = 1 << 22, trace: bool = False,
               ext_arrival=None):
    """Build the ``(State, _Cache) -> (State, _Cache)`` while-loop
    body: one scheduling tick, plus — in ``"event"`` time mode — the
    event jump that compresses the following run of provably no-op
    ticks into a single ``dt`` step (bit-exact either way; see module
    docstring and DESIGN.md §7).

    Every phase is gated so a no-op tick touches as few arrays as
    possible: arrivals and vacates fire only when the cache says their
    event is due, the whole schedule pass sits behind one
    ``would_act`` evaluation (rng-safe — all rng draws live behind the
    preemption trigger, which ``would_act`` mirrors exactly), and the
    post-run jump re-evaluates ``would_act`` only when the tick acted
    or finished jobs (otherwise the pre-run value provably still
    holds: the run phase without finishers only decrements clocks).

    ``time_mode`` defaults to ``cfg.time_mode``; ``s`` and ``P`` may
    be traced scalars (for vmapped sweeps); ``max_ticks`` bounds the
    stall jump and must match the driving loop's bound. ``trace``
    (Python-static) builds the in-jit event emission — off, none of it
    exists in the compiled program (zero cost); on, the State must
    carry a real ring buffer (``init_state(trace_capacity=...)``).

    ``ext_arrival`` (None, or an absolute tick, possibly traced) is
    the streaming engine's round boundary: the submit time of the
    earliest job NOT materialized in this pool. It is folded into
    ``cache.next_arrival`` wherever that scalar is recomputed, so no
    event jump can skip past it (see :func:`_cache_from_state`)."""
    node_cap = jnp.asarray(cfg.cluster.node.as_tuple(), jnp.float32)
    N = jobs.submit.shape[0]
    time_mode = cfg.time_mode if time_mode is None else time_mode
    if time_mode not in ("tick", "event"):
        raise ValueError(f"unknown time_mode {time_mode!r}; "
                         "one of ('tick', 'event')")
    spec = policy_registry.get_policy(cfg.policy)
    preemptive = spec.preemptive
    P = cfg.max_preemptions if P is None else P
    s = cfg.s if s is None else s
    pol = spec.make()                  # decision rule (jax declarations)
    backend = _resolve_score_backend(cfg, spec)
    tc = _trace_ctx(n_nodes) if trace else None
    if preemptive and spec.jax_kind is None:
        raise NotImplementedError(
            f"policy {cfg.policy!r} registers no JAX implementation "
            "(jax_kind); run it on the reference engine")

    def trigger_preemption(st: State, te: jax.Array) -> State:
        if spec.jax_kind == "score":
            def width1(s_):
                s_, v = _score_select(s_, jobs, te, pol, node_cap, s, P,
                                      backend)
                return _signal_one(s_, jobs, v, te, tc)

            def gang(s_):
                # gang ordering keys on the score of the TOTAL gang
                # demand (mirror of gang_select's rank_key call on
                # cand_demand * cand_width); no rng — the gang path
                # has no random fallback, matching the reference
                cand = (s_.state == RUNNING) & ~jobs.is_te
                total = jobs._replace(
                    demand=jobs.demand * jobs.width[:, None]
                    .astype(jnp.float32))
                gscore = pol.jax_score(total, cand, node_cap, s)
                return _gang_select(s_, jobs, te, -gscore, P, score=gscore,
                                    tc=tc)

            return jax.lax.cond(jobs.width[te] == 1, width1, gang, st)

        def width1(s_):
            s_, rank = pol.jax_rank(s_, jobs)      # may consume s_.rng
            return _until_fits_select(s_, jobs, te, rank, P, tc)

        def gang(s_):
            s_, rank = pol.jax_rank(s_, jobs)      # may consume s_.rng
            return _gang_select(s_, jobs, te, rank, P, tc=tc)

        return jax.lax.cond(jobs.width[te] == 1, width1, gang, st)

    queue_pass = _make_queue_pass(jobs, cfg.backfill)
    gate = _make_gate(jobs, preemptive, cfg.backfill, cfg.backfill_depth)
    would_act = _make_would_act_cached(jobs, preemptive, cfg.backfill,
                                       cfg.backfill_depth)

    def head_mask(st):
        q = st.state == QUEUED
        if preemptive:
            q = q & ~jobs.is_te
        return q

    def te_actionable(st: State, ps: _Pass, processed):
        """(queued-TE mask, actionable subset) from the shared pass:
        gang fits now, or the preemption trigger is armed."""
        q = (st.state == QUEUED) & jobs.is_te & ~processed
        has_cand = ((st.state == RUNNING) & ~jobs.is_te).any()
        trigger = (st.te_pending == 0) & ~(ps.fit_pend >= jobs.width) \
            & has_cand
        return q, q & ((ps.fit_now >= jobs.width) | trigger)

    def te_lane(st: State, ps: _Pass):
        """Process queued TEs in queue-key order — but only the
        ACTIONABLE ones (gang fits now, or the preemption trigger is
        armed). A queued TE that is neither is a provable no-op under
        the serial reference walk (no placement, no signal, no rng),
        so every non-actionable TE ahead of the next actionable one is
        skipped wholesale: iterations scale with TEs that actually
        act, not with queue depth. Every action refreshes the shared
        pass, which doubles as the loop's exit evaluation."""
        def cond(carry):
            return carry[3].any()

        def body(carry):
            st, ps, processed, can = carry
            j = jnp.argmin(jnp.where(can, st.queue_key, _INF)) \
                .astype(jnp.int32)
            # everything queued ahead of j is non-actionable: mark it
            # processed together with j itself
            q = (st.state == QUEUED) & jobs.is_te & ~processed
            processed = processed | (q & (st.queue_key <= st.queue_key[j]))
            ok = ps.fit_now[j] >= jobs.width[j]
            row = ps.fits[j]
            nodes = row & (jnp.cumsum(row) <= jobs.width[j]) & ok

            def place(st):
                return _place(st, jobs, j, nodes, tc)

            def blocked(st):
                fits_pending = ps.fit_pend[j] >= jobs.width[j]
                has_cand = ((st.state == RUNNING) & ~jobs.is_te).any()
                do = (st.te_pending[j] == 0) & ~fits_pending & has_cand
                st = jax.lax.cond(do,
                                  lambda s_: trigger_preemption(s_, j),
                                  lambda s_: s_, st)
                # GP=0 victims vacate inline: place the TE NOW, before
                # the BE pass can reclaim the freed nodes (mirrors the
                # reference).
                ok2, nodes2 = _gang_fit(st.free, jobs.demand[j],
                                        jobs.width[j])
                return jax.lax.cond(do & ok2,
                                    lambda s_: _place(s_, jobs, j, nodes2,
                                                      tc),
                                    lambda s_: s_, st)

            st = jax.lax.cond(ok, place, blocked, st)
            ps = queue_pass(st, head_mask(st))
            _, can = te_actionable(st, ps, processed)
            return st, ps, processed, can

        processed0 = jnp.zeros((N,), bool)
        _, can0 = te_actionable(st, ps, processed0)
        st, ps, _, _ = jax.lax.while_loop(
            cond, body, (st, ps, processed0, can0))
        return st, ps

    def be_queue(st: State, ps: _Pass):
        """FIFO head-of-line BE lane: place the head while it fits
        (the pass already holds the head's identity, fit verdict and
        node-fit row — the body is one placement scatter plus the
        pass refresh)."""
        def body(carry):
            st, ps = carry
            j = ps.be_pick
            row = ps.fits[j]
            nodes = row & (jnp.cumsum(row) <= jobs.width[j])
            st = _place(st, jobs, j, nodes, tc)
            ps = queue_pass(st, head_mask(st))
            return st, ps

        return jax.lax.while_loop(lambda c: c[1].be_can, body, (st, ps))

    def be_queue_backfill(st: State, ps: _Pass):
        """Bounded first-fit backfill (``SchedulerCore.schedule``'s
        beyond-paper branch): walk the BE queue in FIFO order, start
        whatever fits, skip (at most ``backfill_depth``) whatever does
        not — skipped jobs keep their keys and are not revisited this
        pass. The pass's ``be_pick``/``nskip`` fold the reference's
        one-job-per-iteration scan into one placement per iteration:
        the pick is placeable iff the skips ahead of it still fit the
        depth budget, and those skips are marked in bulk."""
        depth = jnp.int32(cfg.backfill_depth)

        def cond(carry):
            st, ps, skipped, scanned = carry
            return ps.be_can & (scanned + ps.nskip < depth)

        def body(carry):
            st, ps, skipped, scanned = carry
            j = ps.be_pick
            q = head_mask(st) & ~skipped
            skipped = skipped | (q & (ps.fit_now < jobs.width)
                                 & (st.queue_key < st.queue_key[j]))
            scanned = scanned + ps.nskip
            row = ps.fits[j]
            nodes = row & (jnp.cumsum(row) <= jobs.width[j])
            st = _place(st, jobs, j, nodes, tc)
            if tc is not None:
                # marker after a placement that skipped ahead; aux =
                # cumulative skips this pass (reference `scanned`)
                st = _ev1(st, tc, st.t, obs_schema.BACKFILL, j,
                          aux=scanned, cond=scanned > 0)
            ps = queue_pass(st, head_mask(st) & ~skipped)
            return st, ps, skipped, scanned

        st, ps, _, _ = jax.lax.while_loop(
            cond, body, (st, ps, jnp.zeros((N,), bool), jnp.int32(0)))
        # the lane's pass excludes skipped jobs; refresh over the full
        # queue so the caller's gate re-evaluation sees tick semantics
        return st, queue_pass(st, head_mask(st))

    arrival_keys = (jnp.arange(N, dtype=jnp.float32)
                    if jobs.akey is None else
                    jobs.akey.astype(jnp.float32))

    def arrivals(st: State, cache: _Cache):
        """Queue every submitted job (key = global arrival order:
        slot index for monolithic jobsets, ``Jobs.akey`` for recycled
        pools) — gated on the cached next-arrival tick, so ticks
        between arrivals skip the whole phase."""
        def fire(args):
            st, cache = args
            arrive = (jobs.submit <= st.t) & (st.state == NOT_ARRIVED)
            if tc is not None:
                st = _ev_scan(st, tc, st.t, obs_schema.SUBMIT, arrive)
            state = jnp.where(arrive, QUEUED, st.state)
            st = st._replace(
                state=state,
                queue_key=jnp.where(arrive, arrival_keys, st.queue_key))
            nxt = jnp.min(jnp.where(
                state == NOT_ARRIVED, jobs.submit,
                _BIG)).astype(jnp.int32)
            if ext_arrival is not None:
                nxt = jnp.minimum(nxt,
                                  jnp.asarray(ext_arrival, jnp.int32))
            cache = cache._replace(
                next_arrival=nxt,
                n_q_te=cache.n_q_te + jnp.sum(
                    arrive & jobs.is_te).astype(jnp.int32),
                n_queued=cache.n_queued
                + jnp.sum(arrive).astype(jnp.int32))
            return st, cache

        return jax.lax.cond(cache.next_arrival <= st.t, fire,
                            lambda args: args, (st, cache))

    def vacates(st: State, cache: _Cache):
        """Vacate grace-expired victims (processed in job-index order)
        — gated on the cached (exact) next grace expiry."""
        def fire(args):
            st, cache = args
            vac = (st.state == GRACE) & (st.grace_left <= 0)
            if tc is not None:
                # [GRACE_EXPIRE, VACATE(aux=te), REQUEUE] per job,
                # job-major in index order — aux read BEFORE victim_of
                # is cleared below. GRACE jobs always have GP > 0, so
                # the expiry row is unconditional here. One 3-row
                # append per vacating job (``_ev_scan`` rationale).
                codes = jnp.asarray([obs_schema.GRACE_EXPIRE,
                                     obs_schema.VACATE,
                                     obs_schema.REQUEUE], jnp.int32)

                def vbody(carry):
                    st, m = carry
                    j = jnp.argmax(m).astype(jnp.int32)
                    aux = jnp.stack([jnp.int32(-1),
                                     st.victim_of[j].astype(jnp.int32),
                                     jnp.int32(-1)])
                    rows = _ev_rows(tc, st.t, codes,
                                    jnp.full((3,), j, jnp.int32),
                                    aux=aux)
                    st = _ev_append(st, rows, jnp.ones((3,), bool))
                    return st, m.at[j].set(False)

                st, _ = jax.lax.while_loop(lambda c: c[1].any(), vbody,
                                           (st, vac))
            if jobs.akey is None:
                # rank among the vacating set in slot order (== global
                # arrival order for monolithic jobsets)
                rank = jnp.cumsum(vac) - 1
            else:
                # recycled pool: slot order is arbitrary — rank the
                # vacating set by global arrival order so the requeue
                # keys (top-of-lane, FIFO among same-tick vacates)
                # match the monolithic engine bit-for-bit
                ok = jnp.where(vac, jobs.akey, _INF)
                rank = jnp.sum(ok[None, :] < ok[:, None], axis=1)
            n_vac = jnp.sum(vac)
            te_dec = jnp.zeros((N,), jnp.int32).at[
                jnp.where(vac, st.victim_of, N)].add(1, mode="drop")
            freed = _gang_release(st.assign, jobs.demand, vac)
            st = st._replace(
                queue_key=jnp.where(
                    vac, st.top_key - rank.astype(jnp.float32),
                    st.queue_key),
                top_key=st.top_key - n_vac.astype(jnp.float32),
                free=st.free + freed,
                pending_free=st.pending_free - freed,
                last_vacate=jnp.where(vac, st.t, st.last_vacate),
                te_pending=st.te_pending - te_dec,
                victim_of=jnp.where(vac, -1, st.victim_of),
                assign=st.assign & ~vac[:, None],
                state=jnp.where(vac, QUEUED, st.state),
            )
            in_grace = st.state == GRACE
            cache = cache._replace(
                next_vacate=jnp.where(
                    in_grace.any(),
                    st.t + jnp.min(jnp.where(in_grace, st.grace_left,
                                             _BIG)),
                    _BIG).astype(jnp.int32),
                n_queued=cache.n_queued + n_vac.astype(jnp.int32))
            return st, cache

        return jax.lax.cond(cache.next_vacate <= st.t, fire,
                            lambda args: args, (st, cache))

    def schedule(args):
        """The full schedule pass + cache refresh — runs only on ticks
        where ``would_act`` fired. Computes the shared pass once and
        threads it through both lanes; the lanes' final refresh
        doubles as the event jump's gate re-evaluation (``act_next``),
        so an acting tick never recomputes ``would_act`` from
        scratch."""
        st, cache = args
        ps = queue_pass(st, head_mask(st))
        if preemptive:
            st, ps = te_lane(st, ps)
        st, ps = (be_queue_backfill(st, ps) if cfg.backfill
                  else be_queue(st, ps))
        in_grace = st.state == GRACE
        queued = st.state == QUEUED
        cache = cache._replace(
            next_vacate=jnp.where(
                in_grace.any(),
                st.t + jnp.min(jnp.where(in_grace, st.grace_left, _BIG)),
                _BIG).astype(jnp.int32),
            n_q_te=jnp.sum(queued & jobs.is_te).astype(jnp.int32),
            n_queued=jnp.sum(queued).astype(jnp.int32))
        return st, cache, gate(st, ps)

    def run_minute(st: State, cache: _Cache):
        """Decrement running clocks, record finishers (one scatter per
        finishing job, behind an ``nfin > 0`` gate), decrement grace
        clocks (gated on any grace job existing)."""
        running = st.state == RUNNING
        remaining = st.remaining - running.astype(jnp.int32)
        fin = running & (remaining <= 0)
        nfin = jnp.sum(fin).astype(jnp.int32)
        st = st._replace(remaining=remaining)

        def finish_all(args):
            st, fin = args

            def fbody(carry):
                st, f = carry
                j = jnp.argmax(f).astype(jnp.int32)
                row = st.assign[j]
                if tc is not None:
                    st = _ev1(st, tc, st.t + 1, obs_schema.FINISH, j)
                st = st._replace(
                    state=st.state.at[j].set(DONE),
                    finish=st.finish.at[j].set(st.t + 1),
                    free=st.free + jobs.demand[j][None, :]
                    * row[:, None].astype(jnp.float32),
                    assign=st.assign.at[j].set(jnp.zeros_like(row)),
                    n_done=st.n_done + 1,
                )
                return st, f.at[j].set(False)

            st, _ = jax.lax.while_loop(lambda c: c[1].any(), fbody,
                                       (st, fin))
            return st

        st = jax.lax.cond(nfin > 0, finish_all, lambda args: args[0],
                          (st, fin))
        st = st._replace(
            grace_left=jax.lax.cond(
                cache.next_vacate < _BIG,
                lambda g: g - (st.state == GRACE).astype(jnp.int32),
                lambda g: g, st.grace_left),
            t=st.t + 1,
        )
        return st, nfin

    big = jnp.int32(max_ticks)

    def jump(st: State, cache: _Cache, hold) -> State:
        """Advance ``dt`` quanta in one step — the gap to the next
        event (cached next arrival / grace expiry, plus the masked-min
        next finish) — bulk-decrementing the clocks by the same
        ``dt``. Every skipped tick is a pure countdown (``hold`` is
        False only when ``would_act`` provably stays False), so free
        vectors, queues and the rng stream cannot change before the
        event; ``last_*`` metrics need no adjustment because every
        tick that records them still executes. Plain array math: under
        ``vmap`` the jump is per-lane."""
        def fire(st):
            t1 = st.t
            running = st.state == RUNNING
            in_grace = st.state == GRACE
            # Deltas from t1 (all >= 0): a NOT_ARRIVED job queues at
            # the top of tick submit; a running job with remaining r
            # finishes during tick t1 + r - 1; a GRACE job vacates at
            # the cached expiry. No events pending at all -> jump to
            # max_ticks (the tick loop's stall terminal).
            d_arr = cache.next_arrival - t1
            d_vac = cache.next_vacate - t1
            d_ev = jnp.minimum(d_arr, d_vac)

            def drain(st):
                # Nothing queued: would_act stays False no matter what
                # finishes (every act needs a queued job), so jump
                # straight to the next arrival / grace expiry and
                # retire EVERY finish on the way in one bulk update —
                # k consecutive finish events collapse into this one
                # iteration. With nothing left to arrive or vacate,
                # land on the last finish instead (the loop's natural
                # terminal boundary, same t as tick mode).
                last_fin = jnp.max(jnp.where(running, st.remaining, 0))
                dt = jnp.where(d_ev >= _BIG - t1, last_fin,
                               jnp.clip(d_ev, 0,
                                        jnp.maximum(big - t1, 0)))
                dt = dt.astype(jnp.int32)
                fin = running & (st.remaining <= dt)
                if tc is not None:
                    # the bulk retire must emit the FINISH rows the
                    # skipped ticks would have: sorted by finish time,
                    # job-index order within a tick (first-occurrence
                    # argmin) — bitwise identical to tick mode's
                    # stream, one row per retired job (``_ev_scan``
                    # rationale)
                    ft = jnp.where(fin, t1 + st.remaining, _BIG)

                    def dbody(carry):
                        st, ftm = carry
                        j = jnp.argmin(ftm).astype(jnp.int32)
                        st = _ev1(st, tc, ftm[j], obs_schema.FINISH, j)
                        return st, ftm.at[j].set(_BIG)

                    st, _ = jax.lax.while_loop(
                        lambda c: (c[1] < _BIG).any(), dbody, (st, ft))
                return st._replace(
                    t=t1 + dt,
                    remaining=st.remaining - jnp.where(
                        fin, st.remaining, dt * running.astype(jnp.int32)),
                    state=jnp.where(fin, DONE, st.state),
                    finish=jnp.where(fin, t1 + st.remaining, st.finish),
                    free=st.free + _gang_release(st.assign, jobs.demand,
                                                 fin),
                    assign=st.assign & ~fin[:, None],
                    n_done=st.n_done + jnp.sum(fin),
                    grace_left=st.grace_left
                    - dt * in_grace.astype(jnp.int32),
                )

            def normal(st):
                d_fin = jnp.min(jnp.where(running, st.remaining - 1, big))
                dt = jnp.minimum(d_ev, d_fin)
                dt = jnp.clip(dt, 0, jnp.maximum(big - t1, 0)) \
                    .astype(jnp.int32)
                return st._replace(
                    t=t1 + dt,
                    remaining=st.remaining
                    - dt * running.astype(jnp.int32),
                    grace_left=st.grace_left
                    - dt * in_grace.astype(jnp.int32),
                )

            return jax.lax.cond(cache.n_queued == 0, drain, normal, st)

        return jax.lax.cond(hold, lambda st: st, fire, st)

    def step(carry):
        st, cache = carry
        st, cache = arrivals(st, cache)
        st, cache = vacates(st, cache)
        # Every schedule action starts from a queued job, so an empty
        # queue short-circuits the whole gate.
        act = jax.lax.cond(cache.n_queued > 0,
                           lambda: would_act(st, cache),
                           lambda: jnp.asarray(False))
        st, cache, act_next = jax.lax.cond(
            act, schedule,
            lambda args: (args[0], args[1], jnp.asarray(False)),
            (st, cache))
        st, nfin = run_minute(st, cache)
        if time_mode == "tick":
            return st, cache
        # Event jump. When jobs finished, the freed capacity
        # invalidates the pre-run gate verdict — re-evaluate it (an
        # empty queue stays a no-act); otherwise ``act_next`` — the
        # schedule lanes' own exit evaluation — already answers for
        # the post-run state, which differs only by clock decrements
        # the gate does not read.
        hold_act = jax.lax.cond(
            nfin > 0,
            lambda: jax.lax.cond(cache.n_queued > 0,
                                 lambda: would_act(st, cache),
                                 lambda: jnp.asarray(False)),
            lambda: act_next)
        hold = (st.n_done >= N) | hold_act
        return jump(st, cache, hold), cache

    return step


def make_tick(cfg: SimConfig, jobs: Jobs, n_nodes: int,
              s=None, P=None, time_mode: str = None,
              max_ticks: int = 1 << 22, trace: bool = False):
    """Build a ``State -> State`` step: one scheduling tick ("tick"
    mode) or one executed tick plus the event jump ("event" mode) —
    the per-step public face of :func:`_make_step`, used by the
    invariant suites to observe every intermediate State. The event
    cache is rebuilt from the State on every call (it is a pure
    function of the State), so single-stepping is bit-identical to
    :func:`run`'s threaded loop."""
    step = _make_step(cfg, jobs, n_nodes, s=s, P=P, time_mode=time_mode,
                      max_ticks=max_ticks, trace=trace)

    def tick_step(st: State) -> State:
        st, _ = step((st, _cache_from_state(jobs, st)))
        return st

    return tick_step


def resolve_trace_capacity(cfg: SimConfig, jobs: Jobs,
                           trace_capacity=None) -> int:
    """The static ring capacity a traced run uses:
    ``trace_capacity`` verbatim when given, else
    ``obs.ring.default_capacity`` sized from the jobset and the
    config's P cap."""
    if trace_capacity is not None:
        return int(trace_capacity)
    return obs_ring.default_capacity(jobs.submit.shape[0],
                                     cfg.max_preemptions)


def run(cfg: SimConfig, jobs: Jobs, seed=0,
        max_ticks: int = 1 << 22, s=None, P=None,
        time_mode: str = None, trace: bool = False,
        trace_capacity=None) -> State:
    """Run the full simulation; returns the final state.

    ``time_mode`` ("tick" | "event", default ``cfg.time_mode``) selects
    per-quantum stepping vs the event-compressed jump — bit-identical
    States, wall-clock proportional to events instead of makespan.
    ``trace`` records every scheduler event into the in-jit ring
    buffer (decode with :func:`decode_trace`); off by default and then
    entirely compiled out."""
    cap = resolve_trace_capacity(cfg, jobs, trace_capacity) if trace else 0
    st = init_state(jobs, cfg.cluster.n_nodes, cfg.cluster.node.as_tuple(),
                    seed, trace_capacity=cap)
    return _run_loop(cfg, jobs, st, max_ticks, s, P, time_mode,
                     trace=trace)


def _run_loop(cfg: SimConfig, jobs: Jobs, st: State, max_ticks: int,
              s, P, time_mode: str, trace: bool = False,
              round_end=None) -> State:
    """The traceable core of :func:`run`: drive ``_make_step`` from an
    existing initial State (so :func:`run_jit` can build it eagerly
    and donate its buffers into the jitted loop).

    ``round_end`` (None, or an absolute tick — may be traced) turns
    the loop into ONE streaming macro-round: run until every pool job
    is DONE or ``t`` reaches ``round_end`` (the earliest submit not in
    this pool). The boundary tick itself is NOT executed — the next
    round's first iteration processes it, with the new arrivals packed
    in, exactly as the monolithic loop would have (DESIGN.md §10)."""
    step = _make_step(cfg, jobs, cfg.cluster.n_nodes, s=s, P=P,
                      time_mode=time_mode, max_ticks=max_ticks,
                      trace=trace, ext_arrival=round_end)
    N = jobs.submit.shape[0]

    def cond(carry):
        c = (carry[0].n_done < N) & (carry[0].t < max_ticks)
        if round_end is not None:
            c = c & (carry[0].t < round_end)
        return c

    def body(carry):
        st, cache = step(carry)
        return st._replace(n_iter=st.n_iter + 1), cache

    st, _ = jax.lax.while_loop(
        cond, body, (st, _cache_from_state(jobs, st, round_end)))
    return st


def _run_jit_impl(cfg: SimConfig, jobs: Jobs, seed, time_mode: str,
                  trace: bool = False, trace_capacity: int = 0) -> State:
    st = init_state(jobs, cfg.cluster.n_nodes, cfg.cluster.node.as_tuple(),
                    seed, trace_capacity=trace_capacity if trace else 0)
    return _run_loop(cfg, jobs, st, 1 << 22, None, None, time_mode,
                     trace=trace)


_JIT_STATICS = ("cfg", "time_mode", "trace", "trace_capacity")
_run_jit_full = jax.jit(_run_jit_impl, static_argnames=_JIT_STATICS)
# Same program with the Jobs buffers DONATED into the jit: the sweep
# fabric's memory-flat entry. Safe by construction — init_state
# force-copies ``exec_total`` (the one State field derived from a Jobs
# array), so no live output aliases a donated input.
_run_jit_donated = jax.jit(_run_jit_impl, static_argnames=_JIT_STATICS,
                           donate_argnums=(1,))


def donation_supported() -> bool:
    """Whether the active backend implements input-output aliasing
    (gpu/tpu). The CPU backend silently keeps its copies (XLA warns
    and ignores the donation), so auto-donating callers — the sweep
    fabric — skip it there."""
    return jax.default_backend() in ("gpu", "tpu")


def run_jit(cfg: SimConfig, jobs: Jobs, seed: int = 0,
            time_mode: str = None, trace: bool = False,
            trace_capacity=None, donate: bool = False) -> State:
    """Jitted :func:`run`. The initial State is built INSIDE the jit
    (``seed`` is traced, so sweeping seeds reuses the compilation), so
    no State buffer ever crosses the jit boundary inward: every ~20
    small construction dispatches the old eager init paid per call are
    compiled into the loop program, and XLA owns (and reuses) the
    State buffers end-to-end — the stronger form of the buffer
    donation this entry point used to do. ``trace``/``trace_capacity``
    are jit-static: toggling tracing recompiles (the traced program is
    a different program), sweeping seeds does not.

    ``donate=True`` additionally donates the ``jobs`` buffers into the
    program (the caller's Jobs are CONSUMED; re-running them is an
    error on backends that implement aliasing). Results are identical
    either way — donation only changes buffer ownership. On CPU the
    donation is a no-op (see :func:`donation_supported`)."""
    if not (isinstance(seed, jax.Array) and jnp.issubdtype(
            seed.dtype, jax.dtypes.prng_key)):
        seed = jnp.asarray(seed, jnp.int32)
    cap = resolve_trace_capacity(cfg, jobs, trace_capacity) if trace else 0
    fn = _run_jit_donated if donate else _run_jit_full
    return fn(cfg, jobs, seed, time_mode, trace, cap)


@functools.partial(jax.jit, static_argnames=("cfg", "time_mode", "trace"))
def _run_round_jit(cfg: SimConfig, jobs: Jobs, st: State, round_end,
                   time_mode: str, trace: bool) -> State:
    return _run_loop(cfg, jobs, st, 1 << 22, None, None, time_mode,
                     trace=trace, round_end=round_end)


def run_round(cfg: SimConfig, jobs: Jobs, st: State, round_end=None,
              time_mode: str = None, trace: bool = False) -> State:
    """Resume an in-flight State for one jitted macro-round.

    The streaming engine's inner step (DESIGN.md §10): run the fused
    tick/event loop until every pool job is DONE or ``st.t`` reaches
    ``round_end`` — the submit tick of the earliest job that has not
    been packed into the pool yet (None = no more external arrivals;
    run to completion). ``round_end`` is traced, so every round of a
    streamed replay reuses one compilation; ``jobs`` carries the
    recycled slot pool and MUST have ``Jobs.akey`` stamped with global
    arrival order for queue keys / tie-breaks to match the monolithic
    engine (parity-window contract; use ``score_backend='jnp'`` — the
    fused kernels tie-break by slot index)."""
    re = jnp.asarray(_BIG if round_end is None else round_end, jnp.int32)
    return _run_round_jit(cfg, jobs, st, re, time_mode, trace)


def trace_overflow(st: State) -> jax.Array:
    """Ring-buffer rows dropped past capacity (() i32; 0 with tracing
    off). Non-zero means the trace is TRUNCATED — loud in
    ``result_summary`` and the CLI/bench output."""
    if st.ev_buf.size == 0:
        return jnp.zeros((), jnp.int32)
    cap = st.ev_buf.shape[-2] - 1
    return jnp.maximum(st.ev_n - cap, 0)


def decode_trace(st: State):
    """Decode the final State's ring buffer into the canonical event
    schema: ``(list[obs.schema.Event], overflow)`` — the JAX half of
    the cross-engine trace-parity contract (the reference half is
    ``Simulator(trace=True)``)."""
    if st.ev_buf.size == 0:
        return [], 0
    return obs_ring.decode_ring(st.ev_buf, st.ev_n)


def state_diff_fields(a: State, b: State) -> list:
    """Names of State fields that differ bitwise — rng keys compared by
    key data, the ``n_iter`` counter left out. Empty list == full-State
    bit equality, THE tick-vs-event parity contract; the engine
    benchmark and the parity/property suites all share this one
    definition so a new State field is covered everywhere at once."""
    diff = []
    for f in a._fields:
        if f == "n_iter":
            # a loop-iteration count, not simulation state: tick mode,
            # event mode and single-stepping (``make_tick``) reach the
            # same State in different numbers of iterations
            continue
        x, y = getattr(a, f), getattr(b, f)
        if f == "rng":
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        if not bool((np.asarray(x) == np.asarray(y)).all()):
            diff.append(f)
    return diff


def slowdown(jobs: Jobs, st: State) -> jax.Array:
    waiting = st.finish - jobs.submit - jobs.exec_total
    return 1.0 + waiting / jobs.exec_total


def masked_percentiles(vals, mask, ps) -> dict:
    """``{f"p{p}": percentile of vals[mask]}`` — NaN-safe: when the
    mask selects nothing (a trial with zero valid TE or BE jobs after
    sentinel padding, or no preemption ever resumed), every entry is an
    EXPLICIT ``nan`` rather than whatever a reduction over an all-NaN
    slice happens to produce; nan-aware poolers then exclude the trial
    (DESIGN.md §5)."""
    v = jnp.where(mask, vals, jnp.nan)
    some = mask.any()
    return {f"p{p}": jnp.where(some, jnp.nanpercentile(v, p), jnp.nan)
            for p in ps}


def result_summary(jobs: Jobs, st: State) -> dict:
    """Percentile summary mirroring metrics.pooled_tables (jnp).

    Sentinel (padding) rows are masked out of every statistic; empty
    classes (all-BE / all-TE jobsets) yield explicit ``nan`` rows."""
    sd = slowdown(jobs, st)
    te = jobs.is_te & jobs.valid
    be = ~jobs.is_te & jobs.valid
    out = {}
    for name, m in (("TE", te), ("BE", be)):
        out[name] = masked_percentiles(sd, m, (50, 95, 99))
    pre = jnp.where(be, (st.preempt_count > 0).astype(jnp.float32), jnp.nan)
    out["preempted_frac"] = jnp.where(be.any(), jnp.nanmean(pre), jnp.nan)
    iv_mask = (st.last_resume >= 0) & jobs.valid
    out["intervals"] = masked_percentiles(
        (st.last_resume - st.last_signal).astype(jnp.float32),
        iv_mask, (50, 75, 95, 99))
    # loud observability counters: non-zero fallback_count voids the
    # P-cap exactness claim, non-zero trace_overflow means a truncated
    # trace — both surfaced in CLI and bench output, not just tests
    out["fallback_count"] = st.fallback_count
    out["trace_overflow"] = trace_overflow(st)
    return out

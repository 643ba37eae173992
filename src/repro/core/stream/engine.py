"""StreamEngine: bounded-memory replay of arbitrarily long traces.

The macro-round idiom (DESIGN.md §10): a fixed-capacity slot pool of
``sim_jax`` state — ``capacity`` rows, O(capacity x nodes) memory, one
compilation — driven by an outer host loop that alternates

  1. *pack*: pull the next arrivals from a :class:`JobSource` and
     scatter them into recycled DONE slots (jitted ``_pack``),
     stamping each job's global sequence number into ``Jobs.akey`` so
     queue keys, requeue ranks and victim tie-breaks keep GLOBAL
     arrival order despite arbitrary slot placement;
  2. *run*: one jitted macro-round (``sim_jax.run_round``) — the
     existing fused ``_Pass``/event-jump loop — until every pool job
     is DONE or ``t`` reaches the round boundary (the earliest submit
     NOT yet packed, folded into the engine's next-arrival cache so no
     event jump can overshoot it);
  3. *drain*: decode the per-round ring buffer (sized off CAPACITY,
     ``obs.ring.round_capacity``), remap slot ids to global job ids,
     and stream events/results out (callback sinks or accumulation).

State (``t``, rng, ``top_key``, free vectors, ``fallback_count``, the
live rows) carries across rounds untouched, which is what makes the
streamed run BIT-IDENTICAL to the monolithic engine on the same
workload — the parity-window contract, checked by
:func:`verify_prefix_parity` (deterministic policies on the jnp score
backend; ``fallback_count`` must stay 0).

A slot is recyclable when its job is DONE and no in-grace victim
still references it (``victim_of`` points at TE slots; vacates
decrement ``te_pending`` through it, so a referenced slot must
survive until the grace period resolves). When the pool is full and
an unpacked arrival is overdue, the overdue jobs SPILL to an explicit
host-side FIFO (:class:`_SpillQueue`, order preserved) and rounds
shrink to one tick until slots free up — saturated load degrades
gracefully instead of aborting. Spilling is NOT silent and NOT
parity-preserving: a spilled job is packed later than it arrived, so
the scheduler could not have considered it in between; ``n_spilled``
is surfaced on the result and :func:`verify_prefix_parity` rejects
spilled runs (DESIGN.md §10).

Closed-loop arrivals (``admission=``): the source is wrapped in
``admission.ClosedLoopAdmission``, which discards the stream's submit
times and re-stamps closed-loop admit ticks — the paper's §4.2
load-2.0 regime in bounded memory (:func:`verify_closed_loop_parity`).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.cluster import SimConfig
from repro.core import sim_jax, workload
from repro.core.stream.source import _FIELDS, JobSource, materialize
from repro.core.types import JobSet
from repro.obs import ring as obs_ring
from repro.obs import schema as obs_schema

# Default pool size: K slots per (node x preemption-budget) unit —
# enough for the queue depths the repo's open-loop loads produce.
DEFAULT_SLOTS_PER_NODE = 32

_MAX_TICKS = 1 << 22       # must match sim_jax's stall terminal

# ``Jobs.akey`` carries the global sequence number as float32, whose
# exact-integer range ends at 2^24: the next gid would round onto the
# previous one, silently breaking queue-key / requeue / victim
# tie-break global arrival order. Packing past this limit raises.
AKEY_GID_LIMIT = 1 << 24

# aux carries a TE job id (not a count) on these codes — remapped
# slot->gid at drain time like the job column itself
_AUX_JOB_CODES = (obs_schema.PREEMPT_SIGNAL, obs_schema.VACATE)

_RESULT_COLS = ("submit", "exec_total", "is_te", "width", "finish",
                "preempt_count", "last_signal", "last_vacate",
                "last_resume")


def default_capacity(cfg: SimConfig, P: Optional[int] = None) -> int:
    P = cfg.max_preemptions if P is None else P
    return max(64, DEFAULT_SLOTS_PER_NODE * cfg.cluster.n_nodes
               * max(int(P), 1))


def _empty_pool(capacity: int, n_nodes: int) -> sim_jax.Jobs:
    """An all-sentinel pool: every slot invalid (born DONE), ready to
    be recycled by the first pack."""
    return sim_jax.Jobs(
        submit=jnp.zeros((capacity,), jnp.int32),
        exec_total=jnp.ones((capacity,), jnp.int32),
        demand=jnp.zeros((capacity, 3), jnp.float32),
        is_te=jnp.zeros((capacity,), bool),
        gp=jnp.zeros((capacity,), jnp.int32),
        width=jnp.ones((capacity,), jnp.int32),
        valid=jnp.zeros((capacity,), bool),
        akey=jnp.full((capacity,), jnp.inf, jnp.float32),
    )


@jax.jit
def _pack(jobs: sim_jax.Jobs, st: sim_jax.State, slots: jax.Array,
          chunk: sim_jax.Jobs, n_new: jax.Array):
    """Scatter ``chunk`` (padded to a fixed width) into the DONE slots
    ``slots`` (padding rows point at ``capacity`` and drop), resetting
    every per-slot State field the previous tenant touched. All shapes
    are fixed by ``capacity``, so every round of a replay reuses this
    one compilation. The recycled slots were DONE, so un-DONE-ing
    ``n_new`` of them is the only ``n_done`` adjustment needed."""
    def put(arr, val):
        return arr.at[slots].set(val, mode="drop")

    jobs = jobs._replace(
        submit=put(jobs.submit, chunk.submit),
        exec_total=put(jobs.exec_total, chunk.exec_total),
        demand=put(jobs.demand, chunk.demand),
        is_te=put(jobs.is_te, chunk.is_te),
        gp=put(jobs.gp, chunk.gp),
        width=put(jobs.width, chunk.width),
        valid=put(jobs.valid, True),
        akey=put(jobs.akey, chunk.akey),
    )
    st = st._replace(
        state=put(st.state, sim_jax.NOT_ARRIVED),
        remaining=put(st.remaining, chunk.exec_total),
        assign=put(st.assign, False),
        preempt_count=put(st.preempt_count, 0),
        grace_left=put(st.grace_left, 0),
        queue_key=put(st.queue_key, jnp.inf),
        finish=put(st.finish, -1),
        te_pending=put(st.te_pending, 0),
        victim_of=put(st.victim_of, -1),
        last_signal=put(st.last_signal, -1),
        last_vacate=put(st.last_vacate, -1),
        last_resume=put(st.last_resume, -1),
        awaiting_resume=put(st.awaiting_resume, False),
        n_done=st.n_done - n_new.astype(jnp.int32),
    )
    return jobs, st


class _SpillQueue:
    """Host-side FIFO for arrivals that are due while every slot is
    occupied (module docstring): jobs move here from the source in
    stream order and are packed back out spill-first, so the global
    arrival order — and therefore the gid sequence — is preserved
    exactly. ``n`` is the current depth, ``peak``/``total`` the
    high-water mark and the lifetime spill count surfaced on
    :class:`StreamResult`."""

    def __init__(self):
        self._chunks: List[JobSet] = []
        self._off = 0
        self.n = 0
        self.peak = 0
        self.total = 0

    def push(self, js: JobSet) -> None:
        self._chunks.append(js)
        self.n += js.n
        self.total += js.n
        if self.n > self.peak:
            self.peak = self.n

    def peek_submit(self) -> Optional[int]:
        if not self._chunks:
            return None
        return int(self._chunks[0].submit[self._off])

    def take(self, k: int) -> Optional[JobSet]:
        parts: List[tuple] = []
        got = 0
        while got < k and self._chunks:
            js = self._chunks[0]
            n = min(k - got, js.n - self._off)
            parts.append((js, self._off, self._off + n))
            self._off += n
            got += n
            if self._off == js.n:
                self._chunks.pop(0)
                self._off = 0
        if got == 0:
            return None
        self.n -= got
        return JobSet(**{
            f: np.concatenate([getattr(js, f)[a:b] for js, a, b in parts])
            for f in _FIELDS})


def _np_masked_percentiles(vals, mask, ps) -> Dict[str, float]:
    """numpy twin of ``sim_jax.masked_percentiles`` (same NaN-safe
    empty-class semantics, same linear interpolation)."""
    if not mask.any():
        return {f"p{p}": float("nan") for p in ps}
    v = np.where(mask, vals, np.nan).astype(np.float64)
    return {f"p{p}": float(np.nanpercentile(v, p)) for p in ps}


@dataclass
class StreamResult:
    """Per-job results of a streamed replay, gid-ordered (gid = global
    arrival sequence number). ``summary()`` mirrors
    ``sim_jax.result_summary`` so downstream table formatting is
    engine-agnostic. ``events`` is the remapped canonical stream when
    tracing without an ``event_sink``, else None."""
    n_jobs: int
    capacity: int
    rounds: int
    makespan: int
    fallback_count: int
    trace_overflow: int
    max_live: int
    final_rng: np.ndarray = field(repr=False)
    submit: np.ndarray = field(repr=False)
    exec_total: np.ndarray = field(repr=False)
    is_te: np.ndarray = field(repr=False)
    width: np.ndarray = field(repr=False)
    finish: np.ndarray = field(repr=False)
    preempt_count: np.ndarray = field(repr=False)
    last_signal: np.ndarray = field(repr=False)
    last_vacate: np.ndarray = field(repr=False)
    last_resume: np.ndarray = field(repr=False)
    events: Optional[List] = field(repr=False, default=None)
    # jobs that were due while the pool was full and waited in the
    # host spill queue (lifetime count / high-water depth). Nonzero
    # means the run left the bit-parity domain — the backlog outgrew
    # the pool and packing was delayed (module docstring).
    n_spilled: int = 0
    spill_peak: int = 0
    # engine-loop iterations over the whole run (``State.n_iter``)
    loop_iters: int = 0

    def slowdown(self) -> np.ndarray:
        waiting = self.finish - self.submit - self.exec_total
        return 1.0 + waiting / self.exec_total

    def summary(self) -> dict:
        sd = self.slowdown()
        te, be = self.is_te, ~self.is_te
        out = {"TE": _np_masked_percentiles(sd, te, (50, 95, 99)),
               "BE": _np_masked_percentiles(sd, be, (50, 95, 99))}
        out["preempted_frac"] = (
            float((self.preempt_count[be] > 0).mean()) if be.any()
            else float("nan"))
        iv = (self.last_resume - self.last_signal).astype(np.float64)
        out["intervals"] = _np_masked_percentiles(
            iv, self.last_resume >= 0, (50, 75, 95, 99))
        out["fallback_count"] = self.fallback_count
        out["trace_overflow"] = self.trace_overflow
        out["n_spilled"] = self.n_spilled
        return out


class StreamEngine:
    """Host driver for the macro-round loop (module docstring).

    ``event_sink`` / ``result_sink``: optional per-round callbacks
    (``sink(list_of_events)`` / ``sink(dict_of_np_arrays)``). With a
    sink, the corresponding stream is NOT accumulated — true
    O(capacity) memory end to end; without one, results (a few scalars
    per job) and traced events are collected into the result.

    ``admission``: closed-loop arrival mode (paper §4.2). A float is
    the FIFO-normalized backlog target; ``True`` uses
    ``cfg.workload.load``. The source is wrapped in
    ``admission.ClosedLoopAdmission`` — its submit times are discarded
    and re-stamped as closed-loop admit ticks, bit-exact with the
    monolithic ``workload.closed_loop_submit_times``. ``None``/``0``
    keeps the open-loop path.
    """

    def __init__(self, cfg: SimConfig, source: JobSource,
                 capacity: Optional[int] = None,
                 time_mode: Optional[str] = None,
                 trace: bool = False,
                 trace_capacity: Optional[int] = None,
                 event_sink: Optional[Callable] = None,
                 result_sink: Optional[Callable] = None,
                 admission=None):
        self.cfg = cfg
        self.admission: Optional[float] = None
        if admission:
            from repro.core.stream.admission import ClosedLoopAdmission
            target = (cfg.workload.load if admission is True
                      else float(admission))
            self.admission = target
            source = JobSource(
                ClosedLoopAdmission(cfg, source, target=target))
        self.source = source
        self.capacity = int(capacity if capacity is not None
                            else default_capacity(cfg))
        self.time_mode = cfg.time_mode if time_mode is None else time_mode
        self.trace = bool(trace)
        self.trace_capacity = (
            int(trace_capacity) if trace_capacity is not None
            else obs_ring.round_capacity(self.capacity,
                                         cfg.max_preemptions))
        self.event_sink = event_sink
        self.result_sink = result_sink

    # -- host-side round phases --------------------------------------

    def _reset(self) -> None:
        """Fresh per-run host state (factored out of ``run`` so tests
        can interpose — e.g. forging ``_n_seen`` to hit the akey
        limit without packing 2^24 jobs)."""
        self._slot_gid = np.full(self.capacity, -1, np.int64)
        self._harvested = np.zeros(self.capacity, bool)
        self._n_seen = 0
        self._overflow = 0
        self._events: List = []
        self._batches: List[dict] = []
        self._spill = _SpillQueue()

    def _take_arrivals(self, k: int) -> Optional[JobSet]:
        """Pull up to ``k`` jobs, spill queue first: spilled jobs
        arrived before anything still in the source, so draining them
        first keeps the gid sequence in global arrival order."""
        parts: List[JobSet] = []
        got = 0
        js = self._spill.take(k)
        if js is not None:
            parts.append(js)
            got = js.n
        if got < k:
            js = self.source.take(k - got)
            if js is not None:
                parts.append(js)
        if not parts:
            return None
        if len(parts) == 1:
            return parts[0]
        return JobSet(**{
            f: np.concatenate([getattr(js, f) for js in parts])
            for f in _FIELDS})

    def _pack_round(self, jobs, st, state_h, t: int):
        """Recycle free slots with the next arrivals (spill queue
        first); returns the updated pool and the round boundary (next
        unpacked submit). ``t`` is ``st.t`` on the host."""
        cap = self.capacity
        # a DONE TE slot referenced by an in-grace victim is NOT
        # recyclable: its vacate still decrements te_pending there
        ref = np.zeros(cap, bool)
        grace = state_h == sim_jax.GRACE
        if grace.any():
            vo = np.asarray(st.victim_of)[grace]
            ref[vo[vo >= 0]] = True
        free = np.flatnonzero((state_h == sim_jax.DONE) & ~ref)
        n_packed = 0
        if free.size:
            js = self._take_arrivals(int(free.size))
            if js is not None:
                n_packed = js.n
                if self._n_seen + n_packed > AKEY_GID_LIMIT:
                    raise RuntimeError(
                        f"stream gid would pass {AKEY_GID_LIMIT} "
                        f"(2^24), the float32 akey exact-integer "
                        "limit: queue keys would collide and global "
                        "arrival order would silently break. Split "
                        "the replay at this boundary.")
                slots = np.full(cap, cap, np.int32)    # cap = dropped
                slots[:n_packed] = free[:n_packed]
                gids = np.arange(self._n_seen,
                                 self._n_seen + n_packed, dtype=np.int64)
                chunk = sim_jax.Jobs(
                    submit=self._pad(js.submit, np.int32),
                    exec_total=self._pad(js.exec_total, np.int32),
                    demand=self._pad(js.demand, np.float32),
                    is_te=self._pad(js.is_te, bool),
                    gp=self._pad(js.gp, np.int32),
                    width=self._pad(js.n_nodes, np.int32),
                    valid=jnp.ones((cap,), bool),
                    akey=self._pad(gids, np.float32),
                )
                jobs, st = _pack(jobs, st, jnp.asarray(slots), chunk,
                                 jnp.asarray(n_packed, jnp.int32))
                self._slot_gid[free[:n_packed]] = gids
                self._harvested[free[:n_packed]] = False
                self._n_seen += n_packed
        nxt = self._spill.peek_submit()
        if nxt is None:
            nxt = self.source.peek_submit()
        if nxt is not None and nxt <= t and free.size - n_packed == 0:
            # saturated: arrivals are overdue and every slot is busy.
            # Move the whole due prefix to the host spill queue (stream
            # order preserved) and shrink the round to one tick so the
            # next pack sees freshly freed slots as soon as possible.
            moved = self.source.take_due(t)
            if moved is not None:
                self._spill.push(moved)
            nxt = t + 1
        return jobs, st, nxt

    def _pad(self, a, dtype):
        out = np.zeros((self.capacity,) + np.shape(a)[1:], dtype)
        out[:len(a)] = a
        return out

    def _drain_events(self, st):
        """Decode + slot->gid remap this round's ring; returns the
        State with ``ev_n`` reset for the next round."""
        events, overflow = obs_ring.decode_ring(st.ev_buf, st.ev_n)
        self._overflow += int(overflow)
        gid = self._slot_gid
        remapped = [
            obs_schema.Event(
                t=e.t, code=e.code, job=int(gid[e.job]),
                aux=(int(gid[e.aux])
                     if e.code in _AUX_JOB_CODES and e.aux >= 0
                     else e.aux),
                nodes=e.nodes)
            for e in events]
        if self.event_sink is not None:
            self.event_sink(remapped)
        else:
            self._events.extend(remapped)
        return st._replace(ev_n=jnp.zeros((), jnp.int32))

    def _harvest(self, jobs, st, state_h):
        """Collect per-job results for newly finished slots."""
        done = ((state_h == sim_jax.DONE) & np.asarray(jobs.valid)
                & ~self._harvested)
        idx = np.flatnonzero(done)
        if idx.size == 0:
            return 0
        self._harvested[idx] = True
        batch = {"gid": self._slot_gid[idx]}
        pool = {"submit": jobs.submit, "exec_total": jobs.exec_total,
                "is_te": jobs.is_te, "width": jobs.width,
                "finish": st.finish, "preempt_count": st.preempt_count,
                "last_signal": st.last_signal,
                "last_vacate": st.last_vacate,
                "last_resume": st.last_resume}
        for k, arr in pool.items():
            batch[k] = np.asarray(arr)[idx]
        if self.result_sink is not None:
            self.result_sink(batch)
        else:
            self._batches.append(batch)
        return idx.size

    # -- the macro-round loop ----------------------------------------

    def run(self) -> StreamResult:
        """The macro-round loop. Each round is a ``repro.stream.round``
        profiler span (arg ``round``) tiled by its phases:
        ``repro.stream.pack``, ``.run``, ``.drain`` (traced runs only)
        and ``.harvest``; a zero-length ``repro.stream.counts`` span
        closes it with the round's counts as args. The last round
        span, which finds nothing left to run, holds only its pack.
        Without a profiler session each span costs a level check."""
        cfg, cap = self.cfg, self.capacity
        n_nodes = cfg.cluster.n_nodes
        jobs = _empty_pool(cap, n_nodes)
        st = sim_jax.init_state(
            jobs, n_nodes, cfg.cluster.node.as_tuple(), cfg.seed,
            trace_capacity=self.trace_capacity if self.trace else 0)
        self._reset()
        rounds, n_done, max_live = 0, 0, 0
        t, n_iter = 0, 0                   # st.t and st.n_iter on the host

        while True:
            with TraceAnnotation("repro.stream.round", round=rounds):
                with TraceAnnotation("repro.stream.pack"):
                    seen, spilled = self._n_seen, self._spill.total
                    state_h = np.asarray(st.state)
                    jobs, st, nxt = self._pack_round(jobs, st, state_h, t)
                    live = cap - int(st.n_done)
                    max_live = max(max_live, live)
                if live == 0 and nxt is None:
                    break                  # drained: nothing left anywhere
                before = (t, n_done, self._n_seen)
                with TraceAnnotation("repro.stream.run"):
                    st = sim_jax.run_round(cfg, jobs, st, round_end=nxt,
                                           time_mode=self.time_mode,
                                           trace=self.trace)
                    t, iters = (int(x) for x in
                                jax.device_get((st.t, st.n_iter)))
                    if t >= _MAX_TICKS:
                        raise RuntimeError(
                            f"streamed run stalled: t reached the "
                            f"{_MAX_TICKS}-tick terminal with jobs "
                            "unfinished")
                if self.trace:
                    with TraceAnnotation("repro.stream.drain"):
                        st = self._drain_events(st)
                with TraceAnnotation("repro.stream.harvest"):
                    state_h = np.asarray(st.state)
                    harvested = self._harvest(jobs, st, state_h)
                    n_done += harvested
                    if (t, n_done, self._n_seen) == before:
                        raise RuntimeError(
                            "streamed run made no progress in a round "
                            f"(t={t}, done={n_done}) — engine bug")
                with TraceAnnotation(
                        "repro.stream.counts", round=rounds,
                        packed=self._n_seen - seen, harvested=harvested,
                        live=live, iters=iters - n_iter,
                        spilled=self._spill.total - spilled):
                    pass
                rounds += 1
                n_iter = iters

        return self._finalize(st, rounds, n_done, max_live, n_iter)

    def _finalize(self, st, rounds, n_done, max_live,
                  loop_iters) -> StreamResult:
        if self.result_sink is None:
            gids = np.concatenate([b["gid"] for b in self._batches]) \
                if self._batches else np.zeros(0, np.int64)
            order = np.argsort(gids)
            gids = gids[order]
            if not (gids == np.arange(len(gids))).all():
                raise RuntimeError(
                    "slot recycling lost or duplicated global job ids")
            cols = {k: np.concatenate([b[k] for b in self._batches])[order]
                    if self._batches else np.zeros(0, np.int64)
                    for k in _RESULT_COLS}
        else:
            cols = {k: np.zeros(0, np.int64) for k in _RESULT_COLS}
        return StreamResult(
            n_jobs=n_done, capacity=self.capacity, rounds=rounds,
            makespan=int(st.t), fallback_count=int(st.fallback_count),
            trace_overflow=self._overflow, max_live=max_live,
            final_rng=np.asarray(jax.random.key_data(st.rng)),
            events=(self._events if self.trace
                    and self.event_sink is None else None),
            n_spilled=self._spill.total, spill_peak=self._spill.peak,
            loop_iters=loop_iters, **cols)


def _reject_spilled(res: StreamResult) -> None:
    """Spilled jobs were packed later than they arrived, so the
    scheduler could not have considered them in between — the run left
    the bit-parity domain (module docstring). Checked BEFORE the
    monolithic comparison run: a spilled saturated run often also
    stalls or diverges monolithically."""
    if res.n_spilled:
        raise ValueError(
            f"parity window does not cover spilled runs: "
            f"{res.n_spilled} jobs waited in the host spill queue "
            f"(peak depth {res.spill_peak}) because the pool was "
            "full while they were due; raise capacity")


def _diff_vs_monolithic(cfg: SimConfig, res: StreamResult, js: JobSet,
                        time_mode: Optional[str]) -> List[str]:
    """Run ``js`` through the monolithic ``sim_jax`` engine and return
    the names of any per-job/result fields that differ from the
    streamed result ``res`` (empty list == bit-exact parity)."""
    from repro.core import policy_registry
    jobs = sim_jax.jobs_from_jobset(js)
    st = sim_jax.run_jit(cfg, jobs, cfg.seed, time_mode=time_mode)
    # Score policies' random fallback draws from a pool-size-dependent
    # categorical — any such draw leaves the parity domain. Rank
    # policies' fallback counter (over-P-cap last resort) is
    # deterministic and stays inside it.
    if policy_registry.get_policy(cfg.policy).jax_kind == "score" and (
            res.fallback_count or int(st.fallback_count)):
        raise ValueError(
            "parity window needs fallback_count == 0 for score "
            "policies (the random fallback draw is pool-size "
            f"dependent); got stream={res.fallback_count} "
            f"monolithic={int(st.fallback_count)}")
    mono = {"finish": st.finish, "preempt_count": st.preempt_count,
            "last_signal": st.last_signal,
            "last_vacate": st.last_vacate,
            "last_resume": st.last_resume}
    diff = [k for k, v in mono.items()
            if not (np.asarray(v) == getattr(res, k)).all()]
    if res.makespan != int(st.t):
        diff.append("t")
    if not (res.final_rng
            == np.asarray(jax.random.key_data(st.rng))).all():
        diff.append("rng")
    return diff


def verify_prefix_parity(cfg: SimConfig, n_jobs: int = 512,
                         capacity: int = 160, chunk: int = 128,
                         time_mode: Optional[str] = None) -> List[str]:
    """The parity-window contract, executable: stream a synthetic
    prefix through the macro-round engine AND run the identical
    materialized jobset through the monolithic ``sim_jax`` engine;
    return the names of any per-job/result fields that differ (empty
    list == bit-exact parity). Raises if either run leaves the
    deterministic domain (``fallback_count != 0``) or the streamed run
    spilled. Used by the bench parity rows, the CI smoke and the
    stream test suite."""
    src = JobSource(workload.stream_chunks(cfg, n_jobs, chunk=chunk))
    res = StreamEngine(cfg, src, capacity=capacity,
                       time_mode=time_mode).run()
    _reject_spilled(res)
    js = materialize(JobSource(
        workload.stream_chunks(cfg, n_jobs, chunk=chunk)))
    return _diff_vs_monolithic(cfg, res, js, time_mode)


def verify_closed_loop_parity(cfg: SimConfig, n_jobs: int = 400,
                              capacity: int = 160, chunk: int = 64,
                              time_mode: Optional[str] = None
                              ) -> List[str]:
    """Closed-loop twin of :func:`verify_prefix_parity`: stream a
    synthetic prefix through the engine with ``admission=True`` AND
    run the monolithic pipeline (``closed_loop_submit_times`` to stamp
    admit ticks, then ``sim_jax.run_jit``) on the same job data;
    return the names of any differing fields. Checks the admit ticks
    themselves (``"admit_time"``) on top of the scheduler outcome, so
    an empty list proves the whole streamed closed-loop path —
    admission controller AND macro-round engine — is bit-exact."""
    src = JobSource(workload.stream_chunks(cfg, n_jobs, chunk=chunk))
    res = StreamEngine(cfg, src, capacity=capacity, time_mode=time_mode,
                       admission=True).run()
    _reject_spilled(res)
    data = materialize(JobSource(
        workload.stream_chunks(cfg, n_jobs, chunk=chunk)))
    data.submit = workload.closed_loop_submit_times(cfg, data)
    diff = ([] if np.array_equal(res.submit, data.submit)
            else ["admit_time"])
    return diff + _diff_vs_monolithic(cfg, res, data, time_mode)

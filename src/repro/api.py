"""One Policy API: ``repro.api`` — the single entrypoint for running
(scenario × policy × engine) experiments.

The paper's contribution is a decision rule; the repo's job is to
evaluate decision rules under as many workloads as possible. Both axes
are registries (``core/policy_registry.py``, ``scenarios/registry.py``)
and this facade is where they meet:

    from repro import api
    r = api.run_experiment(scenario="burst-storm", policy="srtp",
                           engine="jax", n_jobs=512, n_nodes=16)
    r.table["TE"]["p95"], r.preempted_frac, r.makespan

``run_experiment`` builds the config (validated against the policy
registry at construction), builds the scenario's ``JobSet``, runs the
chosen engine — ``"reference"`` (numpy) or ``"jax"`` (jit/vmap-able
fixed-capacity engine) — and normalizes the result into an
:class:`ExperimentResult` with the paper-style tables, however it was
produced. Both engines share the tick/event mode switch
(``SimConfig.time_mode``), gang (multi-node) jobs and
``SimConfig.backfill``; ``score_backend="pallas"`` routes score
policies through their registered kernel on the JAX engine.

Batched studies go through the same module: :func:`sensitivity_grid`
and :func:`scenario_sweep` re-export the classic sweep wrappers
(``core/sweep.py``), and :func:`run_table` / :func:`build_table` /
:func:`pooled_tables` expose the device-parallel sweep fabric
underneath them (``core/sweep_fabric.py``, DESIGN.md §11) — trial
tables ``shard_map``-ed over ``mesh_for_sweep``'s 1-D trial mesh,
bit-exact with the single-device vmap. The scenarios CLI, the engine
benchmark and the examples all sit on this facade. DESIGN.md §6.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
from jax.profiler import TraceAnnotation

from repro import scenarios
from repro.configs.cluster import SimConfig
from repro.core import metrics, sim_jax, simulator
from repro.core.policy_registry import (all_policies, get_policy, make,
                                        policy_names, score_backend_names)
from repro.core.sweep import run_sweep, scenario_sweep, sensitivity_grid
from repro.core.sweep_fabric import (SweepResult, TrialTable, build_table,
                                     pooled_tables, run_table)
from repro.core.types import JobSet
from repro.launch.mesh import mesh_for_sweep

ENGINES = ("reference", "jax")
DEFAULT_SCENARIO = "paper-synthetic"

__all__ = [
    "DEFAULT_SCENARIO", "ENGINES", "ExperimentResult", "SweepResult",
    "TrialTable", "all_policies", "build_table", "compare_policies",
    "get_policy", "make", "make_config", "mesh_for_sweep",
    "policy_names", "pooled_tables", "run_experiment", "run_stream",
    "run_sweep", "run_table", "scenario_names", "scenario_sweep",
    "score_backend_names", "sensitivity_grid",
]

scenario_names = scenarios.scenario_names


@dataclass(frozen=True)
class ExperimentResult:
    """Engine-agnostic result of one (scenario, policy, engine) run.

    ``table`` is the paper-style slowdown-percentile table
    (``{"TE": {"p50": ...}, "BE": {...}}``, metrics.format_table-ready);
    ``intervals`` the preemption→reschedule percentiles; ``raw`` the
    engine-native result (``SimResult`` for the reference engine,
    ``(Jobs, State)`` for JAX) for callers that need more.
    """
    scenario: str
    policy: str
    engine: str
    cfg: SimConfig
    table: Dict[str, Dict[str, float]]
    intervals: Dict[str, float]
    preempted_frac: float
    makespan: int
    raw: Any = field(repr=False, compare=False, default=None)
    # Canonical event stream (List[obs.schema.Event]) when the run was
    # traced (``run_experiment(trace=True)``), else None. Identical
    # vocabulary from either engine — the trace-parity contract.
    events: Optional[list] = field(repr=False, compare=False, default=None)
    # Ring-buffer rows dropped in a traced JAX run (0 = complete trace;
    # reference traces never overflow). Surfaced loudly: a nonzero
    # value means the decoded stream is truncated.
    trace_overflow: int = 0
    # Random-fallback invocations on the JAX engine (score policies
    # under cluster pressure). Nonzero means the run left the
    # deterministic cross-engine parity domain (DESIGN.md §8).
    fallback_count: int = 0
    # Engine-loop iterations (``State.n_iter``): one ``while_loop``
    # step each, a tick plus its event jump in event mode. None on the
    # reference engine.
    loop_iters: Optional[int] = None


def make_config(policy: Optional[str] = None, *,
                base: Optional[SimConfig] = None,
                n_jobs: Optional[int] = None, n_nodes: Optional[int] = None,
                seed: Optional[int] = None, s: Optional[float] = None,
                P: Optional[int] = None,
                score_backend: Optional[str] = None,
                backfill: Optional[bool] = None) -> SimConfig:
    """SimConfig from the common experiment knobs (None = keep the
    ``base`` value — including ``policy``, so a caller-configured base
    is never silently re-pointed).

    ``base`` seeds every field not overridden here; construction
    validates ``policy`` / ``s`` / ``P`` / ``score_backend`` against
    the policy registry.
    """
    cfg = base if base is not None else SimConfig()
    repl: Dict[str, Any] = {}
    if policy is not None:
        repl["policy"] = policy
    if n_nodes is not None:
        repl["cluster"] = dataclasses.replace(cfg.cluster, n_nodes=n_nodes)
    if n_jobs is not None:
        repl["workload"] = dataclasses.replace(cfg.workload, n_jobs=n_jobs)
    if seed is not None:
        repl["seed"] = seed
    if s is not None:
        repl["s"] = s
    if P is not None:
        repl["max_preemptions"] = P
    if score_backend is not None:
        repl["score_backend"] = score_backend
    if backfill is not None:
        repl["backfill"] = backfill
    return dataclasses.replace(cfg, **repl) if repl else cfg


def _run_reference(cfg: SimConfig, js: JobSet, mode: str, trace: bool):
    res = simulator.simulate(cfg, js, mode=mode, trace=trace)
    return (metrics.slowdown_table(res), metrics.resched_table(res),
            res.preempted_fraction(), int(res.makespan), res,
            res.trace, 0, 0, None)


def _run_jax(cfg: SimConfig, js: JobSet, mode: str, trace: bool,
             trace_capacity: Optional[int]):
    """One replay, as the profiler spans ``repro.replay.loop`` (the
    jobs' upload and the loop program, up to its final clock on the
    host) and ``repro.replay.summary`` (the summary and its host
    conversions), then a zero-length ``repro.replay.counts`` span with
    args ``iters`` and ``jobs``."""
    with TraceAnnotation("repro.replay.loop"):
        jobs = sim_jax.jobs_from_jobset(js)
        st = sim_jax.run_jit(cfg, jobs, cfg.seed, time_mode=mode,
                             trace=trace, trace_capacity=trace_capacity)
        t, iters = (int(x) for x in jax.device_get((st.t, st.n_iter)))
    with TraceAnnotation("repro.replay.summary"):
        summary = sim_jax.result_summary(jobs, st)
        table = {k: {p: float(v) for p, v in summary[k].items()}
                 for k in ("TE", "BE")}
        intervals = {p: float(v) for p, v in summary["intervals"].items()}
        events, overflow = (None, 0)
        if trace:
            events, overflow = sim_jax.decode_trace(st)
        out = (table, intervals, float(summary["preempted_frac"]), t,
               (jobs, st), events, int(overflow),
               int(summary["fallback_count"]), iters)
    with TraceAnnotation("repro.replay.counts", iters=iters, jobs=js.n):
        pass
    return out


def run_experiment(scenario: str = DEFAULT_SCENARIO,
                   policy: Optional[str] = None,
                   engine: str = "reference", *,
                   cfg: Optional[SimConfig] = None,
                   jobs: Optional[JobSet] = None,
                   n_jobs: Optional[int] = None,
                   n_nodes: Optional[int] = None,
                   seed: Optional[int] = None,
                   s: Optional[float] = None,
                   P: Optional[int] = None,
                   score_backend: Optional[str] = None,
                   backfill: Optional[bool] = None,
                   mode: Optional[str] = None,
                   trace: bool = False,
                   trace_capacity: Optional[int] = None) -> ExperimentResult:
    """Run one (scenario, policy) experiment on the chosen engine.

    Any registered policy runs on any registered scenario through
    either engine with no engine edits — policies declare their
    backends once in ``core/policies.py``. ``jobs`` short-circuits the
    scenario build (e.g. to share one JobSet across policies);
    ``mode`` ("event" | "tick", default ``cfg.time_mode`` — like every
    other entry point) selects the time advancement on BOTH engines
    (results are bit-identical either way; "event" compresses no-op
    ticks — reference DESIGN.md §4, JAX §7). Engine-native output is
    in ``.raw``.

    ``trace=True`` records the canonical scheduler-event stream
    (``obs.schema.Event``) into ``.events`` — via driver hooks on the
    reference engine, via the in-jit ring buffer on the JAX engine
    (decoded post-run; ``.trace_overflow`` counts any dropped rows,
    ``trace_capacity`` overrides the auto-sized ring). Feed ``.events``
    to ``obs.export.write_trace`` (Perfetto / CSV) or
    ``obs.timeseries`` (utilization, queue depth, slowdown
    decomposition). DESIGN.md §8.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; one of {ENGINES}")
    if mode not in (None, "event", "tick"):
        raise ValueError(f"unknown mode {mode!r}; one of ('event', 'tick')")
    cfg = make_config(policy, base=cfg, n_jobs=n_jobs, n_nodes=n_nodes,
                      seed=seed, s=s, P=P, score_backend=score_backend,
                      backfill=backfill)
    if mode is None:
        mode = cfg.time_mode
    js = scenarios.build(scenario, cfg) if jobs is None else jobs
    if engine == "reference":
        (table, intervals, pf, makespan, raw, events, overflow,
         fallback, iters) = _run_reference(cfg, js, mode, trace)
    else:
        (table, intervals, pf, makespan, raw, events, overflow,
         fallback, iters) = _run_jax(cfg, js, mode, trace, trace_capacity)
    return ExperimentResult(
        scenario=scenario, policy=cfg.policy, engine=engine, cfg=cfg,
        table=table, intervals=intervals, preempted_frac=pf,
        makespan=makespan, raw=raw, events=events,
        trace_overflow=overflow, fallback_count=fallback,
        loop_iters=iters)


def run_stream(scenario: str = DEFAULT_SCENARIO,
               policy: Optional[str] = None, *,
               cfg: Optional[SimConfig] = None,
               source=None,
               capacity: Optional[int] = None,
               n_jobs: Optional[int] = None,
               n_nodes: Optional[int] = None,
               seed: Optional[int] = None,
               mode: Optional[str] = None,
               trace: bool = False,
               trace_capacity: Optional[int] = None,
               admission=None) -> ExperimentResult:
    """Replay a scenario through the streaming macro-round engine
    (``core/stream``, DESIGN.md §10) — bounded memory, arbitrary trace
    length, results bit-identical to ``engine="jax"`` on the same
    workload.

    The workload comes from the scenario's registered streaming
    ``source`` (trace readers / chunked generators; scenarios without
    one fall back to a chunked view of the built JobSet), or from an
    explicit ``source`` (a ``core.stream.JobSource``). ``capacity``
    bounds in-flight jobs — memory scales with it, not with the trace
    (default ``stream.default_capacity(cfg)``). ``admission`` turns on
    closed-loop arrivals (paper §4.2): the source's submit times are
    re-stamped as admit ticks holding the FIFO-normalized backlog at
    ``cfg.workload.load`` (``admission=True``) or at an explicit float
    target — the streamed twin of the registry's closed-loop
    scenarios. ``.raw`` holds the
    :class:`repro.core.stream.StreamResult` (per-job arrays, round
    count, peak live jobs, spill counters); ``.events`` the
    gid-remapped canonical stream when traced.
    """
    from repro.core import stream
    if mode not in (None, "event", "tick"):
        raise ValueError(f"unknown mode {mode!r}; one of ('event', 'tick')")
    cfg = make_config(policy, base=cfg, n_jobs=n_jobs, n_nodes=n_nodes,
                      seed=seed)
    if mode is None:
        mode = cfg.time_mode
    if source is None:
        source = scenarios.get_source(scenario, cfg)
    eng = stream.StreamEngine(cfg, source, capacity=capacity,
                              time_mode=mode, trace=trace,
                              trace_capacity=trace_capacity,
                              admission=admission)
    res = eng.run()
    summary = res.summary()
    table = {k: {p: float(v) for p, v in summary[k].items()}
             for k in ("TE", "BE")}
    intervals = {p: float(v) for p, v in summary["intervals"].items()}
    return ExperimentResult(
        scenario=scenario, policy=cfg.policy, engine="stream", cfg=cfg,
        table=table, intervals=intervals,
        preempted_frac=float(summary["preempted_frac"]),
        makespan=res.makespan, raw=res, events=res.events,
        trace_overflow=res.trace_overflow,
        fallback_count=res.fallback_count, loop_iters=res.loop_iters)


def compare_policies(policies, scenario: str = DEFAULT_SCENARIO,
                     engine: str = "reference",
                     **kw) -> Dict[str, ExperimentResult]:
    """Run several policies on ONE shared JobSet (Table 1 shape).

    The scenario is built once from the first policy's config — every
    registered scenario derives its jobset from ``cfg.seed`` /
    ``cfg.workload`` / ``cfg.cluster`` only, so the comparison is
    apples-to-apples by construction.
    """
    policies = list(policies)
    cfg0 = make_config(policies[0], base=kw.get("cfg"),
                       n_jobs=kw.get("n_jobs"), n_nodes=kw.get("n_nodes"),
                       seed=kw.get("seed"))
    js = scenarios.build(scenario, cfg0)
    return {p: run_experiment(scenario, p, engine, jobs=js, **kw)
            for p in policies}

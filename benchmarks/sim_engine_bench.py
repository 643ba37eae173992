"""Simulator-engine benchmarks: reference (numpy) tick vs event-driven
advancement, the JAX engine, and the vmapped sweep throughput that the
mesh distribution relies on.

``python -m benchmarks.sim_engine_bench --json`` additionally emits
``BENCH_sim_engine.json`` — tick vs event-driven throughput (jobs
simulated per second) on a sparse long-horizon workload, with the
bit-exactness of the two modes re-verified in-run (DESIGN.md §4) —
per-scenario timings over the full registered scenario suite
(``repro.scenarios``, DESIGN.md §5): the reference event engine plus
``jax_tick`` vs ``jax_event`` rows for the JAX engine's
event-compressed ``lax.while_loop`` (``SimConfig.time_mode``,
DESIGN.md §7; full-State bit-parity re-verified in-run across the
deterministic policy registry), an ``n_jobs`` scaling axis (256 /
1024 / 4096) tracking the dense-scale reference-vs-``jax_event``
trajectory, and the FitGpp score-path comparison on the JAX engine:
jnp vs the fused Pallas ``schedule_step`` kernel backend
(``SimConfig.score_backend``, DESIGN.md §6), with parity re-verified
in-run. The scenario-suite rows also carry a ``speedup_vs_ref``
gate: ``--check-parity`` fails if any scenario's ``jax_event`` row
is slower than the reference event engine. Configs and sweeps go
through the ``repro.api`` facade; TIMED regions call the engines
directly so the rows measure the engine, not jobset construction or
result normalization, and stay comparable across PRs.

Telemetry (DESIGN.md §8): every scenario row carries ``utilization``
and ``preempt_rate`` columns replayed from a traced reference run
(outside the timed region), plus a ``jax_event_traced`` timing with
its ``trace_overhead`` ratio — the untraced rows have the in-jit
event ring compiled OUT, so tracing-off stays structurally
zero-cost; enabled, expect ~1-1.7x on CPU (per-op dispatch floor
of the per-event emission, DESIGN.md §8 — the untraced
event-compressed step is itself only microseconds long).
``--smoke`` round-trips a tiny trace through both export
formats (``--trace-out`` saves the Perfetto JSON artifact) and
re-verifies the streamed-vs-monolithic bit-parity window.

Streaming (DESIGN.md §10): the JSON artifact opens with a ``stream``
suite — a >=10^5-job synthetic trace through the bounded-memory
macro-round engine (``core/stream``) at fixed slot-pool capacity,
run before everything else so its per-row ``max_rss_mb``
(``resource.getrusage`` high-water mark, platform-aware units; every
suite records it) demonstrates memory scaling with capacity, not
trace length, and an in-run ``parity`` key for the
streamed-vs-monolithic bit-parity window that ``--check-parity``
requires — followed by a ``stream_closed_loop`` suite replaying the
paper's §4.2 load-2.0 closed-loop regime through the same pool
(``StreamEngine(admission=True)``), with its own required ``parity``
key (admit ticks and scheduler outcome bit-exact with the monolithic
``closed_loop_submit_times`` pipeline) and ``n_spilled`` per row.

Sweep fabric (DESIGN.md §11): the artifact closes with a
``sweep_throughput`` suite — a ragged 4-scenario x 4-seed trial table
through ``core/sweep_fabric`` in this process, on the devices it has,
timing configs/sec on 1 device (plain vmap) vs all of them
(``shard_map`` over ``mesh_for_sweep``). For a multi-device host
runtime set ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on
the bench command. ``--check-parity`` requires its ``parity`` row
(sharded bitwise-equal to single-device), its ``compile_reuse`` row
(a seed-only re-run adds no jit-cache entry — the per-call-jit
recompile bug stays fixed) and, with more than one device,
``scaling_x >= 1`` (the sharded fabric must not lose to the vmap).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import resource
import sys
import time
from typing import Dict, List

import numpy as np

from repro import api, scenarios
from repro.configs.cluster import ClusterSpec, SimConfig, WorkloadSpec
from repro.core import metrics, policy_registry, sim_jax, simulator, workload
from repro.core.policy_registry import RNG_ALWAYS
from repro.core.workload import sparse_long_horizon


def _rss_divisor(platform: str = None) -> int:
    """``ru_maxrss`` unit per platform: kilobytes everywhere except
    macOS, where getrusage reports BYTES (the BSD lineage). Returns
    the divisor that yields MB."""
    platform = sys.platform if platform is None else platform
    return (1 << 20) if platform == "darwin" else (1 << 10)


def _rss_mb() -> float:
    """Process peak RSS in MB (platform-aware ``ru_maxrss`` units, see
    :func:`_rss_divisor`). The counter is a high-water mark — per-row
    values are peaks SO FAR, so rows that must attribute memory (the
    stream suites) run first."""
    return (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            / _rss_divisor())


def bench_tick_vs_event(n_jobs: int = 512, policy: str = "fitgpp",
                        n_nodes: int = 8, seed: int = 0) -> dict:
    # Config through the facade; the TIMED region is the engine alone
    # (no jobset build, no result-table normalization), so these rows
    # stay comparable with the numbers from earlier PRs.
    cfg = api.make_config(policy, n_nodes=n_nodes, seed=seed)
    js = sparse_long_horizon(n_jobs, seed=seed)

    t0 = time.perf_counter()
    res_tick = simulator.simulate(cfg, js, mode="tick")
    s_tick = time.perf_counter() - t0

    t0 = time.perf_counter()
    res_event = simulator.simulate(cfg, js, mode="event")
    s_event = time.perf_counter() - t0

    metrics.assert_result_parity(res_tick, res_event)
    return {
        "workload": {"kind": "sparse_long_horizon", "n_jobs": n_jobs,
                     "n_nodes": n_nodes, "policy": policy, "seed": seed,
                     "makespan_ticks": int(res_tick.makespan)},
        "tick": {"seconds": s_tick,
                 "jobs_per_sec": metrics.sim_throughput(res_tick, s_tick)},
        "event": {"seconds": s_event,
                  "jobs_per_sec": metrics.sim_throughput(res_event,
                                                         s_event)},
        "speedup": s_tick / max(s_event, 1e-12),
        "max_rss_mb": _rss_mb(),
        "parity": True,      # assert_result_parity would have raised
    }


def bench_stream(n_jobs: int = 100_000, capacity: int = 2048,
                 n_nodes: int = 8, policy: str = "fitgpp", seed: int = 0,
                 load: float = 0.5, parity_jobs: int = 400) -> Dict:
    """Streaming macro-round engine rows (``core/stream``, DESIGN.md
    §10): a >=10^5-job synthetic trace replayed through the fixed slot
    pool, with ``max_rss_mb`` per row. The bounded-memory claim is the
    near-flat peak RSS between the quarter-length and full-length rows
    at the SAME capacity — memory scales with the pool, not the trace
    — which is why this suite runs before everything else inflates the
    process high-water mark. ``parity`` re-verifies the bit-parity
    window in-run: streamed per-job results / makespan / rng state on
    a prefix equal the monolithic engine exactly
    (``stream.verify_prefix_parity``). Arrivals use sub-critical
    ``load`` so the open-loop backlog stays bounded; the first row
    absorbs the round-kernel compile."""
    from repro.core import stream
    cfg = api.make_config(policy, n_jobs=n_jobs, n_nodes=n_nodes,
                          seed=seed)
    cfg = dataclasses.replace(
        cfg, workload=dataclasses.replace(cfg.workload, load=load))
    # window sized for 5 recycling rounds with the score policy's
    # random fallback never firing (pool-size dependent, so fallback
    # would leave the bit-parity domain — verify_prefix_parity raises)
    diff = stream.verify_prefix_parity(cfg, n_jobs=parity_jobs,
                                       capacity=96, chunk=64)
    if diff:
        raise AssertionError(
            f"stream-vs-monolithic parity violated: {diff}")
    out: Dict = {
        "workload": {"kind": "stream_chunks", "n_nodes": n_nodes,
                     "policy": policy, "seed": seed, "load": load},
        "capacity": capacity, "parity": True,
        "parity_window_jobs": parity_jobs,
    }
    for label, nj in (("quarter", n_jobs // 4), ("full", n_jobs)):
        src = stream.JobSource(
            workload.stream_chunks(cfg, nj, chunk=4096))
        t0 = time.perf_counter()
        res = stream.StreamEngine(cfg, src, capacity=capacity).run()
        s = time.perf_counter() - t0
        out[label] = {"n_jobs": nj, "seconds": s,
                      "jobs_per_sec": nj / max(s, 1e-12),
                      "rounds": res.rounds, "max_live": res.max_live,
                      "capacity": res.capacity,
                      "makespan_ticks": res.makespan,
                      "fallback_count": res.fallback_count,
                      "max_rss_mb": _rss_mb()}
    return out


def bench_stream_closed_loop(n_jobs: int = 100_000, capacity: int = 2048,
                             n_nodes: int = 8, policy: str = "fitgpp",
                             seed: int = 0, load: float = 2.0,
                             parity_jobs: int = 400) -> Dict:
    """Streamed closed-loop admission rows (paper §4.2, DESIGN.md
    §10): the load-2.0 saturated regime through the macro-round engine
    with ``admission=True`` — the arrival process the paper's headline
    tables use, previously monolithic-only. The bounded-memory claim
    is the near-flat ``max_rss_mb`` between the quarter and full rows:
    the closed loop bounds the FIFO backlog, so saturated load streams
    without starving the pool (``n_spilled`` stays 0). ``parity``
    re-verifies the whole streamed path in-run — admit ticks AND
    scheduler outcome bit-exact with the monolithic
    ``closed_loop_submit_times`` + ``run_jit`` pipeline
    (``stream.verify_closed_loop_parity``; lrtp — rank policies stay
    in the deterministic domain at saturation, where score policies'
    random fallback fires)."""
    from repro.core import stream
    pcfg = api.make_config("lrtp", n_jobs=parity_jobs, n_nodes=n_nodes,
                           seed=seed)
    pcfg = dataclasses.replace(
        pcfg, workload=dataclasses.replace(pcfg.workload, load=load))
    diff = stream.verify_closed_loop_parity(pcfg, n_jobs=parity_jobs,
                                            capacity=160, chunk=64)
    if diff:
        raise AssertionError(
            f"streamed closed-loop parity violated: {diff}")
    cfg = api.make_config(policy, n_jobs=n_jobs, n_nodes=n_nodes,
                          seed=seed)
    cfg = dataclasses.replace(
        cfg, workload=dataclasses.replace(cfg.workload, load=load))
    out: Dict = {
        "workload": {"kind": "stream_chunks+closed_loop",
                     "n_nodes": n_nodes, "policy": policy, "seed": seed,
                     "load": load},
        "capacity": capacity, "parity": True,
        "parity_window_jobs": parity_jobs,
    }
    for label, nj in (("quarter", n_jobs // 4), ("full", n_jobs)):
        src = stream.JobSource(
            workload.stream_chunks(cfg, nj, chunk=4096))
        t0 = time.perf_counter()
        res = stream.StreamEngine(cfg, src, capacity=capacity,
                                  admission=True).run()
        s = time.perf_counter() - t0
        out[label] = {"n_jobs": nj, "seconds": s,
                      "jobs_per_sec": nj / max(s, 1e-12),
                      "rounds": res.rounds, "max_live": res.max_live,
                      "capacity": res.capacity,
                      "makespan_ticks": res.makespan,
                      "fallback_count": res.fallback_count,
                      "n_spilled": res.n_spilled,
                      "spill_peak": res.spill_peak,
                      "max_rss_mb": _rss_mb()}
    return out


def _time_jax(cfg: SimConfig, jobs, seed: int, time_mode: str,
              trace: bool = False):
    """Seconds for one jitted run, compile excluded."""
    st = sim_jax.run_jit(cfg, jobs, seed, time_mode=time_mode,
                         trace=trace)                       # compile
    st.t.block_until_ready()
    t0 = time.perf_counter()
    st = sim_jax.run_jit(cfg, jobs, seed, time_mode=time_mode, trace=trace)
    st.t.block_until_ready()
    return time.perf_counter() - t0, st


def bench_jax_tick_vs_event(cfg: SimConfig, js, seed: int) -> Dict:
    """JAX-engine tick vs event-compressed rows for one jobset: timing
    under ``cfg.policy`` (compile excluded), full-State tick-vs-event
    bit-parity re-verified in-run for EVERY registered deterministic
    (non-rng-driven) dual-backend policy."""
    jobs = sim_jax.jobs_from_jobset(js)
    s_tick, st_tick = _time_jax(cfg, jobs, seed, "tick")
    s_event, st_event = _time_jax(cfg, jobs, seed, "event")
    parity = not sim_jax.state_diff_fields(st_tick, st_event)
    if not parity:
        raise AssertionError(
            f"jax tick-vs-event parity violated ({cfg.policy})")
    parity_policies = [sp.name for sp in policy_registry.all_policies()
                       if sp.dual_backend and sp.rng != RNG_ALWAYS]
    for name in parity_policies:
        if name == cfg.policy:
            continue
        pcfg = dataclasses.replace(cfg, policy=name)
        a = sim_jax.run_jit(pcfg, jobs, seed, time_mode="tick")
        b = sim_jax.run_jit(pcfg, jobs, seed, time_mode="event")
        parity = parity and not sim_jax.state_diff_fields(a, b)
        if not parity:
            raise AssertionError(
                f"jax tick-vs-event parity violated ({name})")
    # tracing cost: same jitted event run with the in-jit ring buffer
    # compiled IN (untraced rows above have it compiled OUT — tracing
    # off is structurally zero-cost, not just cheap)
    s_traced, st_traced = _time_jax(cfg, jobs, seed, "event", trace=True)
    return {
        "jax_tick": {"seconds": s_tick,
                     "jobs_per_sec": js.n / max(s_tick, 1e-12)},
        "jax_event": {"seconds": s_event,
                      "jobs_per_sec": js.n / max(s_event, 1e-12)},
        "jax_event_traced": {"seconds": s_traced,
                             "jobs_per_sec": js.n / max(s_traced, 1e-12)},
        "trace_overhead": s_traced / max(s_event, 1e-12),
        "fallback_count": int(st_event.fallback_count),
        "trace_overflow": int(sim_jax.trace_overflow(st_traced)),
        "jax_speedup": s_tick / max(s_event, 1e-12),
        "parity": parity,         # computed; False never reaches here
        "parity_policies": parity_policies,
    }


def bench_scenario_suite(n_jobs: int = 256, n_nodes: int = 8,
                         policy: str = "fitgpp", seed: int = 0) -> Dict:
    """Per-scenario engine rows for every registered scenario + trace
    adapter (trace fixtures keep their native job counts): the
    reference event engine, plus ``jax_tick`` vs ``jax_event`` rows
    (``SimConfig.time_mode``) with tick-vs-event bit-parity re-verified
    across the deterministic policy registry. Gang scenarios
    (gang-heavy, gang-trace-mix, the trace adapters) run the JAX
    engine like everything else. Jobset construction stays OUTSIDE
    the timed regions — these rows measure the engines.

    Each row also carries telemetry columns — time-weighted mean
    ``utilization`` and ``preempt_rate`` (signals per simulated
    minute), replayed from a traced reference run OUTSIDE the timed
    region — plus the tracing-cost columns from
    :func:`bench_jax_tick_vs_event` (``jax_event_traced``,
    ``trace_overhead``, ``fallback_count``, ``trace_overflow``)."""
    from repro.obs import timeseries
    from repro.core.policy_registry import get_policy
    cfg = api.make_config(policy, n_jobs=n_jobs, n_nodes=n_nodes,
                          seed=seed)
    out = {}
    for name in scenarios.scenario_names():
        js = scenarios.build(name, cfg)
        t0 = time.perf_counter()
        res = simulator.simulate(cfg, js, mode="event")
        s = time.perf_counter() - t0
        out[name] = {"n_jobs": js.n, "seconds": s,
                     "n_gangs": int((np.asarray(js.n_nodes) > 1).sum()),
                     "jobs_per_sec": metrics.sim_throughput(res, s),
                     "makespan_ticks": int(res.makespan)}
        tres = simulator.simulate(cfg, js, mode="event", trace=True)
        ts = timeseries.compute_timeseries(
            tres.trace, n_nodes=cfg.cluster.n_nodes, is_te=js.is_te,
            preemptive=get_policy(cfg.policy).preemptive)
        out[name]["utilization"] = ts.mean_utilization()
        out[name]["preempt_rate"] = ts.preempt_rate
        out[name].update(bench_jax_tick_vs_event(cfg, js, seed))
        out[name]["speedup_vs_ref"] = s / max(
            out[name]["jax_event"]["seconds"], 1e-12)
        out[name]["max_rss_mb"] = _rss_mb()
    return out


def bench_njobs_scaling(sizes=(256, 1024, 4096), n_nodes: int = 8,
                        policy: str = "fitgpp", seed: int = 0) -> Dict:
    """Dense-scale trajectory rows: reference event engine vs
    ``jax_event`` jobs/sec for every SIZED registered scenario at each
    ``n_jobs`` (trace fixtures keep their native job counts and are
    skipped here — their rows live in the scenario suite). These are
    the rows the ≥5x-at-1k+ target is defined on; on the CPU container
    they time interpret-mode kernels, so they record the honest CPU
    trajectory rather than the TPU target."""
    out: Dict = {}
    for n in sizes:
        cfg = api.make_config(policy, n_jobs=n, n_nodes=n_nodes, seed=seed)
        rows: Dict = {}
        for name in scenarios.scenario_names():
            js = scenarios.build(name, cfg)
            if js.n != n:              # trace fixture: native job count
                continue
            t0 = time.perf_counter()
            res = simulator.simulate(cfg, js, mode="event")
            s_ref = time.perf_counter() - t0
            jobs = sim_jax.jobs_from_jobset(js)
            s_jax, _ = _time_jax(cfg, jobs, seed, "event")
            rows[name] = {
                "ref_seconds": s_ref,
                "jax_event_seconds": s_jax,
                "ref_jobs_per_sec": metrics.sim_throughput(res, s_ref),
                "jax_jobs_per_sec": js.n / max(s_jax, 1e-12),
                "speedup_vs_ref": s_ref / max(s_jax, 1e-12),
            }
        out[str(n)] = rows
    return out


def bench_score_backend(n_jobs: int = 192, n_nodes: int = 84,
                        seed: int = 0) -> Dict:
    """JAX-engine FitGpp with the schedule pass on jnp vs on the fused
    Pallas ``schedule_step`` kernel (``SimConfig.score_backend``;
    interpret mode off-TPU), compile excluded, parity re-verified."""
    cfg = SimConfig(cluster=ClusterSpec(n_nodes=n_nodes),
                    workload=WorkloadSpec(n_jobs=n_jobs),
                    policy="fitgpp", seed=seed)
    js = workload.generate(cfg)
    jobs = sim_jax.jobs_from_jobset(js)
    out: Dict = {"workload": {"n_jobs": n_jobs, "n_nodes": n_nodes,
                              "seed": seed}}
    finishes = {}
    for backend in ("jnp", "pallas"):
        bcfg = dataclasses.replace(cfg, score_backend=backend)
        st = sim_jax.run_jit(bcfg, jobs, seed)     # compile
        st.t.block_until_ready()
        t0 = time.perf_counter()
        st = sim_jax.run_jit(bcfg, jobs, seed)
        st.t.block_until_ready()
        s = time.perf_counter() - t0
        finishes[backend] = np.asarray(st.finish)
        out[backend] = {"seconds": s, "jobs_per_sec": n_jobs / max(s, 1e-12)}
    parity = bool((finishes["jnp"] == finishes["pallas"]).all())
    if not parity:
        raise AssertionError("score-backend parity violated: jnp vs pallas")
    out["parity"] = parity
    out["max_rss_mb"] = _rss_mb()
    return out


SWEEP_TRIALS = 16        # 4 scenarios x 4 seeds


def bench_sweep_throughput() -> Dict:
    """Sweep-fabric throughput suite (configs/sec) on the devices this
    process has. One ragged 4-scenario x 4-seed trial table through
    the sweep fabric on 1 device (plain vmap) vs all of them
    (``shard_map``), best-of-2 timed runs each, compile excluded:
    configs/sec per device count, bitwise parity, and the
    compile-reuse lock (a seed-only re-run must not add a jit-cache
    entry — the old per-call-jit recompile bug). With one device the
    two runs coincide and no scaling ratio is recorded."""
    import jax

    from repro.core import sweep_fabric as fabric

    n_devices = len(jax.devices())
    cfg = api.make_config("fitgpp", n_jobs=256, n_nodes=8, seed=0)
    names = ("te-flood", "long-tail-be", "burst-storm", "diurnal")
    n_seeds = SWEEP_TRIALS // len(names)
    jobsets = [scenarios.build(nm, dataclasses.replace(cfg, seed=sd))
               for nm in names for sd in range(n_seeds)]
    seeds = np.arange(SWEEP_TRIALS, dtype=np.uint32)
    table = fabric.build_table(jobsets, 4.0, 1, seeds)
    out: Dict = {
        "workload": {"scenarios": list(names), "n_seeds": n_seeds,
                     "n_jobs": 256, "n_nodes": 8, "policy": "fitgpp"},
        "n_trials": SWEEP_TRIALS, "n_devices": n_devices,
        "platform": jax.devices()[0].platform,
    }
    results, cps = {}, {}
    for d in sorted({1, n_devices}):
        # devices=1 resolves to the plain single-device vmap
        # (mesh_for_sweep returns None), devices=N to the shard_map
        # fabric — NOT mesh=None, which means "auto-pick all devices"
        res = fabric.run_table(cfg, table, devices=d,
                               donate=False)              # compile
        if res.n_devices != d:
            raise AssertionError(
                f"sweep asked for {d} devices, fabric used "
                f"{res.n_devices}")
        best = 1e9
        for _ in range(2):
            t0 = time.perf_counter()
            res = fabric.run_table(cfg, table, devices=d, donate=False)
            best = min(best, time.perf_counter() - t0)
        results[d] = res
        cps[d] = SWEEP_TRIALS / best
        out[f"devices_{d}"] = {"seconds": best,
                               "configs_per_sec": cps[d],
                               "sharded": d > 1}
    diff = [k for k in results[1].stats
            if not np.array_equal(results[1].stats[k],
                                  results[n_devices].stats[k],
                                  equal_nan=True)]
    if diff:
        raise AssertionError(
            f"sweep sharded-vs-single parity violated: {diff}")
    out["parity"] = True
    if n_devices > 1:
        out["scaling_x"] = cps[n_devices] / cps[1]
    # compile-reuse lock: fresh seed values, same shapes -> the cached
    # runner must serve the run without a new jit-cache entry
    before = fabric.compile_stats()
    table2 = fabric.build_table(jobsets, 4.0, 1, seeds + 1000)
    fabric.run_table(cfg, table2, devices=n_devices, donate=False)
    after = fabric.compile_stats()
    if after != before:
        raise AssertionError(
            f"sweep compile-reuse violated: {before} -> {after}")
    out["compile_reuse"] = True
    out["compile_stats"] = after
    out["max_rss_mb"] = _rss_mb()
    return out


def _falsy_parity(obj, path: str = "") -> List[str]:
    bad = []
    if isinstance(obj, dict):
        for k, v in obj.items():
            here = f"{path}.{k}" if path else str(k)
            if k == "parity" and not v:
                bad.append(here)
            bad.extend(_falsy_parity(v, here))
    return bad


def check_parity_rows(out: dict) -> List[str]:
    """Problems with the artifact's parity rows: any falsy value, AND
    any row where the expected ``parity`` key is missing entirely.

    The benchmark raises in-run when a comparison fails, so a false
    value should never be emitted — the real hazard is a refactor that
    stops RUNNING a check and drops (or never writes) the key. The CI
    gate therefore requires the key to be present on the tick-vs-event
    row, on every scenario-suite row, and on the score-backend row."""
    bad = _falsy_parity(out)
    if "parity" not in out:
        bad.append("missing: parity (reference tick-vs-event)")
    if "parity" not in out.get("stream", {}):
        bad.append("missing: stream.parity (streamed-vs-monolithic "
                   "bit-parity window)")
    if "parity" not in out.get("stream_closed_loop", {}):
        bad.append("missing: stream_closed_loop.parity (streamed "
                   "closed-loop admission bit-parity window)")
    suite = out.get("scenario_suite")
    if not suite:
        bad.append("missing: scenario_suite")
    else:
        bad.extend(f"missing: scenario_suite.{name}.parity"
                   for name, row in suite.items() if "parity" not in row)
    if "parity" not in out.get("score_backend", {}):
        bad.append("missing: score_backend.parity")
    sweep_row = out.get("sweep_throughput", {})
    if "parity" not in sweep_row:
        bad.append("missing: sweep_throughput.parity (sharded vs "
                   "single-device bitwise)")
    if not sweep_row.get("compile_reuse"):
        bad.append("missing/false: sweep_throughput.compile_reuse "
                   "(seed-only re-run must not recompile)")
    return bad


SPEED_TOL = 1.0          # jax_event must not lose to the reference


def check_speed_rows(out: dict) -> List[str]:
    """Scenario-suite rows where ``jax_event`` is slower than the
    reference event engine: the JAX engine must not LOSE to numpy on
    any registered scenario at the suite size (this is the gate the
    diurnal / trace-proxy regressions used to fail). The scaling rows
    track the dense trajectory and are recorded, not gated — the
    interpret-mode CPU numbers at 4096 are not the TPU target."""
    bad = []
    for name, row in (out.get("scenario_suite") or {}).items():
        sp = row.get("speedup_vs_ref")
        if sp is None:
            bad.append(f"missing: scenario_suite.{name}.speedup_vs_ref")
        elif sp < SPEED_TOL:
            bad.append(f"slow: scenario_suite.{name} jax_event at "
                       f"{sp:.2f}x vs reference")
    if "njobs_scaling" not in out:
        bad.append("missing: njobs_scaling")
    sweep_row = out.get("sweep_throughput", {})
    sx = sweep_row.get("scaling_x")
    if sx is None and sweep_row.get("n_devices", 1) > 1:
        bad.append("missing: sweep_throughput.scaling_x")
    elif sx is not None and sx < SPEED_TOL:
        bad.append(f"slow: sweep_throughput sharded fabric at "
                   f"{sx:.2f}x vs single-device vmap")
    return bad


def emit_json(path: str = "BENCH_sim_engine.json") -> dict:
    # the stream suites run FIRST: their max_rss_mb rows carry the
    # bounded-memory claim and ru_maxrss is a process-wide high-water
    # mark, so nothing may inflate the peak before them
    stream_rows = bench_stream()
    stream_cl_rows = bench_stream_closed_loop()
    out = bench_tick_vs_event()
    out["stream"] = stream_rows
    out["stream_closed_loop"] = stream_cl_rows
    out["scenario_suite"] = bench_scenario_suite()
    out["njobs_scaling"] = bench_njobs_scaling()
    out["score_backend"] = bench_score_backend()
    out["sweep_throughput"] = bench_sweep_throughput()
    bad = check_parity_rows(out) + check_speed_rows(out)
    if bad:
        raise AssertionError(f"bench gates failed: {bad}")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    return out


def smoke(n_jobs: int = 64, seed: int = 0,
          trace_out: str = None) -> None:
    """CI fast-lane smoke: one tiny scenario through the reference
    engine and the JAX engine with the FUSED score backend
    (``score_backend="pallas"`` routes the whole schedule pass through
    the Pallas ``schedule_step`` kernel), asserting jnp-vs-pallas
    full-State parity, PLUS the trace round-trip: traced reference vs
    decoded JAX ring (exact event parity), schema validation, and both
    export formats re-read / re-replayed. ``trace_out`` writes the
    Perfetto JSON as a CI artifact. Seconds, not minutes: one compile
    each."""
    from repro.obs import export, schema, timeseries
    cfg = api.make_config("fitgpp", n_jobs=n_jobs, n_nodes=4, seed=seed)
    js = scenarios.build("paper-synthetic", cfg)
    jobs = sim_jax.jobs_from_jobset(js)
    st_j = sim_jax.run_jit(cfg, jobs, seed, time_mode="event")
    st_p = sim_jax.run_jit(dataclasses.replace(cfg, score_backend="pallas"),
                           jobs, seed, time_mode="event")
    diff = sim_jax.state_diff_fields(st_j, st_p)
    if diff:
        raise SystemExit(f"smoke: jnp-vs-pallas state diff in {diff}")
    # trace round-trip on a deterministic preemption-exercising config
    # (lrtp never takes the random fallback here — asserted, so the
    # cross-engine comparison is exact by contract, DESIGN.md §8)
    cfg = api.make_config("lrtp", n_jobs=n_jobs, n_nodes=6, seed=seed)
    js = scenarios.build("paper-synthetic", cfg)
    res = simulator.simulate(cfg, js, mode="event", trace=True)
    jobs = sim_jax.jobs_from_jobset(js)
    st_t = sim_jax.run_jit(cfg, jobs, seed, time_mode="event", trace=True)
    if int(st_t.fallback_count):
        raise SystemExit("smoke: fallback fired; trace parity not exact")
    events, overflow = sim_jax.decode_trace(st_t)
    if overflow:
        raise SystemExit(f"smoke: trace ring overflowed ({overflow} rows)")
    metrics.assert_trace_parity(res.trace, events)
    schema.validate_events(events, n_jobs=js.n,
                           n_nodes=cfg.cluster.n_nodes)
    # both export formats: CSV must round-trip losslessly, the
    # Perfetto JSON must re-replay into the same telemetry series
    if export.read_csv(export.to_csv(events)) != events:
        raise SystemExit("smoke: CSV trace round-trip diverged")
    ts = timeseries.compute_timeseries(events, cfg.cluster.n_nodes,
                                       is_te=js.is_te)
    pf = export.to_perfetto(events, n_nodes=cfg.cluster.n_nodes,
                            is_te=js.is_te)
    if not pf["traceEvents"]:
        raise SystemExit("smoke: empty Perfetto trace")
    if trace_out:
        export.write_trace(trace_out, events, fmt="perfetto",
                           n_nodes=cfg.cluster.n_nodes, is_te=js.is_te)
    # streamed-engine parity window (DESIGN.md §10): the same jobs
    # through the slot-recycling macro-round engine — with real
    # recycling (capacity < n_jobs) — must equal the monolithic
    # engine bit-exactly; sub-critical load keeps the open-loop
    # backlog inside the pool
    from repro.core import stream
    scfg = api.make_config("fitgpp", n_jobs=160, n_nodes=8, seed=seed)
    scfg = dataclasses.replace(
        scfg, workload=dataclasses.replace(scfg.workload, load=0.5))
    sdiff = stream.verify_prefix_parity(scfg, n_jobs=160, capacity=64,
                                        chunk=48)
    if sdiff:
        raise SystemExit(f"smoke: stream-vs-monolithic diff in {sdiff}")
    # one streamed closed-loop round (§4.2 at load 2.0): admit ticks
    # AND scheduler outcome bit-exact with the monolithic
    # closed_loop_submit_times + run_jit pipeline (rank policy — the
    # score fallback fires at saturation and leaves the parity domain)
    ccfg = api.make_config("lrtp", n_jobs=160, n_nodes=8, seed=seed)
    ccfg = dataclasses.replace(
        ccfg, workload=dataclasses.replace(ccfg.workload, load=2.0))
    cdiff = stream.verify_closed_loop_parity(ccfg, n_jobs=160,
                                             capacity=96, chunk=48)
    if cdiff:
        raise SystemExit(f"smoke: streamed closed-loop diff in {cdiff}")
    print(f"smoke ok: {n_jobs} jobs, fused-backend parity verified, "
          f"{len(events)} events trace-parity ok, "
          f"util {ts.mean_utilization():.2f}, streamed parity ok, "
          f"closed-loop parity ok"
          + (f", trace -> {trace_out}" if trace_out else ""))


def run_all() -> List[tuple]:
    rows = []
    n = 2048
    cfg = SimConfig(workload=WorkloadSpec(n_jobs=n), policy="fitgpp")
    jobs = workload.generate(cfg)

    t0 = time.perf_counter()
    simulator.simulate(cfg, jobs, mode="tick")
    rows.append(("sim_reference_2k_tick", (time.perf_counter() - t0) * 1e6,
                 "numpy heaps, minute ticks"))

    t0 = time.perf_counter()
    simulator.simulate(cfg, jobs, mode="event")
    rows.append(("sim_reference_2k_event", (time.perf_counter() - t0) * 1e6,
                 "numpy heaps, event jumps"))

    ev = bench_tick_vs_event()
    rows.append(("sim_sparse_512_tick", ev["tick"]["seconds"] * 1e6,
                 f"{ev['tick']['jobs_per_sec']:.0f} jobs/s"))
    rows.append(("sim_sparse_512_event", ev["event"]["seconds"] * 1e6,
                 f"{ev['event']['jobs_per_sec']:.0f} jobs/s, "
                 f"{ev['speedup']:.1f}x"))

    jj = sim_jax.jobs_from_jobset(jobs)
    st = sim_jax.run_jit(cfg, jj, 0)           # compile
    st.t.block_until_ready()
    t0 = time.perf_counter()
    st = sim_jax.run_jit(cfg, jj, 0)
    st.t.block_until_ready()
    rows.append(("sim_jax_2k", (time.perf_counter() - t0) * 1e6,
                 "lax.while_loop"))

    t0 = time.perf_counter()
    out = api.sensitivity_grid(cfg, 512, s_vals=[0.0, 2.0, 4.0, 8.0],
                               seeds=[0, 1])
    rows.append(("sim_sweep_8trials", (time.perf_counter() - t0) * 1e6,
                 "vmap(8 sims)"))

    for name, r in bench_scenario_suite().items():
        rows.append((f"scenario_{name}", r["seconds"] * 1e6,
                     f"{r['n_jobs']} jobs, {r['makespan_ticks']} ticks, "
                     f"{r['jobs_per_sec']:.0f} jobs/s, "
                     f"util {r['utilization']:.2f}, "
                     f"{r['preempt_rate']:.3f} preempts/min"))
        if "jax_event" in r:
            rows.append((f"scenario_{name}_jax_event",
                         r["jax_event"]["seconds"] * 1e6,
                         f"{r['jax_event']['jobs_per_sec']:.0f} jobs/s, "
                         f"{r['jax_speedup']:.1f}x vs jax_tick, "
                         f"traced {r['trace_overhead']:.2f}x, "
                         f"fallback {r['fallback_count']}, parity ok"))

    sb = bench_score_backend()
    for backend in ("jnp", "pallas"):
        rows.append((f"sim_jax_score_{backend}",
                     sb[backend]["seconds"] * 1e6,
                     f"{sb[backend]['jobs_per_sec']:.0f} jobs/s, parity ok"))

    sr = bench_stream(n_jobs=8192, capacity=1024)
    rows.append(("sim_stream_8k", sr["full"]["seconds"] * 1e6,
                 f"{sr['full']['jobs_per_sec']:.0f} jobs/s, "
                 f"{sr['full']['rounds']} rounds, capacity 1024, "
                 f"rss {sr['full']['max_rss_mb']:.0f}MB, parity ok"))

    cl = bench_stream_closed_loop(n_jobs=8192, capacity=1024)
    rows.append(("sim_stream_closed_8k", cl["full"]["seconds"] * 1e6,
                 f"{cl['full']['jobs_per_sec']:.0f} jobs/s, load 2.0, "
                 f"{cl['full']['rounds']} rounds, capacity 1024, "
                 f"spilled {cl['full']['n_spilled']}, "
                 f"rss {cl['full']['max_rss_mb']:.0f}MB, parity ok"))

    t0 = time.perf_counter()
    api.scenario_sweep(
        SimConfig(cluster=ClusterSpec(n_nodes=8),
                  workload=WorkloadSpec(n_jobs=256), policy="fitgpp"),
        ["te-flood", "long-tail-be", "burst-storm"], seeds=[0, 1])
    rows.append(("scenario_sweep_ragged_6", (time.perf_counter() - t0) * 1e6,
                 "vmap(3 scenarios x 2 seeds, sentinel-padded)"))

    sw = bench_sweep_throughput()
    sharded = sw[f"devices_{sw['n_devices']}"]
    scaling = (f", {sw['scaling_x']:.1f}x vs 1-device vmap"
               if "scaling_x" in sw else "")
    rows.append((f"sweep_fabric_{sw['n_trials']}trials",
                 sharded["seconds"] * 1e6,
                 f"{sharded['configs_per_sec']:.1f} configs/s on "
                 f"{sw['n_devices']} {sw['platform']} devices{scaling}, "
                 "parity ok"))
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="emit BENCH_sim_engine.json (tick vs event)")
    ap.add_argument("--out", default="BENCH_sim_engine.json")
    ap.add_argument("--check-parity", metavar="PATH",
                    help="validate an existing BENCH json: exit nonzero "
                         "if any in-run parity row is false or any "
                         "scenario's jax_event row lost to the "
                         "reference engine (CI gate)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scenario fused-backend + trace round-trip "
                         "smoke (CI fast lane)")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="with --smoke: write the smoke run's Perfetto "
                         "trace to PATH (CI artifact)")
    args = ap.parse_args(argv)
    if args.check_parity:
        with open(args.check_parity) as f:
            data = json.load(f)
        bad = check_parity_rows(data) + check_speed_rows(data)
        if bad:
            raise SystemExit(f"bench gates failed in {args.check_parity}: "
                             f"{bad}")
        print(f"{args.check_parity}: all parity and speed rows pass")
        return
    if args.smoke:
        smoke(trace_out=args.trace_out)
        return
    if args.json:
        out = emit_json(args.out)
        print(json.dumps(out, indent=2))
        return
    print("name,us_per_call,derived")
    for name, us, derived in run_all():
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()

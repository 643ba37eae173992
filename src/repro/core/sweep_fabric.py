"""Device-parallel sweep fabric: one sharded trial table for the whole
(scenario × policy × seed × s × P) grid.

The paper's sensitivity studies (§4.3, Figs. 4-7) are hundreds of
independent simulations. Each one is a pure-JAX program
(``core/sim_jax.py``), so a sweep is a *trial table* — stacked
``Jobs`` plus per-trial ``s`` / ``P`` / ``seed`` vectors — padded with
sentinel trials to the device count and ``shard_map``-ed over the 1-D
trial mesh from ``launch.mesh.mesh_for_sweep`` (DESIGN.md §11):

    table = sweep_fabric.build_table(jobsets, s_vals, P_vals, seeds)
    res = sweep_fabric.run_table(cfg, table)          # all local devices
    res.stats["te_slowdown"]                          # (T, 3) ndarray

Sharding is bit-exact with the single-device vmap: every lane of a
vmapped ``lax.while_loop`` computes its trial independently (the carry
is per-lane ``select``s), so grouping lanes into shards changes the
schedule, not the values — and it is *faster even on one core*,
because the vmapped loop runs lockstep (every lane steps until the
slowest finishes) while each shard only runs to its own slowest lane.

Axis contract: ``policy`` (and every other ``SimConfig`` field) is
compile-STATIC — one jitted program per config, cached in ``_RUNNERS``
so repeated calls (and seed-only re-runs) never recompile. ``s`` /
``P`` / ``seed`` are TRACED per-trial inputs: a whole sensitivity grid
over them shares one compilation. Multi-policy grids are one
``run_table`` call per policy over the same table.

Donation: with ``donate=True`` (auto on gpu/tpu backends, where XLA
implements input aliasing) the table's ``Jobs`` buffers are donated
into the jitted program, keeping per-shard memory flat; the trial
table is then CONSUMED by the call. ``init_state`` force-copies
``exec_total`` precisely so this aliasing is safe. The CPU backend
ignores donation, so ``donate=None`` resolves to False there.

``core/sweep.py`` (``run_sweep`` / ``sensitivity_grid`` /
``scenario_sweep``) is a thin wrapper over this module; callers reach
both through ``repro.api``.

Self-test (parity of sharded vs single-device, sentinel padding
exercised) — requires a multi-device runtime, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.core.sweep_fabric \\
        --policies deterministic --modes event,tick
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.cluster import SimConfig
from repro.core import metrics, sim_jax
from repro.core.types import JobSet
from repro.launch.mesh import mesh_for_sweep
from repro.sharding import put_trial_sharded, trial_spec

__all__ = [
    "SweepResult", "TrialTable", "build_table", "compile_stats",
    "pad_jobs", "pad_table", "pooled_tables", "run_table",
    "stack_jobsets", "table_from_stacked",
]


# ---------------------------------------------------------------- jobs

def pad_jobs(jobs: sim_jax.Jobs, n_max: int) -> sim_jax.Jobs:
    """Pad a Jobs struct to ``n_max`` rows with sentinel jobs.

    Sentinels carry zero demand, unit execution, ``width=1`` and
    ``valid=False``; ``sim_jax.init_state`` births them DONE so they
    never arrive, queue, run or appear as preemption candidates, and
    every percentile in the per-trial summaries masks them out (the
    sentinel-padding contract, DESIGN.md §5). Real rows keep their
    gang widths through the padding."""
    pad = n_max - jobs.submit.shape[0]
    if pad < 0:
        raise ValueError(f"cannot pad {jobs.submit.shape[0]} jobs "
                         f"down to {n_max}")
    if pad == 0:
        return jobs

    def ext(x, fill):
        tail = jnp.full((pad,) + x.shape[1:], fill, x.dtype)
        return jnp.concatenate([x, tail])

    return sim_jax.Jobs(
        submit=ext(jobs.submit, 0), exec_total=ext(jobs.exec_total, 1),
        demand=ext(jobs.demand, 0.0), is_te=ext(jobs.is_te, False),
        gp=ext(jobs.gp, 0), width=ext(jobs.width, 1),
        valid=ext(jobs.valid, False),
        akey=None if jobs.akey is None else ext(jobs.akey, 0.0))


def stack_jobsets(jobsets: Sequence[JobSet]) -> sim_jax.Jobs:
    """Stack workloads over a leading trial axis.

    Equal-``n`` jobsets stack directly (the original fast path). Ragged
    collections — heterogeneous scenarios, trace replays — are padded to
    the max ``n`` with masked sentinel jobs (``pad_jobs``), so one
    vmapped/shard_mapped sweep can span them all. Gang widths
    (``JobSet.n_nodes`` → ``Jobs.width``) ride through both paths;
    sentinel rows stay width-1."""
    js = [sim_jax.jobs_from_jobset(j) for j in jobsets]
    n_max = max(j.submit.shape[0] for j in js)
    if any(j.submit.shape[0] != n_max for j in js):
        js = [pad_jobs(j, n_max) for j in js]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *js)


# --------------------------------------------------------- trial table

class TrialTable(NamedTuple):
    """The flattened sweep grid: trial t runs ``jobs[t]`` under
    ``(s[t], P[t], seed[t])``. ``n_valid`` counts the real trials; rows
    past it (appended by :func:`pad_table` for even device division)
    are sentinel trials — every job ``valid=False``, so the trial is
    born finished and exits its while_loop immediately."""
    jobs: sim_jax.Jobs       # leaves have leading (T,) axis
    s: jax.Array             # (T,) f32
    P: jax.Array             # (T,) i32
    seed: jax.Array          # (T,) u32
    n_valid: int


def table_from_stacked(jobs: sim_jax.Jobs, s_vals, P_vals,
                       seeds) -> TrialTable:
    """TrialTable from an already-stacked ``Jobs`` batch (the
    ``run_sweep`` calling convention). Scalars broadcast over T."""
    T = int(jobs.submit.shape[0])
    if T == 0:
        raise ValueError("empty trial table")

    def vec(x, dtype):
        a = jnp.asarray(x, dtype)
        if a.ndim == 0:
            a = jnp.full((T,), a, dtype)
        if a.shape != (T,):
            raise ValueError(f"per-trial vector has shape {a.shape}; "
                             f"expected ({T},)")
        return a

    return TrialTable(jobs=jobs, s=vec(s_vals, jnp.float32),
                      P=vec(P_vals, jnp.int32),
                      seed=vec(seeds, jnp.uint32), n_valid=T)


def build_table(jobsets: Sequence[JobSet], s_vals, P_vals,
                seeds) -> TrialTable:
    """TrialTable from one jobset per trial (``stack_jobsets`` pads
    ragged job counts with sentinel JOBS; :func:`pad_table` later pads
    the trial axis with sentinel TRIALS — same ``valid=False``
    contract, different axis)."""
    return table_from_stacked(stack_jobsets(jobsets), s_vals, P_vals,
                              seeds)


def pad_table(table: TrialTable, multiple: int) -> TrialTable:
    """Pad the trial axis to a multiple of ``multiple`` with sentinel
    trials, so an uneven grid still divides the device mesh evenly.
    A sentinel trial is all-sentinel jobs: born DONE, its while_loop
    exits on the first cond check and its summaries are all-nan —
    :func:`run_table` drops the padded rows before returning, and
    :func:`pooled_tables` never sees an invalid job."""
    T = int(table.s.shape[0])
    pad = -T % multiple
    if pad == 0:
        return table

    def ext(x, fill):
        tail = jnp.full((pad,) + x.shape[1:], fill, x.dtype)
        return jnp.concatenate([x, tail])

    j = table.jobs
    jobs = sim_jax.Jobs(
        submit=ext(j.submit, 0), exec_total=ext(j.exec_total, 1),
        demand=ext(j.demand, 0.0), is_te=ext(j.is_te, False),
        gp=ext(j.gp, 0), width=ext(j.width, 1),
        valid=ext(j.valid, False),
        akey=None if j.akey is None else ext(j.akey, 0.0))
    return TrialTable(jobs=jobs, s=ext(table.s, 0.0), P=ext(table.P, 0),
                      seed=ext(table.seed, 0), n_valid=table.n_valid)


# ----------------------------------------------------- per-trial stats

def _masked_pct(vals, mask, ps):
    """Stacked percentiles of ``vals[mask]`` — explicit ``nan`` when
    the mask selects nothing (a trial with zero valid TE or BE jobs
    after sentinel padding): the trial then drops out of every
    nan-aware pooled table instead of contributing garbage."""
    v = jnp.where(mask, vals, jnp.nan)
    some = mask.any()
    return jnp.stack([jnp.where(some, jnp.nanpercentile(v, p), jnp.nan)
                      for p in ps])


def _masked_frac(mask, hit):
    """Fraction of ``mask`` rows with ``hit`` set; nan for an empty
    class (same NaN-safety contract as :func:`_masked_pct`)."""
    frac = jnp.nanmean(jnp.where(mask, hit.astype(jnp.float32), jnp.nan))
    return jnp.where(mask.any(), frac, jnp.nan)


def _trial_percentiles(cfg: SimConfig, jobs: sim_jax.Jobs, s, P_, key,
                       time_mode: Optional[str] = None):
    """The classic ``run_sweep`` per-trial summary dict (kept
    key-for-key: callers index these names)."""
    st = sim_jax.run(cfg, jobs, seed=key, s=s, P=P_, time_mode=time_mode)
    sd = sim_jax.slowdown(jobs, st)
    te = jobs.is_te & jobs.valid

    iv = (st.last_resume - st.last_signal).astype(jnp.float32)
    iv_mask = (st.last_resume >= 0) & jobs.valid
    pc = st.preempt_count
    be = ~jobs.is_te & jobs.valid
    return {
        "te_slowdown": _masked_pct(sd, te, (50, 95, 99)),
        "be_slowdown": _masked_pct(sd, be, (50, 95, 99)),
        "intervals": _masked_pct(iv, iv_mask, (50, 75, 95, 99)),
        "preempted_frac": _masked_frac(be, pc > 0),
        "preempt_1": _masked_frac(be, pc == 1),
        "preempt_2": _masked_frac(be, pc == 2),
        "preempt_3plus": _masked_frac(be, pc >= 3),
        "makespan": st.t,
    }


def _trial_per_job(cfg: SimConfig, jobs: sim_jax.Jobs, s, P_, key,
                   time_mode: Optional[str]):
    """Raw per-job arrays, for host-side pooling ACROSS trials
    (``pooled_tables`` — percentiles over the pooled per-job values,
    the paper's 8-workload pooling, not percentile-of-percentiles).
    Invalid (sentinel) jobs carry nan slowdown / nan interval /
    zero preempt_count; ``intervals`` is the LAST signal→resume gap
    per job (the JAX State tracks the most recent preemption — the
    same statistic ``api.run_experiment(engine="jax")`` reports, while
    the reference event stream can carry several gaps per job)."""
    st = sim_jax.run(cfg, jobs, seed=key, s=s, P=P_, time_mode=time_mode)
    sd = sim_jax.slowdown(jobs, st)
    iv = (st.last_resume - st.last_signal).astype(jnp.float32)
    iv_mask = (st.last_resume >= 0) & jobs.valid
    # valid/is_te ride through as OUTPUTS so pooling never has to read
    # the (possibly donated) input table
    return {
        "slowdown": jnp.where(jobs.valid, sd, jnp.nan),
        "preempt_count": jnp.where(jobs.valid, st.preempt_count, 0),
        "intervals": jnp.where(iv_mask, iv, jnp.nan),
        "valid": jobs.valid,
        "is_te": jobs.is_te,
        "makespan": st.t,
        "fallback_count": st.fallback_count,
    }


_TRIAL_FNS = {"percentiles": _trial_percentiles, "per_job": _trial_per_job}


# -------------------------------------------------------- the runners

# (cfg, time_mode, out, mesh, donate) -> jitted vmapped/shard_mapped
# runner. Module-level so repeated run_table calls — and seed-only
# re-runs, the old per-call jit recompile bug — reuse one compilation.
_RUNNERS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _runner(cfg: SimConfig, time_mode: Optional[str], out: str,
            mesh: Optional[Mesh], donate: bool):
    key = (cfg, time_mode, out, mesh, donate)
    fn = _RUNNERS.get(key)
    if fn is not None:
        return fn
    trial = _TRIAL_FNS[out]

    def one(jobs_t, s, P_, seed):
        return trial(cfg, jobs_t, s, P_, jax.random.key(seed), time_mode)

    batched = jax.vmap(one)
    if mesh is not None:
        spec = trial_spec(mesh)
        batched = jax.shard_map(batched, mesh=mesh, in_specs=(spec,) * 4,
                                out_specs=spec, check_vma=False)
    fn = jax.jit(batched, donate_argnums=(0,) if donate else ())
    _RUNNERS[key] = fn
    return fn


def compile_stats() -> Dict[str, int]:
    """Observability for the compile-once contract: ``runners`` is the
    number of distinct (cfg, mode, out, mesh, donate) programs built;
    ``compiles`` the total jit-cache entries behind them. A seed/s/P
    re-run must leave both unchanged (locked by the bench's
    ``compile_reuse`` row and tests)."""
    return {"runners": len(_RUNNERS),
            "compiles": sum(f._cache_size() for f in _RUNNERS.values())}


# ------------------------------------------------------------ results

@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Host-side result of one fabric run: ``stats`` maps summary name
    to an ndarray with leading trial axis (sentinel-padding rows
    already dropped — every array has exactly ``n_trials`` rows).
    ``out`` records which per-trial summary produced it
    ("percentiles": the classic ``run_sweep`` dict; "per_job": raw
    per-job arrays for :func:`pooled_tables`)."""
    stats: Dict[str, np.ndarray]
    n_trials: int
    n_padded: int
    n_devices: int
    out: str
    time_mode: str

    def __getitem__(self, k: str) -> np.ndarray:
        return self.stats[k]


def run_table(cfg: SimConfig, table: TrialTable, *,
              mesh: Optional[Mesh] = None,
              devices: Optional[int] = None,
              time_mode: Optional[str] = None,
              out: str = "percentiles",
              donate: Optional[bool] = None) -> SweepResult:
    """Run every trial of ``table`` under the static ``cfg``; the one
    entry point everything batches through.

    ``mesh`` (or ``devices``, via ``mesh_for_sweep``; default: every
    local device) picks the trial mesh — the table is sentinel-padded
    to its data-axis size, sharded with ``shard_map`` and gathered
    back to host with the padding rows dropped. A 1-device mesh (or
    ``devices=1``) is the plain single-device vmap; results are
    bit-identical either way. ``time_mode`` defaults to
    ``cfg.time_mode``; ``out`` selects the per-trial summary
    (:data:`_TRIAL_FNS`). ``donate=None`` auto-enables buffer donation
    where XLA supports it (gpu/tpu) — the table is then consumed by
    the call; pass ``donate=False`` to re-run one table."""
    if out not in _TRIAL_FNS:
        raise ValueError(f"unknown out {out!r}; one of "
                         f"{tuple(_TRIAL_FNS)}")
    if time_mode is None:
        time_mode = cfg.time_mode
    T = int(table.s.shape[0])
    if mesh is None:
        mesh = mesh_for_sweep(T, devices=devices)
    spec_axis = None if mesh is None else trial_spec(mesh)[0]
    n_dev = 1 if mesh is None else mesh.shape[spec_axis]
    if n_dev <= 1:
        mesh = None
        n_dev = 1
    if donate is None:
        donate = sim_jax.donation_supported()

    padded = pad_table(table, n_dev)
    args = (padded.jobs, padded.s, padded.P, padded.seed)
    if mesh is not None:
        args = put_trial_sharded(mesh, args)
    raw = _runner(cfg, time_mode, out, mesh, donate)(*args)
    stats = {k: np.asarray(v)[:T] for k, v in raw.items()}
    return SweepResult(stats=stats, n_trials=T,
                       n_padded=int(padded.s.shape[0]) - T,
                       n_devices=n_dev, out=out, time_mode=time_mode)


def pooled_tables(result: SweepResult,
                  trials: Optional[Sequence[int]] = None) -> Dict:
    """Paper-style pooled tables from a ``per_job`` fabric run —
    percentiles over the POOLED per-job values across trials (the
    paper pools its 8 workloads per cell), mirroring
    ``metrics.pooled_tables`` key-for-key. ``trials`` selects the
    subset of trial rows forming one cell (default: all); sentinel
    jobs (and any sentinel-trial rows a caller kept) are masked via
    the ``valid`` output column."""
    if result.out != "per_job":
        raise ValueError("pooled_tables needs a per_job SweepResult; "
                         f"got out={result.out!r}")
    idx = (np.arange(result.n_trials) if trials is None
           else np.asarray(trials, np.intp))
    valid = result.stats["valid"][idx]
    is_te = result.stats["is_te"][idx]
    sd = result.stats["slowdown"][idx]
    pc = result.stats["preempt_count"][idx]
    iv = result.stats["intervals"][idx]
    te, be = valid & is_te, valid & ~is_te
    pc_be = pc[be]
    n_be = len(pc_be) if len(pc_be) else float("nan")
    return {
        "TE": metrics.percentiles(sd[te]),
        "BE": metrics.percentiles(sd[be]),
        "intervals": metrics.percentiles(iv[~np.isnan(iv)],
                                         ps=(50, 75, 95, 99)),
        "preempted_frac": (float((pc_be > 0).mean()) if len(pc_be)
                           else float("nan")),
        "preempt_counts": {
            "1": float((pc_be == 1).sum()) / n_be,
            "2": float((pc_be == 2).sum()) / n_be,
            ">=3": float((pc_be >= 3).sum()) / n_be,
        },
    }


# ----------------------------------------------------------- selftest

def _deterministic_policies() -> List[str]:
    from repro.core import policy_registry
    from repro.core.policy_registry import RNG_ALWAYS
    return [sp.name for sp in policy_registry.all_policies()
            if sp.dual_backend and sp.rng != RNG_ALWAYS]


def _selftest(argv=None) -> None:
    """Sharded-vs-single-device parity on the live device set: every
    requested policy × time mode runs one preemption-heavy grid (a
    trial count that does NOT divide the mesh, so sentinel-trial
    padding is exercised) through the single-device vmap and the
    sharded fabric, asserting bit-identical SweepResult tables.
    Exits loudly when the runtime has fewer than 2 devices — run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    import argparse

    from repro import scenarios
    from repro.configs.cluster import ClusterSpec, WorkloadSpec

    ap = argparse.ArgumentParser(description=_selftest.__doc__)
    ap.add_argument("--policies", default="fitgpp",
                    help="csv, or 'deterministic' for every "
                         "deterministic dual-backend policy")
    ap.add_argument("--modes", default="event", help="csv of time modes")
    ap.add_argument("--scenario", default="burst-storm",
                    help="preemption-heavy scenario family")
    ap.add_argument("--n-jobs", type=int, default=64)
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--n-seeds", type=int, default=3)
    ap.add_argument("--s-vals", default="0,2,4")
    args = ap.parse_args(argv)

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise SystemExit(
            "sweep_fabric selftest needs >= 2 devices; run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    policies = (_deterministic_policies()
                if args.policies == "deterministic"
                else args.policies.split(","))
    modes = args.modes.split(",")
    s_list = [float(x) for x in args.s_vals.split(",")]

    base = SimConfig(cluster=ClusterSpec(n_nodes=args.nodes),
                     workload=WorkloadSpec(n_jobs=args.n_jobs))
    jobsets = [scenarios.build(args.scenario,
                               dataclasses.replace(base, seed=sd))
               for sd in range(args.n_seeds)]
    T = args.n_seeds * len(s_list)
    s_flat = np.repeat(np.asarray(s_list, np.float32), args.n_seeds)
    seeds = np.tile(np.arange(args.n_seeds, dtype=np.uint32),
                    len(s_list))
    table = build_table(jobsets * len(s_list), s_flat, 1, seeds)
    if T <= n_dev or T % n_dev == 0:
        raise SystemExit(f"selftest wants T={T} trials > {n_dev} "
                         f"devices and NOT divisible by them (sentinel "
                         f"padding must be exercised); adjust "
                         f"--n-seeds/--s-vals")

    for pol in policies:
        cfg = dataclasses.replace(base, policy=pol)
        for mode in modes:
            single = run_table(cfg, table, devices=1, time_mode=mode,
                               donate=False)
            shard = run_table(cfg, table, time_mode=mode, donate=False)
            assert shard.n_devices == n_dev and shard.n_padded > 0
            diff = [k for k in single.stats
                    if not np.array_equal(single.stats[k],
                                          shard.stats[k],
                                          equal_nan=True)]
            if diff:
                raise SystemExit(f"parity FAILED: {pol}/{mode} sharded "
                                 f"vs single-device diff in {diff}")
            print(f"ok {pol:12s} {mode:5s}: {T} trials on {n_dev} "
                  f"devices (pad {shard.n_padded}) bit-exact")
    st = compile_stats()
    print(f"selftest ok: {len(policies)} policies x {len(modes)} modes, "
          f"{st['runners']} compiled runners")


if __name__ == "__main__":
    _selftest()

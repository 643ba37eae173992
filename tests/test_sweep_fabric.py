"""Sweep-fabric contracts (DESIGN.md §11).

In-process tests run on the one CPU device the suite sees (the fabric
then takes its single-device vmap path — bit-identical to the sharded
one by construction); actual multi-device sharding parity runs in
SUBPROCESSES under ``XLA_FLAGS=--xla_force_host_platform_device_count``
via the module's selftest CLI, because the flag must be set before jax
initializes. Covered here:

- sentinel-TRIAL padding: a table padded to a device multiple returns
  bit-identical real rows, and the sentinel rows never leak into
  ``SweepResult`` or ``pooled_tables``;
- the ``run_sweep`` wrapper is the fabric (same arrays, classic keys);
- ``pooled_tables`` matches ``metrics.pooled_tables`` on the reference
  engine for a deterministic policy (slowdowns to f32, preemption
  accounting exactly; resched intervals excluded — the fabric pools
  the JAX State's last signal→resume gap per job, the reference
  engine every gap);
- ``mesh_for_sweep`` raises when asked for more devices than exist;
- the compile-once contract: seed/s/P re-runs add no compilations
  (the old per-call ``run_sweep`` recompile bug);
- donation is alias-safe and a no-op on CPU.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import scenarios
from repro.configs.cluster import ClusterSpec, SimConfig, WorkloadSpec
from repro.core import metrics, sim_jax, simulator, sweep_fabric
from repro.core import sweep
from repro.launch.mesh import mesh_for_sweep

def _cfg(policy="fitgpp", n_jobs=64, nodes=8, **kw):
    return SimConfig(cluster=ClusterSpec(n_nodes=nodes),
                     workload=WorkloadSpec(n_jobs=n_jobs),
                     policy=policy, **kw)


def _table(n_seeds=3, n_jobs=64, scenario="burst-storm", s=4.0, P=1):
    base = _cfg(n_jobs=n_jobs)
    jobsets = [scenarios.build(scenario, dataclasses.replace(base, seed=k))
               for k in range(n_seeds)]
    return base, sweep_fabric.build_table(
        jobsets, s, P, np.arange(n_seeds, dtype=np.uint32))


def _assert_stats_equal(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert np.array_equal(a[k], b[k], equal_nan=True), k


class TestSentinelPadding:
    def test_padded_rows_are_dropped_and_bit_exact(self):
        cfg, table = _table(n_seeds=3)
        plain = sweep_fabric.run_table(cfg, table, devices=1,
                                       donate=False)
        padded = sweep_fabric.pad_table(table, 4)
        assert int(padded.s.shape[0]) == 4
        res = sweep_fabric.run_table(cfg, padded, devices=1, donate=False)
        # run_table drops nothing here (the pre-padded table IS the
        # table), so slice the sentinel row off before comparing
        assert res.n_trials == 4
        _assert_stats_equal(plain.stats,
                            {k: v[:3] for k, v in res.stats.items()})

    def test_sentinel_trial_is_born_done(self):
        cfg, table = _table(n_seeds=2)
        padded = sweep_fabric.pad_table(table, 3)
        res = sweep_fabric.run_table(cfg, padded, devices=1, donate=False)
        # the sentinel trial never runs a job: zero makespan, all-nan
        # summaries (every percentile mask is empty)
        assert res.stats["makespan"][2] == 0
        assert np.isnan(res.stats["te_slowdown"][2]).all()
        assert np.isnan(res.stats["preempted_frac"][2])

    def test_sentinel_rows_masked_from_pooled_tables(self):
        cfg, table = _table(n_seeds=2)
        padded = sweep_fabric.pad_table(table, 3)
        ref = sweep_fabric.run_table(cfg, table, devices=1,
                                     out="per_job", donate=False)
        res = sweep_fabric.run_table(cfg, padded, devices=1,
                                     out="per_job", donate=False)
        # pooling all 3 rows of the padded run == pooling the 2 real
        # ones: sentinel jobs are masked via the valid output column
        assert (sweep_fabric.pooled_tables(res)
                == sweep_fabric.pooled_tables(ref))

    def test_pad_table_noop_when_even(self):
        _, table = _table(n_seeds=4)
        assert sweep_fabric.pad_table(table, 2) is table

    def test_build_table_validation(self):
        with pytest.raises(ValueError, match="empty"):
            sweep_fabric.build_table([], 4.0, 1, 0)
        _, table = _table(n_seeds=2)
        with pytest.raises(ValueError, match="shape"):
            sweep_fabric.table_from_stacked(
                table.jobs, np.zeros(3, np.float32), 1, 0)


class TestRunSweepWrapper:
    def test_wrapper_is_the_fabric(self):
        cfg, table = _table(n_seeds=3)
        res = sweep_fabric.run_table(cfg, table, devices=1, donate=False)
        via_wrapper = sweep.run_sweep(
            cfg, table.jobs, table.s, table.P, table.seed, devices=1)
        _assert_stats_equal(res.stats, via_wrapper)

    def test_classic_keys(self):
        cfg, table = _table(n_seeds=2)
        res = sweep_fabric.run_table(cfg, table, devices=1, donate=False)
        assert set(res.stats) == {
            "te_slowdown", "be_slowdown", "intervals", "preempted_frac",
            "preempt_1", "preempt_2", "preempt_3plus", "makespan"}
        assert res.stats["te_slowdown"].shape == (2, 3)
        assert res.stats["intervals"].shape == (2, 4)

    def test_pooled_tables_needs_per_job(self):
        cfg, table = _table(n_seeds=2)
        res = sweep_fabric.run_table(cfg, table, devices=1, donate=False)
        with pytest.raises(ValueError, match="per_job"):
            sweep_fabric.pooled_tables(res)


class TestPooledParity:
    def test_pooled_matches_reference_engine(self):
        """Fabric pooling == metrics.pooled_tables on the reference
        engine for a deterministic preemptive policy: slowdown
        percentiles to f32 precision, preemption accounting exactly.
        Resched intervals are excluded by design (last-gap vs
        every-gap; the engines agree on preempt_count, asserted
        below)."""
        n_seeds = 3
        base = _cfg(policy="lrtp", n_jobs=96)
        jobsets = [scenarios.build("burst-storm",
                                   dataclasses.replace(base, seed=k))
                   for k in range(n_seeds)]
        ref = metrics.pooled_tables(metrics.merge_results(
            [simulator.simulate(dataclasses.replace(base, seed=k), js)
             for k, js in enumerate(jobsets)]))
        table = sweep_fabric.build_table(
            jobsets, 4.0, 1, np.arange(n_seeds, dtype=np.uint32))
        res = sweep_fabric.run_table(base, table, devices=1,
                                     out="per_job", donate=False)
        fab = sweep_fabric.pooled_tables(res)
        for cls in ("TE", "BE"):
            for p, v in ref[cls].items():
                np.testing.assert_allclose(fab[cls][p], v, rtol=1e-6,
                                           err_msg=f"{cls}/{p}")
        assert fab["preempted_frac"] == pytest.approx(
            ref["preempted_frac"], abs=1e-12)
        for k in ("1", "2", ">=3"):
            assert fab["preempt_counts"][k] == pytest.approx(
                ref["preempt_counts"][k], abs=1e-12), k

    def test_cell_subsetting(self):
        # lrtp: exactly dual-backend (fitgpp's rng fallback can pick
        # different victims than the reference engine)
        base = _cfg(policy="lrtp")
        jobsets = [scenarios.build("burst-storm",
                                   dataclasses.replace(base, seed=k))
                   for k in range(3)]
        table = sweep_fabric.build_table(
            jobsets, 4.0, 1, np.arange(3, dtype=np.uint32))
        res = sweep_fabric.run_table(base, table, devices=1,
                                     out="per_job", donate=False)
        one = sweep_fabric.pooled_tables(res, trials=[1])
        cfg1 = dataclasses.replace(base, seed=1)
        ref = metrics.pooled_tables(metrics.merge_results(
            [simulator.simulate(cfg1, jobsets[1])]))
        np.testing.assert_allclose(one["BE"]["p50"], ref["BE"]["p50"],
                                   rtol=1e-6)


class TestMeshForSweep:
    def test_single_device_returns_none(self):
        if len(jax.devices()) != 1:
            pytest.skip("suite runs on one CPU device")
        assert mesh_for_sweep(8) is None

    def test_over_request_warns(self):
        """Asking for more devices than exist raises; there is no
        quiet fallback to fewer."""
        avail = len(jax.devices())
        with pytest.raises(ValueError, match="requested"):
            mesh_for_sweep(64, devices=avail + 7)
        with pytest.raises(ValueError, match="requested"):
            sweep_fabric.run_table(*_table(n_seeds=2), devices=avail + 1,
                                   donate=False)

    def test_capped_by_n_trials(self):
        assert mesh_for_sweep(1, devices=len(jax.devices())) is None

    def test_run_table_auto_mesh_single_device(self):
        if len(jax.devices()) != 1:
            pytest.skip("suite runs on one CPU device")
        cfg, table = _table(n_seeds=2)
        res = sweep_fabric.run_table(cfg, table, donate=False)
        assert res.n_devices == 1 and res.n_padded == 0


class TestCompileOnce:
    def test_seed_only_rerun_compiles_nothing(self):
        """The old run_sweep rebuilt its jitted trial fn per call, so
        sweeping seeds recompiled every time. The fabric caches one
        runner per (cfg, mode, out, mesh, donate): re-running with new
        seeds/s/P must not add runners or jit-cache entries."""
        cfg, table = _table(n_seeds=3)
        sweep_fabric.run_table(cfg, table, devices=1, donate=False)
        before = sweep_fabric.compile_stats()
        reseeded = table._replace(seed=table.seed + 1000,
                                  s=table.s + 1.0)
        sweep_fabric.run_table(cfg, reseeded, devices=1, donate=False)
        assert sweep_fabric.compile_stats() == before

    def test_new_policy_adds_one_runner(self):
        cfg, table = _table(n_seeds=2)
        sweep_fabric.run_table(cfg, table, devices=1, donate=False)
        before = sweep_fabric.compile_stats()["runners"]
        cfg2 = dataclasses.replace(cfg, policy="lrtp")
        sweep_fabric.run_table(cfg2, table, devices=1, donate=False)
        assert sweep_fabric.compile_stats()["runners"] == before + 1


class TestDonation:
    def test_donate_true_bit_exact_on_cpu(self):
        """XLA's CPU backend ignores donation, so donate=True must be
        a pure no-op there (and donation_supported() says so)."""
        assert sim_jax.donation_supported() == (
            jax.default_backend() in ("gpu", "tpu"))
        cfg, table = _table(n_seeds=2)
        base = sweep_fabric.run_table(cfg, table, devices=1,
                                      donate=False)
        donated = sweep_fabric.run_table(cfg, table, devices=1,
                                         donate=True)
        _assert_stats_equal(base.stats, donated.stats)

    def test_run_jit_donated_variant(self):
        cfg = _cfg(n_jobs=48)
        js = scenarios.build("burst-storm", cfg)
        jobs = sim_jax.jobs_from_jobset(js)
        st = sim_jax.run_jit(cfg, jobs)
        std = sim_jax.run_jit(cfg, jobs, donate=True)
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(std)):
            if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
                a, b = jax.random.key_data(a), jax.random.key_data(b)
            a, b = np.asarray(a), np.asarray(b)
            eq_nan = np.issubdtype(a.dtype, np.inexact)
            assert np.array_equal(a, b, equal_nan=eq_nan)


def _run_selftest(extra, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env.setdefault("JAX_PLATFORMS", "cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.core.sweep_fabric"] + extra,
        capture_output=True, text=True, env=env, timeout=timeout)


class TestShardedParitySubprocess:
    """The real multi-device runs: forced 8-device host mesh in a
    subprocess (XLA_FLAGS must precede jax init)."""

    def test_selftest_smoke(self):
        r = _run_selftest(["--policies", "fitgpp", "--modes", "event"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "bit-exact" in r.stdout and "selftest ok" in r.stdout

    @pytest.mark.slow
    def test_selftest_full_matrix(self):
        """Every deterministic dual-backend policy × both time modes,
        preemption-heavy scenario, uneven grid (sentinel trials), all
        sharded-vs-single bit-exact."""
        r = _run_selftest(["--policies", "deterministic",
                           "--modes", "event,tick"], timeout=1800)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "selftest ok" in r.stdout
        assert r.stdout.count("bit-exact") >= 2

"""The comparison sees the timed path broken underneath: each run drives
the harness as a chip run does (the look for a chip skipped), with one
fault planted in the program, and ``correct`` must come out false.

Faults, on each path: a step that returns its state unchanged; an
answer altered where it is produced; half of the batch left out. The
last two also on a gang mix (``gang-<path>``). No cell runs across
chips, so none can leave out an exchange between them."""
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402


def _incorrect(out, check):
    assert not out["correct"]
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]
    assert out["failed"] > 0


def test_replay_state_unchanged(tmp_path, monkeypatch):
    from repro.core import sim_jax

    def frozen(cfg, jobs, seed, time_mode, trace, cap):
        return sim_jax.init_state(jobs, cfg.cluster.n_nodes,
                                  cfg.cluster.node.as_tuple(), seed)
    monkeypatch.setattr(sim_jax, "_run_jit_full", frozen)
    _incorrect(tiny.execute(tmp_path, "replay"), "unfinished_jobs")


def _alter_replay(monkeypatch):
    """One job's finish tick one later, as the replay returns it."""
    from repro import api
    real = api.run_experiment

    def altered(*a, **k):
        r = real(*a, **k)
        jobs, st = r.raw
        return dataclasses.replace(
            r, raw=(jobs, st._replace(finish=st.finish.at[17].add(1))))
    monkeypatch.setattr(api, "run_experiment", altered)


def test_replay_answer_altered(tmp_path, monkeypatch):
    _alter_replay(monkeypatch)
    _incorrect(tiny.execute(tmp_path, "replay"), "finish_mismatch")


def test_stream_state_unchanged(tmp_path, monkeypatch):
    """Planted after the warm-up: the engine stops a round that makes
    no progress, so the unit ends in an error and its jobs count as
    unfinished."""
    from bench import run
    from repro.core import sim_jax
    real = run.run_units

    def broken(*a, **k):
        monkeypatch.setattr(sim_jax, "run_round",
                            lambda cfg, jobs, st, **kw: st)
        return real(*a, **k)
    monkeypatch.setattr(run, "run_units", broken)
    _incorrect(tiny.execute(tmp_path, "stream"), "unfinished_jobs")


def _alter_stream(monkeypatch):
    """One job's preemption count one higher, as the stream returns it."""
    from repro.core.stream import engine
    real = engine.StreamEngine._finalize

    def altered(self, *a):
        res = real(self, *a)
        res.preempt_count[100] += 1
        return res
    monkeypatch.setattr(engine.StreamEngine, "_finalize", altered)


def test_stream_answer_altered(tmp_path, monkeypatch):
    _alter_stream(monkeypatch)
    _incorrect(tiny.execute(tmp_path, "stream"), "preempt_mismatch")


def _half_replay(monkeypatch):
    """The replay program is handed only the first half of the jobs."""
    import jax.numpy as jnp

    from repro.core import sim_jax
    real = sim_jax.jobs_from_jobset

    def half(*a, **k):
        jobs = real(*a, **k)
        n = jobs.valid.shape[0]
        return jobs._replace(valid=jobs.valid & (jnp.arange(n) < n // 2))
    monkeypatch.setattr(sim_jax, "jobs_from_jobset", half)


def test_replay_half_the_batch_left_out(tmp_path, monkeypatch):
    _half_replay(monkeypatch)
    out = tiny.execute(tmp_path, "replay")
    assert not out["correct"] and out["failed"] >= 384 // 2


def _half_stream(monkeypatch):
    """The streaming engine packs only the first half of each batch of
    arrivals it takes from the source."""
    from repro.core.stream import JobSource
    real = JobSource.take

    def half(self, k):
        js = real(self, k)
        if js is None or js.n < 2:
            return js
        return type(js)(**{f: getattr(js, f)[:js.n // 2] for f in (
            "submit", "exec_total", "demand", "is_te", "gp", "n_nodes")})
    monkeypatch.setattr(JobSource, "take", half)


def test_stream_half_the_batch_left_out(tmp_path, monkeypatch):
    _half_stream(monkeypatch)
    out = tiny.execute(tmp_path, "stream")
    assert not out["correct"] and out["failed"] > 0


ALTER = {"replay": (_alter_replay, "finish_mismatch"),
         "stream": (_alter_stream, "preempt_mismatch")}
HALF = {"replay": _half_replay, "stream": _half_stream}


@pytest.mark.parametrize("path", ["replay", "stream"])
def test_gang_answer_altered(tmp_path, monkeypatch, path):
    plant, check = ALTER[path]
    plant(monkeypatch)
    _incorrect(tiny.execute(tmp_path, "gang-" + path), check)


@pytest.mark.parametrize("path", ["replay", "stream"])
def test_gang_half_the_batch_left_out(tmp_path, monkeypatch, path):
    HALF[path](monkeypatch)
    out = tiny.execute(tmp_path, "gang-" + path)
    assert not out["correct"] and out["failed"] > 0

"""The engine loop's iteration counter (``State.n_iter``) and the
schedule pass's named scope.

``n_iter`` counts ``_run_loop``'s ``while_loop`` iterations. It is a
performance counter, not simulation state, so the parity checks leave it
out (``sim_jax.state_diff_fields``); these tests pin what it reads and
how the streamed and monolithic counts relate."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro import api, scenarios
from repro.core import sim_jax, stream, workload


def _jobs(policy="fitgpp", scenario="paper-synthetic", n_jobs=256,
          n_nodes=8):
    cfg = api.make_config(policy, n_jobs=n_jobs, n_nodes=n_nodes, seed=3)
    return cfg, sim_jax.jobs_from_jobset(scenarios.build(scenario, cfg))


@pytest.mark.parametrize("policy,scenario", [("fitgpp", "paper-synthetic"),
                                             ("lrtp", "gang-heavy")])
def test_event_mode_iterates_no_more_than_tick_mode(policy, scenario):
    """Tick mode runs one iteration per tick, so its count is the
    makespan; event mode jumps over no-op ticks and reaches the same
    State in at most as many iterations."""
    cfg, jobs = _jobs(policy, scenario)
    tick = sim_jax.run_jit(cfg, jobs, cfg.seed, time_mode="tick")
    event = sim_jax.run_jit(cfg, jobs, cfg.seed, time_mode="event")
    assert sim_jax.state_diff_fields(tick, event) == []
    assert int(tick.n_iter) == int(tick.t)
    assert 0 < int(event.n_iter) <= int(tick.n_iter)


def test_state_diff_fields_leaves_out_n_iter():
    cfg, jobs = _jobs()
    st = sim_jax.run_jit(cfg, jobs, cfg.seed)
    assert sim_jax.state_diff_fields(
        st, st._replace(n_iter=st.n_iter + 5)) == []
    assert sim_jax.state_diff_fields(
        st, st._replace(t=st.t + 1, n_iter=st.n_iter + 5)) == ["t"]


def test_single_stepping_does_not_count():
    """``make_tick`` steps outside ``_run_loop``: its States keep the
    count they came with."""
    cfg, jobs = _jobs(n_jobs=32)
    st = sim_jax.init_state(jobs, cfg.cluster.n_nodes,
                            cfg.cluster.node.as_tuple(), cfg.seed)
    step = jax.jit(sim_jax.make_tick(cfg, jobs, cfg.cluster.n_nodes))
    assert int(step(step(st)).n_iter) == 0


@pytest.mark.parametrize("policy,mode", [("fitgpp", "event"),
                                         ("lrtp", "tick")])
def test_streamed_loop_iters_equal_monolithic(policy, mode):
    """In the parity window (``verify_prefix_parity``'s setup: load 0.5,
    400 jobs through a 96-slot pool in 64-job chunks) the streamed run
    takes exactly as many loop iterations as the monolithic one. A round
    stops at its boundary tick without running it, and the next round's
    first iteration runs it; a round that empties its pool stops where
    the monolithic loop would have jumped to the same next arrival,
    which the next round's first iteration does. So every monolithic
    iteration happens once, in exactly one round."""
    cfg = api.make_config(policy, n_jobs=400, n_nodes=8, seed=0)
    cfg = dataclasses.replace(
        cfg, workload=dataclasses.replace(cfg.workload, load=0.5))
    res = stream.StreamEngine(
        cfg, stream.JobSource(workload.stream_chunks(cfg, 400, chunk=64)),
        capacity=96, time_mode=mode).run()
    assert res.rounds > 1 and res.n_spilled == 0
    js = stream.materialize(
        stream.JobSource(workload.stream_chunks(cfg, 400, chunk=64)))
    mono = sim_jax.run_jit(cfg, sim_jax.jobs_from_jobset(js), cfg.seed,
                           time_mode=mode)
    assert res.makespan == int(mono.t)
    assert res.loop_iters == int(mono.n_iter) > 0


def test_experiment_result_loop_iters():
    jx = api.run_experiment("paper-synthetic", "fifo", "jax", n_jobs=128,
                            n_nodes=8)
    assert jx.loop_iters == int(jx.raw[1].n_iter) > 0
    ref = api.run_experiment("paper-synthetic", "fifo", "reference",
                             n_jobs=128, n_nodes=8)
    assert ref.loop_iters is None
    st = api.run_stream("paper-synthetic", "fifo", n_jobs=128, n_nodes=8)
    assert st.loop_iters == st.raw.loop_iters > 0


def _strip_metadata(hlo: str) -> str:
    """HLO text without op metadata and the stack-frame tables."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(re.sub(r",? ?metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def test_schedule_scope_changes_only_metadata(monkeypatch):
    """The ``schedule_pass`` named scope reaches the ops' metadata and
    nothing else: the optimised replay program is the same without
    it."""
    cfg, jobs = _jobs(n_jobs=64, n_nodes=4)

    def compiled():
        def impl(jobs, seed):           # a fresh function: no jit cache
            return sim_jax._run_jit_impl(cfg, jobs, seed, "event")
        return jax.jit(impl).lower(jobs, jnp.int32(0)).compile().as_text()

    scoped = compiled()
    monkeypatch.setattr(sim_jax, "_schedule_scope", contextlib.nullcontext)
    plain = compiled()
    assert "schedule_pass" in scoped and "schedule_pass" not in plain
    assert _strip_metadata(scoped) == _strip_metadata(plain)

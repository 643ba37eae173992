"""The benchmark's plain reference and generator, and the comparison's
control at a size a test run holds.

The control is the reference with one guarantee of the configuration
broken, in the program's place; the comparison must find it incorrect
on every seed, on single-node jobs and on gang mixes
(``perfbench_tiny.MIXES``). The reference imports nothing of the
program; here it is checked against the program's own numpy engine,
which it mirrors, job for job."""
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402
from bench import compare, gen, reference, run  # noqa: E402

SEEDS = (5, 7, 9)          # no random victim draw at this size
GANG_SEEDS = (3, 4, 9)     # nor on the gangs mix


@pytest.fixture(scope="module")
def config():
    return tiny.config()


def _jobs(config, seed, n=384):
    return gen.build(config, {"jobs": n, "arrivals": {
        "kind": "closed_loop", "load": 2.0}}, seed)


@pytest.mark.parametrize("mix,broken,seed", [
    pytest.param(None, b, s, id=f"{b}-{s}") for b in ("p_cap", "grace")
    for s in SEEDS] + [
    pytest.param("gangs", b, s, id=f"gangs-{b}-{s}")
    for b in ("p_cap", "grace") for s in GANG_SEEDS])
def test_control_is_incorrect(config, mix, broken, seed):
    config = tiny.config(mix) if mix else config
    js = _jobs(config, seed)
    pol = config["policy"]
    ref = reference.simulate(js, config["cluster"], "fitgpp", pol["s"],
                             pol["P"], seed)
    assert ref.draws == 0
    assert (js.width > 1).any() == bool(mix)
    sound = compare.tally(ref.finish, ref.preempt_count, ref.fallbacks,
                          ref)
    assert compare.passed(compare.checks(compare.total([sound])))
    ctl = compare.control(js, config["cluster"], pol, seed, broken)
    t = compare.tally(ctl.finish, ctl.preempt_count, ctl.fallbacks, ref)
    assert not compare.passed(compare.checks(compare.total([t])))
    assert t["finish_mismatch"] >= 10


@pytest.mark.parametrize("signals", [True, False])
def test_draws_are_replayed_from_the_outputs(config, signals):
    """Another engine with its own random draws (here the reference on
    another generator seed) is followed draw for draw, from its finish
    ticks and preemption counts, with or without its signal ticks."""
    js = _jobs(config, 6)
    cl = config["cluster"]
    other = reference.simulate(js, cl, "fitgpp", 4.0, 1, 6)
    own = reference.simulate(js, cl, "fitgpp", 4.0, 1, 7)
    assert other.fallbacks > 1 and not np.array_equal(own.finish,
                                                      other.finish)
    guide = reference.Guide(other.finish, other.preempt_count,
                            other.last_signal if signals else None)
    ref = reference.simulate(js, cl, "fitgpp", 4.0, 1, 7, guide=guide)
    assert ref.missed == 0 and ref.first_draw == -1
    t = compare.tally(other.finish, other.preempt_count, other.fallbacks,
                      ref)
    assert compare.passed(compare.checks(compare.total([t])))
    assert t["compared"] == js.n
    assert compare.tally(other.finish, other.preempt_count, 0,
                         ref)["draw_mismatch"] == 1


def test_unreplayable_draws_are_a_mismatch(config):
    js = _jobs(config, 6)
    cl = config["cluster"]
    other = reference.simulate(js, cl, "fitgpp", 4.0, 1, 6)
    wrong = other.finish.copy()
    wrong[:] = wrong[::-1]
    ref = reference.simulate(js, cl, "fitgpp", 4.0, 1, 7, max_tries=8,
                             guide=reference.Guide(wrong, other.preempt_count))
    t = compare.tally(wrong, other.preempt_count, other.fallbacks, ref)
    assert ref.missed >= 1 and not compare.passed(
        compare.checks(compare.total([t])))


@pytest.mark.parametrize("mix,seed", [
    pytest.param(None, s, id=str(s)) for s in SEEDS] + [
    pytest.param(m, s, id=f"{m}-{s}") for m in tiny.MIXES for s in SEEDS])
def test_reference_matches_the_programs_numpy_engine(config, mix, seed):
    """Job for job: submit ticks of the closed loop, finish ticks and
    preemption counts. The gang mixes take gang TE victims both ways
    (one victim, and several), gang victims of width-1 TE jobs on their
    best node, and random draws, which the reference's generator draws
    as the program's does."""
    from repro.configs.cluster import ClusterSpec, SimConfig
    from repro.core import simulator, workload
    from repro.core.types import JobSet
    config = tiny.config(mix) if mix else config
    js = _jobs(config, seed)
    assert (js.width > 1).any() == bool(mix)
    data = JobSet(submit=np.zeros(js.n, np.int64), exec_total=js.exec_total,
                  demand=js.demand, is_te=js.is_te, gp=js.gp,
                  n_nodes=js.width)
    cfg = SimConfig(cluster=ClusterSpec(n_nodes=24), seed=seed)
    admit = workload.closed_loop_submit_times(cfg, data)
    assert np.array_equal(admit, js.submit)
    theirs = simulator.simulate(cfg, dataclasses.replace(data, submit=admit))
    ours = reference.simulate(js, config["cluster"], "fitgpp", cfg.s,
                              cfg.max_preemptions, seed)
    assert np.array_equal(theirs.finish, ours.finish)
    assert np.array_equal(theirs.preempt_count, ours.preempt_count)


def test_generator_is_seeded(config):
    a, b = _jobs(config, 11, 128), _jobs(config, 11, 128)
    c = _jobs(config, 2 ** 31 + 11, 128)
    assert np.array_equal(a.submit, b.submit)
    assert np.array_equal(a.demand, b.demand)
    assert not np.array_equal(a.demand, c.demand)
    assert (a.is_te.mean() > 0.1) and (a.gp <= 20).all()


def test_generator_draws_gangs(config):
    """Widths come from a stream of their own: a gang mix draws the same
    jobs as its mix without gangs, and only its widths (and so its
    admit ticks) differ."""
    base = _jobs(config, 11)
    gangs = _jobs(tiny.config("gangs"), 11)
    wide = _jobs(tiny.config("wide-gangs"), 11)
    for js in (gangs, wide):
        for f in ("exec_total", "demand", "is_te", "gp"):
            assert np.array_equal(getattr(js, f), getattr(base, f))
        assert not np.array_equal(js.submit, base.submit)
    assert set(np.unique(gangs.width)) == {1, 2, 3, 4}
    assert 0.4 < (gangs.width > 1).mean() < 0.6
    assert set(np.unique(wide.width)) == {1, 6, 12}
    assert 0.15 < (wide.width > 1).mean() < 0.35
    wide = tiny.config("gangs", nodes=3)
    with pytest.raises(ValueError, match="gang widths"):
        _jobs(wide, 11)


def test_control_script(config, capsys):
    from bench import control
    c = run.load_cell("paper84-replay")
    c.traffic = dict(c.traffic, jobs=384)
    c.config = config
    r = control.readings(c, 5, "p_cap")
    assert r["finish_mismatch"] > 0 and r["compared"] == 384
    c.config = tiny.config("gangs")
    r = control.readings(c, 3, "grace")
    assert r["finish_mismatch"] > 0 and r["compared"] == 384

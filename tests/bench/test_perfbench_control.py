"""The benchmark's plain reference and generator, and the comparison's
control at a size a test run holds.

The control is the reference with one guarantee of the configuration
broken, in the program's place; the comparison must find it incorrect
on every seed. The reference imports nothing of the program; here it is
checked against the program's own numpy engine, which it mirrors."""
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import compare, gen, reference, run  # noqa: E402

SEEDS = (5, 7, 9)          # no random victim draw at this size


@pytest.fixture(scope="module")
def config():
    cfg = run.load_json("configs", "paper-84n")
    return dict(cfg, cluster=dict(cfg["cluster"], nodes=24))


def _jobs(config, seed, n=384):
    return gen.build(config, {"jobs": n, "arrivals": {
        "kind": "closed_loop", "load": 2.0}}, seed)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("broken", ["p_cap", "grace"])
def test_control_is_incorrect(config, seed, broken):
    js = _jobs(config, seed)
    pol = config["policy"]
    ref = reference.simulate(js, config["cluster"], "fitgpp", pol["s"],
                             pol["P"], seed)
    assert ref.fallbacks == 0
    sound = compare.tally(ref.finish, ref.preempt_count, 0, ref)
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


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_matches_the_programs_numpy_engine(config, seed):
    from repro.configs.cluster import ClusterSpec, SimConfig
    from repro.core import simulator, workload
    from repro.core.types import JobSet
    js = _jobs(config, seed)
    data = JobSet(submit=np.zeros(js.n, np.int64), exec_total=js.exec_total,
                  demand=js.demand, is_te=js.is_te, gp=js.gp)
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


def test_control_script(config, capsys):
    from bench import control
    c = run.load_cell("paper84-replay")
    c.traffic = dict(c.traffic, jobs=384)
    c.config = config
    r = control.readings(c, 5, "p_cap")
    assert r["finish_mismatch"] > 0 and r["compared"] == 384

"""The one traffic generator: job sets from a configuration's job mix, a
traffic file's sizes and arrivals, and ``--seed``.

Jobs are drawn per class (TE or BE, ``te_share``) from truncated normals
(resampled a few times, then clipped), GPU requests snapped to the
allocation quanta, grace periods from their own truncated normal, as in
the paper's §4.2 generator. Arrivals:

* ``closed_loop``: the paper's "load kept at ``load`` if scheduled by
  FIFO": a FIFO replay of the jobs (``reference.Simulator`` with an
  admission target) admits the next job whenever the backlog is below
  ``load``; the admit ticks become the submit times every engine sees.

A job set is a ``reference.Jobs``, drawn from
``numpy.random.default_rng((seed, 0))``, so a seed gives the same jobs in
every run.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def trunc_normal(rng, d: dict, size: int) -> np.ndarray:
    out = rng.normal(d["mean"], d["std"], size)
    bad = (out < d["lo"]) | (out > d["hi"])
    for _ in range(8):
        if not bad.any():
            break
        out[bad] = rng.normal(d["mean"], d["std"], int(bad.sum()))
        bad = (out < d["lo"]) | (out > d["hi"])
    return np.clip(out, d["lo"], d["hi"])


def _class(rng, cls: dict, n: int, quanta) -> tuple:
    exec_min = np.maximum(trunc_normal(rng, cls["exec_min"], n), 1.0)
    cpu = np.round(trunc_normal(rng, cls["cpu"], n))
    ram = np.round(trunc_normal(rng, cls["ram_gb"], n))
    q = np.asarray(quanta, np.float64)
    raw = trunc_normal(rng, cls["gpu"], n)
    gpu = q[np.argmin(np.abs(raw[:, None] - q[None, :]), axis=1)]
    demand = np.stack([np.maximum(cpu, 1.0), np.maximum(ram, 1.0),
                       np.maximum(gpu, 0.0)], axis=1)
    return np.round(exec_min).astype(np.int64), demand


def draw(mix: dict, n: int, rng) -> reference.Jobs:
    """``n`` jobs of the configuration's ``jobs`` mix, unsubmitted."""
    if mix.get("gang_share", 0.0):
        raise ValueError("the generator draws single-node jobs only")
    is_te = rng.random(n) < mix["te_share"]
    exec_total = np.zeros(n, np.int64)
    demand = np.zeros((n, 3))
    n_te = int(is_te.sum())
    quanta = mix["gpu_quanta"]
    exec_total[is_te], demand[is_te] = _class(rng, mix["te"], n_te, quanta)
    exec_total[~is_te], demand[~is_te] = _class(rng, mix["be"], n - n_te,
                                                quanta)
    gp = np.round(trunc_normal(rng, mix["grace_min"], n)).astype(np.int64)
    return reference.Jobs(submit=np.zeros(n, np.int64),
                          exec_total=exec_total, demand=demand, is_te=is_te,
                          gp=gp)


def closed_loop(jobs: reference.Jobs, cluster: dict,
                load: float) -> np.ndarray:
    """Admit ticks of a FIFO replay that holds the backlog at ``load``."""
    sim = reference.Simulator(jobs, cluster["nodes"],
                              reference.node_cap(cluster), "fifo", 0.0, 0,
                              seed=0, admission_target=load)
    admit = sim.run().admit_time
    if (admit < 0).any():
        raise RuntimeError("closed-loop admission left jobs unadmitted")
    return admit


def build(config: dict, traffic: dict, seed: int) -> reference.Jobs:
    """The traffic's job set for ``seed``, submit times stamped."""
    arrivals = traffic["arrivals"]
    if arrivals["kind"] != "closed_loop":
        raise ValueError(f"unknown arrivals {arrivals['kind']!r}")
    rng = np.random.default_rng((int(seed), 0))
    js = draw(config["jobs"], int(traffic["jobs"]), rng)
    js.submit = closed_loop(js, config["cluster"], arrivals["load"])
    cap = np.asarray(reference.node_cap(config["cluster"]))
    if not (js.demand <= cap[None, :]).all():
        raise ValueError("a job's demand exceeds one node")
    return js

"""The one traffic generator: job sets from a configuration's job mix, a
traffic file's sizes and arrivals, and ``--seed``.

Jobs are drawn per class (TE or BE, ``te_share``) from truncated normals
(resampled a few times, then clipped), GPU requests snapped to the
allocation quanta, grace periods from their own truncated normal, as in
the paper's §4.2 generator. Gang (multi-node) jobs, the paper's future
work: a job is a gang with the mix's ``gang_share``, of a width drawn
uniformly from the mix's ``gang_widths``. Its demand stays per node.
Arrivals:

* ``closed_loop``: the paper's "load kept at ``load`` if scheduled by
  FIFO": a FIFO replay of the jobs (``reference.Simulator`` with an
  admission target) admits the next job whenever the backlog is below
  ``load``; the admit ticks become the submit times every engine sees.

A job set is a ``reference.Jobs``, drawn from
``numpy.random.default_rng((seed, 0))``, so a seed gives the same jobs in
every run. Widths come from a stream of their own,
``numpy.random.default_rng((seed, 1))``, drawn only where a share is
above 0: a mix without gangs draws exactly the jobs it drew before gangs
were added.
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
    """``n`` jobs of the configuration's ``jobs`` mix, unsubmitted, of
    width 1."""
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


def widths(mix: dict, n: int, nodes: int, seed: int) -> np.ndarray:
    """Each of ``n`` jobs' width: gangs by ``gang_share``, widths
    uniform over ``gang_widths``; all ones, and no draw, where the share
    is 0."""
    share = float(mix.get("gang_share", 0.0))
    width = np.ones(n, np.int64)
    if share <= 0:
        return width
    choices = np.asarray(mix["gang_widths"], np.int64)
    if not ((choices >= 1) & (choices <= nodes)).all():
        raise ValueError(f"gang widths {choices.tolist()} must lie in "
                         f"1..{nodes}, the cluster's nodes")
    rng = np.random.default_rng((int(seed), 1))
    gang = rng.random(n) < share
    width[gang] = rng.choice(choices, int(gang.sum()))
    return width


def closed_loop(jobs: reference.Jobs, cluster: dict,
                load: float) -> np.ndarray:
    """Admit ticks of a FIFO replay that holds the backlog at ``load``
    (a job weighs its width times its cluster-normalised demand)."""
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
    js.width = widths(config["jobs"], js.n, config["cluster"]["nodes"], seed)
    js.submit = closed_loop(js, config["cluster"], arrivals["load"])
    cap = np.asarray(reference.node_cap(config["cluster"]))
    if not (js.demand <= cap[None, :]).all():
        raise ValueError("a job's demand exceeds one node")
    return js

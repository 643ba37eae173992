"""The comparison that decides ``correct``.

Every job of every unit the window finished (a replay or a streamed
replay) is compared with the plain reference
(``bench/reference.py``) on the same jobs: its finish tick and its
preemption count. Both are integers and the semantics are exact, so
each limit is 0.

One part of the semantics is random: when no running BE job is eligible
(Eq. 2) and under the cap ``P``, Eq. 4 falls back to a random running BE
job. The program and the reference draw from different generators, so
the reference replays the program's draw (``reference.simulate`` with a
``Guide``): a running BE job the program preempted more often than the
reference has so far, those the program signalled at this tick first
where its outputs name each job's last preemption tick. A choice whose
replay finishes a job at a tick the program does not have is dropped
and the next tried. A unit whose draws were all replayed is compared
whole, and the program must count as many fallbacks as the reference:
random draws, and gang victims already at the cap ``P`` (rule 6 of
``bench/reference.py``). A draw no choice explains is a mismatch; the
unit is then compared over the jobs finished by that tick (both sides
are the same deterministic process up to there), every job must still
finish, and the program must have counted a fallback too.

The control of the contract is, for a system that states no precision,
the reference with one guarantee of the configuration broken
(:func:`control`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference

LIMITS = {"unfinished_jobs": 0, "finish_mismatch": 0,
          "preempt_mismatch": 0, "draw_mismatch": 0}


def tally(finish, preempt_count, draws: int,
          ref: reference.Result) -> dict:
    """Counts of one unit's jobs against the reference; ``draws`` is the
    program's count of fallbacks in that unit (random victim draws, and
    gang victims at the P cap)."""
    n = len(ref.finish)
    finish = np.asarray(-1 if finish is None else finish, np.int64)
    pc = np.asarray(-1 if preempt_count is None else preempt_count,
                    np.int64)
    if finish.shape != (n,) or pc.shape != (n,):
        finish = np.full(n, -1, np.int64)
        pc = np.full(n, -1, np.int64)
    unfinished = finish < 0
    if ref.first_draw >= 0:
        t = ref.first_draw
        seen = (ref.finish <= t) | (~unfinished & (finish <= t))
        draw_bad = ref.missed + (int(draws) == 0)
    else:
        seen = np.ones(n, bool)
        draw_bad = int(int(draws) != ref.fallbacks)
    bad_f = seen & (finish != ref.finish)
    bad_p = seen & (pc != ref.preempt_count)
    return {"unfinished_jobs": int(unfinished.sum()),
            "finish_mismatch": int(bad_f.sum()),
            "preempt_mismatch": int(bad_p.sum()),
            "draw_mismatch": int(draw_bad),
            "failed": int((unfinished | bad_f | bad_p).sum()),
            "compared": int(seen.sum()), "jobs": n}


def total(tallies) -> dict:
    out = {k: 0 for k in (*LIMITS, "failed", "compared", "jobs")}
    for t in tallies:
        for k in out:
            out[k] += t[k]
    return out


def checks(counts: dict) -> dict:
    """Each number compared beside its limit, in a fixed order."""
    return {k: {"value": counts[k], "limit": lim}
            for k, lim in LIMITS.items()}


def passed(checks_: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks_.values())


def control(jobs: reference.Jobs, cluster: dict, policy: dict, seed: int,
            broken: str) -> reference.Result:
    """The reference with one guarantee of the configuration broken:
    ``p_cap`` lets a job be preempted P + 1 times; ``grace`` vacates
    victims at once instead of after their grace period."""
    P = int(policy["P"])
    if broken == "p_cap":
        P += 1
    elif broken == "grace":
        jobs = dataclasses.replace(jobs, gp=np.zeros_like(jobs.gp))
    else:
        raise ValueError(f"unknown control {broken!r}")
    return reference.simulate(jobs, cluster, policy["name"],
                              policy["s"], P, seed)

"""Plain numpy reference of the scheduling semantics the benchmark holds
the program to. It imports nothing of the program.

A straightforward discrete-time simulator of arXiv:1902.01613 §4.1, with
the multi-node (gang) jobs the paper leaves to future work, under two
policies:

* ``fifo``: one queue, strict head-of-line order, no preemption;
* ``fitgpp``: TE jobs in a priority lane served first; a TE job that
  does not fit may signal victims among running BE jobs (below). A
  victim keeps its resources for its grace period, then re-enters the
  BE lane at the top.

The rules, each as ``core/engine/`` of the program specifies it:

1. Placement. A job of width ``w`` needs its demand, which is per node,
   on ``w`` distinct nodes at once: it starts on the first ``w`` nodes
   that fit it, in ascending node index, or not at all. ``w`` = 1 is
   first-fit. A node fits when its free vector is at least the demand
   less ``FIT_EPS`` in every resource.
2. One node list per job, from its start to its finish or vacate. A
   finish or a vacate releases the demand on every node of the list; a
   preemption signal promises it (``pending``) on every node of the
   list until the vacate.
3. Trigger. A TE job that does not fit signals victims only if no
   victim it signalled is still in grace and fewer than ``w`` nodes fit
   it even counting promised resources (``free + pending``).
4. Victims of a width-1 TE job (Eq. 1-4): the smallest Eq. 3 score
   (the Eq. 1 size of the per-node demand, normalised by the largest
   over all running BE jobs, plus ``s`` times the grace period so
   normalised) among the running BE jobs preempted fewer than ``P``
   times that are eligible by Eq. 2 (TE demand at most the victim's
   demand plus the free vector of its node, within ``FIT_EPS``); ties by
   job index. A gang victim is judged on its node with the most slack:
   the largest smallest ``free + demand - TE demand`` over the
   resources, the first such node in its list. None eligible: one
   running BE job at random (Eq. 4's fallback).
5. Victims of a gang TE job (width ``w``), with no random draw: every
   running BE job is ranked, under the ``P`` cap first, then by the
   Eq. 3 score of its total demand ``width × demand`` (normalised over
   these totals), ties by job index. First choice: the first single
   job in that order, among those under the cap if there are any,
   whose demand given back to ``free`` on its nodes alone leaves ``w``
   nodes that fit. Otherwise jobs are taken in rank order, over the cap
   too, until ``w`` nodes fit on ``free`` plus what they give back; if
   even all of them are not enough, none is taken. Victims are
   signalled in the order taken.
6. Fallbacks, as the program counts them: each random draw of rule 4,
   and each victim of rule 5 already preempted ``P`` times.

The random draws are the one place two correct engines part. Given
another engine's outputs for the same jobs (a :class:`Guide`),
:func:`simulate` replays that engine's draws instead of making its own
(see there), so the rest of the run can still be compared exactly.

One-minute ticks. On a tick: arrivals, grace expiries (job-index order),
the schedule pass, one minute of execution, finishes (job-index order),
grace countdown. Ticks on which nothing can start, preempt, arrive,
finish or expire are skipped in one jump, which changes no result.

With ``admission_target > 0`` submit times are ignored: the next job (in
index order) is admitted whenever the backlog, the sum over admitted
unfinished jobs of the mean cluster-normalised demand times the width,
is below the target. Run under ``fifo`` this gives the paper's
closed-loop arrivals (§4.2), and ``admit_time`` holds the ticks.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass

import numpy as np

NOT_ARRIVED, QUEUED, RUNNING, GRACE, DONE = 0, 1, 2, 3, 4
FIT_EPS = 1e-9
MAX_TICKS = 10_000_000


@dataclass
class Jobs:
    """Struct of arrays over ``n`` jobs; demand per node per (CPU, RAM GB,
    GPU), times in minutes; ``width`` the nodes a job needs at once (all
    ones by default)."""
    submit: np.ndarray
    exec_total: np.ndarray
    demand: np.ndarray
    is_te: np.ndarray
    gp: np.ndarray
    width: np.ndarray = None

    def __post_init__(self):
        if self.width is None:
            self.width = np.ones(len(self.submit), np.int64)

    @property
    def n(self) -> int:
        return len(self.submit)


@dataclass
class Guide:
    """Another engine's per-job outputs for the same jobs: finish tick,
    preemption count and, where it reports it, the tick of the last
    preemption signal."""
    finish: np.ndarray
    preempt_count: np.ndarray
    last_signal: np.ndarray = None


class _Diverged(Exception):
    """A replayed choice of draw led to a finish or a preemption the
    guide does not have."""


@dataclass
class Result:
    finish: np.ndarray
    preempt_count: np.ndarray
    makespan: int
    last_signal: np.ndarray     # tick of each job's last preemption
    # victims past the main rule, as the program counts them: random
    # draws (Eq. 4's fallback) and gang victims already at the P cap
    fallbacks: int
    draws: int                  # random victim draws alone
    first_draw: int             # tick of the first draw not replayed, or -1
    missed: int                 # draws no choice consistent with the guide
    admit_time: np.ndarray
    tries: int = 1              # replays the search for the draws took


class _Lanes:
    """TE and BE FIFO lanes as lazy-deletion heaps of (key, job);
    arrivals take rising tail keys, vacated victims falling top keys."""

    def __init__(self, state):
        self.state = state
        self.heaps = {True: [], False: []}
        self.key = {}
        self.top_key = -1.0
        self.tail_key = 0.0

    def _push(self, j, key, te):
        self.key[j] = key
        heapq.heappush(self.heaps[te], (key, j))

    def push_back(self, j, te):
        self._push(j, self.tail_key, te)
        self.tail_key += 1.0

    def requeue_top(self, j, te):
        self._push(j, self.top_key, te)
        self.top_key -= 1.0

    def reinsert(self, j, te):
        heapq.heappush(self.heaps[te], (self.key[j], j))

    def _valid(self, key, j):
        return self.state[j] == QUEUED and self.key.get(j) == key

    def peek(self, te):
        heap = self.heaps[te]
        while heap:
            key, j = heap[0]
            if self._valid(key, j):
                return j
            heapq.heappop(heap)
        return -1

    def pop(self, te):
        j = self.peek(te)
        if j >= 0:
            heapq.heappop(self.heaps[te])
        return j

    def queued(self, te):
        return [j for key, j in self.heaps[te] if self._valid(key, j)]


class Simulator:
    def __init__(self, jobs: Jobs, n_nodes: int, node_cap, policy: str,
                 s: float, P: int, seed: int,
                 admission_target: float = 0.0, guide: Guide = None,
                 forced: dict = None, search: bool = True):
        if policy not in ("fifo", "fitgpp"):
            raise ValueError(f"the reference runs fifo and fitgpp, "
                             f"not {policy!r}")
        self.jobs = jobs
        self.preemptive = policy == "fitgpp"
        self.s = float(s)
        self.P = int(P)
        self.rng = np.random.default_rng(seed + 104729)
        self.node_cap = np.asarray(node_cap, np.float64)
        self.free = np.tile(self.node_cap, (int(n_nodes), 1))
        self.pending = np.zeros_like(self.free)
        n = jobs.n
        self.demand = np.asarray(jobs.demand, np.float64)
        self.width = np.asarray(jobs.width, np.int64)
        self.remaining = np.asarray(jobs.exec_total, np.int64).copy()
        self.finish_t = np.full(n, -1, np.int64)
        self.state = np.full(n, NOT_ARRIVED, np.int8)
        self.nodes = {}             # running or in-grace job -> its nodes
        self.preempt_count = np.zeros(n, np.int64)
        self.grace_left = np.zeros(n, np.int64)
        self.victim_of = np.full(n, -1, np.int64)
        self.te_pending = np.zeros(n, np.int64)
        self.running, self.running_be, self.grace = set(), set(), set()
        self.n_done = 0
        self.last_signal = np.full(n, -1, np.int64)
        self.guide = guide
        self.forced = forced or {}
        self.search = search
        self.options = {}           # draw index -> jobs it could have been
        self.speculative = []       # draw indices chosen among several
        self.fallbacks = 0
        self.draws = 0
        self.first_draw = -1
        self.missed = 0
        self.lanes = _Lanes(self.state)
        self.target = float(admission_target)
        self.backlog = 0.0
        self.admit_time = np.full(n, -1, np.int64)
        self.frac = (self.demand / (self.node_cap * int(n_nodes))[None, :]
                     ).mean(axis=1) * self.width
        self.order = np.argsort(jobs.submit, kind="stable")
        self.next = 0

    # -- placement ----------------------------------------------------------

    def _fit(self, j):
        """The first ``width`` nodes that fit job ``j``, or None."""
        ok = np.all(self.free >= self.demand[j][None, :] - FIT_EPS, axis=1)
        idx = np.flatnonzero(ok)
        w = self.width[j]
        return idx[:w] if len(idx) >= w else None

    def _fits_with_pending(self, j):
        promised = self.free + self.pending
        ok = np.all(promised >= self.demand[j][None, :] - FIT_EPS, axis=1)
        return int(ok.sum()) >= self.width[j]

    def _te_lane(self, j):
        return self.preemptive and bool(self.jobs.is_te[j])

    # -- lifecycle ----------------------------------------------------------

    def _enqueue(self, j):
        self.state[j] = QUEUED
        self.lanes.push_back(j, self._te_lane(j))

    def _start(self, j, nodes):
        self.state[j] = RUNNING
        self.nodes[j] = nodes
        self.free[nodes] -= self.demand[j]
        self.running.add(j)
        if not self.jobs.is_te[j]:
            self.running_be.add(j)

    def _signal(self, v, te, t):
        gp = int(self.jobs.gp[v])
        self.state[v] = GRACE
        self.grace_left[v] = gp
        self.preempt_count[v] += 1
        self.last_signal[v] = t
        g = self.guide
        if self.speculative and (
                self.preempt_count[v] > g.preempt_count[v]
                or (g.last_signal is not None and g.last_signal[v] != t
                    and self.preempt_count[v] == g.preempt_count[v])):
            raise _Diverged
        self.victim_of[v] = te
        self.te_pending[te] += 1
        self.running.discard(v)
        self.running_be.discard(v)
        self.pending[self.nodes[v]] += self.demand[v]
        if gp <= 0:
            self._vacate(v)
        else:
            self.grace.add(v)

    def _vacate(self, v):
        nodes = self.nodes.pop(v)
        self.free[nodes] += self.demand[v]
        self.pending[nodes] -= self.demand[v]
        self.state[v] = QUEUED
        self.grace.discard(v)
        self.lanes.requeue_top(v, self._te_lane(v))
        te = int(self.victim_of[v])
        if te >= 0:
            self.te_pending[te] -= 1
            self.victim_of[v] = -1

    def _finish(self, j, t):
        self.free[self.nodes.pop(j)] += self.demand[j]
        self.state[j] = DONE
        self.running.discard(j)
        self.running_be.discard(j)
        self.n_done += 1
        self.finish_t[j] = t
        self.backlog -= self.frac[j]
        if self.speculative and self.guide.finish[j] != t:
            raise _Diverged

    # -- Eq. 1-4 ------------------------------------------------------------

    def _candidates(self):
        """Running BE jobs in index order and their grace periods."""
        cand = np.sort(np.fromiter(self.running_be, np.int64,
                                   count=len(self.running_be)))
        gp = np.asarray(self.jobs.gp[cand], np.float64)
        return cand, gp

    def _score(self, d, gp):
        """Eq. 3 over the candidates, of their demand ``d``."""
        size = np.sqrt(np.sum((d / self.node_cap) ** 2, axis=-1))   # Eq. 1
        return (size / max(size.max(initial=0.0), 1e-12)            # Eq. 3
                + self.s * (gp / max(gp.max(initial=0), 1e-12)))

    def _victims(self, te, t):
        if self.width[te] > 1:
            return self._gang_victims(te)
        return [self._pick_victim(te, t)]

    def _best_node(self, v, te):
        """The node of gang victim ``v`` with the most slack for ``te``."""
        nodes = self.nodes[v]
        slack = np.min(self.free[nodes] + self.demand[v][None, :]
                       - self.demand[te][None, :], axis=1)
        return nodes[int(np.argmax(slack))]

    def _pick_victim(self, te, t):
        cand, gp = self._candidates()
        d = self.demand[cand]
        score = self._score(d, gp)
        node = np.fromiter((self.nodes[c][0] if self.width[c] == 1
                            else self._best_node(c, te) for c in cand),
                           np.int64, count=len(cand))
        node_free = self.free[node]
        elig = np.all(self.demand[te][None, :] <= d + node_free + FIT_EPS,
                      axis=1)                                        # Eq. 2
        ok = elig & (self.preempt_count[cand] < self.P)
        if ok.any():                                                 # Eq. 4
            return int(cand[int(np.argmin(np.where(ok, score, np.inf)))])
        k = self.draws
        self.draws += 1
        self.fallbacks += 1
        if self.guide is not None:
            return self._replay_draw(k, cand, t)
        if self.first_draw < 0:
            self.first_draw = t
        return int(cand[int(self.rng.integers(len(cand)))])

    def _gang_victims(self, te):
        """Rule 5 of the module docstring: the victims of gang TE job
        ``te``, in the order they are signalled."""
        cand, gp = self._candidates()
        w = self.width[te]
        need = self.demand[te][None, :] - FIT_EPS

        def n_fit(free):
            return int(np.all(free >= need, axis=1).sum())

        score = self._score(self.demand[cand] * self.width[cand][:, None], gp)
        under = self.preempt_count[cand] < self.P
        order = np.lexsort((score, ~under))
        pool = order[under[order]] if under.any() else order
        for i in pool:
            trial = self.free.copy()
            trial[self.nodes[int(cand[i])]] += self.demand[cand[i]]
            if n_fit(trial) >= w:
                self.fallbacks += int(not under[i])
                return [int(cand[i])]
        given = self.free.copy()
        victims = []
        for i in order:
            if n_fit(given) >= w:
                break
            given[self.nodes[int(cand[i])]] += self.demand[cand[i]]
            victims.append(int(cand[i]))
        if n_fit(given) < w:
            return []
        self.fallbacks += int((self.preempt_count[victims] >= self.P).sum())
        return victims

    def _replay_draw(self, k, cand, t):
        """The guide's draw: a running BE job it preempted more often
        than this run has so far (and, where it reports signal ticks, last
        at this tick or later; those signalled at this tick first)."""
        g = self.guide
        if k in self.forced:
            self.speculative.append(k)
            return self.forced[k]
        ok = g.preempt_count[cand] > self.preempt_count[cand]
        if g.last_signal is not None:
            ok &= g.last_signal[cand] >= t
        opts = cand[ok]
        if g.last_signal is not None:
            opts = opts[np.argsort(g.last_signal[opts] != t, kind="stable")]
        if len(opts) == 1 or (len(opts) and not self.search):
            return int(opts[0])
        if len(opts):
            self.options[k] = [int(v) for v in opts]
            self.speculative.append(k)
            return int(opts[0])
        self.missed += 1
        if self.first_draw < 0:
            self.first_draw = t
        return int(cand[int(self.rng.integers(len(cand)))])

    def _should_trigger(self, j):
        return self.te_pending[j] == 0 and not self._fits_with_pending(j)

    def _schedule(self, t):
        if self.preemptive:
            blocked = []
            while True:
                j = self.lanes.pop(True)
                if j < 0:
                    break
                nodes = self._fit(j)
                if nodes is None and self._should_trigger(j):
                    if self.running_be:
                        for v in self._victims(j, t):
                            self._signal(v, j, t)
                    nodes = self._fit(j)
                if nodes is not None:
                    self._start(j, nodes)
                else:
                    blocked.append(j)
            for j in blocked:
                self.lanes.reinsert(j, True)
        while True:
            head = self.lanes.peek(False)
            if head < 0:
                break
            nodes = self._fit(head)
            if nodes is None:
                break
            self.lanes.pop(False)
            self._start(head, nodes)

    def _would_act(self):
        if self.preemptive:
            for j in self.lanes.queued(True):
                if self._fit(j) is not None:
                    return True
                if self.running_be and self._should_trigger(j):
                    return True
        head = self.lanes.peek(False)
        return head >= 0 and self._fit(head) is not None

    # -- time ---------------------------------------------------------------

    def _step(self, t):
        n = self.jobs.n
        if self.target > 0:
            while self.next < n and self.backlog < self.target:
                j = self.next
                self._enqueue(j)
                self.admit_time[j] = t
                self.backlog += self.frac[j]
                self.next += 1
        else:
            while (self.next < n and
                   self.jobs.submit[self.order[self.next]] <= t):
                self._enqueue(int(self.order[self.next]))
                self.next += 1
        for j in sorted(j for j in self.grace if self.grace_left[j] <= 0):
            self._vacate(j)
        self._schedule(t)
        if self.running:
            run = np.fromiter(self.running, np.int64, count=len(self.running))
            self.remaining[run] -= 1
            for j in np.sort(run[self.remaining[run] <= 0]):
                self._finish(int(j), t + 1)
        self._count_down(1)

    def _count_down(self, k):
        if self.grace:
            g = np.fromiter(self.grace, np.int64, count=len(self.grace))
            self.grace_left[g] -= k

    def _jump(self, t):
        """The next tick that must run; the skipped ticks' countdowns
        are applied in bulk."""
        if self._would_act():
            return t
        n = self.jobs.n
        nxt = None
        if self.target > 0:
            if self.next < n and self.backlog < self.target:
                return t
        elif self.next < n:
            nxt = int(self.jobs.submit[self.order[self.next]])
        run = None
        if self.running:
            run = np.fromiter(self.running, np.int64, count=len(self.running))
            ev = t - 1 + int(self.remaining[run].min())
            nxt = ev if nxt is None else min(nxt, ev)
        if self.grace:
            g = np.fromiter(self.grace, np.int64, count=len(self.grace))
            ev = t + int(self.grace_left[g].min())
            nxt = ev if nxt is None else min(nxt, ev)
        if nxt is None:
            raise RuntimeError("reference stalled: jobs remain but nothing "
                               "is pending")
        if nxt <= t:
            return t
        if nxt >= MAX_TICKS:
            raise RuntimeError(f"reference did not finish in {MAX_TICKS} "
                               "ticks")
        if run is not None:
            self.remaining[run] -= nxt - t
        self._count_down(nxt - t)
        return nxt

    def run(self) -> Result:
        t = 0
        n = self.jobs.n
        while self.n_done < n:
            self._step(t)
            t += 1
            if self.n_done < n:
                t = self._jump(t)
        return Result(finish=self.finish_t.copy(),
                      preempt_count=self.preempt_count.copy(), makespan=t,
                      last_signal=self.last_signal.copy(),
                      fallbacks=self.fallbacks, draws=self.draws,
                      first_draw=self.first_draw,
                      missed=self.missed,
                      admit_time=self.admit_time.copy())


def simulate(jobs: Jobs, cluster: dict, policy: str, s: float, P: int,
             seed: int, guide: Guide = None, max_tries: int = 64) -> Result:
    """One replay of ``jobs`` on ``cluster`` (a configuration's
    ``cluster`` entry: ``nodes`` and ``node``).

    With a ``guide`` the random draws of Eq. 4's fallback replay the
    guide's: where several jobs could have been drawn, each is tried in
    turn (depth first over the draws) until the replay finishes every
    job at the guide's tick; a choice whose replay finishes a job
    elsewhere is dropped there. Where no choice holds, within
    ``max_tries`` replays, the draw counts as ``missed``."""
    args = (jobs, cluster["nodes"], node_cap(cluster), policy, s, P, seed)
    forced, options = {}, {}
    for tries in range(1, max_tries + 1):
        sim = Simulator(*args, guide=guide, forced=dict(forced))
        try:
            res = sim.run()
            res.tries = tries
            return res
        except _Diverged:
            options.update(sim.options)
        stack = list(sim.speculative)
        while stack:
            k = stack.pop()
            opts = options[k]
            i = opts.index(forced.get(k, opts[0])) + 1
            forced = {d: v for d, v in forced.items() if d < k}
            if i < len(opts):
                forced[k] = opts[i]
                break
        else:
            break
    res = Simulator(*args, guide=guide, search=False).run()
    res.missed = max(res.missed, 1)
    res.first_draw = res.first_draw if res.first_draw >= 0 else 0
    res.tries = max_tries
    return res


def node_cap(cluster: dict):
    node = cluster["node"]
    return (float(node["cpu"]), float(node["ram_gb"]), float(node["gpu"]))

"""Preemption decision rules: FitGpp (the paper, Eq. 1-4) + baselines.

A policy answers ONE question: given an incoming TE job that does not
fit anywhere, which running BE job(s) should be signalled to vacate?

Every policy is a :class:`Policy` subclass registered ONCE under
``@register_policy`` (``core/policy_registry.py``) and declares every
backend it supports in that one place:

* **reference (numpy)** — ``select`` / ``rank_key``, operating on
  plain numpy views of the simulator state so the reference engines
  stay transparent;
* **JAX** — ``jax_kind`` names the engine contract the class fulfils:
  ``"rank"`` policies provide ``jax_rank`` (a per-job preemption-order
  value consumed by the engine's signal-until-the-TE-fits loop), and
  ``"score"`` policies provide ``jax_score`` (Eq. 4-shaped: masked
  argmin over eligible candidates, random fallback — the engine owns
  the masking and the fallback);
* **accelerated score backends** (optional) — ``score_backends``
  beyond the default ``"jnp"``, e.g. FitGpp's Pallas ``fitgpp_score``
  kernel as ``"pallas"``, selectable via ``SimConfig.score_backend``
  and dispatched through ``jax_score_accel``.

The jnp/jax imports inside the ``jax_*`` methods are deliberately
lazy: the reference engines never call them, so this module (and the
numpy simulator) stays importable without JAX.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from repro.configs.base import PAPER_S
from repro.core.engine.placement import FIT_EPS
from repro.core.policy_registry import (RNG_ALWAYS, RNG_FALLBACK,
                                        register_policy)


def size_eq1(demand: np.ndarray, node_cap: np.ndarray) -> np.ndarray:
    """Eq. 1: scale-invariant demand size, ||D / capacity||_2.

    demand (..., 3); node_cap (3,).
    """
    return np.sqrt(np.sum((demand / node_cap) ** 2, axis=-1))


def fitgpp_scores(demand: np.ndarray, gp: np.ndarray, node_cap: np.ndarray,
                  s: float) -> np.ndarray:
    """Eq. 3 over the set of running BE jobs.

    Normalizers are max over ALL running BE jobs (the paper's J), not
    just the eligible subset.
    """
    sz = size_eq1(demand, node_cap)
    max_sz = max(sz.max(initial=0.0), 1e-12)
    max_gp = max(gp.max(initial=0), 1e-12)
    return sz / max_sz + s * (gp / max_gp)


def eligible_eq2(te_demand: np.ndarray, demand: np.ndarray,
                 node_free: np.ndarray) -> np.ndarray:
    """Eq. 2: D_TE <= D_j + N_free(node_j), element-wise, per job.

    demand (m, 3) of running BE jobs; node_free (m, 3) free vector of the
    node each candidate runs on. FIT_EPS-tolerant, like every other fit
    check (and like the JAX engine's eligibility mask).
    """
    return np.all(te_demand[None, :] <= demand + node_free + FIT_EPS, axis=1)


def _jax_size_eq1(demand, node_cap):
    """Eq. 1 in jnp — the one jnp mirror of :func:`size_eq1`, shared by
    every score policy's ``jax_score`` and the fused kernel's scores
    (single source for the norm and its summation order)."""
    from repro.kernels.schedule_step import eq1_size
    return eq1_size(demand, node_cap)


class Policy:
    """Base decision rule; subclasses declare their backends (module
    docstring) and register via ``@register_policy``.

    Reference contract — ``select`` returns victim job indices (into
    the global job array); ``rank_key`` returns a per-candidate
    preemption-order key, LOWER = preempt first (used by the engine's
    gang selection; ``cand_demand`` arrives pre-scaled by gang width so
    Eq. 1 sees total demand).

    JAX contract (``st``/``jobs`` are ``sim_jax.State``/``Jobs``):

    * ``jax_kind = "rank"`` → ``jax_rank(st, jobs) -> (st, rank)``:
      rank (N,) float32, HIGHER = preempt first; may consume
      ``st.rng`` (return the advanced state).
    * ``jax_kind = "score"`` → ``jax_score(jobs, cand, node_cap, s)
      -> (N,)`` scores, LOWER = better victim (``cand`` masks running
      BE jobs for any normalizers). The engine applies Eq. 2
      eligibility (against each victim's BEST assigned node — the
      gang-aware ``best_victim_node`` reduction), the P cap, the
      masked argmin and the paper's random fallback. For gang TEs the
      engine re-evaluates the score over TOTAL gang demand
      (``demand * width``) and runs the gang-select strategy instead.
    * extra ``score_backends`` → ``jax_score_accel(backend, jobs, te,
      free, assign, cand, under, node_cap, s, pending_free=...,
      queue_key=..., be_q=...) -> victim index or -1`` (the whole
      schedule pass — score, best-node Eq. 2 reduction, masked
      argmin, gang-fit tiles and BE queue scan — fused on ONE
      accelerated kernel invocation; ``free``/``pending_free`` are
      the (nodes, 3) cluster matrices and ``assign`` the
      (jobs, nodes) placement-mask tile).
    """
    name = "base"
    preemptive = True
    jax_kind: str = None                    # None | "rank" | "score"
    argmin_select = False                   # Eq. 4-style single victim
    score_backends: Tuple[str, ...] = ("jnp",)

    def __init__(self, s: float = PAPER_S):
        self.s = float(s)

    # -- reference (numpy) backend ------------------------------------------

    def select(self, rng, te_demand, cand_ids, cand_demand, cand_node_free,
               cand_gp, cand_remaining, under_cap, all_run_demand,
               all_run_gp, node_cap, free_by_node, cand_node) -> List[int]:
        """Return victim job indices (into the global job array).

        cand_* arrays cover ALL currently running BE jobs; ``under_cap``
        marks those with PreemptionCount < P. ``all_run_*`` equal cand_*
        (kept explicit: Eq. 3 normalizes over all running BE jobs).
        """
        raise NotImplementedError

    def rank_key(self, rng, cand_demand, cand_gp, cand_remaining,
                 node_cap) -> np.ndarray:
        raise NotImplementedError

    # -- JAX backend declarations (lazy jnp; see class docstring) -----------

    def jax_rank(self, st, jobs):
        raise NotImplementedError(f"{self.name}: no jax_rank declared")

    def jax_score(self, jobs, cand, node_cap, s):
        raise NotImplementedError(f"{self.name}: no jax_score declared")

    def jax_score_accel(self, backend, jobs, te, free, assign, cand,
                        under, node_cap, s, *, pending_free=None,
                        queue_key=None, be_q=None):
        raise NotImplementedError(
            f"{self.name}: no accelerated score backend {backend!r}")


@register_policy("fifo", description="Non-preemptive FIFO baseline "
                                     "(TE and BE share one queue)")
class FifoPolicy(Policy):
    preemptive = False

    def select(self, *a, **k) -> List[int]:
        return []


def _argmin_score_select(rng, cand_ids, scores, elig, under_cap) -> List[int]:
    """Eq. 4 shape shared by the score policies: argmin score among
    eligible under-P-cap candidates; fallback (paper): preempt a random
    running BE job — the simulator re-invokes the policy if that did
    not make enough room."""
    mask = elig & under_cap
    if mask.any():
        masked = np.where(mask, scores, np.inf)
        return [int(cand_ids[int(np.argmin(masked))])]
    pick = int(rng.integers(len(cand_ids)))
    return [int(cand_ids[pick])]


@register_policy("fitgpp", rng=RNG_FALLBACK,
                 description="The paper's algorithm (Eq. 1-4): smallest "
                             "sufficient victim, GP-weighted")
class FitGppPolicy(Policy):
    """The paper's algorithm (Eq. 1-4)."""
    jax_kind = "score"
    argmin_select = True
    score_backends = ("jnp", "pallas")

    def select(self, rng, te_demand, cand_ids, cand_demand, cand_node_free,
               cand_gp, cand_remaining, under_cap, all_run_demand,
               all_run_gp, node_cap, free_by_node, cand_node) -> List[int]:
        if len(cand_ids) == 0:
            return []
        scores = fitgpp_scores(all_run_demand, all_run_gp, node_cap, self.s)
        elig = eligible_eq2(te_demand, cand_demand, cand_node_free)
        return _argmin_score_select(rng, cand_ids, scores, elig, under_cap)

    def rank_key(self, rng, cand_demand, cand_gp, cand_remaining,
                 node_cap) -> np.ndarray:
        return fitgpp_scores(cand_demand, cand_gp, node_cap, self.s)

    def jax_score(self, jobs, cand, node_cap, s):
        import jax.numpy as jnp
        sz = _jax_size_eq1(jobs.demand, node_cap)
        max_sz = jnp.maximum(jnp.max(jnp.where(cand, sz, 0.0)), 1e-12)
        max_gp = jnp.maximum(jnp.max(jnp.where(cand, jobs.gp, 0)), 1e-12)
        return sz / max_sz + s * (jobs.gp / max_gp)

    def jax_score_accel(self, backend, jobs, te, free, assign, cand,
                        under, node_cap, s, *, pending_free=None,
                        queue_key=None, be_q=None):
        """The whole Eq. 1-4 pass fused on the Pallas ``schedule_step``
        kernel over the (jobs, nodes) tile — score, best-node Eq. 2
        reduction, masked argmin, gang-fit counts and the BE queue
        scan in one invocation (bit-parity-tested vs ``jax_score``;
        ``s`` may be traced). The victim selection consumes only
        ``.victim``."""
        assert backend == "pallas", backend
        import jax.numpy as jnp
        from repro.kernels import ops as kops
        J = jobs.gp.shape[0]
        M = free.shape[0]
        if pending_free is None:
            pending_free = jnp.zeros((M, 3), jnp.float32)
        if queue_key is None:
            queue_key = jnp.full((J,), jnp.inf, jnp.float32)
        if be_q is None:
            be_q = jnp.zeros((J,), bool)
        ps = kops.schedule_step(
            jobs.demand, jobs.gp.astype(jnp.float32), jobs.width,
            queue_key, assign, free, pending_free, cand, under, be_q,
            jobs.demand[te], node_cap, s=s)
        return ps.victim


@register_policy("lrtp", description="Big-C baseline: longest remaining "
                                     "time preempted first (oracle runtime)")
class LrtpPolicy(Policy):
    """Big-C's policy: Longest Remaining Time Preemption (oracle runtime).

    Keeps preempting, longest-remaining first, until some node could fit
    the TE job (free + signalled victims' demand on that node).
    """
    jax_kind = "rank"

    def select(self, rng, te_demand, cand_ids, cand_demand, cand_node_free,
               cand_gp, cand_remaining, under_cap, all_run_demand,
               all_run_gp, node_cap, free_by_node, cand_node) -> List[int]:
        return _preempt_until_fits(
            order=np.argsort(-cand_remaining, kind="stable"),
            te_demand=te_demand, cand_ids=cand_ids, cand_demand=cand_demand,
            cand_node=cand_node, under_cap=under_cap,
            free_by_node=free_by_node, rng=rng)

    def rank_key(self, rng, cand_demand, cand_gp, cand_remaining,
                 node_cap) -> np.ndarray:
        return -np.asarray(cand_remaining, float)

    def jax_rank(self, st, jobs):
        import jax.numpy as jnp
        return st, st.remaining.astype(jnp.float32)


@register_policy("srtp", description="BEYOND-PAPER: shortest remaining "
                                     "time preempted first (cheap victims, "
                                     "oracle runtime)")
class SrtpPolicy(Policy):
    """Shortest Remaining Time Preemption: the LRTP mirror — victims
    nearest to completion vacate first, minimizing lost work per
    preemption at the cost of delaying almost-done jobs."""
    jax_kind = "rank"

    def select(self, rng, te_demand, cand_ids, cand_demand, cand_node_free,
               cand_gp, cand_remaining, under_cap, all_run_demand,
               all_run_gp, node_cap, free_by_node, cand_node) -> List[int]:
        return _preempt_until_fits(
            order=np.argsort(cand_remaining, kind="stable"),
            te_demand=te_demand, cand_ids=cand_ids, cand_demand=cand_demand,
            cand_node=cand_node, under_cap=under_cap,
            free_by_node=free_by_node, rng=rng)

    def rank_key(self, rng, cand_demand, cand_gp, cand_remaining,
                 node_cap) -> np.ndarray:
        return np.asarray(cand_remaining, float)

    def jax_rank(self, st, jobs):
        import jax.numpy as jnp
        return st, -st.remaining.astype(jnp.float32)


@register_policy("rand", rng=RNG_ALWAYS,
                 description="Random running BE victims until the TE fits")
class RandPolicy(Policy):

    def select(self, rng, te_demand, cand_ids, cand_demand, cand_node_free,
               cand_gp, cand_remaining, under_cap, all_run_demand,
               all_run_gp, node_cap, free_by_node, cand_node) -> List[int]:
        return _preempt_until_fits(
            order=rng.permutation(len(cand_ids)),
            te_demand=te_demand, cand_ids=cand_ids, cand_demand=cand_demand,
            cand_node=cand_node, under_cap=under_cap,
            free_by_node=free_by_node, rng=rng)

    def rank_key(self, rng, cand_demand, cand_gp, cand_remaining,
                 node_cap) -> np.ndarray:
        return rng.random(len(cand_gp))

    jax_kind = "rank"

    def jax_rank(self, st, jobs):
        import jax
        rng, sub = jax.random.split(st.rng)
        return (st._replace(rng=rng),
                jax.random.uniform(sub, st.remaining.shape))


@register_policy("minsize", rng=RNG_FALLBACK,
                 description="BEYOND-PAPER: Eq. 1-only FitGpp ablation "
                             "(smallest sufficient victim, GP-blind)")
class MinSizePolicy(Policy):
    """FitGpp with the grace-period term removed: argmin of the Eq. 1
    size among Eq. 2-eligible candidates. Isolates how much of FitGpp's
    win comes from demand-sufficiency alone vs the GP weighting."""
    jax_kind = "score"
    argmin_select = True

    def select(self, rng, te_demand, cand_ids, cand_demand, cand_node_free,
               cand_gp, cand_remaining, under_cap, all_run_demand,
               all_run_gp, node_cap, free_by_node, cand_node) -> List[int]:
        if len(cand_ids) == 0:
            return []
        scores = size_eq1(all_run_demand, node_cap)
        elig = eligible_eq2(te_demand, cand_demand, cand_node_free)
        return _argmin_score_select(rng, cand_ids, scores, elig, under_cap)

    def rank_key(self, rng, cand_demand, cand_gp, cand_remaining,
                 node_cap) -> np.ndarray:
        return size_eq1(cand_demand, node_cap)

    def jax_score(self, jobs, cand, node_cap, s):
        return _jax_size_eq1(jobs.demand, node_cap)


def _preempt_until_fits(order, te_demand, cand_ids, cand_demand, cand_node,
                        under_cap, free_by_node, rng) -> List[int]:
    """Walk candidates in ``order`` (P-capped first), accumulating pending
    frees per node, until the TE job fits on some node."""
    pending = free_by_node.copy()
    victims: List[int] = []
    # candidates under the cap first; over-cap ones as a last resort
    ordered = [i for i in order if under_cap[i]] + \
              [i for i in order if not under_cap[i]]
    for i in ordered:
        node = int(cand_node[i])
        pending[node] += cand_demand[i]
        victims.append(int(cand_ids[i]))
        if np.all(te_demand <= pending[node] + FIT_EPS):
            return victims
    return victims   # even preempting everyone wasn't enough


def make_policy(name: str, s: float = PAPER_S) -> Policy:
    """Deprecated shim: use ``repro.core.policy_registry.make``."""
    import warnings
    warnings.warn(
        "policies.make_policy is deprecated; use "
        "repro.core.policy_registry.make(name, s=...)",
        DeprecationWarning, stacklevel=2)
    from repro.core.policy_registry import make
    return make(name, s=s)

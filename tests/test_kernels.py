"""Pallas-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU), plus the registry-wired ``score_backend``
engine parity (which needs no dev extras and always runs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                      # CI installs requirements-dev
    HAS_HYPOTHESIS = False

    def given(*a, **k):                  # placeholder decorators so the
        return lambda f: f               # classes below still parse

    settings = given

    class _AnyStrategy:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _AnyStrategy()

# The oracle sweeps were historically gated on the dev extras via a
# module-level importorskip; keep exactly that behavior per class so
# the score-backend suite below can run everywhere. The registered
# ``hypothesis`` marker (pytest.ini) makes the gated subset selectable.
_skip_without_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS,
    reason="property tests need hypothesis (pip install -r "
           "requirements-dev.txt)")


def needs_dev_deps(cls):
    return _skip_without_hypothesis(pytest.mark.hypothesis(cls))

from repro.kernels import ops
from repro.kernels import ref as kref


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@needs_dev_deps
class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Sq,Skv,H,KV,hd,causal,window,cap",
        [
            (2, 256, 256, 4, 2, 64, True, 0, 0.0),
            (1, 128, 256, 4, 1, 128, True, 0, 0.0),     # offset queries
            (2, 256, 256, 8, 8, 64, True, 64, 0.0),     # MHA + window
            (1, 256, 256, 2, 1, 64, False, 0, 0.0),     # bidirectional
            (1, 128, 128, 4, 2, 64, True, 0, 30.0),     # softcap
            (2, 300, 300, 4, 2, 64, True, 0, 0.0),      # padded
            (1, 100, 260, 4, 4, 32, True, 48, 0.0),     # padded + window
        ])
    def test_vs_oracle(self, B, Sq, Skv, H, KV, hd, causal, window, cap,
                       dtype):
        ks = jax.random.split(jax.random.PRNGKey(Sq + Skv + H), 3)
        q = rand(ks[0], (B, Sq, H, hd), dtype)
        k = rand(ks[1], (B, Skv, KV, hd), dtype)
        v = rand(ks[2], (B, Skv, KV, hd), dtype)
        out = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap=cap)
        ref = kref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window, softcap=cap)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)

    def test_matches_model_attention(self):
        """Kernel path == model jnp path through attention.attend."""
        from repro.models import attention, common
        B, S, H, KV, hd = 2, 128, 4, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = rand(ks[0], (B, S, H, hd), jnp.float32)
        k = rand(ks[1], (B, S, KV, hd), jnp.float32)
        v = rand(ks[2], (B, S, KV, hd), jnp.float32)
        mask = common.causal_mask(S, S)
        jnp_out = attention.attend(q, k, v, mask=mask)
        ker_out = ops.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(jnp_out), np.asarray(ker_out),
                                   atol=2e-5, rtol=2e-5)


@needs_dev_deps
class TestLruScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,L,R,h0", [
        (2, 256, 512, False), (2, 300, 130, True), (1, 64, 1024, True),
        (3, 1024, 64, False),
    ])
    def test_vs_oracle(self, B, L, R, h0, dtype):
        ks = jax.random.split(jax.random.PRNGKey(L * R), 3)
        a = jax.nn.sigmoid(rand(ks[0], (B, L, R), jnp.float32)).astype(dtype)
        b = (rand(ks[1], (B, L, R), jnp.float32) * 0.5).astype(dtype)
        h = rand(ks[2], (B, R), dtype) if h0 else None
        out = ops.lru_scan(a, b, h)
        ref = kref.lru_scan_ref(a, b, h)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    def test_matches_hybrid_lru(self):
        from repro.models import hybrid
        B, L, R = 2, 64, 32
        ks = jax.random.split(jax.random.PRNGKey(7), 2)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, L, R)))
        b = jax.random.normal(ks[1], (B, L, R))
        model_scan = hybrid.lru_scan(a, b)
        kernel = ops.lru_scan(a, b)
        np.testing.assert_allclose(np.asarray(model_scan),
                                   np.asarray(kernel), atol=1e-4)


from repro.core.engine.placement import FIT_EPS
from repro.kernels import schedule_step as kss


def _pass_all_backends(demand, gp, width, queue_key, assign, free,
                       pending_free, cand, under, be_q, te, cap,
                       s=4.0, block_j=16):
    """Run the fused schedule pass through all three backends: the
    jit'd ops wrapper (Pallas interpret, padded to the block multiple),
    the portable jnp twin, and the straight-line oracle. Normalizer
    computation mirrors the ops wrapper so the twin/oracle see the
    exact scalars the kernel sees."""
    sz = jnp.sqrt(jnp.sum(jnp.square(demand / cap), -1))
    max_sz = jnp.maximum(jnp.max(jnp.where(cand, sz, 0.0)), 1e-12)
    max_gp = jnp.maximum(jnp.max(jnp.where(cand, gp, 0.0)), 1e-12)
    pal = ops.schedule_step(demand, gp, width, queue_key, assign, free,
                            pending_free, cand, under, be_q, te, cap,
                            s=s, block_j=block_j)
    twin = kss.schedule_step_jnp(demand, gp, width, queue_key, assign,
                                 free, pending_free, cand, under, be_q,
                                 te, cap, max_sz, max_gp, s)
    oracle = kss.SchedulePass(*kref.schedule_step_ref(
        demand, gp, width, queue_key, assign, free, pending_free, cand,
        under, be_q, te, cap, max_sz, max_gp, s, eps=FIT_EPS))
    return pal, twin, oracle


def _assert_pass_equal(a, b):
    for name, x, y in zip(kss.SchedulePass._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


def _rand_instance(J, M, seed):
    """Random gang-shaped pass inputs: single-node and 2-node-gang
    assignments, mixed TE/BE masks, random queue keys."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    demand = jnp.stack([
        jax.random.randint(ks[0], (J,), 1, 33).astype(jnp.float32),
        jax.random.randint(ks[1], (J,), 1, 257).astype(jnp.float32),
        jax.random.randint(ks[2], (J,), 0, 9).astype(jnp.float32)], 1)
    free = jnp.stack([
        jax.random.randint(ks[3], (M,), 0, 16).astype(jnp.float32),
        jax.random.randint(ks[4], (M,), 0, 128).astype(jnp.float32),
        jax.random.randint(ks[5], (M,), 0, 5).astype(jnp.float32)], 1)
    pend = jnp.stack([
        jax.random.randint(ks[6], (M,), 0, 8).astype(jnp.float32),
        jax.random.randint(ks[7], (M,), 0, 64).astype(jnp.float32),
        jax.random.randint(ks[8], (M,), 0, 3).astype(jnp.float32)], 1)
    node = jax.random.randint(ks[5], (J,), 0, M)
    gang = jax.random.bernoulli(ks[3], 0.3, (J,))
    assign = (jax.nn.one_hot(node, M, dtype=bool)
              | (jax.nn.one_hot((node + 1) % M, M, dtype=bool)
                 & gang[:, None]))
    gp = jax.random.randint(ks[0], (J,), 0, 21).astype(jnp.float32)
    width = jnp.where(gang, 2, 1).astype(jnp.int32)
    queue_key = jax.random.uniform(ks[9], (J,)) * 100.0
    cand = jax.random.bernoulli(ks[1], 0.7, (J,))
    under = jax.random.bernoulli(ks[2], 0.9, (J,))
    be_q = jax.random.bernoulli(ks[4], 0.4, (J,))
    te = jnp.array([4.0, 16.0, 4.0])
    cap = jnp.array([32.0, 256.0, 8.0])
    return (demand, gp, width, queue_key, assign, free, pend, cand,
            under, be_q, te, cap)


@needs_dev_deps
class TestScheduleStepKernel:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(4, 600), st.integers(0, 10_000))
    def test_vs_twin_and_oracle_random(self, J, seed):
        """Random gang tiles with ragged J (padded to the 16-block
        multiple inside the ops wrapper): Pallas == jnp twin == oracle
        bit-for-bit on every SchedulePass field."""
        pal, twin, oracle = _pass_all_backends(*_rand_instance(J, 8, seed))
        _assert_pass_equal(pal, twin)
        _assert_pass_equal(pal, oracle)

    def test_matches_numpy_policy(self):
        """Fused-pass victim argmin == policies.FitGppPolicy main path
        (each candidate on its own node, Eq. 2 free vector taken from
        that node — exactly what the reference engine passes)."""
        from repro.core import policies as pol
        rng = np.random.default_rng(0)
        J, M = 64, 4
        demand = np.stack([rng.integers(1, 33, J), rng.integers(1, 257, J),
                           rng.integers(0, 9, J)], 1).astype(float)
        free = np.zeros((M, 3))
        cand_node = (np.arange(J) % M).astype(np.int64)
        assign = np.zeros((J, M), bool)
        assign[np.arange(J), cand_node] = True
        gp = rng.integers(0, 21, J).astype(float)
        te = np.array([4.0, 16.0, 2.0])
        cap = np.array([32.0, 256.0, 8.0])
        p = pol.FitGppPolicy(s=4.0)
        victims = p.select(
            rng=rng, te_demand=te, cand_ids=np.arange(J),
            cand_demand=demand, cand_node_free=free[cand_node], cand_gp=gp,
            cand_remaining=np.ones(J), under_cap=np.ones(J, bool),
            all_run_demand=demand, all_run_gp=gp, node_cap=cap,
            free_by_node=free, cand_node=cand_node)
        ps = ops.schedule_step(
            jnp.asarray(demand, jnp.float32), jnp.asarray(gp, jnp.float32),
            jnp.ones(J, jnp.int32), jnp.zeros(J, jnp.float32),
            jnp.asarray(assign), jnp.asarray(free, jnp.float32),
            jnp.zeros((M, 3), jnp.float32), jnp.ones(J, bool),
            jnp.ones(J, bool), jnp.zeros(J, bool),
            jnp.asarray(te, jnp.float32), jnp.asarray(cap, jnp.float32),
            s=4.0)
        elig = pol.eligible_eq2(te, demand, free[cand_node])
        if elig.any():
            assert victims == [int(ps.victim)]


class TestScheduleStepEdgeCases:
    """Deterministic fused-pass cases that run without dev extras."""

    def _trivial(self, **over):
        J, M = 5, 2
        base = dict(
            demand=jnp.tile(jnp.asarray([[4.0, 16.0, 1.0]]), (J, 1)),
            gp=jnp.arange(J, dtype=jnp.float32),
            width=jnp.ones(J, jnp.int32),
            queue_key=jnp.arange(J, dtype=jnp.float32),
            assign=jnp.zeros((J, M), bool).at[jnp.arange(J), 0].set(True),
            free=jnp.asarray([[32.0, 256.0, 8.0], [32.0, 256.0, 8.0]]),
            pending_free=jnp.zeros((M, 3)),
            cand=jnp.zeros(J, bool), under=jnp.ones(J, bool),
            be_q=jnp.zeros(J, bool),
            te=jnp.asarray([8.0, 32.0, 2.0]),
            cap=jnp.asarray([32.0, 256.0, 8.0]))
        base.update(over)
        return _pass_all_backends(*base.values(), block_j=4)

    def test_empty_queue_no_victim(self):
        """All masks empty: every scalar output is the -1/0 sentinel,
        on every backend (and the backends agree bit-for-bit)."""
        pal, twin, oracle = self._trivial()
        _assert_pass_equal(pal, twin)
        _assert_pass_equal(pal, oracle)
        assert int(pal.victim) == -1
        assert int(pal.be_head) == -1
        assert int(pal.be_pick) == -1
        assert int(pal.nskip) == 0

    def test_ragged_padding_sentinels(self):
        """J=5 padded to the block_j=4 multiple (8): the three pad rows
        carry zero demand (they'd fit everywhere) — the width/key/mask
        sentinels must keep them out of every reduction and count."""
        pal, twin, oracle = self._trivial(
            cand=jnp.ones(5, bool), be_q=jnp.ones(5, bool))
        _assert_pass_equal(pal, twin)
        _assert_pass_equal(pal, oracle)
        assert pal.fits.shape == (5, 2)
        assert int(pal.be_head) == 0          # key order, not pad rows
        assert int(pal.be_pick) == 0

    def test_gang_best_node_reduction(self):
        """A gang candidate is eligible iff its BEST node passes Eq. 2
        — one crowded node must not mask a slack node (and vice versa
        a single-node candidate on the crowded node stays ineligible)."""
        free = jnp.asarray([[0.0, 0.0, 0.0],      # node 0: crowded
                            [32.0, 256.0, 8.0]])  # node 1: wide open
        gang = jnp.asarray([[True, True],         # gang on both
                            [True, False]])       # single on node 0
        over = dict(
            demand=jnp.tile(jnp.asarray([[4.0, 16.0, 2.0]]), (5, 1)),
            free=free, cand=jnp.arange(5) < 2,
            assign=jnp.zeros((5, 2), bool).at[:2].set(gang))
        pal, twin, oracle = self._trivial(**over)
        _assert_pass_equal(pal, twin)
        _assert_pass_equal(pal, oracle)
        assert int(pal.victim) == 0           # gang eligible via node 1
        over["assign"] = jnp.zeros((5, 2), bool).at[:2, 0].set(True)
        pal2, _, _ = self._trivial(**over)
        assert int(pal2.victim) == -1         # both stuck on node 0

    def test_backfill_pick_and_skips(self):
        """be_pick is the min-key FITTING queued BE job; nskip counts
        the non-fitting queued jobs ahead of it in key order (the
        bounded-backfill depth the scan consumes before placing it)."""
        demand = jnp.asarray([[64.0, 16.0, 1.0],   # key 0: never fits
                              [64.0, 16.0, 1.0],   # key 1: never fits
                              [4.0, 16.0, 1.0],    # key 2: fits
                              [4.0, 16.0, 1.0],    # key 3: fits
                              [4.0, 16.0, 1.0]])   # not queued
        pal, twin, oracle = self._trivial(
            demand=demand, be_q=jnp.arange(5) < 4)
        _assert_pass_equal(pal, twin)
        _assert_pass_equal(pal, oracle)
        assert int(pal.be_head) == 0
        assert int(pal.be_pick) == 2
        assert int(pal.nskip) == 2
        np.testing.assert_array_equal(np.asarray(pal.fit_now),
                                      [0, 0, 2, 2, 2])


class TestRemovedFitgppShims:
    """The standalone fitgpp kernel entry points were subsumed by the
    fused pass; stale call sites must fail loudly at CALL time with a
    pointer to schedule_step."""

    def test_ops_fitgpp_select_raises(self):
        with pytest.raises(RuntimeError, match="schedule_step"):
            ops.fitgpp_select(None, None)

    def test_fitgpp_score_module_raises(self):
        from repro.kernels import fitgpp_score
        with pytest.raises(RuntimeError, match="schedule_step"):
            fitgpp_score.fitgpp_score()

class TestFitgppScoreBackend:
    """The registry-wired score-backend switch: a full JAX-engine run
    with ``SimConfig.score_backend="pallas"`` (Eq. 1-4 score + masked
    argmin on the Pallas kernel) is bit-identical to the jnp path."""

    def test_sim_parity_jnp_vs_pallas(self):
        import dataclasses
        from repro.configs.cluster import SimConfig, WorkloadSpec
        from repro.core import sim_jax, workload
        cfg = SimConfig(workload=WorkloadSpec(n_jobs=160), policy="fitgpp",
                        seed=5)
        js = workload.generate(cfg)
        jobs = sim_jax.jobs_from_jobset(js)
        st_jnp = sim_jax.run_jit(cfg, jobs, 5)
        st_pal = sim_jax.run_jit(
            dataclasses.replace(cfg, score_backend="pallas"), jobs, 5)
        np.testing.assert_array_equal(np.asarray(st_pal.finish),
                                      np.asarray(st_jnp.finish))
        np.testing.assert_array_equal(np.asarray(st_pal.preempt_count),
                                      np.asarray(st_jnp.preempt_count))
        np.testing.assert_array_equal(np.asarray(st_pal.last_vacate),
                                      np.asarray(st_jnp.last_vacate))

    def test_traced_s_falls_back_to_jnp(self):
        """A traced ``s`` (vmapped s-sweeps) no longer falls back to
        jnp: the kernel takes ``s`` as data, the resolver keeps the
        requested backend, and a jitted run with a traced ``s`` on the
        kernel equals the jnp path bitwise. A policy that registers no
        such backend still runs its jnp path."""
        import dataclasses
        from repro.configs.cluster import SimConfig, WorkloadSpec
        from repro.core import policy_registry, sim_jax, workload
        cfg = SimConfig(workload=WorkloadSpec(n_jobs=96), policy="fitgpp",
                        seed=3)
        pal = dataclasses.replace(cfg, score_backend="pallas")
        assert sim_jax._resolve_score_backend(
            pal, policy_registry.get_policy("fitgpp")) == "pallas"
        assert sim_jax._resolve_score_backend(
            SimConfig(policy="lrtp", score_backend="pallas"),
            policy_registry.get_policy("lrtp")) == "jnp"
        jobs = sim_jax.jobs_from_jobset(workload.generate(cfg))
        run = jax.jit(lambda c, s: sim_jax.run(c, jobs, seed=3, s=s),
                      static_argnums=0)
        s = jnp.asarray(2.0, jnp.float32)
        assert sim_jax.state_diff_fields(run(cfg, s), run(pal, s)) == []


@needs_dev_deps
class TestSsdChunkKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,L,H,P,N", [
        (2, 256, 2, 64, 32), (1, 512, 4, 64, 128), (2, 128, 2, 32, 16),
    ])
    def test_vs_oracle(self, B, L, H, P, N, dtype):
        ks = jax.random.split(jax.random.PRNGKey(L + H), 4)
        xdt = (rand(ks[0], (B, L, H, P), jnp.float32) * 0.3).astype(dtype)
        loga = -jax.nn.softplus(rand(ks[1], (B, L, H), jnp.float32))
        loga = loga.astype(dtype)
        Bm = (rand(ks[2], (B, L, H, N), jnp.float32) * 0.3).astype(dtype)
        Cm = (rand(ks[3], (B, L, H, N), jnp.float32) * 0.3).astype(dtype)
        out = ops.ssd_chunk(xdt, loga, Bm, Cm)
        # oracle operates per chunk of 256 (matches kernel Q)
        Q = min(256, L)
        outs = []
        for c in range(L // Q):
            sl = slice(c * Q, (c + 1) * Q)
            outs.append(kref.ssd_chunk_ref(xdt[:, sl], loga[:, sl],
                                           Bm[:, sl], Cm[:, sl]))
        ref = jnp.concatenate(outs, axis=1)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    def test_matches_model_ssd_scan(self):
        """kernel == models.ssm.ssd_scan y_diag path (zero init, 1 chunk)."""
        from repro.models import ssm
        B, Q, H, P, N = 1, 64, 2, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(9), 4)
        xdt = jax.random.normal(ks[0], (B, Q, H, P)) * 0.3
        loga = -jax.nn.softplus(jax.random.normal(ks[1], (B, Q, H)))
        Bm = jax.random.normal(ks[2], (B, Q, H, N)) * 0.3
        Cm = jax.random.normal(ks[3], (B, Q, H, N)) * 0.3
        y_scan, _ = ssm.ssd_scan(xdt, loga, Bm, Cm, chunk=Q)
        y_ker = ops.ssd_chunk(xdt, loga, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_scan),
                                   atol=1e-4)

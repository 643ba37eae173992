#!/usr/bin/env python3
"""Smoke run of the FitGpp engines on one TPU chip, at the paper's
deployment (§4.1–4.2: 84 nodes of (32 CPU, 256 GB, 8 GPU), 2^16 jobs at
FIFO-normalized load 2.0, P=1, s=4).

    python3 chip_smoke.py                # one chip, every phase below
    python3 chip_smoke.py --four-chips   # only the sharded sweep fabric,
                                         # on a 4-chip mesh

Phases, in this order, in one process; any failure ends the run with a
non-zero exit code:

1. device     platform, kind and count; anything but a TPU exits here.
2. replay     ``paper-synthetic`` through ``api.run_experiment(engine=
              "jax")`` for fifo, lrtp and fitgpp: every job finishes,
              fifo and lrtp equal the numpy reference engine job for
              job, and fitgpp holds the golden bounds of
              ``tests/test_paper_claims.py`` against FIFO.
3. parity     the same deployment at 8192 jobs: fitgpp takes no random
              fallback and equals the reference job for job; the
              native Pallas schedule pass gives the same final State as
              the jnp path, and alone equals its jnp twin field for
              field at 84 nodes.
4. stream     ``api.run_stream("stream-closed-loop", "fitgpp")`` with
              the default pool harvests every job and spills none; the
              streamed-vs-monolithic parity windows hold for lrtp.
5. sweep      a Fig. 4-shaped grid (fitgpp, s in {0, 1, 2, 4, 8} x 4
              seeds, 4096 jobs) through ``api.run_table`` on one chip,
              with buffer donation and without: bitwise equal.

``--four-chips`` runs only the sweep grid on a 4-device mesh against
the single-device vmap: bitwise equal, on 4 devices.

Lines before the last give each phase's wall time, compilation
included; they are set-up times, not measurements. The last line is
one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

N_NODES = 84                 # paper §4.1
N_JOBS = 1 << 16             # paper §4.2
PARITY_JOBS = 8192
SWEEP_JOBS = 4096
SWEEP_S = (0.0, 1.0, 2.0, 4.0, 8.0)
SWEEP_SEEDS = 4
STREAM_PARITY_JOBS = 8192
TE_P95_CUT = 0.80            # tests/test_paper_claims.py
BE_P50_WORSENING = 0.35


class SmokeError(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def device() -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    return info


def _paper_cfg(policy: str, n_jobs: int, n_nodes: int):
    from repro import api
    from repro.configs.base import PAPER_P, PAPER_S
    cfg = api.make_config(policy, n_jobs=n_jobs, n_nodes=n_nodes,
                          s=PAPER_S, P=PAPER_P)
    check(cfg.workload.load == 2.0, "paper-synthetic runs at load 2.0")
    return cfg


def _same_jobs(policy: str, st, ref) -> None:
    """Per-job finish ticks and preemption counts, JAX vs reference."""
    import numpy as np
    for field in ("finish", "preempt_count"):
        a = np.asarray(getattr(st, field))
        b = np.asarray(getattr(ref, field))
        bad = np.flatnonzero(a != b)
        check(bad.size == 0,
              f"{policy}: {field} differs from the reference engine at "
              f"{bad.size} jobs (first job {bad[:1].tolist()})")


def replay(n_jobs: int = N_JOBS, n_nodes: int = N_NODES) -> dict:
    """Phase 2: monolithic replay of the paper's deployment."""
    import numpy as np

    from repro import api, scenarios
    from repro.core import sim_jax
    cfg = _paper_cfg("fifo", n_jobs, n_nodes)
    js = scenarios.build("paper-synthetic", cfg)
    sd = {}
    for pol in ("fifo", "lrtp", "fitgpp"):
        t0 = time.perf_counter()
        r = api.run_experiment("paper-synthetic", pol, "jax", cfg=cfg,
                               jobs=js)
        jobs, st = r.raw
        done = int(st.n_done)
        log(f"replay {pol}: {done}/{js.n} jobs done at t={int(st.t)}, "
            f"fallback_count={r.fallback_count}, set-up wall "
            f"{time.perf_counter() - t0:.1f} s (compile included)")
        check(done == js.n, f"{pol}: only {done} of {js.n} jobs finished "
                            f"(the loop stopped at t={int(st.t)})")
        if pol != "fitgpp":
            ref = api.run_experiment("paper-synthetic", pol, "reference",
                                     cfg=cfg, jobs=js)
            _same_jobs(pol, st, ref.raw)
        sd[pol] = np.asarray(sim_jax.slowdown(jobs, st))
    te = np.asarray(js.is_te, bool)
    fifo_te95 = np.percentile(sd["fifo"][te], 95)
    fit_te95 = np.percentile(sd["fitgpp"][te], 95)
    cut = 1.0 - fit_te95 / fifo_te95
    worse = np.median(sd["fitgpp"][~te]) / np.median(sd["fifo"][~te]) - 1.0
    log(f"replay fitgpp vs fifo: TE p95 {fifo_te95:.3f} -> {fit_te95:.3f} "
        f"(cut {cut:.1%}), BE p50 change {worse:+.1%}")
    check(cut >= TE_P95_CUT, f"TE p95 cut {cut:.1%} < {TE_P95_CUT:.0%}")
    check(worse <= BE_P50_WORSENING,
          f"BE p50 worsened {worse:.1%} > {BE_P50_WORSENING:.0%}")
    return {"te_p95_cut": float(cut), "be_p50_change": float(worse)}


def _pass_instance(J: int, M: int, seed: int = 0):
    """A random schedule-pass input at the deployment's node shape:
    single-node and 2-node-gang placements, mixed masks."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    demand = np.stack([rng.integers(1, 33, J), rng.integers(1, 257, J),
                       rng.choice([0, 1, 2, 4, 8], J)], 1)
    free = np.stack([rng.integers(0, 33, M), rng.integers(0, 257, M),
                     rng.integers(0, 9, M)], 1)
    pend = np.stack([rng.integers(0, 9, M), rng.integers(0, 65, M),
                     rng.integers(0, 3, M)], 1)
    node = rng.integers(0, M, J)
    gang = rng.random(J) < 0.3
    assign = np.zeros((J, M), bool)
    assign[np.arange(J), node] = True
    assign[np.arange(J), (node + 1) % M] |= gang
    f32 = jnp.float32
    return (jnp.asarray(demand, f32), jnp.asarray(rng.integers(0, 21, J), f32),
            jnp.asarray(np.where(gang, 2, 1), jnp.int32),
            jnp.asarray(rng.permutation(J), f32), jnp.asarray(assign),
            jnp.asarray(free, f32), jnp.asarray(pend, f32),
            jnp.asarray(rng.random(J) < 0.7), jnp.asarray(rng.random(J) < 0.9),
            jnp.asarray(rng.random(J) < 0.4),
            jnp.asarray([4.0, 16.0, 4.0], f32),
            jnp.asarray([32.0, 256.0, 8.0], f32))


def kernel_pass(J: int, M: int, native: bool) -> None:
    """The fused pass alone: Pallas equals the jnp twin field for
    field; ``native`` also asserts a Mosaic kernel in the program."""
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import PAPER_S
    from repro.kernels import ops
    from repro.kernels import schedule_step as kss
    args = _pass_instance(J, M)
    demand, gp, cand, cap = args[0], args[1], args[7], args[11]
    if native:
        hlo = ops.schedule_step.lower(*args, s=PAPER_S).as_text()
        check("tpu_custom_call" in hlo,
              "the Pallas schedule pass did not compile to a TPU kernel")
    pal = ops.schedule_step(*args, s=PAPER_S)
    sz = kss.eq1_size(demand, cap)
    max_sz = jnp.maximum(jnp.max(jnp.where(cand, sz, 0.0)), 1e-12)
    max_gp = jnp.maximum(jnp.max(jnp.where(cand, gp, 0.0)), 1e-12)
    twin = kss.schedule_step_jnp(*args, max_sz, max_gp, PAPER_S)
    for name, a, b in zip(kss.SchedulePass._fields, pal, twin):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"schedule pass at J={J}, M={M}: Pallas {name} differs "
              "from the jnp twin")
    log(f"kernel: fused pass J={J} M={M} equals the jnp twin "
        f"(victim {int(pal.victim)}, pick {int(pal.be_pick)}, "
        f"nskip {int(pal.nskip)})")


def parity(n_jobs: int = PARITY_JOBS, n_nodes: int = N_NODES,
           native: bool = True) -> None:
    """Phase 3: exact fitgpp parity and the Pallas path."""
    from repro import api, scenarios
    from repro.core import sim_jax
    from repro.core.stream import default_capacity
    cfg = _paper_cfg("fitgpp", n_jobs, n_nodes)
    js = scenarios.build("paper-synthetic", cfg)
    t0 = time.perf_counter()
    jnp_run = api.run_experiment("paper-synthetic", "fitgpp", "jax",
                                 cfg=cfg, jobs=js)
    check(jnp_run.fallback_count == 0,
          f"fitgpp took the random fallback {jnp_run.fallback_count} "
          "times; exact parity needs none")
    ref = api.run_experiment("paper-synthetic", "fitgpp", "reference",
                             cfg=cfg, jobs=js)
    _same_jobs("fitgpp", jnp_run.raw[1], ref.raw)
    pal_run = api.run_experiment("paper-synthetic", "fitgpp", "jax",
                                 cfg=cfg, jobs=js, score_backend="pallas")
    diff = sim_jax.state_diff_fields(jnp_run.raw[1], pal_run.raw[1])
    check(not diff, f"pallas vs jnp final State differs in {diff}")
    log(f"parity fitgpp: {js.n} jobs equal the reference, pallas State "
        f"equals jnp, set-up wall {time.perf_counter() - t0:.1f} s "
        "(compile included)")
    pool = default_capacity(cfg)
    for J in (pool, n_jobs):
        kernel_pass(J, n_nodes, native)


def stream_phase(n_jobs: int = N_JOBS, n_nodes: int = N_NODES,
                 parity_jobs: int = STREAM_PARITY_JOBS) -> None:
    """Phase 4: the streamed online path."""
    from repro import api
    from repro.core import stream
    t0 = time.perf_counter()
    r = api.run_stream("stream-closed-loop", "fitgpp", n_jobs=n_jobs,
                       n_nodes=n_nodes)
    res = r.raw
    log(f"stream fitgpp: {res.n_jobs}/{n_jobs} jobs harvested through "
        f"{res.capacity} slots in {res.rounds} rounds (peak live "
        f"{res.max_live}, spilled {res.n_spilled}), set-up wall "
        f"{time.perf_counter() - t0:.1f} s (compile included)")
    check(res.capacity == stream.default_capacity(r.cfg),
          "stream ran with a non-default pool")
    check(res.n_jobs == n_jobs, f"stream harvested {res.n_jobs} of "
                                f"{n_jobs} jobs")
    check(res.n_spilled == 0, f"stream spilled {res.n_spilled} jobs")
    cfg = _paper_cfg("lrtp", parity_jobs, n_nodes)
    cap = stream.default_capacity(cfg)
    t0 = time.perf_counter()
    diff = stream.verify_closed_loop_parity(cfg, n_jobs=parity_jobs,
                                            capacity=cap, chunk=1024)
    check(not diff, f"streamed closed loop differs in {diff}")
    open_cfg = dataclasses.replace(
        cfg, workload=dataclasses.replace(cfg.workload, load=0.5))
    diff = stream.verify_prefix_parity(open_cfg, n_jobs=parity_jobs,
                                       capacity=cap, chunk=1024)
    check(not diff, f"streamed prefix differs in {diff}")
    log(f"stream lrtp: closed-loop and prefix parity windows hold at "
        f"{parity_jobs} jobs through {cap} slots, set-up wall "
        f"{time.perf_counter() - t0:.1f} s (compile included)")


def _sweep_table(cfg, n_seeds: int, s_vals):
    import numpy as np

    from repro import api, scenarios
    jobsets = [scenarios.build("paper-synthetic",
                               dataclasses.replace(cfg, seed=k))
               for k in range(n_seeds)]
    return api.build_table(
        jobsets * len(s_vals),
        np.repeat(np.asarray(s_vals, np.float32), n_seeds), 1,
        np.tile(np.arange(n_seeds, dtype=np.uint32), len(s_vals)))


def _same_stats(a, b, what: str) -> None:
    import numpy as np
    check(a.stats.keys() == b.stats.keys(), f"{what}: different summaries")
    diff = [k for k in a.stats
            if not np.array_equal(a.stats[k], b.stats[k], equal_nan=True)]
    check(not diff, f"{what}: results differ in {diff}")
    check(np.isfinite(a.stats["te_slowdown"]).all()
          and (a.stats["te_slowdown"] >= 1.0).all(),
          f"{what}: TE slowdowns are not finite and >= 1")


def sweep(n_jobs: int = SWEEP_JOBS, n_nodes: int = N_NODES,
          n_seeds: int = SWEEP_SEEDS, s_vals=SWEEP_S,
          n_devices: int = 1) -> None:
    """Phase 5 (``n_devices=1``): donated vs kept buffers on one chip.
    ``--four-chips`` (``n_devices=4``): sharded fabric vs the
    single-device vmap."""
    from repro import api
    cfg = _paper_cfg("fitgpp", n_jobs, n_nodes)
    n_trials = n_seeds * len(s_vals)
    t0 = time.perf_counter()
    if n_devices == 1:
        a = api.run_table(cfg, _sweep_table(cfg, n_seeds, s_vals),
                          devices=1)                   # default donate
        b = api.run_table(cfg, _sweep_table(cfg, n_seeds, s_vals),
                          devices=1, donate=False)
        what = "donated vs kept buffers"
    else:
        a = api.run_table(cfg, _sweep_table(cfg, n_seeds, s_vals),
                          devices=n_devices)
        b = api.run_table(cfg, _sweep_table(cfg, n_seeds, s_vals),
                          devices=1, donate=False)
        check(a.n_devices == n_devices,
              f"sharded fabric ran on {a.n_devices} devices, not "
              f"{n_devices}")
        what = f"{n_devices}-device sharded vs single-device vmap"
    check(a.n_trials == n_trials, f"{a.n_trials} trials, not {n_trials}")
    _same_stats(a, b, what)
    log(f"sweep: {n_trials} trials of {n_jobs} jobs, {what}: bitwise "
        f"equal, set-up wall {time.perf_counter() - t0:.1f} s "
        "(compile included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sweep fabric sharded over 4 chips "
                         "against the single-device vmap")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    info = device()
    if info["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, found platform "
              f"{info['platform']!r}", file=sys.stderr)
        return 2
    from repro import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    if args.four_chips:
        check(info["count"] >= 4, f"--four-chips needs 4 devices, found "
                                  f"{info['count']}")
        sweep(n_devices=4)
    else:
        replay()
        parity()
        stream_phase()
        sweep()
    log(f"all phases passed, set-up wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

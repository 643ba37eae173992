"""Scenario runner CLI.

    PYTHONPATH=src python -m repro.scenarios list [--kind synthetic|trace]
    PYTHONPATH=src python -m repro.scenarios describe NAME [--n-jobs 512]
    PYTHONPATH=src python -m repro.scenarios run NAME [--policy fitgpp]
        [--engine reference|jax] [--score-backend jnp|pallas]
        [--n-jobs 512] [--nodes 16] [--seed 0] [--mode event|tick]
        [--trace out.json [--trace-format perfetto|csv]]
        [--stream [--capacity N]]
    PYTHONPATH=src python -m repro.scenarios sweep NAME [NAME ...]
        [--seeds 0,1] [--n-jobs 256] [--policy fitgpp]
        [--mode event|tick] [--devices N] [--mesh auto|off]

``run`` replays one scenario through ``repro.api.run_experiment`` on
either engine (any registered policy — the choices come from the
policy registry) and prints the paper-style slowdown table; with
``--stream`` it goes through the bounded-memory macro-round engine
(``repro.api.run_stream``, DESIGN.md §10) instead, whose memory
scales with ``--capacity`` rather than the trace length. ``sweep``
batches every (scenario, seed) trial — ragged job counts included —
into one vmapped JAX sweep. ``describe`` adds one-pass streamed
workload stats (job counts, TE/BE split, reader drop accounting) for
scenarios with a registered streaming source.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro import api, scenarios
from repro.configs.cluster import ClusterSpec, SimConfig, WorkloadSpec
from repro.core import metrics


def _cfg(args, seed=None) -> SimConfig:
    return SimConfig(
        cluster=ClusterSpec(n_nodes=args.nodes),
        workload=WorkloadSpec(n_jobs=args.n_jobs),
        policy=args.policy,
        score_backend=getattr(args, "score_backend", "jnp"),
        time_mode=getattr(args, "mode", "event"),
        seed=args.seed if seed is None else seed)


def cmd_list(args) -> None:
    rows = scenarios.all_scenarios(args.kind)
    width = max(len(s.name) for s in rows)
    for sc in rows:
        print(f"{sc.name:{width}s}  [{sc.kind}]  {sc.description}")
    n_syn = len(scenarios.scenario_names(scenarios.SYNTHETIC))
    n_tr = len(scenarios.scenario_names(scenarios.TRACE))
    print(f"\n{n_syn} synthetic scenarios, {n_tr} trace adapters")


def cmd_describe(args) -> None:
    sc = scenarios.get_scenario(args.name)
    print(f"{sc.name} [{sc.kind}]\n  {sc.description}")
    lines = (sc.fn.__doc__ or "").strip().splitlines()
    if lines and lines[0].strip() == sc.description:
        lines = lines[1:]                      # summary already printed
    if any(ln.strip() for ln in lines):
        print("\n" + "\n".join(f"  {ln.strip()}" for ln in lines))
    if sc.knobs:
        print("\n  knobs:")
        for k, v in sc.knobs:
            print(f"    {k:28s} {v}")
    if sc.source is not None:
        # one bounded-memory pass over the registered stream: job
        # counts, class split and reader drop accounting in one read
        from repro.core import stream
        cfg = SimConfig(cluster=ClusterSpec(n_nodes=args.nodes),
                        workload=WorkloadSpec(n_jobs=args.n_jobs),
                        seed=args.seed)
        info = stream.scan(sc.source(cfg))
        print(f"\n  stream (one pass, n_jobs={args.n_jobs}):")
        print(f"    {info.n_jobs} jobs: {info.n_te} TE / {info.n_be} BE, "
              f"{info.n_gang} gangs; horizon {info.horizon} min, "
              f"{info.total_exec_min} exec-min total")
        ts = info.stats
        if ts is not None:
            print(f"    kept {ts.n_jobs}/{ts.n_rows} rows (dropped: "
                  f"{ts.n_malformed} malformed, {ts.n_zero_runtime} "
                  f"zero-runtime, {ts.n_too_wide} too-wide, "
                  f"{ts.n_filtered_status} status-filtered)")


def cmd_run(args) -> None:
    cfg = _cfg(args)
    if args.stream:
        r = api.run_stream(args.name, cfg.policy, cfg=cfg,
                           capacity=args.capacity, mode=args.mode,
                           trace=bool(args.trace),
                           admission=(True if args.closed_loop else None))
        res = r.raw
        arrivals = ("closed-loop "
                    f"(load {cfg.workload.load:g}) " if args.closed_loop
                    else "")
        print(f"{args.name}: {res.n_jobs} jobs streamed {arrivals}through "
              f"{res.capacity} slots in {res.rounds} rounds "
              f"(peak live {res.max_live}, spilled {res.n_spilled}), "
              f"policy={cfg.policy}, engine=stream, "
              f"nodes={cfg.cluster.n_nodes}")
        print(metrics.format_table(
            {r.policy: r.table},
            f"slowdown percentiles (makespan {r.makespan} min)"))
        print(f"resched intervals [min]: p50={r.intervals['p50']:.1f} "
              f"p95={r.intervals['p95']:.1f}   preempted "
              f"{r.preempted_frac * 100:.1f}% of BE jobs")
        print(f"fallback_count={r.fallback_count} "
              f"trace_overflow={r.trace_overflow}")
        if args.trace:
            from repro.obs import export
            export.write_trace(args.trace, r.events,
                               fmt=args.trace_format,
                               n_nodes=cfg.cluster.n_nodes,
                               is_te=res.is_te,
                               preemptive=api.get_policy(
                                   cfg.policy).preemptive)
            print(f"{len(r.events)} events -> {args.trace} "
                  f"[{args.trace_format}]"
                  + (f" (WARNING: {r.trace_overflow} rows dropped)"
                     if r.trace_overflow else ""))
        return
    js = scenarios.build(args.name, cfg)
    gangs = int((np.asarray(js.n_nodes) > 1).sum())
    print(f"{args.name}: {js.n} jobs ({int(js.is_te.sum())} TE, "
          f"{gangs} gangs), horizon {int(js.submit.max())} min, "
          f"policy={cfg.policy}, engine={args.engine}, "
          f"nodes={cfg.cluster.n_nodes}")
    r = api.run_experiment(args.name, cfg.policy, args.engine, cfg=cfg,
                           jobs=js, mode=args.mode, trace=bool(args.trace))
    print(metrics.format_table(
        {r.policy: r.table},
        f"slowdown percentiles (makespan {r.makespan} min)"))
    print(f"resched intervals [min]: p50={r.intervals['p50']:.1f} "
          f"p95={r.intervals['p95']:.1f}   preempted "
          f"{r.preempted_frac * 100:.1f}% of BE jobs")
    if args.engine == "jax":
        print(f"fallback_count={r.fallback_count} "
              f"trace_overflow={r.trace_overflow}")
    if args.trace:
        from repro.obs import export
        export.write_trace(args.trace, r.events,
                           fmt=args.trace_format,
                           n_nodes=cfg.cluster.n_nodes,
                           is_te=np.asarray(js.is_te),
                           preemptive=api.get_policy(cfg.policy).preemptive)
        print(f"{len(r.events)} events -> {args.trace} "
              f"[{args.trace_format}]"
              + (f" (WARNING: {r.trace_overflow} rows dropped)"
                 if r.trace_overflow else ""))


def cmd_sweep(args) -> None:
    import jax
    seeds = [int(s) for s in args.seeds.split(",")]
    devices = 1 if args.mesh == "off" else args.devices
    out = api.scenario_sweep(_cfg(args), args.names, seeds,
                             devices=devices)
    n_trials = len(args.names) * len(seeds)
    mesh = api.mesh_for_sweep(n_trials, devices=devices)
    n_dev = 1 if mesh is None else mesh.devices.size
    print(f"ragged sweep: {len(args.names)} scenarios x {len(seeds)} "
          f"seeds, policy={args.policy} (seed-averaged), "
          f"{n_dev}/{len(jax.devices())} devices")
    hdr = f"{'scenario':22s} | {'TE p50':>8s} {'TE p95':>8s} " \
          f"| {'BE p50':>8s} {'BE p95':>8s} | {'preempted':>9s}"
    print(hdr + "\n" + "-" * len(hdr))
    for i, name in enumerate(args.names):
        te = np.nanmean(out["te_slowdown"][i], axis=0)
        be = np.nanmean(out["be_slowdown"][i], axis=0)
        pf = np.nanmean(out["preempted_frac"][i])
        print(f"{name:22s} | {te[0]:8.2f} {te[1]:8.2f} "
              f"| {be[0]:8.2f} {be[1]:8.2f} | {pf * 100:8.1f}%")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python -m repro.scenarios",
                                 description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("list", help="list registered scenarios")
    p.add_argument("--kind", choices=(scenarios.SYNTHETIC, scenarios.TRACE),
                   default=None)
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("describe", help="knobs + doc for one scenario "
                                        "(+ one-pass stream stats when "
                                        "it has a streaming source)")
    p.add_argument("name")
    p.add_argument("--n-jobs", type=int, default=512,
                   help="stream length for the one-pass stats (512)")
    p.add_argument("--nodes", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.set_defaults(fn=cmd_describe)

    def sim_args(p):
        p.add_argument("--policy", default="fitgpp",
                       choices=api.policy_names())
        p.add_argument("--n-jobs", type=int, default=512)
        p.add_argument("--nodes", type=int, default=16)
        p.add_argument("--seed", type=int, default=0)

    p = sub.add_parser("run", help="replay through either engine "
                                   "(repro.api.run_experiment)")
    p.add_argument("name")
    sim_args(p)
    p.add_argument("--engine", default="reference", choices=api.ENGINES)
    p.add_argument("--mode", default="event", choices=("event", "tick"),
                   help="time advancement, either engine (bit-identical; "
                        "event skips no-op ticks)")
    p.add_argument("--score-backend", default="jnp",
                   choices=api.score_backend_names(),
                   help="JAX-engine score path for score policies")
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="record the canonical event stream (both "
                        "engines; in-jit ring buffer on jax) and write "
                        "it to PATH")
    p.add_argument("--trace-format", default="perfetto",
                   choices=("perfetto", "csv"),
                   help="trace file format: Chrome/Perfetto JSON "
                        "(load in ui.perfetto.dev) or lossless CSV")
    p.add_argument("--stream", action="store_true",
                   help="replay through the bounded-memory streaming "
                        "engine (core/stream): memory scales with "
                        "--capacity, not --n-jobs")
    p.add_argument("--capacity", type=int, default=None,
                   help="streaming slot-pool size (default "
                        "32 x nodes x max_preemptions)")
    p.add_argument("--closed-loop", action="store_true",
                   help="with --stream: re-stamp the source's submit "
                        "times as closed-loop admit ticks holding the "
                        "FIFO backlog at the workload load (paper "
                        "§4.2; bit-exact with the monolithic "
                        "closed-loop scenarios)")
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("sweep", help="ragged multi-scenario JAX sweep")
    p.add_argument("names", nargs="+")
    sim_args(p)
    p.add_argument("--seeds", default="0,1")
    p.add_argument("--mode", default="event", choices=("event", "tick"),
                   help="JAX-engine time advancement inside the vmapped "
                        "sweep (per-lane event jumps)")
    p.add_argument("--devices", type=int, default=None,
                   help="cap the sweep-fabric trial mesh at N devices "
                        "(default: every local device; more than are "
                        "present is an error)")
    p.add_argument("--mesh", default="auto", choices=("auto", "off"),
                   help="'off' forces the single-device vmap "
                        "(bit-identical to the sharded run)")
    p.set_defaults(fn=cmd_sweep)

    args = ap.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()

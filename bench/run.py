#!/usr/bin/env python3
"""Runs one benchmark cell once, on the accelerator it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell ``bench/cells/<cell>.json`` names a
configuration ``bench/configs/<config>.json`` and a traffic mix
``bench/traffic/<traffic>.json``; the traffic names a path,
``bench/paths/<path>.py``, which drives the program; each per-layer
metric ``<m>`` of ``BENCHMARK.json`` is read by ``bench/metrics/<m>.py``.

A run: job sets from the seed (``bench/gen.py``), warm-up (every program
the window uses is compiled, from the persistent cache in
``<checkout>/.jax_cache`` after a checkout's first run), then whole units
back to back until ``--seconds`` have passed, then the comparison with
the plain reference (``bench/compare.py``). A traced run (``--trace 1``)
puts one unit under the profiler instead; where the cell has per-layer
metrics from the host clock, it first runs the untraced window too, and
those metrics are read from it. Counters go to standard
output first; its last line is one JSON object. The numbers compared,
each beside its limit, are the last lines of standard error. A run that
finds no TPU, or fewer chips than the cell asks for, exits 2 and prints
no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE = os.path.join(ROOT, ".jax_cache")


def find(kind: str, name: str, ext: str, dirs=(BENCH,)) -> str:
    """``<dir>/<kind>/<name><ext>`` in the first of ``dirs`` that has it."""
    for d in dirs:
        path = os.path.join(d, kind, name + ext)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {list(dirs)}")


def load_json(kind: str, name: str, dirs=(BENCH,)) -> dict:
    with open(find(kind, name, ".json", dirs)) as f:
        return json.load(f)


def load_module(kind: str, name: str, dirs=(BENCH,)):
    path = find(kind, name, ".py", dirs)
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, dirs=(BENCH,)) -> SimpleNamespace:
    """A cell with its configuration and traffic, all found by name."""
    cell = load_json("cells", name, dirs)
    return SimpleNamespace(
        name=name, cell=cell, dirs=tuple(dirs),
        config=load_json("configs", cell["config"], dirs),
        traffic=load_json("traffic", cell["traffic"], dirs))


def manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def e2e_for(man: dict, workload: str) -> list:
    return [m for m in man["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer_for(man: dict, workload: str) -> list:
    """The per-layer metrics a cell reports: those that list it, and
    those without a list whose moved metric the cell reports."""
    reports = {m["name"] for m in e2e_for(man, workload)}
    return [m for m in man["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"]
                                 in reports else [])]


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) from
    JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax._src import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


class HostLoad:
    """What the host did besides the program: the process's CPU time,
    the time spent in Python's garbage collector (and its full
    collections), and the context switches other processes forced."""

    def __init__(self):
        import gc
        self.gc_s, self.gc_full, self._t = 0.0, 0, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc_s += time.perf_counter() - self._t
            self.gc_full += info["generation"] == 2
            self._t = None

    def snapshot(self) -> tuple:
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (time.process_time(), self.gc_s, self.gc_full, ru.ru_nivcsw)

    @staticmethod
    def counters(a: tuple, b: tuple) -> dict:
        keys = ("cpu_s", "gc_s", "gc_full", "forced_switches")
        return {f"window_{k}": y - x for k, x, y in zip(keys, a, b)}


def run_units(path, name: str, seconds: float, n_units: int, annotate):
    """Whole units back to back: until ``seconds`` have passed, or
    ``n_units`` of them when ``n_units`` is set. A unit that raises ends
    the window; it is reported, and its jobs count as unfinished.
    Returns the units, the window's length, whether one crashed, and
    each unit's wall time."""
    units, unit_s, crashed = [], [], 0
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        try:
            with annotate(f"bench.{name}.unit"):
                units.append(path.unit())
        except Exception:                          # noqa: BLE001
            traceback.print_exc()
            crashed = 1
            break
        unit_s.append(time.perf_counter() - t)
        if n_units and len(units) >= n_units:
            break
        if not n_units and time.perf_counter() - t0 >= seconds:
            break
    return units, time.perf_counter() - t0, crashed, unit_s


class Tracer:
    """The profiler session of a traced run. ``start`` and ``stop``
    bracket the traced window (a ``bench.window`` span) once, and only
    while the traced unit runs (``armed``); a path that traces part of a
    unit calls them itself."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.span = None
        self.done = False
        self.armed = False

    def start(self):
        import jax
        if self.span is not None or self.done or not self.armed:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()

    def stop(self):
        import jax
        if self.span is None:
            return
        self.span.__exit__(None, None, None)
        self.span = None
        jax.profiler.stop_trace()
        self.done = True


def _traced_unit(path, name, tracer):
    """One unit under the profiler."""
    import jax
    tracer.armed = True
    if not getattr(path, "traces_itself", False):
        tracer.start()
    try:
        return run_units(path, name, 0, 1, jax.profiler.TraceAnnotation)
    finally:
        tracer.stop()
        tracer.armed = False


def execute(args, man: dict, c: SimpleNamespace, devices, used,
            t0: float = T0, compiles=None) -> dict:
    """Everything of a run after the look for a chip; returns the result
    line's object. ``used`` are the devices the cell runs on."""
    import numpy as np

    from bench import compare, gen
    compiles = compiles or CompileCounter()
    host = HostLoad()
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    ctx = SimpleNamespace(config=c.config, traffic=c.traffic,
                          seed=args.seed, chips=len(used),
                          trace=bool(args.trace),
                          tracer=Tracer(logdir) if logdir else None,
                          jobs=gen.build(c.config, c.traffic, args.seed))
    log(f"jobs: {ctx.jobs.n}, {time.perf_counter() - t0:.3f} s into set-up")
    path_name = c.traffic["path"]
    mod = load_module("paths", path_name, c.dirs)
    path = mod.Path(ctx)
    path.warm()
    setup_s = time.perf_counter() - t0
    log(f"set-up: {setup_s:.3f} s, {compiles.n} compiles")

    layer = per_layer_for(man, c.name) if args.trace else []
    before = compiles.n
    units, window_s, crashed, unit_s, timed = [], 0.0, 0, [], {}
    load = [host.snapshot()]
    if not args.trace or any(m["source"] == "host_clock" for m in layer):
        units, window_s, crashed, unit_s = run_units(
            path, path_name, args.seconds, 0,
            lambda _: contextlib.nullcontext())
        timed = path.metrics(units, window_s) if units else {}
    load.append(host.snapshot())
    traced = []
    if args.trace and not crashed:
        traced, _, crashed, _ = _traced_unit(path, path_name, ctx.tracer)
    in_window = compiles.n - before
    # counters describe the untraced window where there is one
    counted = list(units) or traced
    units += traced
    path.fetch(units)
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in used]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(peaks)}
    counters = {"window_s": window_s, "units": len(units) - len(traced),
                "unit_s": unit_s, **HostLoad.counters(*load),
                "traced_units": len(traced), "units_crashed": crashed,
                "compiles_in_window": in_window,
                **(path.counters(counted) if counted else {}),
                "peak_bytes_in_use": peaks}
    out_metrics, breakdown = {}, None
    if logdir:
        from bench import trace
        try:
            tr = trace.load(logdir, [d.id for d in used])
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        view = SimpleNamespace(trace=tr, units=len(traced), path=path_name,
                               timed=timed)
        for m in layer:
            value = load_module("metrics", m["name"], c.dirs).read(view)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = tr.breakdown()
    else:
        timed["setup_s"] = setup_s
        for m in e2e_for(man, c.name):
            if m["name"] in timed:
                out_metrics[m["name"]] = {"value": timed[m["name"]],
                                          "unit": m["unit"]}
    results = path.results(units)
    # a unit that crashed finished none of its jobs
    results += [(None, None, 0, None)] * crashed
    del units, traced, path     # the program's state goes before the reference
    refs, tallies = {}, []
    for finish, pc, draws, guide in results:
        h = None if guide is None else hashlib.sha1(b"".join(
            np.ascontiguousarray(a).tobytes()
            for a in (guide.finish, guide.preempt_count, guide.last_signal)
            if a is not None)).hexdigest()
        if h not in refs:
            refs[h] = mod.reference_for(ctx, guide)
        tallies.append(compare.tally(finish, pc, draws, refs[h]))
    counts = compare.total(tallies)
    counters["reference_runs"] = len(refs)
    counters["reference_draws"] = sorted(r.fallbacks for r in refs.values())
    counters["jobs_compared"] = counts["compared"]
    for k, v in counters.items():
        log(f"{k}: {v}")
    checks = compare.checks(counts)
    out = {"correct": compare.passed(checks),
           "attempted": counts["jobs"],
           "failed": counts["failed"], "metrics": out_metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    man = manifest()
    if args.workload not in {w["name"] for w in man["workloads"]}:
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    c = load_cell(args.workload)
    chips = int(c.cell["chips"])
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    from repro import compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {compile_cache.enable()}")
    out = execute(args, man, c, devices, devices[:chips])
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

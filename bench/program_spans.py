"""The program's own profiler spans, and the per-layer numbers they give.

The stream driver (``core/stream/engine.py``) and the replay facade
(``api._run_jax``) mark their phases with ``jax.profiler.TraceAnnotation``
spans named ``repro.*``; counts ride as the spans' args. They land on the
host plane of the same trace as the device events, on the same clock.
The functions here take a ``trace.Trace`` and those spans as a sorted
list of ``(start_ns, end_ns, name, args)``, ``args`` mapping each stat
to an int where it is one, and compute the numbers a per-layer reader
would report:

- ``phase_ms``: mean duration of a phase span (``repro.stream.pack``,
  ``repro.stream.harvest``), in ms;
- ``idle_attributed_share``: the share of the device's idle time in the
  traced window that lies inside a phase span of the stream driver, in %;
- ``iter_device_us``: a program's device time over the engine-loop
  iterations that the ``iters`` args count, round by round, in µs;
- ``loop_iters_per_job``: ``iters / jobs`` of the ``repro.replay.counts``
  spans.

Each returns None where the trace holds nothing to read (a program
without the spans). ``bench/trace.py`` keeps only the harness's
``bench.*`` spans, so no reader calls these yet.
"""
from __future__ import annotations

from bench import trace as btrace

STREAM_PHASES = ("repro.stream.pack", "repro.stream.run",
                 "repro.stream.drain", "repro.stream.harvest")


def _in_window(tr, spans, name):
    a, b = tr.window()
    return [s for s in spans if s[2] == name and s[0] >= a and s[1] <= b]


def phase_ms(tr, spans, name: str):
    """Mean duration in ms of the ``name`` spans inside the window."""
    got = _in_window(tr, spans, name)
    if not got:
        return None
    return sum(e - s for s, e, _, _ in got) / len(got) / 1e6


def idle_attributed_share(tr, spans, phases=STREAM_PHASES):
    """Share of the device idle time in the window that lies inside a
    span of ``phases``, in %, over the chips used."""
    cover = btrace.union((s, e) for s, e, n, _ in spans if n in phases)
    if not cover:
        return None
    idle = inside = 0.0
    for dev in tr.devices:
        for s, e, _, _ in tr.idle_gaps(dev):
            idle += e - s
            inside += btrace.covered(cover, s, e)
    return 100.0 * inside / idle if idle > 0 else None


def iter_device_us(tr, spans, program: str, work: str, counts: str):
    """Device time of ``program`` per engine-loop iteration, in µs. Each
    ``counts`` span in the window is paired with the ``work`` span just
    before it (the round's ``repro.stream.run``, the replay's
    ``repro.replay.loop``); a pair counts, with its ``iters``, where
    executions of ``program`` overlap that work span."""
    a, b = tr.window()
    runs = tr.executions(program)
    iters, busy, last = 0, 0.0, None
    for s, e, n, args in spans:
        if n == work:
            last = (s, e)
        elif n == counts and last is not None and s >= a and e <= b:
            got = [y - x for x, y in runs if x < last[1] and y > last[0]]
            if got:
                iters += args.get("iters", 0)
                busy += sum(got)
            last = None
    return busy / iters / 1e3 if iters else None


def loop_iters_per_job(tr, spans, counts: str = "repro.replay.counts"):
    """Engine-loop iterations per job of the replays in the window."""
    got = _in_window(tr, spans, counts)
    jobs = sum(a.get("jobs", 0) for *_, a in got)
    if not jobs:
        return None
    return sum(a.get("iters", 0) for *_, a in got) / jobs

"""The one reduction from a JAX profiler trace to what the per-layer
metrics read.

On a TPU each chip is a plane ``/device:TPU:<id>``; its ``XLA Modules``
line holds one event per program execution, named ``jit_<function>(<
hash>)``, and ``XLA Ops`` one per operation where the profiler traced
operations. On the CPU backend (the tests) operations run on host
threads and carry ``hlo_module`` and ``device_ordinal`` stats; a
program's execution is then the union of its operations. The harness's
host phases are ``jax.profiler.TraceAnnotation`` spans named
``bench.*`` on the host plane, on the same clock.

Busy time is the union of the intervals in which a program runs on the
device; idle share is 1 minus busy over the traced window (the
``bench.window`` span).
"""
from __future__ import annotations

import glob
import os
import re
import warnings
from collections import defaultdict
from dataclasses import dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HASH = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit__run_round_jit(8518...)`` -> ``jit__run_round_jit``."""
    return _HASH.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%while.93 = (s32[]...) while(...)`` -> ``while.93``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` pairs."""
    out = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, a: float, b: float) -> float:
    """Length of ``[a, b]`` that the merged intervals cover."""
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged)


@dataclass
class Trace:
    """Device programs and operations per chip, host spans, in ns."""
    programs: dict = field(default_factory=dict)   # id -> [(s, e, name)]
    ops: dict = field(default_factory=dict)        # id -> [(s, e, name)]
    spans: list = field(default_factory=list)      # [(s, e, name)]
    devices: tuple = ()

    def __post_init__(self):
        self._busy = {d: union((s, e) for s, e, _ in self.programs.get(d, ()))
                      for d in self.devices}

    def spans_named(self, name: str) -> list:
        return sorted((s, e) for s, e, n in self.spans if n == name)

    def window(self) -> tuple:
        w = self.spans_named("bench.window")
        if w:
            return w[0]
        ends = [x for d in self.devices for x in self._busy[d]]
        return (min(s for s, _ in ends), max(e for _, e in ends))

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) / 1e9

    def busy_ns(self, dev, a: float = None, b: float = None) -> float:
        if a is None:
            a, b = self.window()
        return covered(self._busy.get(dev, []), a, b)

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips used."""
        return (sum(self.busy_ns(d) for d in self.devices)
                / len(self.devices) / 1e9)

    def idle_share(self):
        """1 minus busy over the window, in %, averaged over the chips;
        None for an empty window."""
        w = self.window_s()
        return 100.0 * (1.0 - self.busy_s() / w) if w > 0 else None

    def executions(self, program: str, dev=None) -> list:
        """``(start, end)`` of each execution of ``program`` (a program
        name such as ``jit__run_round_jit``) in the window."""
        a, b = self.window()
        devs = self.devices if dev is None else (dev,)
        return [(s, e) for d in devs for s, e, n in self.programs.get(d, ())
                if n == program and s >= a and e <= b]

    def idle_gaps(self, dev) -> list:
        """``(start, end, before, after)`` of each idle stretch of ``dev``
        in the window, with the programs on either side."""
        a, b = self.window()
        progs = sorted((s, e, n) for s, e, n in self.programs.get(dev, ())
                       if e > a and s < b)
        gaps, last_end, last = [], a, "window start"
        for s, e, n in progs:
            if s > last_end:
                gaps.append((last_end, s, last, n))
            if e >= last_end:
                last_end, last = e, n
        if b > last_end:
            gaps.append((last_end, b, last, "window end"))
        return gaps

    def host_phase(self, a: float, b: float) -> str:
        """The innermost harness span around the middle of ``[a, b]``."""
        mid = (a + b) / 2
        inside = [(e - s, n) for s, e, n in self.spans
                  if s <= mid <= e and n != "bench.window"]
        return min(inside)[1] if inside else "outside any unit"

    def breakdown(self) -> dict:
        """The device operations (programs where operations were not
        traced) that took most time, summed over chips, and the idle
        time grouped by the programs on either side and the harness's
        host phase, longest first; at most ten of each, in seconds."""
        a, b = self.window()
        busy = defaultdict(float)
        src = self.ops if any(self.ops.values()) else self.programs
        name = op_name if src is self.ops else (lambda n: n)
        for d in self.devices:
            for s, e, n in src.get(d, ()):
                if s >= a and e <= b:
                    busy[name(n)] += (e - s) / 1e9
        idle = defaultdict(float)
        for d in self.devices:
            for s, e, before, after in self.idle_gaps(d):
                idle[f"{before} -> {after} during "
                     f"{self.host_phase(s, e)}"] += (e - s) / 1e9
        top = lambda m: [[k, v] for k, v in sorted(  # noqa: E731
            m.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(busy), "idle_gaps": top(idle)}


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def load(logdir: str, devices) -> Trace:
    """Read the one ``.xplane.pb`` under ``logdir`` for ``devices`` (the
    ids of the chips the run used)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(files)}")
    with warnings.catch_warnings():
        # the profiler's stat type warns on every read on Python 3.12
        warnings.simplefilter("ignore", DeprecationWarning)
        return _reduce(ProfileData.from_file(files[0]), devices)


def _reduce(data, devices) -> Trace:
    programs, ops, spans = defaultdict(list), defaultdict(list), []
    host_ops = defaultdict(list)
    tpu = False
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            tpu = True
            dev = int(m.group(1))
            for line in plane.lines:
                dest = {"XLA Modules": programs, "XLA Ops": ops}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    n = program_name(ev.name) if dest is programs else ev.name
                    dest[dev].append((ev.start_ns, ev.end_ns, n))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
                        continue
                    module = _stat(ev, "hlo_module")
                    if module is not None and not ev.name.startswith("end: "):
                        dev = int(_stat(ev, "device_ordinal") or 0)
                        host_ops[dev].append((ev.start_ns, ev.end_ns,
                                              ev.name, str(module)))
    if not tpu:
        # CPU backend: a program's execution is the union of its ops,
        # grouped by the run that issued them.
        for dev, evs in host_ops.items():
            by_module = defaultdict(list)
            for s, e, n, module in evs:
                ops[dev].append((s, e, n))
                by_module[module].append((s, e))
            for module, iv in by_module.items():
                programs[dev].extend((s, e, module) for s, e in union(iv))
    devs = tuple(int(d) for d in devices)
    return Trace(programs={d: sorted(programs.get(d, [])) for d in devs},
                 ops={d: ops.get(d, []) for d in devs},
                 spans=sorted(spans), devices=devs)

"""Streamed replay: the traffic's job set, already admitted, fed through
the macro-round engine (``api.run_stream`` over a ``JobSource``) with
the default slot pool. A unit is one whole streamed replay.

Round boundaries come from the host clock at the engine's one call per
round into the source (``peek_submit``), so the program is not touched.
Traced, the traffic's ``trace_rounds`` ``[first, count]`` bound the
profiled window to those rounds of the unit: the device trace holds one
event per operation, millions per second.
A round runs from one call to the next: device round, drain, harvest,
the state sync and the next pack. A round whose boundary is an arrival
(the call returned a submit tick) is a decision round; the final round
of a replay, which runs the pool dry, is not.
"""
from __future__ import annotations

import time

import numpy as np

from bench import program, reference

E2E = ("stream_jobs_per_s", "round_ms_p95")


def _source_class():
    import jax

    from repro.core.stream import JobSource

    class TimedSource(JobSource):
        """``JobSource`` that notes the host time of each round boundary
        and, when traced, spans each round with a host annotation."""

        def __init__(self, chunks, marks, annotate, tracer=None,
                     rounds=None):
            super().__init__(chunks)
            self.marks = marks
            self.annotate = annotate
            self.tracer = tracer
            self.rounds = rounds
            self.span = None

        def peek_submit(self):
            t, cpu = time.perf_counter(), time.process_time()
            self.close()
            if self.tracer is not None:
                k, (first, count) = len(self.marks), self.rounds
                if k == first + count:
                    self.tracer.stop()
                elif k == first:
                    self.tracer.start()
            nxt = super().peek_submit()
            # after the round that ran the pool dry the engine stops
            last = bool(self.marks) and not self.marks[-1][1]
            self.marks.append((t, nxt is not None, cpu))
            if self.annotate and not last:
                self.span = jax.profiler.TraceAnnotation(
                    "bench.stream.round" if nxt is not None
                    else "bench.stream.drain")
                self.span.__enter__()
            return nxt

        def close(self):
            if self.span is not None:
                self.span.__exit__(None, None, None)
                self.span = None

    return TimedSource


class Path:
    def __init__(self, ctx):
        from repro import api
        self.api = api
        self.js = program.jobset(ctx.jobs)
        self.cfg = program.sim_config(ctx.config, self.js.n, ctx.seed)
        self.chunk = int(ctx.traffic["chunk"])
        self.annotate = ctx.trace
        rounds = ctx.traffic.get("trace_rounds")
        self.tracer = ctx.tracer if ctx.trace and rounds else None
        self.rounds = rounds
        self.traces_itself = self.tracer is not None
        self.Source = _source_class()

    def _chunks(self, n):
        from repro.core.types import JobSet
        fields = ("submit", "exec_total", "demand", "is_te", "gp", "n_nodes")
        for a in range(0, n, self.chunk):
            b = min(a + self.chunk, n)
            yield JobSet(**{f: getattr(self.js, f)[a:b] for f in fields})

    def _run(self, n, tracer=None):
        marks = []
        src = self.Source(self._chunks(n), marks, self.annotate, tracer,
                          self.rounds)
        try:
            r = self.api.run_stream(cfg=self.cfg, source=src)
        finally:
            src.close()
        return r.raw, marks

    def warm(self):
        """A streamed replay of the first chunks: the pool's shapes fix
        every program, so this compiles all of them."""
        self._run(min(self.js.n, 2 * self.chunk))

    def unit(self):
        t0 = time.perf_counter()
        res, marks = self._run(self.js.n, self.tracer)
        wall = time.perf_counter() - t0
        dec = [k for k in range(len(marks) - 1) if marks[k][1]]
        ms = lambda k, i: (marks[k + 1][i] - marks[k][i]) * 1e3  # noqa: E731
        rounds = [ms(k, 0) for k in dec]
        return {"res": res, "jobs": int(res.n_jobs), "round_ms": rounds,
                "round_cpu_ms": [ms(k, 2) for k in dec],
                "outside_ms": wall * 1e3 - sum(rounds)}

    def metrics(self, units, window_s):
        rounds = np.concatenate([u["round_ms"] for u in units])
        return {"stream_jobs_per_s":
                sum(u["jobs"] for u in units) / window_s,
                "round_ms_p95": float(np.percentile(rounds, 95))}

    def counters(self, units):
        res = [u["res"] for u in units]
        return {"replays": len(units), "jobs_per_replay": self.js.n,
                "capacity": res[0].capacity,
                "rounds": sum(r.rounds for r in res),
                "decision_rounds": sum(len(u["round_ms"]) for u in units),
                "max_live": max(r.max_live for r in res),
                "n_spilled": sum(r.n_spilled for r in res),
                "fallback_count": max(r.fallback_count for r in res),
                "round_ms_p95": self.metrics(units, 1.0)["round_ms_p95"],
                # where a slow window lost its time: its slowest decision
                # rounds as (unit, round, wall ms, process CPU ms), and
                # each unit's time outside its decision rounds
                "slowest_rounds": sorted(
                    ((i, k, w, c) for i, u in enumerate(units)
                     for k, (w, c) in enumerate(zip(u["round_ms"],
                                                    u["round_cpu_ms"]))),
                    key=lambda r: -r[2])[:3],
                "outside_rounds_ms": [u["outside_ms"] for u in units]}

    def fetch(self, units):
        pass

    def results(self, units):
        out = []
        for u in units:
            res = u["res"]
            n = self.js.n
            finish = np.full(n, -1, np.int64)
            pc = np.zeros(n, np.int64)
            signal = np.full(n, -1, np.int64)
            k = min(len(res.finish), n)
            finish[:k] = res.finish[:k]
            pc[:k] = res.preempt_count[:k]
            signal[:k] = res.last_signal[:k]
            out.append((finish, pc, res.fallback_count,
                        reference.Guide(finish, pc, signal)))
        return out


def reference_for(ctx, guide):
    pol = ctx.config["policy"]
    return reference.simulate(ctx.jobs, ctx.config["cluster"],
                              pol["name"], pol["s"], pol["P"],
                              program.seed32(ctx.seed), guide=guide)

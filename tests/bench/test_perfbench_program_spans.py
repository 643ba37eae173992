"""The program's own profiler spans (``repro.*``) and what they give.

Hand-made traces with known readings for each number of
``bench/program_spans.py``; the same trace with and without the program's
spans, read identically by every per-layer reader of ``bench/metrics``;
and small streamed and monolithic replays recorded under the profiler on
the CPU, reduced by ``bench/trace.py``, whose spans nest and count as the
program documents."""
import glob
import os
import sys
import warnings
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import program_spans as ps  # noqa: E402
from bench import run, trace  # noqa: E402

MS = 1e6
READERS = sorted(os.path.basename(p)[:-3] for p in
                 glob.glob(os.path.join(ROOT, "bench", "metrics", "*.py")))


def _program_spans(data) -> list:
    """``(start, end, name, args)`` of each ``repro.*`` host event of a
    ``ProfileData``, sorted, with int args: what ``bench/trace.py``'s
    host-plane walk would keep for the readers of the program's spans."""
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    args = {k: int(v) for k, v in ev.stats}
                    out.append((ev.start_ns, ev.end_ns, ev.name, args))
    return sorted(out, key=lambda s: s[:3])


def _stream_trace():
    """Two 10 ms rounds in a 0..20 ms window: each packs for 2 ms (the
    device idle), runs 6 ms (the round program busy 2..7 ms of it),
    harvests 1 ms (idle), with a 1 ms gap in no phase span."""
    progs, spans, prog_spans = [], [(0, 20 * MS, "bench.window")], []
    for k in range(2):
        o = k * 10 * MS
        progs.append((o + 2 * MS, o + 7 * MS, "jit__run_round_jit"))
        prog_spans += [
            (o, o + 10 * MS, "repro.stream.round", {"round": k}),
            (o, o + 2 * MS, "repro.stream.pack", {}),
            (o + 2 * MS, o + 8 * MS, "repro.stream.run", {}),
            (o + 8 * MS, o + 9 * MS, "repro.stream.harvest", {}),
            (o + 9 * MS, o + 9 * MS, "repro.stream.counts",
             {"round": k, "iters": 25 * (k + 1), "harvested": 3}),
        ]
    tr = trace.Trace(programs={0: progs}, ops={0: []}, spans=spans,
                     devices=(0,))
    return tr, sorted(prog_spans, key=lambda s: s[:3])


def test_stream_readings():
    tr, spans = _stream_trace()
    assert ps.phase_ms(tr, spans, "repro.stream.pack") == pytest.approx(2.0)
    assert ps.phase_ms(tr, spans, "repro.stream.harvest") == \
        pytest.approx(1.0)
    # idle 0..2 (pack), 7..10 (run, harvest, none), 10..12 (pack),
    # 17..20 (run, harvest, none): 10 ms, of which 8 ms lie in phases
    assert ps.idle_attributed_share(tr, spans) == pytest.approx(80.0)
    # 10 ms of round program over 25 + 50 iterations
    assert ps.iter_device_us(tr, spans, "jit__run_round_jit",
                             "repro.stream.run", "repro.stream.counts") == \
        pytest.approx(10 * 1e3 / 75)


def test_round_without_its_program_is_left_out():
    """A round whose program execution the trace lost adds neither its
    iterations nor any time: the second round alone is read."""
    tr, spans = _stream_trace()
    tr = trace.Trace(programs={0: tr.programs[0][1:]}, ops={0: []},
                     spans=tr.spans, devices=(0,))
    assert ps.iter_device_us(tr, spans, "jit__run_round_jit",
                             "repro.stream.run", "repro.stream.counts") == \
        pytest.approx(5 * 1e3 / 50)


def test_replay_readings():
    tr = trace.Trace(programs={0: [(1e9, 4e9, "jit__run_jit_impl")]},
                     ops={0: []}, spans=[(0, 5e9, "bench.window")],
                     devices=(0,))
    spans = [(0, 4.5e9, "repro.replay.loop", {}),
             (4.5e9, 4.9e9, "repro.replay.summary", {}),
             (4.9e9, 4.9e9, "repro.replay.counts",
              {"iters": 150_000, "jobs": 60_000})]
    assert ps.loop_iters_per_job(tr, spans) == pytest.approx(2.5)
    assert ps.iter_device_us(tr, spans, "jit__run_jit_impl",
                             "repro.replay.loop", "repro.replay.counts") == \
        pytest.approx(20.0)
    # loop_iters_per_job x iter_device_us x jobs is the loop's device time
    assert 2.5 * 20.0 * 60_000 / 1e6 == pytest.approx(3.0)


def test_nothing_to_read():
    """A trace of a program without the spans reads None everywhere,
    and raises nowhere."""
    tr, _ = _stream_trace()
    assert ps.phase_ms(tr, [], "repro.stream.pack") is None
    assert ps.idle_attributed_share(tr, []) is None
    assert ps.iter_device_us(tr, [], "jit__run_round_jit",
                             "repro.stream.run", "repro.stream.counts") is None
    assert ps.loop_iters_per_job(tr, []) is None


def _profile(with_program_spans: bool):
    """A fake ``ProfileData``: one TPU plane with programs and ops, the
    host plane with the harness's spans and, optionally, the program's
    spans with args."""
    ev = lambda n, s, e, stats=(): NS(  # noqa: E731
        name=n, start_ns=s, end_ns=e, stats=list(stats))
    modules = [ev("jit__run_round_jit(123)", 2 * MS, 7 * MS),
               ev("jit__run_round_jit(123)", 12 * MS, 17 * MS),
               ev("jit__pack(9)", 10.5 * MS, 11 * MS),
               ev("jit__run_jit_impl(7)", 18 * MS, 19 * MS)]
    ops = [ev("%while.9 = (s32[]) while(%x)", 2 * MS, 7 * MS),
           ev("%fusion.1 = f32[] fusion(%y)", 12 * MS, 13 * MS)]
    host = [ev("bench.window", 0, 20 * MS),
            ev("bench.stream.round", 1 * MS, 11 * MS),
            ev("bench.stream.drain", 11 * MS, 19 * MS)]
    if with_program_spans:
        for k in range(2):
            o = k * 10 * MS
            host += [ev("repro.stream.round", o, o + 10 * MS,
                        [("round", k)]),
                     ev("repro.stream.pack", o, o + 2 * MS),
                     ev("repro.stream.run", o + 2 * MS, o + 8 * MS),
                     ev("repro.stream.harvest", o + 8 * MS, o + 9 * MS),
                     ev("repro.stream.counts", o + 9 * MS, o + 9 * MS,
                        [("round", k), ("iters", 40)])]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules",
                                           events=modules),
                                        NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)])])


def test_existing_readers_unchanged_by_program_spans():
    plain = trace._reduce(_profile(False), [0])
    spanned = trace._reduce(_profile(True), [0])
    assert spanned.spans == plain.spans
    assert spanned.breakdown() == plain.breakdown()
    assert len(READERS) >= 6
    for name in READERS:
        view = NS(trace=None, units=1, path="stream",
                  timed={"round_ms_p95": 131.0})
        got = []
        for tr in (plain, spanned):
            view.trace = tr
            got.append(run.load_module("metrics", name).read(view))
        assert got[0] == got[1], name
    # and the program spans are there to read
    assert len(_program_spans(_profile(True))) == 10
    assert _program_spans(_profile(False)) == []


def _record(tmp_path, fn):
    """``fn``'s result, the ``trace.Trace`` of one call of it under the
    profiler (in a ``bench.window`` span, as the harness traces a unit)
    and the program's spans from the same trace."""
    import jax
    from jax.profiler import ProfileData
    fn()                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            out = fn()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(files[0])
        return out, trace._reduce(data, [0]), _program_spans(data)


def test_recorded_stream_spans(tmp_path):
    """A small streamed replay on the CPU: every round that ran has its
    pack, run and harvest, in that order, inside its round span; the
    counts close it with the same round number; the last round span,
    which finds nothing left, holds only its pack. Every reading of
    ``bench/program_spans.py`` is there on the reduced trace."""
    from repro import api
    out, tr, spans = _record(tmp_path, lambda: api.run_stream(
        "paper-synthetic", "fifo", n_jobs=300, n_nodes=8, capacity=64))
    res = out.raw
    assert res.rounds > 3
    named = lambda n: [s for s in spans if s[2] == n]  # noqa: E731
    rounds, counts = named("repro.stream.round"), named("repro.stream.counts")
    assert [r[3]["round"] for r in rounds] == list(range(res.rounds + 1))
    assert [c[3]["round"] for c in counts] == list(range(res.rounds))
    for (s, e, _, a) in rounds:
        kids = [x[2].rsplit(".", 1)[1] for x in spans
                if s <= x[0] and x[1] <= e and x[2] != "repro.stream.round"]
        if a["round"] < res.rounds:
            assert kids == ["pack", "run", "harvest", "counts"], kids
        else:
            assert kids == ["pack"], kids
    assert sum(c[3]["harvested"] for c in counts) == res.n_jobs == 300
    assert sum(c[3]["packed"] for c in counts) == 300
    assert sum(c[3]["iters"] for c in counts) == res.loop_iters > 0
    assert sum(c[3]["spilled"] for c in counts) == res.n_spilled
    assert out.loop_iters == res.loop_iters
    assert max(c[3]["live"] for c in counts) == res.max_live
    for phase in ("repro.stream.pack", "repro.stream.harvest"):
        assert ps.phase_ms(tr, spans, phase) > 0
    assert 0 < ps.idle_attributed_share(tr, spans) <= 100
    assert ps.iter_device_us(tr, spans, "jit__run_round_jit",
                             "repro.stream.run", "repro.stream.counts") > 0


def test_recorded_stream_drain_span(tmp_path):
    """With the event ring on, each round drains between run and
    harvest."""
    from repro import api
    _, _, spans = _record(tmp_path, lambda: api.run_stream(
        "paper-synthetic", "fifo", n_jobs=120, n_nodes=8, capacity=64,
        trace=True))
    phases = [s[2].rsplit(".", 1)[1] for s in spans
              if s[2] in ps.STREAM_PHASES]
    body = phases[:-1]                     # the last pack finds nothing
    assert body == ["pack", "run", "drain", "harvest"] * (len(body) // 4)


def test_recorded_replay_spans(tmp_path):
    from repro import api
    out, tr, spans = _record(tmp_path, lambda: api.run_experiment(
        "paper-synthetic", "fitgpp", "jax", n_jobs=200, n_nodes=8))
    assert [s[2] for s in spans] == ["repro.replay.loop",
                                     "repro.replay.summary",
                                     "repro.replay.counts"]
    loop, summary, counts = spans
    assert loop[1] <= summary[0] and summary[1] <= counts[0]
    assert counts[3] == {"iters": out.loop_iters, "jobs": 200}
    assert 0 < out.loop_iters == int(out.raw[1].n_iter)
    assert ps.loop_iters_per_job(tr, spans) == out.loop_iters / 200
    assert ps.iter_device_us(tr, spans, "jit__run_jit_impl",
                             "repro.replay.loop", "repro.replay.counts") > 0

"""The benchmark's trace reduction and its per-layer metric readers.

Hand-made traces with known answers, and a small profiler trace recorded
on the CPU in the test (where operations run on host threads and carry
their program's name)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run, trace  # noqa: E402

MS = 1e6


def _trace(programs, spans, devices=(0,)):
    return trace.Trace(programs=programs, ops={d: [] for d in devices},
                       spans=sorted(spans), devices=tuple(devices))


def _read(name, tr, units=1, timed=None):
    view = type("View", (), {"trace": tr, "units": units,
                             "timed": timed or {}})()
    return run.load_module("metrics", name).read(view)


def test_union_and_covered():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (7, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert trace.covered(merged, 2, 6) == 2
    assert trace.covered(merged, 10, 20) == 0


def test_names():
    assert trace.program_name("jit__run_round_jit(8518459724)") == \
        "jit__run_round_jit"
    assert trace.op_name("%while.93 = (s32[]) while(%x)") == "while.93"


def test_busy_idle_and_gaps():
    # window 0..100 ms; programs busy 10..40 and 50..60 (one overlap)
    tr = _trace({0: [(10 * MS, 30 * MS, "jit_a"), (20 * MS, 40 * MS, "jit_a"),
                     (50 * MS, 60 * MS, "jit_b")]},
                [(0, 100 * MS, "bench.window"),
                 (0, 45 * MS, "bench.replay.unit"),
                 (45 * MS, 100 * MS, "bench.replay.unit")])
    assert tr.window_s() == pytest.approx(0.1)
    assert tr.busy_s() == pytest.approx(0.04)
    assert _read("device_idle_share.replay", tr) == pytest.approx(60.0)
    gaps = tr.idle_gaps(0)
    assert [(g[2], g[3]) for g in gaps] == [
        ("window start", "jit_a"), ("jit_a", "jit_b"), ("jit_b", "window end")]
    assert [(g[1] - g[0]) / MS for g in gaps] == pytest.approx([10, 10, 40])
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["jit_a", pytest.approx(0.04)]
    assert bd["idle_gaps"][0][0] == "jit_b -> window end during " \
                                    "bench.replay.unit"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_program_readers():
    runs = [(i * 10 * MS, (i * 10 + 4) * MS, "jit__run_round_jit")
            for i in range(5)]
    tr = _trace({0: runs + [(41 * MS, 42 * MS, "jit__pack")]},
                [(0, 50 * MS, "bench.window")]
                + [(i * 10 * MS, (i + 1) * 10 * MS, "bench.stream.round")
                   for i in range(4)]
                + [(40 * MS, 50 * MS, "bench.stream.drain")])
    assert _read("round_device_ms.stream", tr) == pytest.approx(4.0)
    # each 10 ms round holds 4 ms of device time (the pack overlaps it)
    assert _read("host_ms_per_round.stream", tr) == pytest.approx(6.0)
    # the round tail is read from the host clock of the untraced window
    assert _read("round_ms_p95.saturn262-stream", tr) is None
    assert _read("round_ms_p95.saturn262-stream", tr,
                 timed={"round_ms_p95": 437.5}) == 437.5
    assert _read("loop_device_s.replay", tr) is None
    tr2 = _trace({0: [(0, 3e9, "jit__run_jit_impl"),
                      (4e9, 5e9, "jit__run_jit_impl")]},
                 [(0, 6e9, "bench.window")])
    assert _read("loop_device_s.replay", tr2) == pytest.approx(2.0)


def test_busy_is_averaged_over_chips():
    tr = _trace({0: [(0, 30 * MS, "jit_one")], 1: [(0, 10 * MS, "jit_one")]},
                [(0, 40 * MS, "bench.window")], devices=(0, 1))
    assert tr.busy_s() == pytest.approx(0.02)
    assert _read("device_idle_share.stream", tr) == pytest.approx(50.0)
    assert tr.breakdown()["device_ops"] == [["jit_one", pytest.approx(0.04)]]


def test_readers_find_nothing():
    tr = _trace({0: [(0, MS, "jit_x")]}, [(0, 2 * MS, "bench.window")])
    for name in ("host_ms_per_round.stream", "round_device_ms.stream",
                 "round_ms_p95.saturn262-stream"):
        assert _read(name, tr) is None


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return jax.lax.fori_loop(0, 20, lambda i, y: jnp.sin(y) @ y, x)

    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.replay.unit"):
                    f(x).block_until_ready()
    tr = trace.load(str(tmp_path), [0])
    assert len(tr.spans_named("bench.replay.unit")) == 3
    assert "jit_f" in {n for _, _, n in tr.programs[0]}
    assert 0 < tr.busy_s() <= tr.window_s()
    idle = _read("device_idle_share.replay", tr)
    assert 0 <= idle < 100
    bd = tr.breakdown()
    assert bd["device_ops"] and len(bd["device_ops"]) <= 10

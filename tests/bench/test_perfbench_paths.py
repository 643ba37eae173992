"""Each path of the benchmark driven at a tiny size on the CPU through
the harness, traced and not; and the command itself, which refuses to
run anywhere but on a TPU, and without the program beside it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402

ROOT = tiny.ROOT


@pytest.mark.parametrize("kind", ["replay", "stream", "gang-replay",
                                  "gang-stream"])
def test_path_runs_and_is_correct(tmp_path, kind, capsys):
    out = tiny.execute(tmp_path, kind)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    path = kind.removeprefix("gang-")
    assert set(out["metrics"]) == {"setup_s", *tiny.E2E[path]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert "compiles_in_window: 0" in capsys.readouterr().out


@pytest.mark.parametrize("kind", ["replay", "stream"])
def test_traced_path_reports_per_layer(tmp_path, kind):
    out = tiny.execute(tmp_path, kind, trace=1)
    assert out["correct"], out["checks"]
    assert out["metrics"] and "setup_s" not in out["metrics"]
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert out["breakdown"]["device_ops"]
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_stream_traces_only_its_rounds(tmp_path, monkeypatch):
    """A stream traffic's ``trace_rounds`` bound the profiled window to
    those rounds: the window holds exactly that many round spans."""
    traffic = dict(tiny.TRAFFIC["stream"], trace_rounds=[0, 1])
    monkeypatch.setitem(tiny.TRAFFIC, "stream", traffic)
    out = tiny.execute(tmp_path, "stream", trace=1)
    assert out["correct"], out["checks"]
    assert out["metrics"]["round_device_ms.stream"]["value"] > 0
    assert 0 < out["device"]["window_s"] < 5


def test_traced_run_reads_host_clock_metrics_from_its_window(
        tmp_path, capsys, monkeypatch):
    """A host-clock per-layer metric is read from the untraced window
    that a traced run then runs first; the profiler records the traced
    unit's ``trace_rounds`` only, not those of the window's units."""
    import jax

    from bench import run
    traffic = dict(tiny.TRAFFIC["stream"], trace_rounds=[1, 1])
    monkeypatch.setitem(tiny.TRAFFIC, "stream", traffic)
    events, run_units, start = [], run.run_units, jax.profiler.start_trace

    def units(*a, **k):
        events.append("units")
        out = run_units(*a, **k)
        events.append(f"{len(out[0])} done")
        return out
    monkeypatch.setattr(run, "run_units", units)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: events.append("trace") or start(*a,
                                                                         **k))
    name = "round_ms_p95.saturn262-stream"
    out = tiny.execute(tmp_path, "stream", trace=1, per_layer=(name,))
    assert out["correct"], out["checks"]
    assert out["metrics"][name]["value"] > 0
    printed = capsys.readouterr().out
    assert "traced_units: 1" in printed and "\nunits: 0\n" not in printed
    assert events[0] == "units" and events[2:] == ["units", "trace",
                                                   "1 done"]
    assert out["metrics"]["round_device_ms.stream"]["value"] > 0
    plain = tiny.execute(tmp_path / "b", "stream", trace=1)
    assert name not in plain["metrics"]


def _run(cwd, script, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, "--workload", "paper84-replay", "--seed",
         "1", "--seconds", "1", "--trace", "0", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_command_refuses_the_cpu():
    r = _run(ROOT, os.path.join("bench", "run.py"))
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and not _printed_result(r.stdout)


def test_command_needs_the_program(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's own
    directories runs nothing."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), os.path.join("bench", "run.py"))
    assert r.returncode != 0 and not _printed_result(r.stdout)


def test_unknown_cell_is_refused():
    r = subprocess.run([sys.executable, os.path.join("bench", "run.py"),
                        "--workload", "no-such-cell", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 2 and not _printed_result(r.stdout)

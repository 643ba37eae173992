"""Rehearsal of ``chip_smoke.py`` on the CPU.

The smoke's phase functions run here at tiny sizes, to check their
control flow and their comparisons (the chip run uses the paper's
deployment). The script itself must refuse to run anywhere but on a
TPU, and the compile-cache helper it calls must place the cache where
the entry-point contract says.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_replay_rehearsal(smoke):
    out = smoke.replay(n_jobs=512, n_nodes=8)
    assert out["te_p95_cut"] >= smoke.TE_P95_CUT
    assert out["be_p50_change"] <= smoke.BE_P50_WORSENING


def test_parity_rehearsal(smoke):
    # 16 nodes x 256 jobs takes no random fallback (exact parity needs
    # none); the kernel runs in interpret mode here
    smoke.parity(n_jobs=256, n_nodes=16, native=False)


def test_stream_rehearsal(smoke):
    smoke.stream_phase(n_jobs=1024, n_nodes=8, parity_jobs=512)


def test_sweep_rehearsal(smoke):
    smoke.sweep(n_jobs=128, n_nodes=8, n_seeds=2, s_vals=(0.0, 4.0))


def test_sweep_more_devices_than_exist_fails(smoke):
    with pytest.raises(ValueError, match="requested"):
        smoke.sweep(n_jobs=64, n_nodes=4, n_seeds=1, s_vals=(4.0,),
                    n_devices=len(jax.devices()) + 1)


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_exits_nonzero_on_cpu():
    r = _run(ROOT, SCRIPT)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert "platform=cpu" in r.stdout
    assert not _printed_result(r.stdout)


def test_exits_nonzero_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    r = _run(tmp_path, str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert not _printed_result(r.stdout)


def test_main_path_imports_nothing_that_sets_xla_flags():
    """The dry-run and the perf probes set ``XLA_FLAGS`` when imported;
    nothing on the main path may import them."""
    code = ("import os, sys\n"
            "import chip_smoke, benchmarks.sim_engine_bench\n"
            "import repro.api, repro.core.stream, repro.scenarios.__main__\n"
            "bad = [m for m in sys.modules if 'dryrun' in m or 'probe' in m]\n"
            "assert not bad and 'XLA_FLAGS' not in os.environ, bad\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]


def test_library_import_sets_no_cache():
    import repro.api  # noqa: F401
    import repro.core.stream  # noqa: F401
    assert (jax.config.jax_compilation_cache_dir
            == os.environ.get(compile_cache.ENV))


def test_compile_cache_env_dir(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

"""``BENCHMARK.json`` against the limits the benchmark is held to, and
the harness's lookup by name: a cell, configuration, traffic mix, path
or metric is added by adding files."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank|hidden|intermediate|latent|state|proj|"
                   r"head|expansion|experts_per)")


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def _line(s, most=200):
    return isinstance(s, str) and 1 <= len(s) <= most and "\n" not in s \
        and "\t" not in s


def test_top_level(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(man["paths"]) <= 16
    for p in man["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = man["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd:
        if "/" in w or w.endswith(".py"):
            assert any(w.startswith(p + "/") for p in man["paths"]), w
    r = man["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_files(man):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[k]]
    for n in names:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in man[k]}) == len(man[k])
    metrics = man["end_to_end"] + man["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for p in man["paths"]:
        for dirpath, _, files in os.walk(os.path.join(ROOT, p)):
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                if "__pycache__" not in rel:
                    assert PATH.match(rel), rel


def test_configs(man):
    used = {w["config"] for w in man["workloads"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used, f"{c['name']} has no cell"
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
        assert body["guarantees"] and body["policy"]["name"] == "fitgpp"
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files)


def test_cells(man):
    pairs = set()
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        c = run.load_cell(w["name"])
        assert {k: c.cell[k] for k in ("config", "traffic", "chips", "why")} \
            == {k: w[k] for k in ("config", "traffic", "chips", "why")}
        mod = run.load_module("paths", c.traffic["path"])
        reports = {m["name"] for m in run.e2e_for(man, w["name"])}
        assert "setup_s" in reports and len(reports) >= 2
        assert reports - {"setup_s"} <= set(mod.E2E)
        assert run.per_layer_for(man, w["name"]), w["name"]
    n4 = sum(w["chips"] == 4 for w in man["workloads"])
    assert n4 <= max(1, len(man["workloads"]) // 2)


def test_metrics(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in man["workloads"]}
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= cells
    layers = {}
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"]) and m["moves"] in e2e
        for w in m.get("workloads", sorted(cells)):
            assert w in cells
            assert m["moves"] in {x["name"] for x in run.e2e_for(man, w)}, \
                (m["name"], w)
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_a_cell_is_added_by_files(tmp_path, man):
    """A new cell, traffic mix and metric in a directory of their own
    are found by name beside the committed ones, with no edit."""
    for kind in ("cells", "traffic", "metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "traffic" / "replay-tiny.json").write_text(json.dumps(
        {"path": "replay", "jobs": 64,
         "arrivals": {"kind": "closed_loop", "load": 2.0}}))
    (tmp_path / "cells" / "paper84-tiny.json").write_text(json.dumps(
        {"config": "paper-84n", "traffic": "replay-tiny", "chips": 1,
         "why": "a cell added by files"}))
    (tmp_path / "metrics" / "tiny_count.py").write_text(
        "def read(view):\n    return float(view.units)\n")
    dirs = (str(tmp_path), run.BENCH)
    c = run.load_cell("paper84-tiny", dirs)
    assert c.config["cluster"]["nodes"] == 84
    assert c.traffic["jobs"] == 64
    assert hasattr(run.load_module("paths", c.traffic["path"], dirs), "Path")
    extra = dict(man, per_layer=man["per_layer"] + [
        {"name": "tiny_count", "unit": "1", "better": "higher",
         "source": "host_clock", "layer": "x", "moves": "replay_jobs_per_s",
         "workloads": ["paper84-tiny"]}])
    found = run.per_layer_for(extra, "paper84-tiny")
    assert [m["name"] for m in found] == ["tiny_count"]
    # without a list, a metric goes to every cell that reports what it
    # moves: here the new cell reports no end-to-end metric of its own
    free = dict(extra["per_layer"][-1], moves="stream_jobs_per_s")
    del free["workloads"]
    loose = dict(man, per_layer=[free])
    assert run.per_layer_for(loose, "paper84-stream") == [free]
    assert run.per_layer_for(loose, "saturn262-stream") == [free]
    assert run.per_layer_for(loose, "paper84-replay") == []
    reader = run.load_module("metrics", "tiny_count", dirs)
    assert reader.read(type("V", (), {"units": 3})()) == 3.0

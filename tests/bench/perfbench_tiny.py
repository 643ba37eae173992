"""Tiny cells for driving the benchmark's harness on the CPU: a cell
directory made in a temporary directory, and a run through
``bench.run.execute`` (everything of a run after the look for a chip).

The sizes and seeds take no random victim draw, so every job is
compared exactly: 24 nodes, 384 jobs (seed 5) for the replay, 1,536
jobs through the 768-slot pool (seed 9) for the stream."""
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

LOAD = {"kind": "closed_loop", "load": 2.0}
TRAFFIC = {
    "replay": {"path": "replay", "jobs": 384, "arrivals": LOAD},
    "stream": {"path": "stream", "jobs": 1536, "chunk": 256,
               "arrivals": LOAD},
}
SEED = {"replay": 5, "stream": 9}
E2E = {"replay": ["replay_jobs_per_s"],
       "stream": ["stream_jobs_per_s", "round_ms_p95"]}


def cell(tmp_path, kind: str, per_layer=()):
    """(manifest, cell) of a tiny ``kind`` cell named ``tiny-<kind>``,
    which reports the per-layer metrics of its path (``*.<kind>``) and
    those named in ``per_layer``."""
    d = str(tmp_path)
    for k in ("cells", "configs", "traffic"):
        os.makedirs(os.path.join(d, k), exist_ok=True)
    cfg = run.load_json("configs", "paper-84n")
    cfg.update(name="tiny-24n", cluster=dict(cfg["cluster"], nodes=24))
    name = f"tiny-{kind}"
    files = {"configs/tiny-24n": cfg, f"traffic/{name}": TRAFFIC[kind],
             f"cells/{name}": {"config": "tiny-24n", "traffic": name,
                               "chips": 1, "why": "a CPU rehearsal"}}
    for rel, body in files.items():
        with open(os.path.join(d, rel + ".json"), "w") as f:
            json.dump(body, f)
    man = run.manifest()
    man["workloads"].append({"name": name, "config": "tiny-24n",
                             "traffic": name, "chips": 1, "why": "x"})
    for m in man["end_to_end"]:
        if m["name"] in E2E[kind]:
            m["workloads"].append(name)
    for m in man["per_layer"]:
        if m["name"].endswith("." + kind) or m["name"] in per_layer:
            m["workloads"].append(name)
    return man, run.load_cell(name, (d, run.BENCH))


def execute(tmp_path, kind: str, trace: int = 0, seconds: float = 0.2,
            seed: int = None, per_layer=()) -> dict:
    import jax
    man, c = cell(tmp_path, kind, per_layer)
    args = SimpleNamespace(seed=SEED[kind] if seed is None else seed,
                           seconds=seconds, trace=trace)
    devices = jax.devices()
    return run.execute(args, man, c, devices, devices[:1],
                       t0=time.perf_counter())

"""Tiny cells for driving the benchmark's harness on the CPU: a cell
directory made in a temporary directory, and a run through
``bench.run.execute`` (everything of a run after the look for a chip).

The sizes and seeds of the single-node cells take no random victim
draw, so every job is compared exactly: 24 nodes, 384 jobs (seed 5) for
the replay, 1,536 jobs through the 768-slot pool (seed 9) for the
stream. A ``gang-`` cell runs the same path on a gang mix (``MIXES``);
the gang stream's seed takes random draws, which the comparison replays
from the program's outputs."""
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
SEED = {"replay": 5, "stream": 9, "gang-replay": 3, "gang-stream": 9}
E2E = {"replay": ["replay_jobs_per_s"],
       "stream": ["stream_jobs_per_s", "round_ms_p95"]}
# gang mixes over paper-84n's jobs: half of them gangs of 2, 3 or 4
# nodes, or a quarter gangs of 6 or 12 (up to half the 24 nodes)
MIXES = {"gangs": {"gang_share": 0.5, "gang_widths": [2, 3, 4]},
         "wide-gangs": {"gang_share": 0.25, "gang_widths": [6, 12]}}


def config(mix: str = None, nodes: int = 24) -> dict:
    """paper-84n cut to ``nodes``, with the gang mix ``mix`` if given."""
    cfg = run.load_json("configs", "paper-84n")
    jobs = dict(cfg["jobs"], **MIXES[mix]) if mix else cfg["jobs"]
    return dict(cfg, name=f"tiny-{nodes}n" + (f"-{mix}" if mix else ""),
                cluster=dict(cfg["cluster"], nodes=nodes), jobs=jobs)


def cell(tmp_path, kind: str, per_layer=()):
    """(manifest, cell) of a tiny ``kind`` cell named ``tiny-<kind>``,
    which reports the per-layer metrics of its path (``*.<path>``) and
    those named in ``per_layer``. ``gang-<path>`` runs ``<path>`` on the
    ``gangs`` mix."""
    d = str(tmp_path)
    for k in ("cells", "configs", "traffic"):
        os.makedirs(os.path.join(d, k), exist_ok=True)
    path = kind.removeprefix("gang-")
    cfg = config("gangs" if path != kind else None)
    name = f"tiny-{kind}"
    files = {f"configs/{cfg['name']}": cfg, f"traffic/{name}": TRAFFIC[path],
             f"cells/{name}": {"config": cfg["name"], "traffic": name,
                               "chips": 1, "why": "a CPU rehearsal"}}
    for rel, body in files.items():
        with open(os.path.join(d, rel + ".json"), "w") as f:
            json.dump(body, f)
    man = run.manifest()
    man["workloads"].append({"name": name, "config": cfg["name"],
                             "traffic": name, "chips": 1, "why": "x"})
    for m in man["end_to_end"]:
        if m["name"] in E2E[path]:
            m["workloads"].append(name)
    for m in man["per_layer"]:
        if m["name"].endswith("." + path) or m["name"] in per_layer:
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

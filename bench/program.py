"""What the paths hand the program: its config and its job sets, built
from a benchmark configuration and the generator's job sets. Nothing
here reaches the reference or the comparison."""
from __future__ import annotations

import numpy as np


def seed32(seed: int) -> int:
    """The program keys its random state by a 32-bit signed integer;
    ``--seed`` may be larger."""
    return int(seed) % (2 ** 31 - 1)


def sim_config(config: dict, n_jobs: int, seed: int):
    from repro.configs.cluster import (ClusterSpec, NodeSpec, SimConfig,
                                       WorkloadSpec)
    node = config["cluster"]["node"]
    pol = config["policy"]
    return SimConfig(
        cluster=ClusterSpec(n_nodes=int(config["cluster"]["nodes"]),
                            node=NodeSpec(cpu=float(node["cpu"]),
                                          ram=float(node["ram_gb"]),
                                          gpu=float(node["gpu"]))),
        workload=WorkloadSpec(n_jobs=int(n_jobs)),
        policy=pol["name"], s=float(pol["s"]),
        max_preemptions=int(pol["P"]), seed=seed32(seed))


def jobset(js):
    """A generator job set as the program's ``JobSet``."""
    from repro.core.types import JobSet
    return JobSet(submit=np.asarray(js.submit, np.int64),
                  exec_total=np.asarray(js.exec_total, np.int64),
                  demand=np.asarray(js.demand, np.float64),
                  is_te=np.asarray(js.is_te, bool),
                  gp=np.asarray(js.gp, np.int64),
                  n_nodes=np.asarray(js.width, np.int64))

#!/usr/bin/env python3
"""Readings of the comparison's control at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed: the cell's job sets, and the reference with one guarantee
of the configuration broken (``compare.control``: ``p_cap``, ``grace``)
in the program's place, compared with the plain reference as a run's
outputs are. Gang jobs keep their widths in the control. Prints one
JSON line per seed and control with each number compared. Numpy only;
the benchmark's runs never call it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(c, seed: int, broken: str) -> dict:
    from bench import compare, gen, program, reference, run
    mod = run.load_module("paths", c.traffic["path"], c.dirs)
    ctx = SimpleNamespace(config=c.config, traffic=c.traffic, seed=seed,
                          jobs=gen.build(c.config, c.traffic, seed))
    ctl = compare.control(ctx.jobs, c.config["cluster"], c.config["policy"],
                          program.seed32(seed), broken)
    ref = mod.reference_for(ctx, reference.Guide(
        ctl.finish, ctl.preempt_count, ctl.last_signal))
    counts = compare.total([compare.tally(ctl.finish, ctl.preempt_count,
                                          ctl.fallbacks, ref)])
    return {k: counts[k] for k in (*compare.LIMITS, "compared", "jobs")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="p_cap,grace")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT]
    from bench import run
    c = run.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for broken in args.controls.split(","):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": broken,
                              **readings(c, seed, broken)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

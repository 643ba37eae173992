"""Monolithic replay: the traffic's job set replayed whole, back to back,
through ``api.run_experiment(engine="jax")`` (one ``sim_jax.run_jit``
while-loop program per replay). A unit is one replay."""
from __future__ import annotations

import numpy as np

from bench import program, reference

E2E = ("replay_jobs_per_s",)


class Path:
    def __init__(self, ctx):
        from repro import api
        self.api = api
        self.js = program.jobset(ctx.jobs)
        self.cfg = program.sim_config(ctx.config, self.js.n, ctx.seed)

    def warm(self):
        """Compile the replay program and the summary on a job set of the
        same shape whose rows are all padding, so the warm-up loop ends
        at once."""
        import jax
        import jax.numpy as jnp

        from repro.core import sim_jax
        jobs = sim_jax.jobs_from_jobset(self.js)
        pad = jobs._replace(valid=jnp.zeros_like(jobs.valid))
        st = sim_jax.run_jit(self.cfg, pad, self.cfg.seed,
                             time_mode=self.cfg.time_mode)
        jax.block_until_ready(sim_jax.result_summary(pad, st))

    def unit(self):
        r = self.api.run_experiment("paper-synthetic", self.cfg.policy,
                                    "jax", cfg=self.cfg, jobs=self.js)
        _, st = r.raw
        return {"finish": st.finish, "preempt_count": st.preempt_count,
                "last_signal": st.last_signal,
                "fallback_count": r.fallback_count, "jobs": self.js.n}

    def metrics(self, units, window_s):
        return {"replay_jobs_per_s":
                sum(u["jobs"] for u in units) / window_s}

    def counters(self, units):
        return {"replays": len(units), "jobs_per_replay": self.js.n,
                "fallback_count": max(u["fallback_count"] for u in units)}

    def fetch(self, units):
        for u in units:
            u["finish"] = np.asarray(u["finish"])
            u["preempt_count"] = np.asarray(u["preempt_count"])
            u["last_signal"] = np.asarray(u["last_signal"])

    def results(self, units):
        """(finish, preempt_count, random draws, guide) per unit."""
        return [(u["finish"], u["preempt_count"], u["fallback_count"],
                 reference.Guide(u["finish"], u["preempt_count"],
                                 u["last_signal"])) for u in units]


def reference_for(ctx, guide):
    pol = ctx.config["policy"]
    return reference.simulate(ctx.jobs, ctx.config["cluster"],
                              pol["name"], pol["s"], pol["P"],
                              program.seed32(ctx.seed), guide=guide)

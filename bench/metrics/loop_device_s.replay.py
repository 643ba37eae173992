"""Device time of the replay program (``sim_jax.run_jit``'s jitted
``_run_jit_impl``) per execution, in s."""


def read(view):
    runs = view.trace.executions("jit__run_jit_impl")
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / 1e9

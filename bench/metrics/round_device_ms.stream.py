"""Device time of the round program (``sim_jax._run_round_jit``) per
execution, in ms."""


def read(view):
    runs = view.trace.executions("jit__run_round_jit")
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / 1e6

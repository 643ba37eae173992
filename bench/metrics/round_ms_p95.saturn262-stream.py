"""95th percentile of the decision rounds' wall times on the host clock,
in ms, over every decision round of the traced run's untraced window.
It stands for ``round_ms_p95`` in a cell whose window holds too few
rounds for a tail among the end-to-end metrics."""


def read(view):
    return view.timed.get("round_ms_p95")

"""Host time per macro-round of the streaming engine: each round's wall
time (its ``bench.stream.round`` or ``bench.stream.drain`` span) minus
the device busy time inside it, in ms, averaged over the rounds."""


def read(view):
    tr = view.trace
    rounds = (tr.spans_named("bench.stream.round")
              + tr.spans_named("bench.stream.drain"))
    if not rounds:
        return None
    dev = tr.devices[0]
    return sum((e - s - tr.busy_ns(dev, s, e)) / 1e6
               for s, e in rounds) / len(rounds)

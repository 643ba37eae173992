"""1 minus the union of device-busy intervals over the traced window,
in %, averaged over the chips used."""


def read(view):
    return view.trace.idle_share()

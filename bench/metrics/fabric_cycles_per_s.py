"""Simulated fabric-cycles completed in the window, summed over every
fabric simulated at once, over the window's wall time (host clock; the
window ends when the whole final state is ready)."""


def read(ctx):
    w = ctx["window"]
    return w["fabric_cycles"] / w["seconds"] if "fabric_cycles" in w else None

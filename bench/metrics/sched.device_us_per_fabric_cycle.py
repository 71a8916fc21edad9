"""Device time of scheduled DMA per simulated fabric-cycle in the traced
calls (us): ops whose name stack holds the ``noc.sched`` scope (each
stream's destination, burst length and receive gate looked up at its
issue index), split by the ``a2a`` kind from the trace. Absent where the
program has no such scope."""


def read(ctx):
    t, w = ctx["trace"], ctx["window"]
    s = w.get("scope_s", {}).get("noc.sched")
    if t is None or s is None:
        return None
    return 1e6 * s / (ctx["traced_calls"] * w["cycles_per_call"] * w["fabrics"])

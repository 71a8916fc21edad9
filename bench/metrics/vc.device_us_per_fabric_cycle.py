"""Device time of the router's virtual-channel work per simulated
fabric-cycle in the traced calls (us): ops whose name stack holds the
``noc.vc`` scope (the VC slot expansion and dateline gather of the
request lookup, the per-VC legs of link traversal), split by the ``a2a``
kind from the trace. Absent where the program has no such scope."""


def read(ctx):
    t, w = ctx["trace"], ctx["window"]
    s = w.get("scope_s", {}).get("noc.vc")
    if t is None or s is None:
        return None
    return 1e6 * s / (ctx["traced_calls"] * w["cycles_per_call"] * w["fabrics"])

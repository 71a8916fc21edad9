"""Device op executions per scan step in the traced calls, where one step
is one simulated cycle of every fabric batched together."""


def read(ctx):
    t, w = ctx["trace"], ctx["window"]
    if t is None:
        return None
    n_ops = sum(d["n_ops"] for d in t["devices"].values()) / len(t["devices"])
    return n_ops / (ctx["traced_calls"] * w["cycles_per_call"])

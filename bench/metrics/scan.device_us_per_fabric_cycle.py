"""Device busy time per simulated fabric-cycle in the traced calls (us):
the union of the chip's op intervals over the fabric-cycles those calls
simulated, summed over every fabric simulated at once."""


def read(ctx):
    t, w = ctx["trace"], ctx["window"]
    if t is None:
        return None
    cycles = ctx["traced_calls"] * w["cycles_per_call"] * w["fabrics"]
    return 1e6 * t["busy_s"] / cycles

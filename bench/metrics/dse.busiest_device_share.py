"""Busy time of the busiest chip over the traced pass's window (%): the
share of the pass that the chip holding the most groups spends working."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * max(d["busy_s"] for d in t["devices"].values()) / t["window_s"]

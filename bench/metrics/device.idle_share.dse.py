"""Share of the traced pass in which no operation ran, averaged over the
chips: 100 x (1 - mean busy time over the window), busy time being the
union of a chip's op intervals (%)."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else 100.0 * (1.0 - t["busy_s"] / t["window_s"])

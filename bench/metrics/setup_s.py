"""Process start until the measured window opens (host clock)."""


def read(ctx):
    return ctx["setup_s"]

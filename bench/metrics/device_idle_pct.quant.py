"""Share (%) of the traced quantize job in which no operation ran on
the device."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_pct

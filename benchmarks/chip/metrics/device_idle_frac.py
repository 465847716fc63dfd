"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window."""


def read(ctx):
    return ctx.reduced.idle_frac

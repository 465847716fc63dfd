"""Device milliseconds per generation of the jitted programs whose name
matches a pattern; shared by the per-program metrics."""
import re


def per_generation_ms(ctx, pattern):
    rx = re.compile(pattern)
    secs = [s for name, s in ctx.reduced.module_s().items()
            if rx.fullmatch(name)]
    if not secs:
        return None
    return sum(secs) * 1e3 / ctx.generations

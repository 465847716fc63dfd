"""Device time per generation of the EA step, in ms.  The step is jitted
from a functools.partial, which the trace names ``jit__unknown``; it is
the only such program on a one-chip generation."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _module import per_generation_ms  # noqa: E402

PATTERN = r"_unknown"


def read(ctx):
    return per_generation_ms(ctx, PATTERN)

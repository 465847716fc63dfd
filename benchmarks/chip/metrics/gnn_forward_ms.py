"""Device time per generation of the ``population_logits_zoo`` program, in ms."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _module import per_generation_ms  # noqa: E402

PATTERN = r"population_logits_zoo"


def read(ctx):
    return per_generation_ms(ctx, PATTERN)

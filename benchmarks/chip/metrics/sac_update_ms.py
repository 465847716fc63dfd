"""Device time per generation of the ``update_scan`` program, in ms."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _module import per_generation_ms  # noqa: E402

PATTERN = r"update_scan"


def read(ctx):
    return per_generation_ms(ctx, PATTERN)

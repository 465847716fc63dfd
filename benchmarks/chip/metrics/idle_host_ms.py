"""Device-idle ms per generation inside the ``generation`` span and under
none of the sync, replay and dispatch spans: host work between
programs (see _spans.py)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import idle_ms  # noqa: E402


def read(ctx):
    return idle_ms(ctx, "host")

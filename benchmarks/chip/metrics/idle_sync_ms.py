"""Device-idle ms per generation while the host is in a blocking
device-to-host read: the ``device_read`` span (see _spans.py)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import idle_ms  # noqa: E402


def read(ctx):
    return idle_ms(ctx, "sync")

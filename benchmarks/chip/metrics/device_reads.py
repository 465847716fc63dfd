"""Blocking device-to-host reads per generation: the ``obs/device_read``
events of the traced window over the generations (see _spans.py)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import count_per_generation  # noqa: E402


def read(ctx):
    return count_per_generation(ctx, "device_read")

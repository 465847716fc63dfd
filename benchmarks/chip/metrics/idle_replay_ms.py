"""Device-idle ms per generation while the host fills or samples the
replay bank or uploads the SAC batch: the ``replay.insert``,
``replay.sample`` and ``sac.upload`` spans (see _spans.py)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import idle_ms  # noqa: E402


def read(ctx):
    return idle_ms(ctx, "replay")

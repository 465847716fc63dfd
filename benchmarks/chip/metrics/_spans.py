"""Device-idle time of the traced generations, attributed to what the
host was doing: the program's own spans, which ``repro.obs`` writes into
the profiler trace as ``obs/<name>`` annotations while a session records.
They are read from the host line that holds the harness's
``bench.generation`` annotations.

Each idle nanosecond of device 0 inside the annotated window (the
intervals ``device_idle_frac`` counts, on one chip) goes to the
innermost ``obs/`` span open at that instant; an idle interval that
straddles a span boundary is split there.  The spans fall in five parts:

- ``sync``: ``device_read`` (a blocking device-to-host read);
- ``replay``: ``replay.insert``, ``replay.sample``, ``sac.upload``;
- ``dispatch``: the spans that launch device programs (``DISPATCH``);
- ``host``: every other instant inside an ``obs/generation`` span (the
  self time of ``generation``, ``host_sync``, ``sac_update`` and
  ``sac.read``, and ``bookkeeping``);
- ``outside``: idle time outside every ``obs/generation`` span (the
  harness between generations).

The five add up to device 0's idle time in the window.  A trace with no
``obs/generation`` span (a program that writes none) reads None.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce as tr  # noqa: E402

PREFIX = "obs/"
DISPATCH = ("rollout.gnn", "rollout.boltzmann", "rollout.pg", "fitness",
            "evaluate", "evolve", "sac.scan", "migrate")
PART = {"device_read": "sync",
        "replay.insert": "replay", "replay.sample": "replay",
        "sac.upload": "replay",
        **{name: "dispatch" for name in DISPATCH}}
PARTS = ("sync", "replay", "dispatch", "host", "outside")


def obs_spans(reduced):
    """(start_ns, end_ns, name) of the ``obs/`` events that overlap the
    window, on the host line of the harness's annotations, the prefix
    taken off; None where that line holds no ``obs/generation``."""
    host = getattr(reduced, "host", None)
    for ln in (host.lines if host is not None else []):
        if not any(e.name == tr.ANNOTATION for e in ln.events):
            continue
        spans = [(e.start_ns, e.end_ns, e.name[len(PREFIX):])
                 for e in ln.events if e.name.startswith(PREFIX)
                 and e.end_ns > reduced.lo and e.start_ns < reduced.hi]
        if any(name == "generation" for _, _, name in spans):
            return spans
    return None


def device_idle(reduced):
    """Device 0's idle intervals inside the annotated window."""
    ops = reduced.devices[0].line(tr.OPS_LINE)
    busy = tr.union(tr.clip(((e.start_ns, e.end_ns)
                             for e in (ops.events if ops else [])),
                            reduced.lo, reduced.hi))
    return tr.gaps(busy, reduced.lo, reduced.hi)


def innermost(spans):
    """Disjoint, sorted (start, end, part) segments covering every
    instant some span is open, each labelled by the innermost open
    span's part.  Spans of one thread nest; a child that outlasts its
    parent (timestamp rounding) is cut at the parent's end."""
    out, stack, t = [], [], None     # stack: [end, name]

    def label(upto):
        if stack and upto > t:
            in_gen = any(name == "generation" for _, name in stack)
            part = PART.get(stack[-1][1], "host") if in_gen else "outside"
            out.append((t, upto, part))

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][0] <= s:
            label(stack[-1][0])
            t = stack.pop()[0]
        label(s)
        t = s
        stack.append([min(e, stack[-1][0]) if stack else e, name])
    while stack:
        label(stack[-1][0])
        t = stack.pop()[0]
    return out


def idle_by_part(reduced):
    """Device-idle nanoseconds of the window per part (``PARTS``), or
    None where the trace holds no program spans."""
    spans = obs_spans(reduced)
    if spans is None:
        return None
    segs = innermost(spans)
    labelled = sorted(segs + [(s, e, "outside") for s, e in tr.gaps(
        tr.union((s, e) for s, e, _ in segs), reduced.lo, reduced.hi)])
    idle = device_idle(reduced)
    out = dict.fromkeys(PARTS, 0.0)
    i = j = 0
    while i < len(idle) and j < len(labelled):
        s = max(idle[i][0], labelled[j][0])
        e = min(idle[i][1], labelled[j][1])
        if e > s:
            out[labelled[j][2]] += e - s
        if idle[i][1] < labelled[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_ms(ctx, part):
    """Device-idle ms per generation under ``part``, or None."""
    parts = idle_by_part(ctx.reduced)
    if parts is None:
        return None
    return parts[part] * 1e-6 / ctx.generations


def count_per_generation(ctx, name):
    """``obs/<name>`` events in the window per generation, or None."""
    spans = obs_spans(ctx.reduced)
    if spans is None:
        return None
    return sum(1 for _, _, n in spans if n == name) / ctx.generations

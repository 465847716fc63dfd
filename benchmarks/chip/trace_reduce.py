"""Reduction of a ``jax.profiler`` trace (an XSpace ``.xplane.pb``) to
the benchmark's device numbers.

The trace is read into plain ``Plane`` / ``Line`` / ``Event`` records
first, so everything below is ordinary Python over intervals and can be
tested on a small recorded trace without a chip.  Nothing here imports
JAX at module level.

Conventions, taken from traces of this program on a TPU v5e:

- a device is a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line
  holds one event per executed HLO op (fusions, custom calls such as
  the Pallas kernels), its ``XLA Modules`` line one event per executed
  program, named after the jitted function (``jit_<name>(<id>)``);
- the host is the plane ``/host:CPU``; the harness wraps every timed
  ``generation()`` call in a ``TraceAnnotation`` named
  ``ANNOTATION``, which bounds the traced window.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANNOTATION = "bench.generation"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]

    def line(self, name: str) -> Optional[Line]:
        for ln in self.lines:
            if ln.name == name:
                return ln
        return None


def from_profile_data(pd) -> List[Plane]:
    """``jax.profiler.ProfileData`` -> plain records."""
    return [Plane(p.name, [Line(ln.name, [Event(e.name, e.start_ns,
                                                e.duration_ns)
                                          for e in ln.events])
                           for ln in p.lines])
            for p in pd.planes]


def load_dir(trace_dir: str) -> List[Plane]:
    """Read the one ``.xplane.pb`` a ``jax.profiler.trace`` wrote."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile_data(ProfileData.from_file(files[-1]))


# ------------------------------------------------------------ intervals
def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# ------------------------------------------------------------ the trace
def device_planes(planes: Sequence[Plane]) -> List[Plane]:
    return [p for p in planes if DEVICE_PLANE.match(p.name)]


def host_plane(planes: Sequence[Plane]) -> Optional[Plane]:
    for p in planes:
        if p.name == HOST_PLANE:
            return p
    return None


def annotated_window(planes: Sequence[Plane], annotation: str = ANNOTATION
                     ) -> Tuple[float, float, int]:
    """(start_ns, end_ns, count) spanned by the harness's annotations."""
    host = host_plane(planes)
    evs = [e for ln in (host.lines if host else []) for e in ln.events
           if e.name == annotation]
    if not evs:
        raise ValueError(f"no {annotation!r} annotation in the trace")
    return (min(e.start_ns for e in evs), max(e.end_ns for e in evs),
            len(evs))


def op_key(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...), kind=kLoop, ...``); its key is
    the instruction name, with the custom-call target for custom calls
    (``%custom-call.3 tpu_custom_call``)."""
    name = event_name.split(" = ", 1)[0].strip()
    m = re.search(r'custom_call_target="([^"]+)"', event_name)
    return f"{name} {m.group(1)}" if m else name


def module_key(event_name: str) -> str:
    """``jit_update_scan(123)`` -> ``update_scan``: the jitted function's
    name without the ``jit_`` prefix and the program id."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


class Reduced:
    """Per-window device numbers of one trace, averaged over devices."""

    def __init__(self, planes: Sequence[Plane],
                 annotation: str = ANNOTATION):
        self.lo, self.hi, self.n_annotated = annotated_window(
            planes, annotation)
        self.devices = device_planes(planes)
        if not self.devices:
            raise ValueError("no device plane in the trace")
        self.host = host_plane(planes)
        self.window_s = (self.hi - self.lo) * 1e-9
        self._ops = []       # per device: clipped (name, start, end)
        for p in self.devices:
            ln = p.line(OPS_LINE)
            self._ops.append([(e.name, max(e.start_ns, self.lo),
                               min(e.end_ns, self.hi))
                              for e in (ln.events if ln else [])
                              if e.end_ns > self.lo
                              and e.start_ns < self.hi])
        self._mods = []
        for p in self.devices:
            ln = p.line(MODULES_LINE)
            self._mods.append([(e.name, max(e.start_ns, self.lo),
                                min(e.end_ns, self.hi))
                               for e in (ln.events if ln else [])
                               if e.end_ns > self.lo
                               and e.start_ns < self.hi])
        self._busy = [union((s, e) for _, s, e in ops) for ops in self._ops]

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        return sum(covered(b) for b in self._busy) * 1e-9 / self.n_devices

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_s(self) -> Dict[str, float]:
        """Device seconds per jitted program (by ``module_key``),
        averaged over devices."""
        out: Dict[str, float] = {}
        for mods in self._mods:
            for name, s, e in mods:
                k = module_key(name)
                out[k] = out.get(k, 0.0) + (e - s) * 1e-9
        return {k: v / self.n_devices for k, v in out.items()}

    def op_s(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern``
        (a regular expression), averaged over devices."""
        rx = re.compile(pattern)
        return sum((e - s) for ops in self._ops for name, s, e in ops
                   if rx.search(name)) * 1e-9 / self.n_devices

    def top_ops(self, k: int = 10) -> List[List]:
        """The k ops that took most device time, each named by its
        program and instruction (``update_scan/%fusion.12``):
        [name, seconds]."""
        tot: Dict[str, float] = {}
        for ops, mods in zip(self._ops, self._mods):
            mods = sorted(mods, key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for name, s, e in ops:
                i = bisect.bisect_right(starts, s) - 1
                prog = (module_key(mods[i][0]) if i >= 0 and s < mods[i][2]
                        else "?")
                key = f"{prog}/{op_key(name)}"
                tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / self.n_devices] for n, v in best]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The k longest idle gaps of device 0, each labelled by the
        innermost host event running at its midpoint: [label, seconds]."""
        found = sorted(gaps(self._busy[0], self.lo, self.hi),
                       key=lambda g: g[0] - g[1])[:k]
        return [[self._host_label((s + e) / 2), (e - s) * 1e-9]
                for s, e in found]

    def _host_label(self, t: float) -> str:
        best, best_len = "host idle", None
        for ln in (self.host.lines if self.host else []):
            for ev in ln.events:
                if ev.start_ns <= t < ev.end_ns and (
                        best_len is None or ev.dur_ns < best_len):
                    best, best_len = ev.name, ev.dur_ns
        return best

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(10),
                "idle_gaps": self.idle_gaps(10)}

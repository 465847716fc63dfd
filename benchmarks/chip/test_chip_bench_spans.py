"""The program's own spans in the profiler trace, and the per-layer
metrics that attribute the device's idle time to them (metrics/_spans.py).

The attribution is checked on a small trace in the layout of a TPU v5e
trace of this program (``XLA Ops`` on the device plane; the harness's
``bench.generation`` and the program's ``obs/`` annotations on one host
line), parsed from an XSpace text proto.  The bridge itself is checked
by tracing one generation of a tiny ``ZooEGRL`` on the CPU.  No chip is
needed."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "metrics"))

import _spans  # noqa: E402
import cells  # noqa: E402
import trace_reduce as tr  # noqa: E402
from test_chip_bench_trace import _plane  # noqa: E402

_OPS = [(f"%fusion.{i} = f32[4]{{0}} fusion(f32[4]{{0}} %a), kind=kLoop",
         s, e) for i, (s, e) in enumerate(
    [(0, 1500), (2500, 3700), (4000, 5200), (7100, 8200), (10000, 12500),
     (16000, 20000)])]
# idle: [1500, 2500] [3700, 4000] [5200, 7100] [8200, 10000] [12500, 16000]
_HOST = [
    ("bench.generation", 0, 10000),
    ("obs/generation", 500, 9500),
    ("obs/evaluate", 1000, 2000),
    ("obs/host_sync", 3000, 6000),
    ("obs/device_read", 3500, 4500),
    ("obs/device_read", 5000, 5800),
    ("obs/sac_update", 6500, 9000),
    ("obs/replay.sample", 6600, 7000),
    ("obs/sac.scan", 7000, 7200),
    ("obs/sac.read", 8000, 8800),
    ("obs/device_read", 8100, 8700),
    ("bench.generation", 10000, 20000),
    ("obs/generation", 10500, 19500),
    ("obs/device_read", 12000, 13000),
    ("obs/bookkeeping", 14000, 15000),
]
# idle ns per part, worked out by hand from the two tables above
_WANT = {"sync": 300 + 600 + 500 + 500,
         "replay": 400,
         "dispatch": 500 + 100,
         "host": 500 + (200 + 500 + 100) + (100 + 200 + 500) + 3000,
         "outside": 500}
_METRIC = {"idle_sync_ms": "sync", "idle_replay_ms": "replay",
           "idle_dispatch_ms": "dispatch", "idle_host_ms": "host"}


def _reduced(host):
    from jax.profiler import ProfileData
    text = "\n".join([
        _plane(1, "/device:TPU:0", [("XLA Ops", _OPS)]),
        _plane(2, "/host:CPU", [("python", host),
                                ("other thread",
                                 [("obs/device_read", 1500, 2500)])]),
    ])
    return tr.Reduced(tr.from_profile_data(ProfileData.from_text_proto(text)))


@pytest.fixture(scope="module")
def reduced():
    return _reduced(_HOST)


class _Ctx:
    generations = 2


def _ctx(reduced):
    ctx = _Ctx()
    ctx.reduced = reduced
    return ctx


def test_idle_goes_to_the_innermost_span(reduced):
    assert _spans.idle_by_part(reduced) == pytest.approx(_WANT)


def test_a_gap_across_a_span_boundary_is_split(reduced):
    """The idle interval [1500, 2500] straddles the end of
    ``obs/evaluate`` (2000): half is dispatch, half the generation's own
    host time, not all of it one label by its midpoint."""
    segs = _spans.innermost(_spans.obs_spans(reduced))
    assert [seg for seg in segs if seg[0] < 3000 and seg[1] > 1000] == [
        (1000, 2000, "dispatch"), (2000, 3000, "host")]


def test_parts_partition_the_idle_time(reduced):
    """The four metrics plus the idle time outside every generation are
    device_idle_frac x window per generation."""
    ctx = _ctx(reduced)
    got = {m: cells.metric_reader(m)(ctx) for m in _METRIC}
    assert got == pytest.approx(
        {m: _WANT[p] * 1e-6 / 2 for m, p in _METRIC.items()})
    idle_ms = (cells.metric_reader("device_idle_frac")(ctx)
               * reduced.window_s * 1e3 / ctx.generations)
    outside = _spans.idle_by_part(reduced)["outside"] * 1e-6 / 2
    assert sum(got.values()) + outside == pytest.approx(idle_ms)
    assert idle_ms == pytest.approx(8500e-6 / 2)


def test_device_reads_count(reduced):
    """Reads on the annotated host line only, per generation."""
    assert cells.metric_reader("device_reads")(_ctx(reduced)) == 4 / 2


def test_a_child_that_outlasts_its_parent_is_cut():
    segs = _spans.innermost([(0, 100, "generation"), (50, 120, "evolve")])
    assert segs == [(0, 50, "host"), (50, 100, "dispatch")]


@pytest.mark.parametrize("metric", sorted(_METRIC) + ["device_reads"])
def test_a_program_without_spans_reads_nothing(metric):
    """A program that writes no ``obs/`` spans gives no value, never 0;
    nor does a reduction without a host plane."""
    bare = [ev for ev in _HOST if not ev[0].startswith("obs/")]
    assert cells.metric_reader(metric)(_ctx(_reduced(bare))) is None

    class NoHost:
        pass
    assert cells.metric_reader(metric)(_ctx(NoHost())) is None


def test_generation_spans_reach_the_profiler_trace(tmp_path):
    """One generation of a tiny two-graph ZooEGRL under a profiler
    session with obs off: the program's spans are ``obs/`` annotations
    nested in ``obs/generation`` on the harness's host line, every one
    of them has a part, and the ``device_read`` events are the
    ``egrl.device_reads`` counter's increase."""
    sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                    "src"))
    import jax
    from repro import obs
    from repro.core.egrl import EGRLConfig, ZooEGRL
    from repro.core.sac import SACConfig
    from repro.graphs.zoo import resnet50, resnet101

    cfg = EGRLConfig(pop_size=6, boltzmann_frac=0.34, elites=1, seed=0,
                     sac=SACConfig(batch=4))
    with obs.override(mode="off"):
        algo = ZooEGRL([resnet50(), resnet101()], cfg, mode="egrl")
        algo.generation()                  # compiles outside the trace
        reads = obs.counter("egrl.device_reads")
        before = reads.value
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation(tr.ANNOTATION):
                rec = algo.generation()
        assert obs.span("after") is obs.NOOP_SPAN
    assert "critic_loss" in rec
    planes = tr.load_dir(str(tmp_path))

    class Host:
        pass
    red = Host()
    red.host = tr.host_plane(planes)
    red.lo, red.hi, _ = tr.annotated_window(planes)
    spans = _spans.obs_spans(red)
    names = [n for _, _, n in spans]
    (g0, g1), = [(s, e) for s, e, n in spans if n == "generation"]
    assert all(g0 <= s and e <= g1 for s, e, _ in spans)
    assert {"evaluate", "host_sync", "device_read", "sac_update",
            "replay.sample", "replay.insert", "sac.upload", "sac.scan",
            "sac.read", "fitness", "evolve", "bookkeeping",
            "migrate"} <= set(names)
    host_only = {"generation", "host_sync", "sac_update", "sac.read",
                 "bookkeeping"}
    assert set(names) <= set(_spans.PART) | host_only
    assert names.count("device_read") == reads.value - before > 0

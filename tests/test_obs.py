"""Flight recorder (repro.obs): off-mode is strictly zero-event (the
serve path never touches the tracer), JSONL and ring sinks agree
line-for-line, histogram buckets land where the edge math says, spans
close correctly under exceptions, the env knob fails loud, service
faults leave attributed spans without wedging the queue, and the
first call of a fresh evolve program is split out as a ``jit_compile``
span while the second driver with the same config compiles nothing.

Clocking: tests inject ``FakeClock`` (tests/_fake_clock.py) and assert
EXACT durations — advances are binary-exact fractions so float
round-trips cannot flake.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from _fake_clock import FakeClock
from repro import obs
from repro.obs.log import get_logger
from repro.obs.metrics import Histogram, log_edges
from repro.obs.trace import NOOP_SPAN, Tracer
from repro.serving.placement_service import (PlacementRequest,
                                             PlacementService)


@pytest.fixture(autouse=True)
def _isolate_obs_state():
    """Tests that reset()/configure() the global state must not leak it
    into the rest of the suite (override() already restores itself)."""
    prev = obs._STATE
    yield
    if obs._STATE is not prev and obs._STATE is not None:
        obs._STATE.close()
    obs._STATE = prev


# --------------------------------------------------------------- metrics

def test_log_edges_spacing():
    edges = log_edges()                      # 1e-3 .. 1e5, 4 per decade
    assert edges[0] == pytest.approx(1e-3) and edges[-1] == pytest.approx(1e5)
    assert len(edges) == 8 * 4 + 1
    for a, b in zip(edges, edges[1:]):
        assert b / a == pytest.approx(10 ** 0.25)


def test_histogram_bucket_boundaries_and_overflow():
    h = Histogram("t", (), edges=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 10.0, 10.1, 100.0, 1000.0):
        h.observe(v)
    # bucket i covers (edges[i-1], edges[i]] — a boundary value lands at
    # its OWN edge; the trailing slot is the > edges[-1] overflow
    assert h.counts == [2, 1, 2, 1]
    assert h.count == 6 and h.vmin == 0.5 and h.vmax == 1000.0


def test_histogram_quantiles_upper_edge_estimate():
    h = Histogram("t", (), edges=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.quantile(0.25) == 1.0           # smallest covering edge
    assert h.quantile(0.50) == 10.0
    assert h.quantile(0.75) == 100.0
    assert h.quantile(1.00) == 500.0         # overflow -> exact max
    qs = [h.quantile(q) for q in (0.1, 0.3, 0.5, 0.7, 0.9, 1.0)]
    assert qs == sorted(qs)                  # monotonic in q
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 500.0
    assert s["sum"] == pytest.approx(555.5)


def test_registry_labels_are_distinct_series():
    r = obs.MetricsRegistry()
    r.counter("served").inc(3)
    r.histogram("wall_ms", path="hit").observe(2.0)
    r.histogram("wall_ms", path="miss").observe(200.0)
    assert r.histogram("wall_ms", path="hit") is r.histogram("wall_ms",
                                                             path="hit")
    snap = r.snapshot()
    assert snap["counters"]["served"] == 3
    assert snap["histograms"]["wall_ms{path=hit}"]["count"] == 1
    assert snap["histograms"]["wall_ms{path=miss}"]["count"] == 1


# ----------------------------------------------------------------- spans

def test_span_tree_exact_durations_with_fake_clock():
    fc = FakeClock()
    with obs.override(mode="mem", clock=fc):
        with obs.span("outer", a=1) as sp:
            fc.advance(0.25)
            with obs.span("inner"):
                fc.advance(0.125)
            fc.advance(0.5)
            sp.set(done=True)
        inner, outer = obs.drain()
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert outer["id"] == 0 and outer["parent"] is None
    assert inner["id"] == 1 and inner["parent"] == 0
    assert outer["ts"] == 0.0 and inner["ts"] == 0.25
    assert inner["dur_ms"] == 125.0
    assert outer["dur_ms"] == 875.0
    assert inner["dur_ms"] <= outer["dur_ms"]        # child-sum <= parent
    assert outer["attrs"] == {"a": 1, "done": True}


def test_exception_closes_spans_with_error_attr():
    fc = FakeClock()
    with obs.override(mode="mem", clock=fc) as st:
        with pytest.raises(RuntimeError, match="boom"):
            with obs.span("outer"):
                fc.advance(0.25)
                with obs.span("inner"):
                    fc.advance(0.25)
                    raise RuntimeError("boom")
        inner, outer = obs.drain()
        assert st.tracer._stack == []                # nothing leaked open
    assert inner["attrs"]["error"] == "RuntimeError: boom"
    assert outer["attrs"]["error"] == "RuntimeError: boom"
    assert inner["dur_ms"] == 250.0 and outer["dur_ms"] == 500.0


def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with obs.override(mode="jsonl", path=path):
        with obs.span("outer", k=1):
            with obs.span("inner"):
                pass
        get_logger("t").info("hello", n=3)
        obs.emit_metrics()
        ring = obs.events()
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    assert [e["type"] for e in lines] == ["span", "span", "log", "metrics"]
    assert lines == ring                     # the sinks agree event-for-event
    assert lines[2]["logger"] == "t" and lines[2]["fields"] == {"n": 3}


def test_repro_obs_env_fails_loud(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "verbose")
    with pytest.raises(ValueError, match="REPRO_OBS"):
        obs.reset()
    monkeypatch.setenv("REPRO_OBS", "mem")
    assert obs.reset().mode == "mem" and obs.enabled()


# ------------------------------------------------------------ serve path

def test_off_mode_serve_path_never_touches_the_tracer(monkeypatch):
    """REPRO_OBS=off is strictly zero-event: two full requests (one
    miss with refinement, one hit) create NO span, the ring stays
    empty, and obs.span hands back the shared no-op singleton — while
    the always-on metrics still count, so stats() is correct."""
    calls = []
    orig = Tracer.span

    def spy(self, name, **attrs):
        calls.append(name)
        return orig(self, name, **attrs)

    monkeypatch.setattr(Tracer, "span", spy)
    with obs.override(mode="off"):
        assert obs.span("anything") is NOOP_SPAN
        svc = PlacementService(seed=0)
        res = svc.run([PlacementRequest(0, "qwen3-0.6b", "decode_32k")])
        res += svc.run([PlacementRequest(1, "qwen3-0.6b", "decode_32k")])
        assert obs.events() == []
    assert calls == []
    assert all(r.ok for r in res)
    st = svc.stats()
    assert st["served"] == 2 and st["hits"] == 1 and st["misses"] == 1


def test_service_fault_spans_close_and_queue_drains():
    """A refinement crash leaves attributed ``refine_class`` error
    spans (batch + per-graph retry), a clean ``tick`` span, the fault
    counter bumped and the queue drained — the flight recorder never
    wedges the service it watches."""
    with obs.override(mode="mem"):
        svc = PlacementService(seed=0)
        assert svc.submit(
            PlacementRequest(0, "qwen3-0.6b", "decode_32k")) is None

        def boom(n_class, items):
            raise RuntimeError("simulated evaluator crash")

        svc._refine_class = boom
        res = svc.run_until_drained()
        ev = obs.drain()
    assert len(res) == 1 and not res[0].ok
    assert "simulated evaluator crash" in res[0].error
    st = svc.stats()
    assert st["queued"] == 0 and st["failed"] == 1 and st["faults"] >= 1
    spans = [e for e in ev if e["type"] == "span"]
    refine = [e for e in spans if e["name"] == "refine_class"]
    assert refine and all("error" in e["attrs"] for e in refine)
    assert "simulated evaluator crash" in refine[0]["attrs"]["error"]
    ticks = [e for e in spans if e["name"] == "tick"]
    assert ticks and all("error" not in e["attrs"] for e in ticks)


def test_compile_span_first_vs_second_same_class():
    """Compile-vs-execute attribution: a FRESH evolve-program config
    (tournament_k=2 is used by no other driver in the suite) makes the
    first generation carry exactly one ``jit_compile`` span nested
    under generation/evolve; a second driver with the SAME config hits
    the lru-cached compiled program and traces zero compile spans."""
    import dataclasses as dc

    from repro.core.egrl import EGRLConfig, ZooEGRL
    from repro.graphs.batch import build_graph_batch
    from repro.graphs.extract import extract_for

    graphs = [extract_for("qwen3-0.6b", "decode_32k"),
              extract_for("mamba2-780m", "decode_32k")]
    # the service's canonical class-256 geometry (shared compiled
    # population programs — see test_placement_service.py)
    batch = build_graph_batch(
        [dc.replace(g, name=f"slot{i}") for i, g in enumerate(graphs)],
        n_max=256, w_max=256, in_width=4, release_width=4)
    kw = dict(pop_size=8, tournament_k=2)

    with obs.override(mode="mem"):
        first = ZooEGRL(graphs, EGRLConfig(seed=0, **kw), mode="ea",
                        zoo=batch)
        first.generation()
        ev1 = obs.drain()
        second = ZooEGRL(graphs, EGRLConfig(seed=1, **kw), mode="ea",
                         zoo=batch)
        second.generation()
        ev2 = obs.drain()

    comp = [e for e in ev1 if e["type"] == "span"
            and e["name"] == "jit_compile"
            and e["attrs"].get("what") == "evolve_program"]
    assert len(comp) == 1
    assert comp[0]["attrs"]["tournament_k"] == 2
    by_id = {e["id"]: e for e in ev1 if e["type"] == "span"}
    chain, e = [], comp[0]
    while e["parent"] is not None:
        e = by_id[e["parent"]]
        chain.append(e["name"])
    assert chain == ["evolve", "generation"]
    gen = [e for e in ev1 if e["type"] == "span"
           and e["name"] == "generation"]
    assert len(gen) == 1 and gen[0]["attrs"]["driver"] == "zoo"
    assert np.isfinite(gen[0]["attrs"]["gen_best"])
    assert np.isfinite(gen[0]["attrs"]["gen_mean"])

    assert not any(e["type"] == "span" and e["name"] == "jit_compile"
                   for e in ev2), "second driver must reuse the executable"


def test_tracer_is_thread_safe():
    """PR 9 runs refinement slots on a worker thread while the submit
    path keeps tracing hits: span stacks are per-thread (a worker span
    roots at parent=None, never under another thread's open span), ids
    stay unique under concurrency, and every span is emitted."""
    import threading

    with obs.override(mode="mem"):

        def worker(tag):
            for _ in range(200):
                with obs.span("w_outer", tag=tag):
                    with obs.span("w_inner", tag=tag):
                        pass

        threads = [threading.Thread(target=worker, args=(t,), daemon=True)
                   for t in range(2)]
        with obs.span("main_outer"):
            for t in threads:
                t.start()
            for _ in range(200):
                with obs.span("main_inner"):
                    pass
            for t in threads:
                t.join()
        events = [e for e in obs.drain() if e["type"] == "span"]

    ids = [e["id"] for e in events]
    assert len(ids) == len(set(ids)), "span ids must be unique"
    by_id = {e["id"]: e for e in events}
    for e in events:
        if e["name"] == "main_inner":
            assert by_id[e["parent"]]["name"] == "main_outer"
        elif e["name"] == "w_inner":
            p = by_id[e["parent"]]
            assert p["name"] == "w_outer" and \
                p["attrs"]["tag"] == e["attrs"]["tag"], \
                "a worker span must parent within its own thread"
        elif e["name"] == "w_outer":
            assert e["parent"] is None, \
                "worker roots must not nest under another thread's span"
    assert sum(e["name"] == "main_inner" for e in events) == 200
    assert {e["name"] for e in events} >= {"w_outer", "w_inner",
                                           "main_outer"}


# -------------------------------------------------------- profiler bridge

def test_repro_obs_imports_no_jax():
    """The recorder stays dependency-free: the profiler bridge looks the
    annotation type up only once jax is loaded."""
    import os
    import subprocess
    import sys

    src = os.path.dirname(os.path.dirname(os.path.dirname(obs.__file__)))
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro.obs; print('jax' in sys.modules)"],
        capture_output=True, text=True, check=True,
        env=dict(os.environ, PYTHONPATH=src))
    assert p.stdout.strip() == "False"


@pytest.mark.parametrize("mode", ["off", "mem"])
def test_spans_enter_the_profiler_trace_while_it_records(mode, tmp_path):
    """While a profiler session records, a span also writes an
    ``obs/<name>`` annotation (off mode included, without any sink
    event); ``set`` passes on to its metadata; mem-mode ring events keep
    their schema; with the session stopped, off mode is NOOP_SPAN again."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with obs.override(mode=mode):
        assert (obs.span("before") is NOOP_SPAN) == (mode == "off")
        with jax.profiler.trace(str(tmp_path)):
            with obs.span("outer", rows=3) as sp:
                with obs.span("inner"):
                    pass
                sp.set(best=1.5)
        assert (obs.span("after") is NOOP_SPAN) == (mode == "off")
        ev = obs.drain()
    if mode == "off":
        assert ev == []
    else:
        assert [(e["name"], e["attrs"]) for e in ev] == [
            ("inner", {}), ("outer", {"rows": 3, "best": 1.5})]
        assert all(set(e) == {"type", "name", "id", "parent", "ts",
                              "dur_ms", "attrs"} for e in ev)
    pd = ProfileData.from_file(glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    got = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
           for p in pd.planes for ln in p.lines for e in ln.events
           if e.name.startswith("obs/")}
    assert set(got) == {"obs/outer", "obs/inner"}
    assert got["obs/outer"][0] <= got["obs/inner"][0] <= \
        got["obs/inner"][1] <= got["obs/outer"][1]


def test_device_read_counts_and_returns_the_value():
    import jax.numpy as jnp
    x = jnp.arange(5, dtype=jnp.float32) / 3
    reads = obs.counter("egrl.device_reads")
    before = reads.value
    with obs.override(mode="mem"):
        a = obs.device_read(np.asarray, x)
        f = obs.device_read(float, x[1])
        ev = obs.drain()
    assert np.array_equal(a, np.asarray(x)) and f == float(x[1])
    assert reads.value - before == 2
    assert [e["name"] for e in ev] == ["device_read", "device_read"]

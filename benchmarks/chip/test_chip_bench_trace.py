"""Trace reduction on a small trace in the layout a TPU v5e trace of this
program has (device plane with ``XLA Ops`` / ``XLA Modules`` lines, host
plane with the harness's per-generation annotations).  CPU only: the
trace is parsed from an XSpace text proto, and no backend is started."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cells  # noqa: E402
import trace_reduce as tr  # noqa: E402

_OPS = [  # (name, start_ns, end_ns) on the XLA Ops line
    ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop", 1000, 2000),
    ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %b), kind=kLoop", 1500, 3000),
    ("%while.4 = (f32[4]{0}) while((f32[4]{0}) %t), condition=%c", 6000,
     9000),
    ('%gat.3 = f32[8]{0} custom-call(f32[8]{0} %z), '
     'custom_call_target="tpu_custom_call"', 11000, 13000),
    ("%fusion.9 = f32[4]{0} fusion(f32[4]{0} %d), kind=kLoop", 14000, 15000),
    ("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %e), kind=kLoop", 19500, 20500),
    ("%fusion.8 = f32[4]{0} fusion(f32[4]{0} %f), kind=kLoop", 25000, 26000),
]
_MODULES = [
    ("jit_population_logits_zoo(11)", 1000, 3000),
    ("jit_update_scan(12)", 6000, 9000),
    ("jit_population_logits_zoo(11)", 11000, 13000),
    ("jit__unknown(13)", 14000, 15000),
    ("jit_evaluate_population_zoo(14)", 19500, 20500),
    ("jit_evaluate_population_zoo(14)", 25000, 26000),
]
_HOST = [
    ("bench.generation", 0, 10000),
    ("bench.generation", 10000, 20000),
    ("$array.py:631 _value", 4000, 6000),
]


def _line(lid, name, events, meta):
    out = [f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0']
    for ev_name, s, e in events:
        mid = meta.setdefault(ev_name, len(meta) + 1)
        out.append(f"events {{ metadata_id: {mid} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000} }}")
    out.append("}")
    return "\n".join(out)


def _plane(pid, name, lines):
    meta = {}
    body = [_line(i + 1, ln, evs, meta) for i, (ln, evs) in enumerate(lines)]
    md = [f"event_metadata {{ key: {i} value {{ id: {i} name: "
          f"{_quote(n)} }} }}" for n, i in meta.items()]
    return f'planes {{ id: {pid} name: "{name}"\n' + "\n".join(body + md) \
        + "\n}"


def _quote(s):
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    text = "\n".join([
        _plane(1, "/device:TPU:0", [("XLA Modules", _MODULES),
                                    ("XLA Ops", _OPS)]),
        _plane(2, "/host:CPU", [("python", _HOST)]),
    ])
    planes = tr.from_profile_data(ProfileData.from_text_proto(text))
    return tr.Reduced(planes)


def test_window_busy_and_idle(reduced):
    assert reduced.n_annotated == 2
    assert reduced.window_s == pytest.approx(20000e-9)
    # union inside the window: 2000 + 3000 + 2000 + 1000 + 500 ns
    assert reduced.busy_s == pytest.approx(8500e-9)
    assert reduced.idle_frac == pytest.approx(1 - 8500 / 20000)


def test_module_time_by_program(reduced):
    mods = reduced.module_s()
    assert mods["population_logits_zoo"] == pytest.approx(4000e-9)
    assert mods["update_scan"] == pytest.approx(3000e-9)
    assert mods["_unknown"] == pytest.approx(1000e-9)
    # clipped at the window's end; the event after it is left out
    assert mods["evaluate_population_zoo"] == pytest.approx(500e-9)


def test_kernel_op_time(reduced):
    assert reduced.op_s("tpu_custom_call") == pytest.approx(2000e-9)
    assert tr.op_key(_OPS[3][0]) == "%gat.3 tpu_custom_call"


def test_breakdown(reduced):
    bd = reduced.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    top = dict((n, s) for n, s in bd["device_ops"])
    assert top["update_scan/%while.4"] == pytest.approx(3000e-9)
    assert top["population_logits_zoo/%gat.3 tpu_custom_call"] == \
        pytest.approx(2000e-9)
    gaps = bd["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx(
        [4500e-9, 3000e-9, 2000e-9, 1000e-9, 1000e-9])
    assert gaps[0][0] == "bench.generation"
    assert gaps[1][0] == "$array.py:631 _value"   # innermost host event
    assert len(bd["device_ops"]) <= 10 and len(gaps) <= 10


class _Ctx:
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("name, want", [
    ("device_idle_frac", 1 - 8500 / 20000),
    ("gnn_forward_ms", 4000e-9 * 1e3 / 2),
    ("sac_update_ms", 3000e-9 * 1e3 / 2),
    ("evolve_ms", 1000e-9 * 1e3 / 2),
    ("evaluate_ms", 500e-9 * 1e3 / 2),
    ("generation_mfu", 100 * 1e6 / (10000e-9 * 197e12)),
])
def test_metric_readers(reduced, name, want):
    ctx = _Ctx()
    ctx.reduced, ctx.generations, ctx.gen_flops = reduced, 2, 1e6
    assert cells.metric_reader(name)(ctx) == pytest.approx(want)


def test_reader_finds_nothing(reduced):
    """A program that is not in the trace gives no value, never 0."""
    ctx = _Ctx()
    ctx.reduced, ctx.generations, ctx.gen_flops = reduced, 2, 0.0
    assert cells.metric_reader("generation_mfu")(ctx) is None
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m", os.path.join(cells.HERE, "metrics", "_module.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.per_generation_ms(ctx, "no_such_program") is None


def test_intervals():
    assert tr.union([(5, 6), (1, 3), (2, 4), (7, 7)]) == [(1, 4), (5, 6)]
    assert tr.gaps([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]
    assert tr.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]

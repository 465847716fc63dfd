"""The harness on the CPU: cells, configurations, traffic mixes and
per-layer metrics are found by name; the result line has the contract's
keys; without a TPU a run exits non-zero and prints no result."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import testbench  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    return root, testbench.make(root)


def test_benchmark_json_names_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    cell_names = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = cells.Cell(ROOT, w["name"])          # every file is there
        assert cell.chips == 1
        assert cell.config["name"] == w["config"]
        assert cell.per_layer() and cell.end_to_end()
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= cell_names
        assert m["moves"] == "generation_ms"
        assert callable(cells.metric_reader(m["name"]))


def test_added_files_are_found_by_name(tmp_path):
    """A new configuration, traffic mix and per-layer metric are files and
    BENCHMARK.json entries; no harness code names them."""
    root = str(tmp_path)
    base = testbench.make(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = json.load(open(os.path.join(base, "configs", "small.json")))
    conf["name"] = "other"
    json.dump(conf, open(os.path.join(base, "configs", "other.json"), "w"))
    traffic = json.load(open(os.path.join(base, "traffic", "ea.json")))
    traffic["trace_generations"] = 3
    json.dump(traffic, open(os.path.join(base, "traffic", "burst.json"),
                            "w"))
    shutil.copy(os.path.join(base, "limits", "small.ea.json"),
                os.path.join(base, "limits", "other.burst.json"))
    with open(os.path.join(base, "metrics", "host_share.py"), "w") as f:
        f.write("def read(ctx):\n    return 0.25 * ctx.generations\n")
    bench["configs"].append({"name": "other", "source": "x", "why": "x",
                             "file": "benchmarks/chip/configs/other.json",
                             "reduced": []})
    bench["workloads"].append({"name": "other.burst", "config": "other",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "host_share", "unit": "frac",
                               "better": "lower", "source": "device_trace",
                               "layer": "host", "moves": "generation_ms",
                               "workloads": ["other.burst"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = cells.Cell(root, "other.burst", base=base)
    assert cell.config["name"] == "other"
    assert cell.traffic["trace_generations"] == 3

    class Ctx:
        generations = 4
    assert cells.read_metrics(cell, Ctx()) == {
        "host_share": {"value": 1.0, "unit": "frac"}}
    assert [m["name"] for m in cell.per_layer()] == ["host_share"]


def test_a_metric_that_reads_nothing_is_missing(small):
    """A per-layer metric whose program is gone from the trace is named,
    so the run fails instead of leaving it out unseen."""
    root, base = small
    cell = cells.Cell(root, "small.egrl", base=base)

    class Reduced:
        idle_frac = 0.2
        window_s = 1.0

        def module_s(self):
            return {"population_logits_zoo": 0.01}

        def op_s(self, pattern):
            return 0.0

    class Ctx:
        reduced, generations, peaks = Reduced(), 2, None
        gen_flops, gat_calls = 0, []
    got = cells.read_metrics(cell, Ctx())
    assert set(got) == {"device_idle_frac", "gnn_forward_ms"}
    assert set(cells.missing_metrics(cell, got)) == {
        m["name"] for m in cell.per_layer()} - set(got)


def test_result_line_keys(small):
    """A whole run on the CPU (the chip check skipped) ends in the
    contract's keys, with the compared numbers last."""
    import run
    root, base = small
    cell = cells.Cell(root, "small.ea", base=base)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    res = run.run(cell, 2 ** 31 + 12345, 1.0, False, require_tpu=False,
                  cache=False)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(res["metrics"]) == {"generation_ms", "setup_s"}
    assert res["metrics"]["generation_ms"]["unit"] == "ms"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is True, res["checks"]
    assert all(set(c) == {"value", "limit"} for c in res["checks"].values())


def _run_cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        workload = json.load(f)["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_cpu_run_exits_without_result():
    p = _run_cli(ROOT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_bare_checkout_exits_without_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program to run."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())

"""The ``mimo_v2_flash`` configuration on the CPU: the reference reads
its two stage graphs exactly as the program does, the compiler baseline
fits each stage's share, the graphs the cell's run builds are the plain
accounting's node by node, and a small-population copy of the cell runs
through ``run.run`` to a correct result that its control does not
reach."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import accounting  # noqa: E402
import cells  # noqa: E402
import correct  # noqa: E402
import reference as ref  # noqa: E402
import testbench  # noqa: E402

SEED = 2_718_281_829
with open(os.path.join(HERE, "configs", "mimo_v2_flash.json")) as f:
    CONF = json.load(f)
GRAPHS = sorted(CONF["graphs"])


@pytest.mark.parametrize("name", GRAPHS)
def test_reference_inputs_and_simulator(name):
    """Features and the rectifier agree exactly with the program's on
    each stage graph; the reward within float32 rounding."""
    import jax.numpy as jnp
    from repro.graphs.zoo import WORKLOADS
    from repro.memsim.compiler import compiler_reference
    from repro.memsim.simulator import build_sim_graph, evaluate_population
    g = WORKLOADS[name]()
    assert g.n == CONF["graphs"][name]
    ga = ref.graph_arrays(g)
    np.testing.assert_array_equal(ref.features(g), g.features())
    maps = np.random.default_rng(0).integers(0, 3, (8, g.n, 2)).astype(
        np.int32)
    maps[0] = 0
    maps[1] = ref.heuristic_mapping(g)
    sg = build_sim_graph(g)
    _, clat = compiler_reference(g)
    res = evaluate_population(sg, jnp.asarray(maps), jnp.float32(clat))
    rect, eps = ref.rectify_rows(ga, maps)
    np.testing.assert_array_equal(rect, np.asarray(res["rectified"]))
    np.testing.assert_array_equal(eps, np.asarray(res["eps"]))
    want = ref.rewards(g, ga, maps, 5.0)
    np.testing.assert_allclose(np.asarray(res["reward"]), want, rtol=1e-5)


@pytest.mark.parametrize("name", GRAPHS)
def test_the_share_fits_and_the_fast_tiers_bind(name):
    """The compiler baseline's mapping of each stage needs no
    rectification (the share fits in HBM), while the fast tiers cannot
    take its largest tensors."""
    from repro.graphs.zoo import WORKLOADS
    g = WORKLOADS[name]()
    ga = ref.graph_arrays(g)
    _, eps = ref.rectify_rows(ga, ref.heuristic_mapping(g)[None])
    assert eps[0] == 0.0
    assert sum(nd.weight_bytes for nd in g.nodes) < ref.CAPACITY[ref.HBM]
    all_cmem = np.full((1, g.n, 2), ref.CMEM, np.int32)
    _, eps = ref.rectify_rows(ga, all_cmem)
    assert eps[0] > 0.5


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """The benchmark's small CPU cell, its graphs replaced by this
    configuration's, with the chip cell's limits."""
    root = str(tmp_path_factory.mktemp("bench_mimo"))
    base = testbench.make(root, cells_limits="mimo_v2_flash.egrl")
    path = os.path.join(base, "configs", "small.json")
    with open(path) as f:
        conf = json.load(f)
    conf.update(graphs=CONF["graphs"],
                expected_buckets=CONF["expected_buckets"])
    with open(path, "w") as f:
        json.dump(conf, f)
    return cells.Cell(root, "small.egrl", base=base)


def test_small_run_is_correct_and_the_control_is_not(small):
    import run
    res = run.run(small, SEED, 1.0, False, require_tpu=False, cache=False,
                  variants=("control",))
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is True, res["checks"]
    limits = small.limits["limits"]
    assert not correct.verdict(res["variants"]["control"], limits), \
        res["variants"]["control"]


def test_the_run_builds_the_accounted_graphs(small, monkeypatch):
    """The graphs ``run.build`` hands the timed path are, node by node,
    ``accounting.py``'s for each stage.  A run's ``correct`` compares
    program and reference on these same graphs, so it cannot see a wrong
    extractor; this holds the extractor to the published keys."""
    import run
    monkeypatch.delenv("REPRO_GAT_BACKEND", raising=False)
    algo, graphs = run.build(small, SEED)
    del algo
    assert sorted(g.name for g in graphs) == GRAPHS
    for g in graphs:
        want = accounting.stage_nodes(CONF, int(g.name.rsplit("pp", 1)[1]))
        assert [nd.op for nd in g.nodes] == [w["op"] for w in want]
        for i, (nd, w) in enumerate(zip(g.nodes, want)):
            for got, ref_ in ((nd.weight_bytes, w["weight_bytes"]),
                              (nd.flops, w["flops"]),
                              (nd.weight_access_frac, w["access"]),
                              (nd.ofm_bytes, w["act_bytes"])):
                assert got == pytest.approx(ref_, rel=1e-9), (g.name, i)

"""The ``optimize_placement`` entry point: --arch x --shape -> plan,
trained by a one-graph ``ZooEGRL``."""
import jax.numpy as jnp

from repro.graphs.zoo import resnet50
from repro.launch.optimize_placement import optimize
from repro.memsim import tiers as T
from repro.memsim.compiler import compiler_reference
from repro.memsim.simulator import build_sim_graph, evaluate


def test_optimize_plan_reports_its_own_mapping():
    """The plan's speedup is a fresh ``evaluate`` of the driver's best
    mapping, ``ops`` has one entry per node in node order with that
    mapping's tiers, and the budget is counted in env steps."""
    plan, algo = optimize("resnet50", "decode_32k", steps=42, mode="egrl",
                          seed=0, log=None)
    g = resnet50()
    mapping = algo.best_mapping[0]
    assert mapping.shape == (g.n, 2)
    _, clat = compiler_reference(g)
    res = evaluate(build_sim_graph(g), jnp.asarray(mapping),
                   jnp.float32(clat))
    assert plan["speedup_vs_compiler"] == float(res["speedup"])
    assert plan["graph_nodes"] == g.n and plan["mode"] == "egrl"
    assert plan["env_steps"] == algo.steps >= 42
    tiers = [t.name for t in T.TIERS]
    assert [op["index"] for op in plan["ops"]] == list(range(g.n))
    for op, row in zip(plan["ops"], mapping):
        assert op["weight_tier"] == tiers[int(row[0])]
        assert op["act_tier"] == tiers[int(row[1])]

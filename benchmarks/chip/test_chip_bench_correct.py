"""The comparison that decides ``correct``, on the CPU at a small size:
the reference agrees with the program where both are exact, a sound run
is correct, and the control and every fault a cell can have come out
not correct."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import cells  # noqa: E402
import correct  # noqa: E402
import reference as ref  # noqa: E402
import testbench  # noqa: E402

SEED = 3_000_000_019


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """The small cells with the chip cells' limits."""
    root = str(tmp_path_factory.mktemp("bench"))
    base = testbench.make(root)
    return lambda name: cells.Cell(root, name, base=base)


@pytest.fixture(scope="module")
def small_cpu(tmp_path_factory):
    """The small cells with limits for what a CPU run can show."""
    root = str(tmp_path_factory.mktemp("bench_cpu"))
    base = testbench.make(root, limits=testbench.CPU_LIMITS)
    return lambda name: cells.Cell(root, name, base=base)


def _run(cell, **kw):
    import run
    return run.run(cell, SEED, 1.0, False, require_tpu=False, cache=False,
                   **kw)


def test_reference_layouts_are_the_programs():
    """The flat genome layout the reference unpacks is the order in which
    the program flattens its parameter trees."""
    import jax
    from repro.core import gnn, sac
    from repro.utils.params import init_params
    tree = gnn.init_gnn(jax.random.PRNGKey(0), 19)
    got = [(n, s) for n, s in ref.gnn_layout(19)]
    want = [(".".join(str(k.key) for k in path), tuple(x.shape))
            for path, x in jax.tree_util.tree_leaves_with_path(tree)]
    assert got == want
    tree = init_params(sac.critic_defs(19), jax.random.PRNGKey(0))
    want = [(".".join(str(k.key) for k in path), tuple(x.shape))
            for path, x in jax.tree_util.tree_leaves_with_path(tree)]
    assert ref.critic_layout(19) == want


@pytest.mark.parametrize("name", ["resnet50", "bert"])
def test_reference_inputs_and_simulator(name):
    """Features and the rectifier agree exactly with the program's; the
    reward within float32 rounding of the program's latency sums."""
    import jax.numpy as jnp
    from repro.graphs.zoo import WORKLOADS
    from repro.memsim.compiler import compiler_reference
    from repro.memsim.simulator import build_sim_graph, evaluate_population
    g = WORKLOADS[name]()
    ga = ref.graph_arrays(g)
    np.testing.assert_array_equal(ref.features(g), g.features())
    maps = np.random.default_rng(0).integers(0, 3, (8, g.n, 2)).astype(
        np.int32)
    maps[0] = 0
    sg = build_sim_graph(g)
    _, clat = compiler_reference(g)
    res = evaluate_population(sg, jnp.asarray(maps), jnp.float32(clat))
    rect, eps = ref.rectify_rows(ga, maps)
    np.testing.assert_array_equal(rect, np.asarray(res["rectified"]))
    np.testing.assert_array_equal(eps, np.asarray(res["eps"]))
    want = ref.rewards(g, ga, maps, 5.0)
    np.testing.assert_allclose(np.asarray(res["reward"]), want, rtol=1e-5)


def test_sound_run_is_correct_and_the_control_is_not(small, small_cpu):
    cell = small("small.egrl")
    res = _run(cell, variants=correct.VARIANTS[1:])
    assert res["correct"] is True, res["checks"]
    cpu_limits = small_cpu("small.egrl").limits["limits"]
    assert correct.verdict(res["variants"]["program"], cpu_limits)
    limits = cell.limits["limits"]
    assert not correct.verdict(res["variants"]["control"], limits), \
        res["variants"]["control"]
    for v in correct.VARIANTS[2:]:
        assert not correct.verdict(res["variants"][v], cpu_limits), v


def _state_unchanged(algo):
    import jax.numpy as jnp
    z = jnp.float32(0.0)
    algo.learner._update_scan = (
        lambda a, c, oa, oc, *rest: (a, c, oa, oc, z, z, z))


def _half_batch(algo):
    scan = algo.learner._update_scan

    def half(a, c, oa, oc, acts, rews, noise):
        b = rews[0].shape[2] // 2
        return scan(a, c, oa, oc, tuple(x[:, :, :b] for x in acts),
                    tuple(x[:, :, :b] for x in rews),
                    tuple(x[:, :, :b] for x in noise))

    algo.learner._update_scan = half


def _update_lost(algo):
    """The learner computes each update and keeps the state it had."""
    learner = algo.learner
    update = learner.update

    def lost(*a, **k):
        state = (learner.actor, learner.critic, learner.opt_a, learner.opt_c)
        out = update(*a, **k)
        learner.actor, learner.critic, learner.opt_a, learner.opt_c = state
        return out

    learner.update = lost


def _moments_reset(algo):
    """The optimizers' moments and step counts restart after each update."""
    from repro.core import sac
    learner = algo.learner
    update = learner.update

    def reset(*a, **k):
        out = update(*a, **k)
        learner.opt_a = sac._adam_init(learner.actor)
        learner.opt_c = sac._adam_init(learner.critic)
        return out

    learner.update = reset


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _update_lost, _moments_reset],
                         ids=["state_unchanged", "half_batch", "update_lost",
                              "moments_reset"])
def test_a_broken_update_is_not_correct(small_cpu, fault):
    res = _run(small_cpu("small.egrl"), fault=fault)
    assert res["correct"] is False, res["checks"]


def _simulator_untapped(algo):
    """The generation reaches the simulator by another name than the
    module global the check taps (as a fused or renamed program would)."""
    from repro.core import egrl
    orig = egrl.evaluate_population_bucketed
    generation = algo.generation

    def untapped():
        tapped = egrl.evaluate_population_bucketed
        egrl.evaluate_population_bucketed = orig
        try:
            return generation()
        finally:
            egrl.evaluate_population_bucketed = tapped

    algo.generation = untapped


def test_a_layer_the_check_cannot_see_is_not_correct(small_cpu):
    res = _run(small_cpu("small.ea"), fault=_simulator_untapped)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["reward_gap"]["value"] is None


@pytest.mark.parametrize("cell", ["small.egrl", "small.ea"])
def test_an_altered_answer_is_not_correct(small_cpu, monkeypatch, cell):
    """One reward changed where the simulator produces it."""
    from repro.core import egrl
    orig = egrl.evaluate_population_bucketed

    def altered(zoo, maps, *a, **k):
        out = dict(orig(zoo, maps, *a, **k))
        out["reward"] = out["reward"].at[0, 0].add(1.0)
        return out

    monkeypatch.setattr(egrl, "evaluate_population_bucketed", altered)
    res = _run(small_cpu(cell))
    assert res["correct"] is False, res["checks"]

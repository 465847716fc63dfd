"""A zoo generation scores every population part in one simulator call
per size bucket: the GNN, Boltzmann and PG rows (padding rows included)
are joined along the row axis, evaluated once, and sliced back per part.

Per-row simulator math is independent of the other rows, so the joined
evaluation, sliced per part, must be bitwise the per-part
``evaluate_population_bucketed`` calls it replaced, and so must the
per-part fitness vectors.  A ("pop",)-sharded population pads the joined
stack to a multiple of the shard count and must give the single-device
trajectory bit for bit."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.egrl import _MERGE_ROLLOUTS, _fitness
from repro.graphs.bucketed import build_bucketed_zoo
from repro.graphs.graph import WorkloadGraph
from repro.graphs.zoo import bert, resnet50, resnet101, tiny_gpt
from repro.memsim.batch import (SCALARS, aggregate_rewards,
                                evaluate_population_bucketed)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# (rows in the stack, real rows): the GNN and Boltzmann parts carry
# padding rows, as a sharded population's parts do; the PG part has none
PARTS = {"g": (8, 5), "b": (4, 2), "pg": (1, 1)}


def _gpts():
    """14 and 25 nodes."""
    out = []
    for layers in (1, 2):
        g = tiny_gpt(seq=16, layers=layers, d=64, heads=1)
        out.append(WorkloadGraph(f"gpt{layers}", g.nodes, g.edges))
    return out


def _zoo(name):
    if name == "paper":
        zoo = build_bucketed_zoo([resnet50(), resnet101(), bert()], "auto")
        assert zoo.n_buckets == 3
    else:
        zoo = build_bucketed_zoo(_gpts(), "off")
        assert zoo.n_buckets == 1
    return zoo


def _part_maps(zoo, seed):
    """Per part, per bucket (rows, G_k, N_max_k, 2) mappings over every
    tier, garbage in the padding node slots too."""
    key = jax.random.PRNGKey(seed)
    out = {}
    for name, (rows, _) in PARTS.items():
        stacks = []
        for b in zoo.buckets:
            key, k = jax.random.split(key)
            stacks.append(jax.random.randint(
                k, (rows, b.n_graphs, b.n_max, 2), 0, 3, jnp.int32))
        out[name] = tuple(stacks)
    return out


@pytest.mark.parametrize("zoo_name", ["paper", "one_bucket"])
def test_joined_evaluation_is_the_per_part_calls(zoo_name):
    zoo = _zoo(zoo_name)
    parts = _part_maps(zoo, seed=len(zoo_name))
    merged = _MERGE_ROLLOUTS(tuple(parts.values()), pad=0)
    joined = evaluate_population_bucketed(zoo, merged, 5.0)
    bounds, off = [], 0
    for name, (rows, _) in PARTS.items():
        alone = evaluate_population_bucketed(zoo, parts[name], 5.0)
        for key in SCALARS:
            got = np.asarray(joined[key])[off:off + rows]
            want = np.asarray(alone[key])
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(got, want), (name, key)
        for k, rect in enumerate(joined["rectified"]):
            assert np.array_equal(np.asarray(rect)[off:off + rows],
                                  np.asarray(alone["rectified"][k]))
        bounds.append((off, off + rows))
        off += rows
    for mode in ("mean", "worst"):
        fit, fit_all = _fitness(joined["reward"], tuple(bounds), mode)
        per_part = jax.jit(lambda r: aggregate_rewards(r, mode))
        for (name, _), (a, b), f in zip(PARTS.items(), bounds, fit):
            want = np.asarray(per_part(evaluate_population_bucketed(
                zoo, parts[name], 5.0)["reward"]))
            assert np.array_equal(np.asarray(f), want), (name, mode)
            assert np.array_equal(np.asarray(fit_all)[a:b], want)


def test_padding_rows_join_the_stack_at_its_end():
    """``pad`` zero rows follow every part's rows in each bucket."""
    zoo = _zoo("paper")
    parts = _part_maps(zoo, seed=7)
    merged = _MERGE_ROLLOUTS(tuple(parts.values()), pad=3)
    rows = sum(r for r, _ in PARTS.values())
    for k, m in enumerate(merged):
        m = np.asarray(m)
        assert m.shape[0] == rows + 3
        assert np.array_equal(m[:rows], np.concatenate(
            [np.asarray(parts[n][k]) for n in PARTS]))
        assert not m[rows:].any()


def test_sharded_egrl_generation_matches_single_device():
    """An ``egrl`` zoo over a 4-shard ("pop",) mesh: the joined stack of
    8 GNN, 4 Boltzmann and 1 PG rows is padded to 16 rows and keeps the
    row sharding, and the trajectory, best rewards and best mappings are
    the single-device run's bit for bit."""
    code = """
import numpy as np
from jax.sharding import PartitionSpec
from repro.core import egrl
from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.core.sac import SACConfig
from repro.graphs.graph import WorkloadGraph
from repro.graphs.zoo import tiny_gpt

graphs = [WorkloadGraph(f"gpt{n}", g.nodes, g.edges) for n, g in
          ((n, tiny_gpt(seq=16, layers=n, d=64, heads=1)) for n in (1, 2))]
orig = egrl.evaluate_population_bucketed
seen = []

def tap(zoo, maps, *a, **k):
    seen.append(maps[0].sharding)
    return orig(zoo, maps, *a, **k)

egrl.evaluate_population_bucketed = tap
cfg = EGRLConfig(pop_size=8, boltzmann_frac=0.25, elites=2, seed=0,
                 sac=SACConfig(batch=4))
runs = {}
for shards in ("off", 4):
    algo = ZooEGRL(graphs, cfg, mode="egrl", fitness_agg="mean",
                   buckets="auto", pop_shards=shards, dispatch="off")
    seen.clear()
    recs = [algo.generation() for _ in range(3)]
    assert len(seen) == 3     # one joined evaluation a generation
    if shards == 4:
        assert all(len(s.device_set) == 4
                   and s.spec == PartitionSpec("pop") for s in seen), seen
    runs[shards] = algo, [(r["gen_best_fitness"], r["gen_mean_fitness"],
                           r["best_fitness"]) for r in recs]
sharded = runs[4][0]
assert (sharded.n_g_pad, sharded.n_b_pad) == (8, 4)
assert sharded._merge_pad == 3 and sharded._merged_rows == 16
assert runs["off"][0]._merge_pad == 0
assert runs["off"][1] == runs[4][1], (runs["off"][1], runs[4][1])
a, b = runs["off"][0], runs[4][0]
assert np.array_equal(a.best_reward, b.best_reward)
for x, y in zip(a.best_mapping, b.best_mapping):
    assert np.array_equal(x, y)
assert len(a.bank) == len(b.bank)
print("JOINED-SHARD-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC, REPRO_GAT_BACKEND="chunked")
    for k in ("REPRO_POP_SHARDS", "REPRO_MODEL_SHARDS",
              "REPRO_BUCKET_DISPATCH", "REPRO_ZOO_BUCKETS"):
        env.pop(k, None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "JOINED-SHARD-OK" in out.stdout

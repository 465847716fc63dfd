"""Population-sharding tests (PR 2): the sharded EA path must be
bit-identical to the single-device path — sharding is a capacity knob,
not a different algorithm.

Multi-device cases run in subprocesses with XLA-forced host devices
(the main test process keeps 1 device per the assignment, and the
device count is fixed at first jax init)."""
import os
import subprocess
import sys

import pytest

import jax

from repro.distributed.population import PopSharding, resolve_pop_sharding

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 4) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    env.pop("REPRO_POP_SHARDS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_resolve_pop_sharding_single_device():
    """Explicit-off policies resolve to the fallback path everywhere;
    the device-count-dependent cases only assert on a 1-device host."""
    assert resolve_pop_sharding(12, 4, "off") == PopSharding(None, 1)
    assert resolve_pop_sharding(12, 4, 1) == PopSharding(None, 1)
    assert resolve_pop_sharding(0, 0, "auto") == PopSharding(None, 1)
    if len(jax.devices()) == 1:
        assert resolve_pop_sharding(12, 4, "auto") == PopSharding(None, 1)
        with pytest.raises(ValueError, match="device"):
            resolve_pop_sharding(12, 4, 4)


def test_resolve_pop_sharding_policies_multi_device():
    run_py("""
import pytest
from repro.distributed.population import resolve_pop_sharding
# auto: all visible devices; non-dividing splits are PADDED, not
# downgraded to fewer shards (PR 3)
s = resolve_pop_sharding(12, 4, "auto")
assert s.n_shards == 4 and s.padded(12, 4) == (12, 4)
s = resolve_pop_sharding(51, 13, "auto")                    # pop 64 @ 0.2
assert s.n_shards == 4 and s.padded(51, 13) == (52, 16)
s = resolve_pop_sharding(48, 16, "auto")                    # pop 64 @ 0.25
assert s.n_shards == 4 and s.padded(48, 16) == (48, 16)
s = resolve_pop_sharding(6, 2, "auto")
assert s.n_shards == 4 and s.padded(6, 2) == (8, 4)
# auto never exceeds the larger sub-population
assert resolve_pop_sharding(3, 2, "auto").n_shards == 3
# explicit non-dividing shard counts now pad too
s = resolve_pop_sharding(51, 13, 4)
assert s.n_shards == 4 and s.padded(51, 13) == (52, 16)
s = resolve_pop_sharding(12, 4, 2)
assert s.n_shards == 2 and s.mesh.shape == {"pop": 2}
print("OK")
""")


def test_sharded_evolve_bit_identical():
    """evolve_sharded == evolve bitwise for every dividing shard count,
    and elite selection (leading rows) agrees across shard counts."""
    out = run_py("""
import jax, jax.numpy as jnp
from functools import partial
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import ea, boltzmann as bz
from repro.launch.mesh import make_mesh

n_g, n_b, n, v = 12, 4, 8, 40
kw = dict(n_nodes=n, e_g=3, e_b=1, tournament_k=3, crossover_prob=0.7,
          mut_prob=0.9, mut_frac=0.1, mut_std=0.1)
g_pop = jax.random.normal(jax.random.PRNGKey(0), (n_g, v))
b_pop = jax.random.normal(jax.random.PRNGKey(1), (n_b, bz.flat_size(n)))
fit_g = jax.random.uniform(jax.random.PRNGKey(2), (n_g,))
fit_b = jax.random.uniform(jax.random.PRNGKey(3), (n_b,))
logits = jax.random.normal(jax.random.PRNGKey(4), (n_g, n, 2, 3))
key = jax.random.PRNGKey(5)

ref_g, ref_b = jax.jit(partial(ea.evolve, **kw))(
    key, g_pop, fit_g, b_pop, fit_b, logits)
for s in (1, 2, 4):
    mesh = make_mesh((s,), ("pop",))
    sh = NamedSharding(mesh, P("pop"))
    args = [jax.device_put(x, sh) for x in (g_pop, fit_g, b_pop, fit_b, logits)]
    out_g, out_b = jax.jit(partial(ea.evolve_sharded, mesh, **kw))(key, *args)
    assert (out_g == ref_g).all(), f"GNN pop diverged at {s} shards"
    assert (out_b == ref_b).all(), f"Boltzmann pop diverged at {s} shards"
    # elite invariant: leading rows are the fitness-sorted elites
    order = jnp.argsort(-fit_g)
    assert (out_g[:3] == g_pop[order[:3]]).all()
# non-dividing mesh fails loudly instead of desynchronizing slots
mesh3 = make_mesh((3,), ("pop",))
try:
    ea.evolve_sharded(mesh3, key, g_pop, fit_g, b_pop, fit_b, logits, **kw)
except ValueError as e:
    assert "divisible" in str(e)
else:
    raise AssertionError("expected ValueError for 12/4 over 3 shards")
print("BITWISE-OK")
""")
    assert "BITWISE-OK" in out


def test_egrl_trajectory_matches_across_sharding():
    """EA-mode generations produce the same rewards/fitness trajectory
    sharded over 4 devices as on a single device (small pop, fast)."""
    out = run_py("""
from repro.core.egrl import EGRL, EGRLConfig
from repro.graphs.zoo import resnet50

g = resnet50()
cfg = EGRLConfig(pop_size=16, boltzmann_frac=0.25, elites=4, seed=0)
trajs = {}
for shards in (1, 4):
    algo = EGRL(g, cfg, mode="ea", pop_shards=shards)
    assert algo.pop_sharding.n_shards == shards
    trajs[shards] = [(r["gen_best_reward"], r["gen_mean_reward"])
                     for r in (algo.generation() for _ in range(4))]
assert trajs[1] == trajs[4], f"{trajs[1]} != {trajs[4]}"
print("TRAJ-OK")
""")
    assert "TRAJ-OK" in out


def test_padded_trajectory_matches_unpadded_single_device():
    """PR 3: a population split that does NOT divide the device count is
    padded with masked slots, and the real-row reward trajectory is
    bit-identical to the unpadded single-device run (13/3 padded to
    16/4 over 4 shards)."""
    out = run_py("""
from repro.core.egrl import EGRL, EGRLConfig
from repro.graphs.zoo import resnet50

g = resnet50()
cfg = EGRLConfig(pop_size=16, boltzmann_frac=0.2, elites=4, seed=0)
trajs = {}
for shards in ("off", 4):
    algo = EGRL(g, cfg, mode="ea", pop_shards=shards)
    assert (algo.n_g, algo.n_b) == (13, 3)
    if shards == 4:
        assert (algo.n_g_pad, algo.n_b_pad) == (16, 4)
        assert algo.gnn_pop.shape[0] == 16
    trajs[shards] = [(r["gen_best_reward"], r["gen_mean_reward"])
                     for r in (algo.generation() for _ in range(4))]
assert trajs["off"] == trajs[4], f'{trajs["off"]} != {trajs[4]}'
print("PAD-OK")
""")
    assert "PAD-OK" in out


def test_zoo_egrl_trajectory_matches_across_sharding():
    """The multi-workload ZooEGRL composes with ("pop",) sharding: the
    fitness trajectory over a padded 4-shard mesh matches single-device
    (pop 8 -> 6/2 padded to 8/4)."""
    out = run_py("""
from repro.core.egrl import ZooEGRL, EGRLConfig
from repro.graphs.zoo import resnet50, resnet101

cfg = EGRLConfig(pop_size=8, boltzmann_frac=0.25, elites=2, seed=0)
trajs = {}
for shards in ("off", 4):
    algo = ZooEGRL([resnet50(), resnet101()], cfg, pop_shards=shards)
    trajs[shards] = [(r["gen_best_fitness"], r["gen_mean_fitness"])
                     for r in (algo.generation() for _ in range(3))]
assert trajs["off"] == trajs[4], f'{trajs["off"]} != {trajs[4]}'
print("ZOO-SHARD-OK")
""")
    assert "ZOO-SHARD-OK" in out


@pytest.mark.slow
def test_pop64_elite_fitness_trajectory_matches():
    """Acceptance: a pop-64 EA run sharded over a 4-device mesh yields
    the same elite fitness trajectory as the single-device run."""
    out = run_py("""
from repro.core.egrl import EGRL, EGRLConfig
from repro.graphs.zoo import resnet50

g = resnet50()
cfg = EGRLConfig(pop_size=64, boltzmann_frac=0.25, elites=8, seed=0)
trajs = {}
for shards in (1, 4):
    algo = EGRL(g, cfg, mode="ea", pop_shards=shards)
    assert (algo.n_g, algo.n_b) == (48, 16)
    assert algo.pop_sharding.n_shards == shards
    trajs[shards] = [(r["gen_best_reward"], r["best_reward"])
                     for r in (algo.generation() for _ in range(3))]
assert trajs[1] == trajs[4], f"{trajs[1]} != {trajs[4]}"
print("POP64-OK")
""")
    assert "POP64-OK" in out

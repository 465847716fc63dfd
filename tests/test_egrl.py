"""EGRL component + integration tests (paper Algorithm 2 invariants),
against the device-resident stacked-population implementation."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import boltzmann as bz
from repro.core import ea as ea_mod
from repro.core import gnn
from repro.core.egrl import EGRLConfig, ZooEGRL, evaluate_gnn_zoo
from repro.graphs.zoo import resnet50


def test_gnn_forward_shapes():
    g = resnet50()
    feats, adj = jnp.asarray(g.features()), jnp.asarray(g.adjacency())
    p = gnn.init_gnn(jax.random.PRNGKey(0), feats.shape[1])
    logits = gnn.gnn_forward(p, feats, adj)
    assert logits.shape == (g.n, 2, 3)
    acts = gnn.sample_actions(jax.random.PRNGKey(1), logits)
    assert acts.shape == (g.n, 2)
    assert int(acts.min()) >= 0 and int(acts.max()) <= 2


def test_gnn_flat_roundtrip():
    p = gnn.init_gnn(jax.random.PRNGKey(0), 19)
    vec = gnn.flatten_params(p)
    p2 = gnn.unflatten_params(p, vec)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2)):
        assert (a == b).all()


def test_boltzmann_flat_roundtrip():
    b = bz.init_boltzmann(jax.random.PRNGKey(0), 16)
    flat = bz.to_flat(*b)
    assert flat.shape == (bz.flat_size(16),)
    b2 = bz.from_flat(flat, 16)
    assert (b2.prior == b.prior).all() and (b2.log_t == b.log_t).all()
    # batched round-trip (how the EA stores a sub-population)
    flats = jnp.stack([flat, flat + 1.0])
    bb = bz.from_flat(flats, 16)
    assert bb.prior.shape == (2, 16, 2, 3) and bb.log_t.shape == (2, 16, 2)


def test_boltzmann_temperature_controls_entropy():
    """Appendix E: higher T -> higher sampling entropy (seeded sweep,
    formerly a hypothesis property test)."""
    rng = np.random.default_rng(0)

    def ent(bb):
        lg = bz.boltzmann_logits(bb)
        lp = jax.nn.log_softmax(lg, -1)
        return float(-(jnp.exp(lp) * lp).sum(-1).mean())

    for _ in range(20):
        log_t = float(rng.uniform(-2.0, 2.0))
        seed = int(rng.integers(0, 2 ** 31 - 1))
        b = bz.init_boltzmann(jax.random.PRNGKey(seed), 16)
        hot = bz.Boltzmann(b.prior, jnp.full_like(b.log_t, log_t + 1.0))
        cold = bz.Boltzmann(b.prior, jnp.full_like(b.log_t, log_t - 1.0))
        assert ent(hot) >= ent(cold) - 1e-6


def test_tournament_prefers_fit():
    fitness = jnp.asarray([0.0, 10.0, 1.0, 2.0])
    idx = ea_mod.tournament_indices(jax.random.PRNGKey(0), fitness, 200, 3)
    assert idx.shape == (200,)
    # the argmax individual must win far more often than uniform
    assert float((idx == 1).mean()) > 0.5


def test_crossover_mixes_genomes():
    a, b = jnp.zeros(100), jnp.ones(100)
    c = ea_mod.single_point_crossover(jax.random.PRNGKey(3), a, b)
    assert 0 < float(c.sum()) < 100


def test_evolve_preserves_shapes_and_elites():
    n_g, n_b, n, v = 6, 2, 8, 40
    key = jax.random.PRNGKey(0)
    gnn_pop = jax.random.normal(key, (n_g, v))
    bz_pop = jax.random.normal(jax.random.PRNGKey(1),
                               (n_b, bz.flat_size(n)))
    fit_g = jnp.asarray([3.0, 1.0, 7.0, 2.0, 5.0, 0.0])
    fit_b = jnp.asarray([1.0, 4.0])
    logits = jax.random.normal(jax.random.PRNGKey(2), (n_g, n, 2, 3))
    new_g, new_b = ea_mod.evolve(
        jax.random.PRNGKey(4), gnn_pop, fit_g, bz_pop, fit_b, logits,
        n_nodes=n, e_g=2, e_b=1, tournament_k=3, crossover_prob=0.7,
        mut_prob=0.9, mut_frac=0.1, mut_std=0.1)
    assert new_g.shape == (n_g, v) and new_b.shape == (n_b, bz.flat_size(n))
    # elites survive unchanged, sorted by fitness (rows 0..e-1)
    assert (new_g[0] == gnn_pop[2]).all()   # fitness 7.0
    assert (new_g[1] == gnn_pop[4]).all()   # fitness 5.0
    assert (new_b[0] == bz_pop[1]).all()    # fitness 4.0


def test_boltzmann_children_seeded_from_gnn_elite_posterior():
    """Alg 2 lines 16-18: a Boltzmann child that draws a GNN mate takes
    the elite's posterior logits as its prior.  With e_b=0 the mate pool
    is GNN-only and crossover_prob=1/mut_prob=0 make seeding
    deterministic, so every child prior must equal the top elite's
    logits bit-for-bit."""
    n_g, n_b, n, v = 3, 3, 8, 40
    gnn_pop = jax.random.normal(jax.random.PRNGKey(0), (n_g, v))
    bz_pop = jax.random.normal(jax.random.PRNGKey(1), (n_b, bz.flat_size(n)))
    fit_g = jnp.asarray([1.0, 9.0, 2.0])                 # elite = row 1
    logits = jax.random.normal(jax.random.PRNGKey(2), (n_g, n, 2, 3))
    _, new_b = ea_mod.evolve(
        jax.random.PRNGKey(3), gnn_pop, fit_g, bz_pop,
        jnp.asarray([0.5, 0.1, 0.2]), logits,
        n_nodes=n, e_g=1, e_b=0, tournament_k=2, crossover_prob=1.0,
        mut_prob=0.0, mut_frac=0.1, mut_std=0.1)
    for row in new_b:
        child = bz.from_flat(row, n)
        assert (child.prior == logits[1]).all()
        # seeded log-temperature: log(0.5) + 0.1 * N(0, 1)
        assert float(jnp.abs(child.log_t - jnp.log(0.5)).max()) < 1.0


def test_egrl_population_is_device_resident():
    g = resnet50()
    algo = ZooEGRL([g], EGRLConfig(total_steps=21, pop_size=4, elites=1),
                   mode="egrl")
    assert algo.gnn_pop.shape[0] == algo.n_g
    assert algo.bz_pop.shape == (algo.n_b, bz.flat_size(g.n))
    algo.generation()
    assert isinstance(algo.gnn_pop, jnp.ndarray)
    assert algo.steps == algo.n_g + algo.n_b + 1   # + pg rollout


@pytest.mark.slow
def test_egrl_improves_over_random_and_learns_validity():
    g = resnet50()
    algo = ZooEGRL([g], EGRLConfig(total_steps=200, seed=0), mode="egrl")
    algo.train()
    assert algo.best_reward[0] > 0  # found valid maps
    best = algo.history[-1]["best_reward_per_graph"][g.name]
    assert best / algo.cfg.reward_scale > 0.9  # near/above compiler
    assert len(algo.bank) == algo.steps  # every rollout hits the bank


def test_ea_only_and_pg_only_run():
    g = resnet50()
    for mode in ("ea", "pg"):
        algo = ZooEGRL([g], EGRLConfig(total_steps=45, seed=1), mode=mode)
        algo.train()
        assert algo.steps >= 45


@pytest.mark.slow
def test_zero_shot_transfer_api():
    g = resnet50()
    algo = ZooEGRL([g], EGRLConfig(total_steps=63, seed=0), mode="egrl")
    algo.train()
    vec = algo.best_gnn_vec()
    sp = evaluate_gnn_zoo([resnet50()], vec, samples=2)[g.name]
    assert sp >= 0.0

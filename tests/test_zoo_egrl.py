"""Multi-workload EGRL (ZooEGRL) + the masked batched GNN forward + the
1k+-node synthetic zoo graphs + the ZooSAC policy-gradient member."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import gnn
from repro.core.egrl import (EGRLConfig, ZooEGRL, evaluate_gnn_on,
                             evaluate_gnn_zoo)
from repro.core.replay import ReplayBank, ReplayBuffer
from repro.core.sac import SACConfig, SACLearner, ZooSAC
from repro.graphs.batch import build_graph_batch
from repro.graphs.zoo import (EXTRACTED_WORKLOADS, PAPER_WORKLOADS,
                              SMALL_WORKLOADS, SYNTH_WORKLOADS, WORKLOADS,
                              dense_cnn,
                              moe_transformer, resnet50, resnet101,
                              workload_sizes)


# ------------------------------------------------------- zoo registry
def test_zoo_registry_contains_1k_graphs():
    assert (set(PAPER_WORKLOADS) | set(SYNTH_WORKLOADS)
            | set(SMALL_WORKLOADS) | set(EXTRACTED_WORKLOADS)
            == set(WORKLOADS))
    big = {name: f().n for name, f in SYNTH_WORKLOADS.items()}
    assert len(big) >= 2
    for name, n in big.items():
        assert n >= 1000, f"{name} has only {n} nodes"


def test_zoo_registry_small_size_classes():
    """The <200-node workloads that give the BucketedZoo real small
    size classes, and the lazy size cache that makes bucket assignment
    cheap (no SimGraph build)."""
    small = {name: f() for name, f in SMALL_WORKLOADS.items()}
    assert len(small) >= 2
    for name, g in small.items():
        assert g.n < 200, f"{name} has {g.n} nodes"
        g.validate()
        # the lazy registry sizes match the built graph exactly
        assert workload_sizes(name) == (g.n, g.ring_width())
    # cache is stable across calls
    for name in WORKLOADS:
        assert workload_sizes(name) == workload_sizes(name)


def test_synth_graphs_validate_and_stress_the_ring():
    g = dense_cnn()
    # dense fan-in: activation lifetimes span whole blocks
    last = np.zeros(g.n, np.int64)
    for s, d in g.edges:
        last[s] = max(last[s], d)
    w = int((last - np.arange(g.n)).max()) + 1
    assert w > 60
    m = moe_transformer()
    fracs = [nd.weight_access_frac for nd in m.nodes
             if nd.op == "expert_bank"]
    assert fracs and all(0 < f < 1 for f in fracs)   # cold expert weights


# ------------------------------------------- masked batched GNN forward
def test_gnn_zoo_forward_matches_per_graph():
    """Real-node logits of the padded batched forward match the unpadded
    per-graph forward to float tolerance (XLA regroups the attention
    reductions with the padded length, so bitwise is not expected)."""
    graphs = [resnet50(), resnet101()]
    gb = build_graph_batch(graphs)
    p = gnn.init_gnn(jax.random.PRNGKey(0), gb.feats.shape[-1])
    zoo = gnn.gnn_forward_zoo(p, gb.feats, gb.adj, gb.node_mask,
                              gb.n_nodes)
    assert zoo.shape == (2, gb.n_max, 2, 3)
    for i, g in enumerate(graphs):
        ref = gnn.gnn_forward(p, jnp.asarray(g.features()),
                              jnp.asarray(g.adjacency()))
        np.testing.assert_allclose(np.asarray(zoo[i, :g.n]),
                                   np.asarray(ref), rtol=1e-4, atol=1e-5)
        assert (np.asarray(zoo[i, g.n:]) == 0.0).all()


def test_gnn_zoo_forward_ignores_padding_content_bitwise():
    """Garbage in padding feature rows must not change ANY output bit —
    the masking discipline, not float tolerance."""
    graphs = [resnet50(), resnet101()]
    gb = build_graph_batch(graphs)
    p = gnn.init_gnn(jax.random.PRNGKey(1), gb.feats.shape[-1])
    fwd = jax.jit(lambda f: gnn.gnn_forward_zoo(
        p, f, gb.adj, gb.node_mask, gb.n_nodes))
    clean = fwd(gb.feats)
    rng = np.random.default_rng(2)
    dirty = np.asarray(gb.feats).copy()
    for i, g in enumerate(graphs):
        dirty[i, g.n:] = rng.standard_normal(dirty[i, g.n:].shape)
    assert (np.asarray(clean) == np.asarray(fwd(jnp.asarray(dirty)))).all()


def test_population_logits_zoo_shape():
    graphs = [resnet50(), resnet101()]
    gb = build_graph_batch(graphs)
    template = gnn.init_gnn(jax.random.PRNGKey(0), gb.feats.shape[-1])
    pop = jnp.stack([gnn.flatten_params(
        gnn.init_gnn(jax.random.PRNGKey(i), gb.feats.shape[-1]))
        for i in range(3)])
    out = gnn.population_logits_zoo(template, gb.feats, gb.adj,
                                    gb.node_mask, gb.n_nodes, pop)
    assert out.shape == (3, 2, gb.n_max, 2, 3)


# ------------------------------------------------------------- ZooEGRL
def test_zoo_egrl_trains_and_tracks_per_graph_best():
    cfg = EGRLConfig(pop_size=8, boltzmann_frac=0.25, elites=2, seed=0)
    algo = ZooEGRL([resnet50(), resnet101()], cfg)
    recs = [algo.generation() for _ in range(3)]
    # one env step per (genome, graph) rollout
    assert algo.steps == 3 * algo.cfg.pop_size * algo.n_graphs
    assert set(recs[-1]["best_reward_per_graph"]) == {"resnet50",
                                                      "resnet101"}
    for gi, g in enumerate((resnet50(), resnet101())):
        assert algo.best_mapping[gi] is not None
        assert algo.best_mapping[gi].shape == (g.n, 2)
    # best-so-far fitness is monotone
    bests = [r["best_fitness"] for r in recs]
    assert bests == sorted(bests)
    # a trained zoo GNN drops into the per-graph transfer API
    sp = evaluate_gnn_on(resnet50(), algo.best_gnn_vec(), samples=2)
    assert sp >= 0.0


def test_zoo_egrl_worst_case_fitness_is_min_over_graphs():
    cfg = EGRLConfig(pop_size=6, boltzmann_frac=0.34, elites=2, seed=3)
    mean_a = ZooEGRL([resnet50(), resnet101()], cfg, fitness_agg="mean")
    worst_a = ZooEGRL([resnet50(), resnet101()], cfg, fitness_agg="worst")
    rm, rw = mean_a.generation(), worst_a.generation()
    # same seed => same rollouts; the aggregate differs unless degenerate
    assert rm["steps"] == rw["steps"]
    assert rw["gen_best_fitness"] <= rm["gen_best_fitness"] + 1e-6


def test_zoo_egrl_env_var_and_validation(monkeypatch):
    monkeypatch.setenv("REPRO_FITNESS_AGG", "worst")
    algo = ZooEGRL([resnet50()], EGRLConfig(pop_size=4, elites=1, seed=0))
    assert algo.agg == "worst"
    monkeypatch.setenv("REPRO_FITNESS_AGG", "median")
    with pytest.raises(ValueError, match="REPRO_FITNESS_AGG"):
        ZooEGRL([resnet50()], EGRLConfig(pop_size=4, elites=1, seed=0))


def test_zoo_egrl_single_graph_matches_graph_semantics():
    """A one-graph zoo is just per-graph EA training on the batched
    path: rewards must be plausible (valid maps found) and mappings
    must have the graph's own length."""
    g = resnet50()
    cfg = EGRLConfig(pop_size=8, boltzmann_frac=0.25, elites=2, seed=1)
    algo = ZooEGRL([g], cfg, fitness_agg="mean")
    algo.train(total_steps=3 * 8)
    assert algo.best_mapping[0].shape == (g.n, 2)
    assert algo.best_reward[0] > 0        # found valid maps on resnet50


@pytest.mark.slow
def test_zoo_egrl_with_1k_graphs():
    cfg = EGRLConfig(pop_size=6, boltzmann_frac=0.34, elites=2, seed=0)
    algo = ZooEGRL([resnet50(), moe_transformer(), dense_cnn()], cfg)
    rec = algo.generation()
    # the mixed-size zoo buckets: resnet50 peels off the 1k graphs
    assert algo.zoo.n_buckets >= 2
    assert max(b.n_max for b in algo.zoo.buckets) >= 1000
    assert min(b.n_max for b in algo.zoo.buckets) < 200
    assert len(rec["best_reward_per_graph"]) == 3


# ------------------------------------------------- ZooSAC (the PG member)
def test_zoo_sac_single_graph_matches_sac_learner():
    """The zoo learner on a one-graph batch IS the single-graph learner:
    same init key stream, same replay draw order, same PRNGKey(17) noise
    chain — losses and updated parameters must agree to ~1e-6 (the zoo
    losses are per-graph SACLearner losses averaged over G=1; remaining
    diffs are XLA refusion of the masked identities)."""
    g = resnet50()
    gb = build_graph_batch([g])
    key = jax.random.PRNGKey(5)
    ref = SACLearner(jnp.asarray(g.features()), jnp.asarray(g.adjacency()),
                     key)
    zoo = ZooSAC(gb, key)
    for a, b in zip(jax.tree.leaves(ref.actor), jax.tree.leaves(zoo.actor)):
        assert (a == b).all()                 # identical init
    for a, b in zip(jax.tree.leaves(ref.critic),
                    jax.tree.leaves(zoo.critic)):
        assert (a == b).all()

    rng = np.random.default_rng(0)
    acts = rng.integers(0, 3, (40, g.n, 2))
    rews = rng.standard_normal(40).astype(np.float32)
    buf = ReplayBuffer(g.n, seed=0)
    buf.add_batch(acts, rews)
    bank = ReplayBank([gb.n_max], seed=0)
    bank.add_batch(acts[:, None], rews[:, None])

    info_ref = ref.update(buf, steps=3)
    info_zoo = zoo.update(bank, steps=3)
    assert info_ref and info_zoo
    for k in ("critic_loss", "actor_loss", "entropy"):
        np.testing.assert_allclose(info_zoo[k], info_ref[k],
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(ref.actor), jax.tree.leaves(zoo.actor)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=2e-5)
    for a, b in zip(jax.tree.leaves(ref.critic),
                    jax.tree.leaves(zoo.critic)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=2e-5)


def test_zoo_sac_critic_ignores_padding_content():
    """Q values are a function of the real subgraph only: garbage in the
    padded feature rows / action slots must not change them (the critic
    counterpart of the zoo forward's content-inertness)."""
    from repro.core.sac import critic_forward_masked, critic_defs
    from repro.utils.params import init_params

    graphs = [resnet50(), resnet101()]
    gb = build_graph_batch(graphs)
    p = init_params(critic_defs(gb.n_features), jax.random.PRNGKey(3))
    oh = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(4),
                           (gb.n_graphs, gb.n_max, 2), 0, 3), 3)
    fwd = jax.jit(lambda f, a: jax.vmap(
        lambda fi, ai, mi, ohi: critic_forward_masked(p, fi, ai, mi, ohi))(
        f, gb.adj, gb.node_mask, a))
    clean = fwd(gb.feats, oh)
    rng = np.random.default_rng(5)
    feats_d = np.asarray(gb.feats).copy()
    oh_d = np.asarray(oh).copy()
    for i, g in enumerate(graphs):
        feats_d[i, g.n:] = rng.standard_normal(feats_d[i, g.n:].shape)
        oh_d[i, g.n:] = rng.standard_normal(oh_d[i, g.n:].shape)
    dirty = fwd(jnp.asarray(feats_d), jnp.asarray(oh_d))
    for c, d in zip(clean, dirty):
        np.testing.assert_allclose(np.asarray(c), np.asarray(d),
                                   rtol=1e-6, atol=1e-6)


def test_zoo_egrl_full_mode_trains_with_sac_member():
    """"egrl" mode: PG rollouts score zoo-wide, the bank fills per
    graph, the learner updates once warm, and losses surface in the
    generation record."""
    cfg = EGRLConfig(pop_size=6, boltzmann_frac=0.34, elites=1, seed=0,
                     sac=SACConfig(batch=8))
    algo = ZooEGRL([resnet50(), resnet101()], cfg, mode="egrl")
    assert algo.learner is not None and algo.bank is not None
    r1 = algo.generation()
    # 7 rollout rows (pop 6 + 1 PG) x 2 graphs of env steps
    assert algo.steps == 7 * 2
    assert len(algo.bank) == 7            # per-graph transitions
    assert "critic_loss" not in r1        # bank (7) still < sac batch (8)
    r2 = algo.generation()
    assert {"critic_loss", "actor_loss", "entropy"} <= set(r2)
    assert len(algo.bank) == 14
    # a trained zoo GNN still drops into both transfer APIs
    assert algo.best_gnn_vec() is not None


def test_zoo_egrl_pg_migration_writes_only_the_last_gnn_row():
    cfg = EGRLConfig(pop_size=6, boltzmann_frac=0.34, elites=1, seed=0,
                     sac=SACConfig(batch=4))
    algo = ZooEGRL([resnet50(), resnet101()], cfg, mode="egrl")
    algo.generation()
    pop = np.asarray(algo.gnn_pop)
    vec = jnp.arange(pop.shape[1], dtype=algo.gnn_pop.dtype)
    new = np.asarray(algo._migrate(algo.gnn_pop, vec))
    assert (new[algo.n_g - 1] == np.asarray(vec)).all()
    others = np.arange(pop.shape[0]) != algo.n_g - 1
    assert (new[others] == pop[others]).all()   # bitwise untouched


def test_zoo_egrl_ea_mode_has_no_pg_state():
    """Disabling the PG member must leave the EA path untouched: no
    learner, no bank, and the template drawn from the FIRST key (the
    PR 3 PRNG contract, so EA trajectories stay bit-identical)."""
    cfg = EGRLConfig(pop_size=4, elites=1, seed=0)
    algo = ZooEGRL([resnet50()], cfg, mode="ea")
    assert algo.learner is None and algo.bank is None
    _, k0 = jax.random.split(jax.random.PRNGKey(cfg.seed))
    want = gnn.init_gnn(k0, algo.zoo.n_features)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(algo._template)):
        assert (a == b).all()


def test_launch_train_zoo_report():
    """The zoo training entry point wires ZooEGRL + the batched
    zero-shot sweep into one report ("pg" ablation keeps it fast; the
    zero-shot vec falls back to the ZooSAC actor there)."""
    from repro.launch.train_zoo import train_zoo

    report, algo = train_zoo(["resnet50"], holdout=["resnet101"], steps=2,
                             mode="pg", agg="mean", seed=0, log=None)
    assert set(report["train_best_speedup"]) == {"resnet50"}
    assert set(report["zero_shot_speedup"]) == {"resnet101"}
    assert report["env_steps"] >= 2 and report["agg"] == "mean"
    assert all(sp >= 0.0 for sp in report["zero_shot_speedup"].values())


def test_evaluate_gnn_zoo_matches_greedy_floor():
    """The batched zero-shot sweep reports at least the greedy mapping's
    speedup per graph (stochastic samples can only improve the max), and
    names line up with the input order."""
    graphs = [resnet50(), resnet101()]
    n_feat = graphs[0].features().shape[1]
    vec = gnn.flatten_params(gnn.init_gnn(jax.random.PRNGKey(0), n_feat))
    out = evaluate_gnn_zoo(graphs, vec, samples=2, seed=0)
    assert set(out) == {"resnet50", "resnet101"}
    greedy_only = evaluate_gnn_zoo(graphs, vec, samples=0, seed=0)
    for name in out:
        assert out[name] >= greedy_only[name] - 1e-6
        assert out[name] >= 0.0

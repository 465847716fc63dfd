"""EGRL driver (Algorithm 2): mixed EA population (GNN + Boltzmann) and a
SAC learner sharing one replay buffer, with PG->EA migration and
GNN->Boltzmann prior seeding.

Device-resident generation (beyond-paper optimization): the population
is stored as stacked arrays — GNN genomes as one (n_g, V) flat-parameter
matrix, Boltzmann genomes as one (n_b, F) flat matrix — and a generation
is a series of jitted device calls:

1. ONE vmapped GNN forward over the stacked parameter matrix (one per
   size bucket in the zoo driver),
2. ONE vmapped Boltzmann sample (+ one batched PG rollout sample),
3. one vmapped simulator call per population part in the per-graph
   driver (memsim.simulator; GNN / Boltzmann / PG mappings are scored
   separately so the sharded parts keep their ("pop",) placement — see
   generation()); the zoo driver joins the parts' rows and scores them
   in one simulator call per size bucket,
4. ONE jitted EA step (core/ea.py: tournament, crossover, seeding,
   mutation over the stacked genomes) plus a jitted migration row
   write for the PG policy.

The zoo driver runs the glue between those programs (every key split of
the generation, the samplers' bucket slices, the EA step's seeding
grid, the zoo-order gathers, the fitness means, the actor flatten of
the migration, the join of the parts' rollout rows) inside a few more
jitted programs: a warm 3-bucket egrl generation launches 29 programs,
13 of them the SAC learner's batch upload
(tests/test_generation_launches.py), where the eager glue made 114.
Results come back to the host in one blocking read per result array (9
per 3-bucket egrl generation, counted by the ``egrl.device_reads``
counter), for the replay buffer, best-mapping tracking and logging.
The seed implementation instead kept a Python list of per-individual
genomes: building each child ran 1-3 host RNG ops plus device
transfers, serializing the inner loop.

Population sharding (PR 2, padding PR 3): when more than one device is
visible (see repro.distributed.population for the REPRO_POP_SHARDS
policy), the stacked genome arrays carry a NamedSharding over a 1-D
("pop",) mesh; sub-populations that do not divide the shard count are
padded with masked rows (-inf fitness, PRNG draws sized by the real
counts) so the real-row trajectory still matches the unpadded
single-device run bit for bit.
Rollout sampling and simulator evaluation then partition automatically
under jit and the GNN forward runs shard by shard under ``shard_map``
(per-genome work is independent),
while the EA step runs ea.evolve_sharded — shard-local
crossover/mutation/seeding with fitness all_gather + exact psum gathers
for elites and parents — and PG migration writes through a jitted
scatter that keeps the population sharding.  All paths are bit-identical
to the single-device ones (tests/test_ea_sharding.py), so sharding is a
pure capacity/throughput knob, not a different algorithm.

Modes: "egrl" (full), "ea" (ablate PG), "pg" (ablate EA) — the paper's
baseline agents.

Multi-workload training (PR 3, PG member PR 4, size buckets PR 5):
``ZooEGRL`` evolves ONE population against a whole workload zoo — the
graphs live in a size-bucketed ``BucketedZoo`` (one ``GraphBatch`` per
size class, policy ``REPRO_ZOO_BUCKETS``), per-generation fitness is a
selectable aggregate (mean / worst-case, ``REPRO_FITNESS_AGG``) of
per-graph rewards, evaluated in one jitted device call PER BUCKET
(memsim.batch.evaluate_population_bucketed) so small workloads don't
pay the biggest graph's padded scan.  GNN genomes transfer unchanged
(their parameters are graph-size independent); Boltzmann genomes span
the bucket-major padded node grid ``sum_k(G_k · N_max_k)``.  In "egrl"
mode the population is seeded by ``ZooSAC`` — the batched
multi-workload SAC learner (core/sac.py) trained from a per-zoo-index
``ReplayBank`` — with the same PG->EA migration as the per-graph
driver, so the zoo path runs the full hybrid of the paper instead of
the EA-only ablation.  Single-bucket zoos are bit-identical to the
flat GraphBatch path (see graphs/bucketed.py's PRNG discipline).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import boltzmann as bz
from repro.core import ea as ea_mod
from repro.core import gnn
from repro.core.replay import ReplayBank, ReplayBuffer
from repro.core.sac import SACConfig, SACLearner, ZooSAC
from repro.distributed.dispatch import BucketDispatcher
from repro.distributed.population import resolve_pop_sharding
from repro.graphs.batch import GraphBatch
from repro.graphs.bucketed import (BucketedZoo, bucket_keys_batch,
                                   build_bucketed_zoo)
from repro.graphs.graph import WorkloadGraph
from repro.memsim.batch import (aggregate_rewards,
                                evaluate_population_bucketed)
from repro.memsim.compiler import compiler_reference
from repro.memsim.simulator import build_sim_graph, evaluate_population
from repro.utils.envpolicy import env_policy


def _pad_rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Extend a stacked (P, ...) array with zero rows up to ``rows``."""
    if x.shape[0] == rows:
        return x
    pad = jnp.zeros((rows - x.shape[0],) + x.shape[1:], x.dtype)
    return jnp.concatenate([x, pad])


def _pad_keys(keys: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Extend a (P, 2) key array to ``rows`` by repeating the last key
    (padding rows sample throwaway mappings that are never consumed),
    WITHOUT touching the split stream of the real rows — split(k, n)
    has no prefix property, so the caller must split with the REAL
    count."""
    if keys.shape[0] == rows:
        return keys
    rep = jnp.broadcast_to(keys[-1:], (rows - keys.shape[0],)
                           + keys.shape[1:])
    return jnp.concatenate([keys, rep])


def _evolve_with_fitness_mask(evolve_fn, n_g, n_g_pad, n_b, n_b_pad,
                              key, gnn_pop, fit_g, bz_pop, fit_b, logits):
    """Pin padding rows' fitness to -inf before the EA step.  Jitted
    together with the evolve call so a ("pop",)-sharded fitness vector
    stays sharded through the mask."""
    if n_g_pad > n_g:
        fit_g = jnp.where(jnp.arange(n_g_pad) < n_g, fit_g, -jnp.inf)
    if n_b_pad > n_b:
        fit_b = jnp.where(jnp.arange(n_b_pad) < n_b, fit_b, -jnp.inf)
    return evolve_fn(key, gnn_pop, fit_g, bz_pop, fit_b, logits)


# ---------------------------------------------------------------------------
# Module-level population programs.  These used to be per-instance
# ``jax.jit`` closures capturing the driver's arrays, so EVERY fresh
# driver recompiled identical programs (tens of seconds for the GNN
# population forward).  Hoisted to module scope, the jit cache keys on
# (function identity, arg shapes/dtypes, pytree structure, static
# backend) only — a new driver instance over an already-seen geometry
# reuses the compiled executables.  That is what makes short-budget
# refinement viable for the persistent placement service
# (serving/placement_service.py), which constructs a fresh ``ZooEGRL``
# per miss batch on a canonical padding grid.  The population-SHARDED
# paths keep per-instance closures: their mesh / out_shardings are
# instance state (and multi-device runs amortize compiles anyway).

_POP_LOGITS = jax.jit(gnn.population_logits, static_argnames=("backend",))
_POP_LOGITS_ZOO = jax.jit(gnn.population_logits_zoo,
                          static_argnames=("backend",))
_SAMPLE_ACTIONS = jax.jit(jax.vmap(gnn.sample_actions))


@partial(jax.jit, static_argnames=("idx",))
def _migrate_row(pop, params, idx):
    """PG migration: flatten the learner's actor (or take a flat (V,)
    genome as is: a lone array flattens to itself) and write it into
    row ``idx`` (static: no scalar upload per call).  One executable
    per pop geometry and parameter structure, shared by every driver
    instance."""
    return pop.at[idx].set(gnn.flatten_params(params))


@jax.jit
def _bz_sample_pop(keys, pops):
    """Vmapped Boltzmann sample over one stacked (P, flat) sub-population.
    The node count is recovered from the flat width (``bz.flat_size`` is
    linear), so one program serves every driver geometry."""
    n = pops.shape[-1] // bz.flat_size(1)
    return jax.vmap(lambda k, f: bz.sample(k, bz.from_flat(f, n)))(keys, pops)


# ---------------------------------------------------------------------------
# ZooEGRL's per-generation glue, one program per step.  Run eagerly,
# every key split, slice, reshape and concatenate between the real
# programs is a launch of its own, and the device idles while the host
# dispatches it.  These programs only split keys and move data, so they
# give the eager sequence's values bit for bit
# (tests/test_generation_launches.py).

@partial(jax.jit, static_argnames=("n_g", "n_g_pad", "n_b", "n_b_pad",
                                   "n_buckets"))
def _generation_keys(key, *, n_g, n_g_pad, n_b, n_b_pad, n_buckets):
    """Every key a zoo generation draws from the driver's stream, in the
    eager order (GNN rows, Boltzmann rows, EA step): returns the next
    driver key and a dict with the per-bucket GNN row keys ``"g"``
    (``bucket_keys_batch`` of the padded row keys), the padded
    Boltzmann row keys ``"b"`` and the EA step's key ``"evolve"``.
    Row keys are split with the REAL count (split(k, n) has no prefix
    property) and repeated into the padding rows."""
    out = {}
    if n_g:
        key, k = jax.random.split(key)
        out["g"] = tuple(bucket_keys_batch(
            _pad_keys(jax.random.split(k, n_g), n_g_pad), n_buckets))
    if n_b:
        key, k = jax.random.split(key)
        out["b"] = _pad_keys(jax.random.split(k, n_b), n_b_pad)
    if n_g or n_b:
        key, out["evolve"] = jax.random.split(key)
    return key, out


def _seeding_grid(logits):
    """Per-bucket (P, G_k, N_max_k, 2, 3) logits -> the bucket-major
    (P, n_eff, 2, 3) grid the EA step seeds Boltzmann genomes from
    (matching the bz genome layout; a flat reshape at K = 1)."""
    return jnp.concatenate([lg.reshape(lg.shape[0], -1, 2, 3)
                            for lg in logits], axis=1)


_SEEDING_GRID = jax.jit(_seeding_grid)


@jax.jit
def _sample_gnn_rollouts(bucket_keys, logits):
    """Per-bucket GNN rollout mappings (one vmapped sample per bucket
    from its row keys) and the EA step's seeding grid, in one launch."""
    maps = tuple(jax.vmap(gnn.sample_actions)(k, lg)
                 for k, lg in zip(bucket_keys, logits))
    return maps, _seeding_grid(logits)


@partial(jax.jit, static_argnames=("layout",))
def _sample_bz_rollouts(keys, pops, layout):
    """One flat (n_eff, 2) Boltzmann sample per genome, split into the
    per-bucket (P, G_k, N_max_k, 2) stacks; ``layout`` is the buckets'
    (G_k, N_max_k), bucket-major."""
    flat = _bz_sample_pop(keys, pops)
    out, off = [], 0
    for g, n in layout:
        out.append(flat[:, off:off + g * n].reshape(-1, g, n, 2))
        off += g * n
    return tuple(out)


def _merge_rollouts(parts, pad):
    """Every part's per-bucket (P_part, G_k, N_max_k, 2) rollout stacks
    joined along the row axis in part order, bucket by bucket, with
    ``pad`` zero rows at the end of each bucket's stack: the rows of the
    whole generation, scored in one simulator call per bucket."""
    out = []
    for stacks in zip(*parts):
        rows = list(stacks)
        if pad:
            rows.append(jnp.zeros((pad,) + stacks[0].shape[1:],
                                  stacks[0].dtype))
        out.append(jnp.concatenate(rows))
    return tuple(out)


_MERGE_ROLLOUTS = jax.jit(_merge_rollouts, static_argnames=("pad",))


@partial(jax.jit, static_argnames=("bounds", "mode"))
def _fitness(rewards, bounds, mode):
    """Merged (P_total, G) rewards -> each part's fitness vector (rows
    ``start:stop`` of ``bounds``, part order) and their concatenation,
    which the host reads in one go."""
    fit = tuple(aggregate_rewards(rewards[a:b], mode) for a, b in bounds)
    return fit, jnp.concatenate(fit)


def _compile_tracked(fn, what, **attrs):
    """Compile-vs-execute attribution: jax traces AND compiles
    synchronously inside a jitted callable's first call, so wrapping
    that first call in a distinct ``jit_compile`` span (config as
    attributes) splits first-compile time out of the surrounding
    execute span without any added sync.  Later calls pass through on a
    single flag check.  Shared by ``_evolve_program`` (one flag per
    cached config, so a recompile storm shows up as repeated
    ``jit_compile`` spans) and the gat_tune dispatch."""
    state = {"first": True}

    def wrapper(*a, **kw):
        if state["first"]:
            state["first"] = False
            with obs.span("jit_compile", what=what, **attrs):
                return fn(*a, **kw)
        return fn(*a, **kw)

    return wrapper


@lru_cache(maxsize=None)
def _evolve_program(n_g, n_g_pad, n_b, n_b_pad, n_nodes, e_g, e_b,
                    tournament_k, crossover_prob, mut_prob, mut_frac,
                    mut_std):
    """One jitted EA step per (population split, EA hyperparameter)
    tuple.  ``jax.jit(partial(...))`` caches by the partial's identity,
    so the lru_cache makes repeated driver construction with the same
    config hand back the SAME callable — and with it the compiled
    executable."""
    base = partial(ea_mod.evolve, n_nodes=n_nodes, e_g=e_g, e_b=e_b,
                   n_g=n_g, n_b=n_b, tournament_k=tournament_k,
                   crossover_prob=crossover_prob, mut_prob=mut_prob,
                   mut_frac=mut_frac, mut_std=mut_std)
    return _compile_tracked(
        jax.jit(partial(_evolve_with_fitness_mask, base,
                        n_g, n_g_pad, n_b, n_b_pad)),
        "evolve_program", n_g=n_g, n_b=n_b, n_nodes=n_nodes,
        tournament_k=tournament_k)


class _EvoPopulation:
    """Shared population scaffolding for the per-graph ``EGRL`` and the
    multi-workload ``ZooEGRL``: the fixed-slot population split + elite
    formulas, stacked-genome init, sharded/padded placement, and the
    jitted evolve wiring.  Keeping this in ONE place means a fix to
    e.g. the padding discipline applies to both drivers.

    The subclass must set ``self.cfg``, ``self.mode``, ``self.key`` and
    ``self._template`` before calling ``_init_populations`` — note the
    PRNG contract: EGRL's template is the SAC actor (no key consumed),
    ZooEGRL draws one key for its template first.
    """

    def _k(self):
        self.key, k = jax.random.split(self.key)
        return k

    def _split_population(self):
        """Fixed encoding slots (see core/ea.py): n_b Boltzmann + n_g
        GNN genomes whose counts never change; elites split
        proportionally."""
        cfg = self.cfg
        if self.mode == "pg":
            self.n_g = self.n_b = 0
        else:
            self.n_b = max(1, int(round(cfg.pop_size * cfg.boltzmann_frac)))
            self.n_g = cfg.pop_size - self.n_b
        self.e_g = min(self.n_g, max(1, round(
            cfg.elites * self.n_g / max(cfg.pop_size, 1)))) if self.n_g else 0
        self.e_b = min(self.n_b, max(0, cfg.elites - self.e_g))

    def _init_populations(self, n_features: int, bz_nodes: int, pop_shards):
        """Stacked genome arrays (GNN: (n_g, V) flat params; Boltzmann:
        (n_b, F) flats over ``bz_nodes`` node slots), their placement —
        single device, or row-sharded over a ("pop",) mesh per the
        repro.distributed.population policy — and the jitted evolve
        call.  A shard count that does not divide a sub-population is
        handled by padding with masked rows: zero genomes whose fitness
        the evolve wrapper pins to -inf, invisible to the real-row
        trajectory."""
        cfg = self.cfg
        vec0 = gnn.flatten_params(self._template)
        self.gnn_pop = (jnp.stack([
            gnn.flatten_params(gnn.init_gnn(self._k(), n_features))
            for _ in range(self.n_g)]) if self.n_g
            else jnp.zeros((0, vec0.shape[0])))
        self.bz_pop = (jnp.stack([
            bz.to_flat(*bz.init_boltzmann(self._k(), bz_nodes))
            for _ in range(self.n_b)]) if self.n_b
            else jnp.zeros((0, bz.flat_size(bz_nodes))))

        self.pop_sharding = resolve_pop_sharding(
            self.n_g, self.n_b, pop_shards)
        self.n_g_pad, self.n_b_pad = self.pop_sharding.padded(
            self.n_g, self.n_b)
        self.gnn_pop = self.pop_sharding.put(
            _pad_rows(self.gnn_pop, self.n_g_pad))
        self.bz_pop = self.pop_sharding.put(
            _pad_rows(self.bz_pop, self.n_b_pad))

        if self.pop_sharding.active:
            # sharded paths stay per-instance: mesh/out_shardings are
            # instance state (see the module-level program comment)
            base_evolve = partial(
                ea_mod.evolve_sharded, self.pop_sharding.mesh,
                n_nodes=bz_nodes, e_g=self.e_g, e_b=self.e_b, n_g=self.n_g,
                n_b=self.n_b, tournament_k=cfg.tournament_k,
                crossover_prob=cfg.crossover_prob, mut_prob=cfg.mut_prob,
                mut_frac=cfg.mut_frac, mut_std=cfg.mut_std)
            self._evolve = jax.jit(partial(
                _evolve_with_fitness_mask, base_evolve,
                self.n_g, self.n_g_pad, self.n_b, self.n_b_pad))
            # PG migration: jitted flatten + row write into the last
            # REAL GNN slot, landing back in the population sharding (a
            # collective scatter, not a host copy).  Takes the actor
            # itself or its flat (V,) genome.  Shared by EGRL and
            # ZooEGRL — both learners' actors flatten to the same (V,)
            # genome encoding (GNN parameters are graph-size
            # independent).
            self._migrate = jax.jit(
                lambda pop, params: pop.at[self.n_g - 1].set(
                    gnn.flatten_params(params)),
                out_shardings=self.pop_sharding.sharding)
        else:
            self._evolve = _evolve_program(
                self.n_g, self.n_g_pad, self.n_b, self.n_b_pad,
                bz_nodes, self.e_g, self.e_b, cfg.tournament_k,
                cfg.crossover_prob, cfg.mut_prob, cfg.mut_frac,
                cfg.mut_std)
            self._migrate = lambda pop, params: _migrate_row(
                pop, params, idx=self.n_g - 1)

    # ------------------------------------------------------- warm start
    def _prior_logits(self, vec: jnp.ndarray) -> jnp.ndarray:
        """Posterior logits of the flat GNN params ``vec`` over this
        driver's Boltzmann node grid (subclass hook: (N, 2, 3) for the
        per-graph driver, the bucket-major (n_eff, 2, 3) grid for the
        zoo driver)."""
        raise NotImplementedError

    def prior_logits(self, vec) -> jnp.ndarray:
        """Public wrapper over the driver's Boltzmann-grid posterior
        logits for flat GNN params ``vec`` — the placement service
        blends these with neighbor-mapping one-hots before passing the
        result back through ``warm_start(logits=...)``."""
        return self._prior_logits(jnp.asarray(vec, jnp.float32))

    def warm_start(self, vec, *, gnn_frac: float = 0.5,
                   noise_std: float = 0.05, t_init: float = 0.5,
                   logits=None):
        """Seed the population from a trained policy's flat GNN params
        (zero-shot warm start — how the placement service turns its
        accumulated prior into a head start for each miss batch's
        refinement).  GNN row 0 becomes the prior EXACTLY (so one elite
        generation preserves it verbatim), the next ``gnn_frac`` of the
        sub-population noisy copies, the rest keep their random init
        for diversity; EVERY Boltzmann genome is re-seeded from the
        prior's posterior logits (Algorithm 2's GNN->Boltzmann seeding,
        applied at init time via ``bz.seed_from_logits``).  Draws from
        the driver's key stream, so warm-started trajectories are
        deterministic per (cfg.seed, call order); padded sharding rows
        stay untouched and the result is re-placed in the population
        sharding.

        ``logits`` (optional, the driver's Boltzmann node grid shape —
        see ``_prior_logits``) overrides the prior's posterior logits
        for the Boltzmann re-seeding: the placement service passes a
        blend of the GNN prior's logits and one-hot logits derived from
        a nearest-neighbor's committed MAPPING, so a near-identical
        graph's refinement starts from its neighbor's answer instead of
        the prior alone.  The GNN rows still seed from ``vec``."""
        vec = jnp.asarray(vec, jnp.float32)
        if self.n_g:
            n_seed = max(1, int(round(gnn_frac * self.n_g)))
            rows = [vec] + [
                vec + noise_std * jax.random.normal(self._k(), vec.shape)
                for _ in range(n_seed - 1)]
            self.gnn_pop = self.pop_sharding.put(jnp.concatenate(
                [jnp.stack(rows), self.gnn_pop[n_seed:]]))
        if self.n_b:
            logits = (self._prior_logits(vec) if logits is None
                      else jnp.asarray(logits, jnp.float32))
            seeds = [bz.seed_from_logits(logits, self._k(), t_init)
                     for _ in range(self.n_b)]
            rows = [bz.to_flat(b.prior, b.log_t) for b in seeds]
            self.bz_pop = self.pop_sharding.put(jnp.concatenate(
                [jnp.stack(rows), self.bz_pop[self.n_b:]]))


@dataclasses.dataclass
class EGRLConfig:
    pop_size: int = 20
    elites: int = 4
    boltzmann_frac: float = 0.2       # Table 2
    mut_prob: float = 0.9
    mut_frac: float = 0.1
    mut_std: float = 0.1
    crossover_prob: float = 0.7
    tournament_k: int = 3
    total_steps: int = 4000           # Table 2
    pg_rollouts: int = 1
    reward_scale: float = 5.0
    migrate_every: int = 1
    seed: int = 0
    sac: SACConfig = dataclasses.field(default_factory=SACConfig)


class EGRL(_EvoPopulation):
    def __init__(self, graph: WorkloadGraph, cfg: EGRLConfig = EGRLConfig(),
                 mode: str = "egrl", pop_shards=None):
        """``pop_shards`` overrides the REPRO_POP_SHARDS policy (int,
        "auto", or "off"); default: resolve from the environment."""
        assert mode in ("egrl", "ea", "pg")
        self.g = graph
        self.cfg = cfg
        self.mode = mode
        self.key = jax.random.PRNGKey(cfg.seed)

        self.feats = jnp.asarray(graph.features())
        self.adj = jnp.asarray(graph.adjacency())
        self.sg = build_sim_graph(graph)
        _, self.ref_latency = compiler_reference(graph)
        self.ref_latency = jnp.float32(self.ref_latency)

        self.learner = SACLearner(self.feats, self.adj, self._k(), cfg.sac)
        self.buffer = ReplayBuffer(graph.n, seed=cfg.seed)
        self._template = self.learner.actor

        # ---- stacked populations + placement + evolve (_EvoPopulation)
        self._split_population()
        self._init_populations(self.feats.shape[1], graph.n, pop_shards)

        # ---- vmapped population programs: bound module-level jits, so
        # a second EGRL on the same graph geometry reuses the compiled
        # executables; a sharded population runs its forward shard by
        # shard (``PopSharding.map_rows``), single rows on one device
        self._row_gnn_logits = partial(
            _POP_LOGITS, self._template, self.feats, self.adj)
        self._pop_gnn_logits = partial(
            self.pop_sharding.map_rows(gnn.population_logits)
            if self.pop_sharding.active else _POP_LOGITS,
            self._template, self.feats, self.adj)
        self._pop_sample = _SAMPLE_ACTIONS
        self._pop_boltz = _bz_sample_pop

        self.steps = 0
        self.best_reward = -np.inf
        self.best_mapping: Optional[np.ndarray] = None
        self.history: List[Dict] = []

    # --------------------------------------------------------- generation
    def generation(self) -> Dict:
        # span timing note: jax dispatch is async, so the rollout /
        # evolve child spans measure DISPATCH (+ compile on a first
        # call, split out as jit_compile by _compile_tracked); the
        # device waits land in the device_read spans (host_sync's
        # result reads, sac.read's loss reads), which instrumentation
        # neither adds nor moves.
        with obs.span("generation", driver="egrl", mode=self.mode) as sp:
            return self._generation(sp)

    def _generation(self, sp) -> Dict:
        cfg = self.cfg
        n_g, n_b = self.n_g, self.n_b

        # ---- rollouts: stacked device calls, nothing leaves the device.
        # Each part (GNN pop, Boltzmann pop, PG rollouts) is evaluated
        # separately: concatenating the pop-sharded population samples
        # with the single-device PG mappings would resolve the result to
        # fully-replicated and throw away the ("pop",) sharding, so the
        # per-part calls keep evaluation shard-local AND hand the EA its
        # fitness vectors without slicing a mixed array.  Per-mapping
        # math is row-independent, so the rewards are bitwise the same
        # as one fused call.
        parts, results = {}, {}
        # rows beyond these are masked padding slots (divisible sharding)
        real = {"g": n_g, "b": n_b}
        logits_g = None
        if n_g:
            with obs.span("rollout.gnn", rows=n_g):
                logits_g = self._pop_gnn_logits(self.gnn_pop)
                # keys are split with the REAL count (split(k, n) has
                # no prefix property) and repeated into the padding rows
                parts["g"] = self._pop_sample(_pad_keys(
                    jax.random.split(self._k(), n_g), self.n_g_pad),
                    logits_g)
        if n_b:
            with obs.span("rollout.boltzmann", rows=n_b):
                parts["b"] = self._pop_boltz(_pad_keys(
                    jax.random.split(self._k(), n_b), self.n_b_pad),
                    self.bz_pop)
        if self.mode != "ea":
            with obs.span("rollout.pg", rows=cfg.pg_rollouts):
                parts["pg"] = self.learner.explore_actions(cfg.pg_rollouts)
        with obs.span("evaluate", parts=len(parts)):
            for name, maps in parts.items():
                results[name] = evaluate_population(
                    self.sg, maps, self.ref_latency, cfg.reward_scale)

        # ---- EA step (Algorithm 2 lines 8-25), still on device
        if n_g or n_b:
            with obs.span("evolve"):
                empty = jnp.zeros((0,), jnp.float32)
                self.gnn_pop, self.bz_pop = self._evolve(
                    self._k(),
                    self.gnn_pop,
                    results["g"]["reward"] if n_g else empty,
                    self.bz_pop,
                    results["b"]["reward"] if n_b else empty,
                    logits_g if logits_g is not None
                    else jnp.zeros((0, self.g.n, 2, 3)))

        # ---- host sync: one blocking read per result array, for the
        # buffer + logging (padding rows are sliced away — they never
        # hit the buffer, the step count or the best-mapping tracking)
        def np_real(name, x):
            a = obs.device_read(np.asarray, x)
            return a[:real[name]] if name in real else a

        with obs.span("host_sync"):
            per_part = {n: np_real(n, results[n]["reward"])
                        for n in parts}
            rewards = np.concatenate(list(per_part.values()))
            maps_np = np.concatenate(
                [np_real(n, m) for n, m in parts.items()])
            valid = np.concatenate(
                [np_real(n, results[n]["valid"]) for n in parts])
        with obs.span("replay.insert"):
            self.buffer.add_batch(maps_np, rewards)
        with obs.span("bookkeeping"):
            self.steps += len(maps_np)
            gen_best = int(np.argmax(rewards))
            if rewards[gen_best] > self.best_reward:
                self.best_reward = float(rewards[gen_best])
                self.best_mapping = maps_np[gen_best].copy()

        # ---- PG updates: one gradient step per env step this generation
        info = {}
        if self.mode != "ea":
            info = self.learner.update(self.buffer, len(maps_np))
            # ---- migration: PG weights into the last GNN slot, the
            # lowest-ranked child (Algorithm 2's replace-weakest: in the
            # seed code fresh children carried -inf fitness, so argmin
            # always picked a child, never an elite).  When every GNN
            # slot is an elite (n_g == e_g) skip, preserving elitism.
            if self.mode == "egrl" and n_g > self.e_g:
                obs.counter("egrl.migrations").inc()
                with obs.span("migrate"):
                    self.gnn_pop = self._migrate(self.gnn_pop,
                                                 self.learner.actor)
        obs.gauge("egrl.replay_occupancy").set(len(self.buffer))

        rec = {
            "steps": self.steps,
            "gen_best_reward": float(rewards.max()),
            "gen_mean_reward": float(rewards.mean()),
            "best_reward": self.best_reward,
            "best_speedup": self.best_reward / cfg.reward_scale
            if self.best_reward > 0 else 0.0,
            "valid_frac": float(valid.mean()),
            **info,
        }
        # per-member-type attribution from the host copies the loop
        # already made — no extra device fetch
        sp.set(steps=self.steps, gen_best=rec["gen_best_reward"],
               gen_mean=rec["gen_mean_reward"], best=self.best_reward,
               valid_frac=rec["valid_frac"],
               **{f"best_{n}": float(v.max())
                  for n, v in per_part.items() if v.size})
        self.history.append(rec)
        return rec

    def train(self, total_steps: Optional[int] = None, log=None):
        total = total_steps or self.cfg.total_steps
        while self.steps < total:
            rec = self.generation()
            if log and len(self.history) % 10 == 1:
                log(f"[{self.mode}] steps {rec['steps']:5d} "
                    f"best speedup {rec['best_speedup']:.3f} "
                    f"valid {rec['valid_frac']:.2f}")
        return self.history

    # ----------------------------------------------------- deployment API
    def _prior_logits(self, vec):
        return self._row_gnn_logits(vec[None])[0]

    def best_policy_logits(self):
        """Logits of the top-ranked policy in the population (deployment):
        the best GNN, else the SAC actor, else the best Boltzmann prior
        (Boltzmann-only "ea" ablation — crashed in the seed code)."""
        if self.n_g:
            return self._prior_logits(jnp.asarray(self.best_gnn_vec()))
        if self.mode != "ea":
            return self.learner.policy_logits()
        return bz.boltzmann_logits(bz.from_flat(self.bz_pop[0], self.g.n))

    def best_gnn_vec(self) -> Optional[np.ndarray]:
        """Flat params of the best GNN (row 0 is the top elite after a
        generation; before any generation, an arbitrary init member)."""
        if self.n_g:
            return np.asarray(self.gnn_pop[0])
        return np.asarray(gnn.flatten_params(self.learner.actor))


class ZooEGRL(_EvoPopulation):
    """Multi-workload EGRL: one EA population trained against the whole
    workload zoo, every generation scored in one jitted device call PER
    SIZE BUCKET.

    The graphs are grouped into a ``BucketedZoo`` (PR 5,
    ``REPRO_ZOO_BUCKETS`` / the ``buckets`` argument): K GraphBatches,
    each padded only to its own (N_max_k, W_max_k), so small workloads
    no longer pay the biggest graph's scan length and ring width.
    Per-genome mappings are per-bucket (G_k, N_max_k, 2) stacks;
    ``evaluate_population_bucketed`` returns per-graph rewards (P, G)
    in ZOO order, folded into one fitness scalar per genome by
    ``fitness_agg``:

    - ``"mean"`` — average reward across the zoo (generalist);
    - ``"worst"`` — minimax: the weakest graph's reward, so evolution
      cannot trade one workload off against another.

    GNN genomes are the same (V,) flat parameter vectors as the
    per-graph ``EGRL`` (Graph U-Net weights are graph-size independent;
    the per-bucket forwards mask padding, see core.gnn), so populations
    transfer between per-graph and zoo training — and between bucketing
    policies.  Boltzmann genomes span the bucket-major padded node grid
    ``n_eff = sum_k(G_k * N_max_k)`` — one prior/temperature table per
    (graph, node) slot — reusing the flat encoding with ``n_nodes =
    n_eff``; for a single-bucket zoo this is exactly the flat G · N_max
    grid, and ALL single-bucket trajectories are bit-identical to the
    flat-GraphBatch path (per-bucket PRNG keys come from
    ``bucket_keys``, which consumes the caller's key unchanged at K=1).

    Modes mirror the per-graph driver: "egrl" (full hybrid — the
    ``ZooSAC`` learner contributes ``pg_rollouts`` zoo-wide exploration
    rows, trains from the per-zoo-index ``ReplayBank`` with one batched
    gradient step per rollout row, and migrates its actor into the last
    real GNN slot), "ea" (ablate PG — no learner, no bank) and "pg"
    (ablate EA).  Composes with the ("pop",) population sharding
    exactly like ``EGRL`` — every per-bucket call is still a pure vmap
    over the population axis, the EA step handles padded slots, and
    migration is a jitted row write with ``out_shardings`` pinned to
    the population sharding.

    A generation's rollout rows — the GNN part, the Boltzmann part and
    the PG rows, with their padding rows — are joined along the row axis
    (part order g, b, pg) and scored in ONE simulator call per bucket: a
    call's time follows its scan's sequential steps, not its rows, so
    one call per part would pay every scan once per part.  Each part's
    rows sit at a static offset of the joined stack; the fitness program
    and the host read slice them back out.  Under ("pop",) sharding the
    joined stack is padded to a multiple of the shard count, so it keeps
    the row sharding; the host drops those rows like the parts' own
    padding rows.
    """

    def __init__(self, graphs: Sequence[WorkloadGraph],
                 cfg: EGRLConfig = EGRLConfig(), mode: str = "ea",
                 fitness_agg: Optional[str] = None, pop_shards=None,
                 zoo: Optional[BucketedZoo] = None, buckets=None,
                 dispatch=None):
        """``zoo`` reuses a prebuilt ``BucketedZoo`` (or a flat
        ``GraphBatch``, wrapped as one bucket); ``buckets`` overrides
        the ``REPRO_ZOO_BUCKETS`` policy ("auto" / "off" / int /
        "autotune"); ``dispatch`` overrides ``REPRO_BUCKET_DISPATCH``
        ("auto" / "off" / "async" — see distributed/dispatch.py)."""
        assert mode in ("egrl", "ea", "pg")
        self.mode = mode
        self.cfg = cfg
        self.agg = env_policy("REPRO_FITNESS_AGG", choices=("mean", "worst"),
                              default="mean", override=fitness_agg)
        if isinstance(zoo, GraphBatch):
            zoo = BucketedZoo.from_batch(zoo)
        self.zoo = zoo if zoo is not None else build_bucketed_zoo(
            graphs, buckets)
        self.n_graphs = self.zoo.n_graphs
        self.n_nodes = self.zoo.real_sizes()       # per zoo graph
        self.n_eff = self.zoo.n_eff                # Boltzmann node grid
        self.key = jax.random.PRNGKey(cfg.seed)

        n_features = self.zoo.n_features
        if mode == "ea":
            # PRNG contract unchanged from the EA-only driver: the
            # template is the FIRST key draw, so EA-mode trajectories
            # stay bit-identical with the PG member disabled
            self.learner, self.bank = None, None
            self._template = gnn.init_gnn(self._k(), n_features)
        else:
            # mirror EGRL: the learner key is drawn first and the SAC
            # actor doubles as the population template
            self.learner = ZooSAC(self.zoo, self._k(), cfg.sac)
            self.bank = ReplayBank(self.zoo.node_slots, seed=cfg.seed)
            self._template = self.learner.actor
        # ---- stacked populations + placement + evolve (_EvoPopulation)
        self._split_population()
        self._init_populations(n_features, self.n_eff, pop_shards)

        # per-bucket population forwards: bound module-level jits, so a
        # single-bucket zoo traces exactly the flat path AND a second
        # ZooEGRL over the same bucket geometry (the placement service
        # builds one per miss batch on a canonical padding grid) reuses
        # the compiled executables; K buckets -> K cached entries per
        # geometry (K small and static, so retracing is bounded).
        # Single rows (warm-start priors) always run on one device.
        self._bucket_logits = [
            partial(_POP_LOGITS_ZOO, self._template, b.feats, b.adj,
                    b.node_mask, b.n_nodes)
            for b in self.zoo.buckets]
        # Boltzmann genomes sample ONE flat (n_eff, 2) grid, split into
        # per-bucket (G_k, N_max_k, 2) stacks by this bucket-major layout
        # (a single bucket reduces to the flat reshape)
        self._bz_layout = tuple((b.n_graphs, b.n_max)
                                for b in self.zoo.buckets)
        # what the EA step takes for a part this driver does not have
        self._no_fitness = jnp.zeros((0,), jnp.float32)
        self._no_grid = jnp.zeros((0, self.n_eff, 2, 3))

        # the generation's rollout rows, joined in part order: each
        # part's first row, row count in the joined stack and real rows
        # (the rest of a part are its padding rows); a sharded stack is
        # padded to a multiple of the shard count to keep its sharding
        rows = [("g", self.n_g_pad, self.n_g), ("b", self.n_b_pad, self.n_b),
                ("pg", cfg.pg_rollouts if mode != "ea" else 0,
                 cfg.pg_rollouts)]
        self._parts, off = {}, 0
        for name, n, real in rows:
            if n:
                self._parts[name] = (off, n, real)
                off += n
        self._fit_bounds = tuple((o, o + n) for o, n, _ in
                                 self._parts.values())
        self._merge_pad = 0
        self._merge = _MERGE_ROLLOUTS
        if self.pop_sharding.active:
            self._merge_pad = -off % self.pop_sharding.n_shards
            self._merge = jax.jit(_merge_rollouts, static_argnames=("pad",),
                                  out_shardings=self.pop_sharding.sharding)
        self._merged_rows = off + self._merge_pad

        # bucket-parallel dispatch (PR 10): place each bucket's pipeline
        # on its own device so generation wall time approaches the
        # slowest bucket, not the sum.  Mutually exclusive with the
        # ("pop",) sharding — sharded arrays already span every device.
        self.dispatch: Optional[BucketDispatcher] = None
        if not self.pop_sharding.active:
            d = BucketDispatcher(self.zoo, self._template, policy=dispatch)
            self.dispatch = d if d.active else None

        # wide-layout gate (PR 10, 2-D ("pop", "model") mesh): buckets
        # whose forward dominates the generation re-lay the population
        # rows over the flattened ("pop", "model") super-axis — a pure
        # row split over pop*model devices, so per-row results stay
        # bit-identical — while cheap buckets keep the replicated-over-
        # "model" layout (a re-layout costs a collective; only the big
        # buckets earn it back).  "Big" = within 2x of the costliest
        # bucket's G * N^2 forward proxy.
        if self.pop_sharding.active and self.pop_sharding.model_shards > 1:
            costs = [b.n_graphs * b.n_max ** 2 for b in self.zoo.buckets]
            top = max(costs)
            self._wide_bucket = tuple(c * 2 >= top for c in costs)
        else:
            self._wide_bucket = (False,) * self.zoo.n_buckets
        # a sharded population runs each bucket's forward shard by shard
        # in its bucket's row layout (per-instance programs, like the
        # sharded evolve)
        self._pop_logits = self._bucket_logits
        if self.pop_sharding.active:
            self._pop_logits = [
                partial(self.pop_sharding.map_rows(
                    gnn.population_logits_zoo, wide=w), self._template,
                    b.feats, b.adj, b.node_mask, b.n_nodes)
                for w, b in zip(self._wide_bucket, self.zoo.buckets)]

        self.steps = 0
        self.best_reward = np.full(self.n_graphs, -np.inf)
        self.best_mapping: List[Optional[np.ndarray]] = [None] * self.n_graphs
        self.best_fitness = -np.inf
        self.history: List[Dict] = []

    def generation(self) -> Dict:
        # same dispatch-vs-sync span semantics as EGRL.generation
        with obs.span("generation", driver="zoo", mode=self.mode) as sp:
            return self._generation(sp)

    def _draw_keys(self) -> Dict:
        """Every key this generation draws from the driver's stream, in
        one launch (``_generation_keys``); the serial, sharded and
        dispatch paths all take their keys from it."""
        self.key, keys = _generation_keys(
            self.key, n_g=self.n_g, n_g_pad=self.n_g_pad, n_b=self.n_b,
            n_b_pad=self.n_b_pad, n_buckets=self.zoo.n_buckets)
        return keys

    def _generation(self, sp) -> Dict:
        cfg = self.cfg
        n_g, n_b = self.n_g, self.n_b
        zoo = self.zoo
        # parts[name]: per-bucket tuple of (P_pad, G_k, N_max_k, 2)
        parts = {}
        grid = self._no_grid
        keys = {}
        dsp = self.dispatch
        if n_g:
            with obs.span("rollout.gnn", rows=n_g,
                          dispatch=dsp is not None):
                keys = self._draw_keys()
                if dsp is not None:
                    # per-bucket forwards and samples issued on their
                    # own devices (donated population replicas); logits
                    # and samples pulled back to the primary device for
                    # the EA step's seeding grid and the join of the
                    # parts' rows.  Same programs, same keys — bitwise
                    # the serial path's values.
                    logits_dev = dsp.forward(self.gnn_pop)
                    parts["g"] = tuple(dsp.pull(
                        dsp.sample(keys["g"], logits_dev)))
                    grid = _SEEDING_GRID(dsp.pull(logits_dev))
                else:
                    # 2-D mesh: dominant buckets take the wide row
                    # layout (rows over pop*model devices), the rest
                    # read the ("pop",)-sharded matrix as-is
                    wide_pop = (self.pop_sharding.put_wide(self.gnn_pop)
                                if any(self._wide_bucket) else None)
                    logits_g = [
                        f(wide_pop if self._wide_bucket[k]
                          else self.gnn_pop)
                        for k, f in enumerate(self._pop_logits)]
                    parts["g"], grid = _sample_gnn_rollouts(keys["g"],
                                                            logits_g)
        if n_b:
            with obs.span("rollout.boltzmann", rows=n_b):
                if not keys:
                    keys = self._draw_keys()
                parts["b"] = _sample_bz_rollouts(keys["b"], self.bz_pop,
                                                 self._bz_layout)
        if self.mode != "ea":
            with obs.span("rollout.pg", rows=cfg.pg_rollouts):
                parts["pg"] = self.learner.explore_actions(cfg.pg_rollouts)
        with obs.span("evaluate", parts=len(parts), rows=self._merged_rows,
                      buckets=zoo.n_buckets, dispatch=dsp is not None):
            # per-bucket (P_total, G_k, N_max_k, 2): every part's rows
            # (the dispatcher ships each bucket's stack to its device)
            merged = self._merge(tuple(parts.values()), pad=self._merge_pad)
            results = (dsp.evaluate(merged, cfg.reward_scale)
                       if dsp is not None else evaluate_population_bucketed(
                           zoo, merged, cfg.reward_scale))  # zoo order

        # ---- EA step on the aggregate fitness, still on device
        with obs.span("fitness"):
            fit, fit_all = _fitness(results["reward"], self._fit_bounds,
                                    self.agg)
            fit = dict(zip(self._parts, fit))
        if n_g or n_b:
            with obs.span("evolve"):
                self.gnn_pop, self.bz_pop = self._evolve(
                    keys["evolve"],
                    self.gnn_pop, fit.get("g", self._no_fitness),
                    self.bz_pop, fit.get("b", self._no_fitness), grid)

        # ---- host sync: one blocking read per result array (reward,
        # valid, fitness and one per bucket for the rollout rows), then
        # each part's real rows sliced out on the host, in part order
        def real_rows(a):
            return np.concatenate([a[o:o + r]
                                   for o, _, r in self._parts.values()])

        with obs.span("host_sync"):
            read = partial(obs.device_read, np.asarray)
            rewards = real_rows(read(results["reward"]))   # (P, G) zoo order
            valid = real_rows(read(results["valid"]))
            fit_np = read(fit_all)
            # per-bucket host copies of the rollout rows (real rows only)
            maps_np = [real_rows(read(m)) for m in merged]
            per_part_fit = {n: fit_np[o:o + r]
                            for n, (o, _, r) in self._parts.items()}
            fitness = np.concatenate(list(per_part_fit.values()))
        with obs.span("bookkeeping"):
            self.steps += rewards.size      # one env step per (genome, graph)
            # per-graph action stacks in the SAME part order as `rewards`
            # rows (g, b, pg) — graph gi's rows live at its (bucket, slot)
            acts_by_graph = [
                maps_np[zoo.graph_bucket[gi]][:, zoo.graph_slot[gi]]
                for gi in range(self.n_graphs)]
            for gi in range(self.n_graphs):
                b = int(np.argmax(rewards[:, gi]))
                if rewards[b, gi] > self.best_reward[gi]:
                    self.best_reward[gi] = float(rewards[b, gi])
                    self.best_mapping[gi] = acts_by_graph[gi][
                        b, :self.n_nodes[gi]].copy()
            self.best_fitness = max(self.best_fitness,
                                    float(fitness.max()))

        # ---- PG member: bank insert, one batched zoo-wide gradient
        # step per rollout row (the update scan consumes a per-bucket
        # (G_k, B) batch per step, so this matches EGRL's
        # one-step-per-env-step budget at the row level), then
        # migration into the last real GNN slot
        info = {}
        if self.mode != "ea":
            with obs.span("replay.insert"):
                for gi in range(self.n_graphs):
                    self.bank.add_graph(gi, acts_by_graph[gi],
                                        rewards[:, gi])
            info = self.learner.update(self.bank, len(rewards))
            if self.mode == "egrl" and n_g > self.e_g:
                obs.counter("egrl.migrations").inc()
                with obs.span("migrate"):
                    self.gnn_pop = self._migrate(self.gnn_pop,
                                                 self.learner.actor)
        if self.bank is not None:
            obs.gauge("egrl.replay_occupancy").set(len(self.bank))

        rec = {
            "steps": self.steps,
            "gen_best_fitness": float(fitness.max()),
            "gen_mean_fitness": float(fitness.mean()),
            "best_fitness": self.best_fitness,
            "valid_frac": float(valid.mean()),
            "best_reward_per_graph": {
                name: float(self.best_reward[i])
                for i, name in enumerate(zoo.names)},
            **info,
        }
        # per-member-type attribution from the already-synced host
        # copies (per_part_fit) — no extra device fetch
        sp.set(steps=self.steps, gen_best=rec["gen_best_fitness"],
               gen_mean=rec["gen_mean_fitness"], best=self.best_fitness,
               valid_frac=rec["valid_frac"],
               **{f"best_{n}": float(v.max())
                  for n, v in per_part_fit.items() if v.size})
        self.history.append(rec)
        return rec

    def train(self, total_steps: Optional[int] = None, log=None):
        total = total_steps or self.cfg.total_steps
        while self.steps < total:
            rec = self.generation()
            if log and len(self.history) % 10 == 1:
                log(f"[zoo/{self.agg}] steps {rec['steps']:6d} "
                    f"best fitness {rec['best_fitness']:.3f} "
                    f"valid {rec['valid_frac']:.2f}")
        return self.history

    def _prior_logits(self, vec):
        # bucket-major (n_eff, 2, 3) grid, matching the bz genome layout
        return jnp.concatenate(
            [f(vec[None]).reshape(1, -1, 2, 3)
             for f in self._bucket_logits], axis=1)[0]

    def best_gnn_vec(self) -> Optional[np.ndarray]:
        """Flat params of the best GNN after a generation (row 0); usable
        directly by the per-graph ``EGRL`` / ``evaluate_gnn_on`` and the
        batched ``evaluate_gnn_zoo``.  Falls back to the ZooSAC actor
        when there is no GNN sub-population ("pg" ablation)."""
        if self.n_g:
            return np.asarray(self.gnn_pop[0])
        if self.learner is not None:
            return np.asarray(gnn.flatten_params(self.learner.actor))
        return None


def evaluate_gnn_on(graph: WorkloadGraph, vec: np.ndarray,
                    n_features: int = None, samples: int = 8, seed: int = 0):
    """Zero-shot transfer (Fig 5): apply a trained GNN policy to another
    workload, report the best speedup over `samples` stochastic rollouts."""
    feats = jnp.asarray(graph.features())
    adj = jnp.asarray(graph.adjacency())
    template = gnn.init_gnn(jax.random.PRNGKey(0), feats.shape[1])
    params = gnn.unflatten_params(template, jnp.asarray(vec))
    logits = gnn.gnn_forward(params, feats, adj)
    keys = jax.random.split(jax.random.PRNGKey(seed), samples)
    acts = jax.vmap(lambda k: gnn.sample_actions(k, logits))(keys)
    acts = jnp.concatenate([acts, gnn.greedy_actions(logits)[None]], 0)
    sg = build_sim_graph(graph)
    _, ref = compiler_reference(graph)
    res = evaluate_population(sg, acts, jnp.float32(ref))
    return float(np.max(np.asarray(res["speedup"])))


def evaluate_gnn_zoo(graphs: Sequence[WorkloadGraph], vec: np.ndarray,
                     samples: int = 8, seed: int = 0,
                     batch=None):
    """Zero-shot transfer (Fig 5) over a whole workload zoo through the
    bucketed path: one masked zoo forward + one population evaluation
    PER SIZE BUCKET score ``samples`` stochastic rollouts (plus the
    greedy mapping) on every graph — each bucket padded only to its own
    N_max_k, so the sweep no longer pays the biggest graph's width for
    the small ones.  Returns {graph name: best speedup} in zoo order.
    Pass ``batch`` to reuse a prebuilt ``BucketedZoo`` (e.g. the one a
    ``ZooEGRL`` trained against) or a flat ``GraphBatch`` (wrapped as
    one bucket — the pre-bucketing behavior, bit-identical)."""
    if batch is None:
        zoo = build_bucketed_zoo(graphs)
    elif isinstance(batch, GraphBatch):
        zoo = BucketedZoo.from_batch(batch)
    else:
        zoo = batch
    template = gnn.init_gnn(jax.random.PRNGKey(0), zoo.n_features)
    params = gnn.unflatten_params(template, jnp.asarray(vec))
    logits = gnn.gnn_forward_bucketed(params, zoo.buckets)
    # the same seed keys roll every bucket (one stochastic policy
    # rollout = one sample index across the whole zoo, as the flat
    # path had it; K == 1 draws exactly the flat stream)
    keys = jax.random.split(jax.random.PRNGKey(seed), samples)
    acts = []
    for lg in logits:
        a = jax.vmap(lambda k: gnn.sample_actions(k, lg))(keys)
        acts.append(jnp.concatenate([a, gnn.greedy_actions(lg)[None]], 0))
    res = evaluate_population_bucketed(zoo, acts)      # (S+1, G) arrays
    best = np.asarray(res["speedup"]).max(axis=0)
    return {name: float(best[i]) for i, name in enumerate(zoo.names)}

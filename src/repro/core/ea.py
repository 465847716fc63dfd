"""Evolutionary operators over the mixed population (Algorithm 2),
device-resident and mesh-shardable: genomes live as stacked (P, ...)
arrays and one jitted ``evolve`` call runs tournament selection,
single-point crossover, GNN->Boltzmann prior seeding, and Gaussian
mutation for a whole generation — no per-child Python loop, no
host<->device ping-pong.

Fixed encoding slots (deviation from the seed's list-of-Individuals
implementation): the population holds ``n_g`` GNN genomes and ``n_b``
Boltzmann genomes whose counts never change.  Tournament selection runs
within each encoding; elites are split proportionally.  The paper's
cross-type information pathway (Figure 2 / Alg 2 lines 16-18) is kept:
a Boltzmann child that draws a GNN elite as its crossover mate is
re-seeded from that elite's posterior logits.  The seed code instead let
children change encoding (a GNN x Boltzmann cross produced a Boltzmann
child, drifting the mix over time); fixed slots pin the mix at
``boltzmann_frac`` so every array keeps a static shape and the whole
step stays inside one XLA program.

Boltzmann genomes travel through the EA as flat vectors
(see repro.core.boltzmann.to_flat / from_flat); the prior block and the
log-temperature block get their own mutation scales, matching the seed
operators.

Population sharding (PR 2).  ``_evolve_core`` is written so the SAME
math runs single-device or row-sharded over a 1-D ``("pop",)`` mesh
axis (``evolve_sharded``), bit-identically:

- *Global/replicated randomness*: every O(P)-sized random draw —
  tournament candidate indices, mate indices, crossover/mutation gate
  coins, the per-child PRNG keys — is derived from the generation key
  alone and computed identically on every shard (a few KiB of ints, not
  genome-sized), so the choice of shard count cannot change it.
- *Shard-local heavy work*: crossover blends, mutation noise and
  GNN->Boltzmann prior seeding — the O(P * V) work — run only for the
  population rows a shard owns, using that row's replicated per-child
  key.  ``vmap`` over per-child keys makes each row's computation
  independent of its neighbours, so computing a subset of rows is
  bit-identical to computing all of them.
- *Collectives*: fitness is ``all_gather``-ed (so ranking/top-k is a
  replicated argsort over the full (P,) vector); the small replicated
  fetches (elite genomes, elite posteriors — ``_gather_rows``)
  clip-gather local candidates, zero the rows the shard does not own,
  and ``psum`` — each output row is one genome plus exact IEEE zeros,
  so the gather is bitwise ``full[idx]``.  The population-length parent
  fetch (``_gather_to_slots``) is routed as a ``ppermute`` ring
  instead: each shard's (P/S, V) block visits every shard and child
  slots copy their parent row as the owning block passes, so the
  per-shard transient is O(P/S · V) (the earlier psum_scatter
  formulation materialized a population-length masked buffer per
  shard) and no float reduction is involved at all.  Both fetches
  require the query indices to be replicated.

Padded populations (PR 3): when the real sub-population sizes do not
divide the shard count, repro.distributed.population pads the stacked
arrays with masked rows.  ``n_g``/``n_b`` keep the REAL sizes: every
random draw is sized/bounded by them and the caller hands padding rows
``-inf`` fitness, so pads are never elites, parents or mates and the
real-row trajectory is bit-identical to the unpadded single-device run;
padding slots just receive throwaway children.

Invariants relied on by callers and tests:

- elites occupy the leading rows of each sub-population, sorted by
  fitness (row 0 = best) — ``egrl.best_gnn_vec`` and the PG-migration
  slot (last GNN row) depend on this layout;
- ``evolve_sharded(mesh_S, ...) == evolve(...)`` bitwise for any shard
  count S dividing both n_g and n_b (tests/test_ea_sharding.py);
- the single-device ``evolve`` consumes PRNG keys in the same order as
  the PR 1 implementation, so seeded trajectories are preserved.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core import boltzmann as bz

POP_AXIS = "pop"   # mesh axis name the population is sharded over


def tournament_indices(key, fitness: jnp.ndarray, n_picks: int,
                       k: int, n_pool: Optional[int] = None) -> jnp.ndarray:
    """(n_picks,) winner indices; each pick is the argmax-fitness of k
    uniform draws with replacement (Alg 2 tournament selection).
    ``n_pool`` restricts the draw to the first ``n_pool`` rows — the
    REAL rows of a padded population — and defaults to all of them, so
    the PRNG stream of an unpadded run is unchanged."""
    cands = jax.random.randint(key, (n_picks, k), 0,
                               n_pool or fitness.shape[0])
    return cands[jnp.arange(n_picks), jnp.argmax(fitness[cands], axis=1)]


def single_point_crossover(key, mate: jnp.ndarray,
                           child: jnp.ndarray) -> jnp.ndarray:
    """concat(mate[:pt], child[pt:]) for a uniform pt in [1, V)."""
    v = mate.shape[-1]
    pt = jax.random.randint(key, (), 1, v)
    return jnp.where(jnp.arange(v) < pt, mate, child)


def mutate_gnn(key, genome: jnp.ndarray, *, frac: float, std: float,
               super_prob: float = 0.05) -> jnp.ndarray:
    """Per-gene Gaussian noise scaled by |g|+0.05 on a `frac` subset;
    whole-genome super-mutation (10x std) with prob `super_prob`."""
    k1, k2, k3 = jax.random.split(key, 3)
    sd = jnp.where(jax.random.uniform(k1) < super_prob, std * 10.0, std)
    mask = jax.random.uniform(k2, genome.shape) < frac
    noise = jax.random.normal(k3, genome.shape) * sd * (jnp.abs(genome) + 0.05)
    return genome + noise * mask


def mutate_boltz(key, flat: jnp.ndarray, *, n_nodes: int,
                 frac: float) -> jnp.ndarray:
    """Seed operators on the flat encoding: prior noise 0.3, log_t noise
    0.2, both on a `3*frac` subset; log_t clipped to [-3, 2]."""
    n_prior = bz.prior_size(n_nodes)
    kp, kt, mp, mt = jax.random.split(key, 4)
    prior, log_t = flat[:n_prior], flat[n_prior:]
    prior = prior + (jax.random.normal(kp, prior.shape) * 0.3
                     * (jax.random.uniform(mp, prior.shape) < frac * 3))
    log_t = log_t + (jax.random.normal(kt, log_t.shape) * 0.2
                     * (jax.random.uniform(mt, log_t.shape) < frac * 3))
    return jnp.concatenate([prior, jnp.clip(log_t, -3.0, 2.0)])


# --------------------------------------------------- sharding primitives
def _all_gather(x: jnp.ndarray, axis_name: Optional[str]) -> jnp.ndarray:
    """Local shard -> full global vector (identity when unsharded)."""
    if axis_name is None:
        return x
    return jax.lax.all_gather(x, axis_name, tiled=True)


def _masked_rows(loc: jnp.ndarray, idx: jnp.ndarray,
                 axis_name: str) -> jnp.ndarray:
    """This shard's contribution to a global row gather: local candidates
    clip-gathered, rows the shard does not own zeroed.  ``idx`` holds
    global row indices and MUST be replicated (identical on every
    shard), else the psum/psum_scatter reductions below mix answers to
    different queries."""
    chunk = loc.shape[0]
    li = idx - jax.lax.axis_index(axis_name) * chunk
    own = (li >= 0) & (li < chunk)
    rows = loc[jnp.clip(li, 0, max(chunk - 1, 0))]
    mask = own.reshape(own.shape + (1,) * (rows.ndim - own.ndim))
    return jnp.where(mask, rows, jnp.zeros_like(rows))


def _gather_rows(loc: jnp.ndarray, idx: jnp.ndarray,
                 axis_name: Optional[str]) -> jnp.ndarray:
    """Rows of a row-sharded array at replicated *global* indices; the
    result is replicated.  Every output row is one genome plus exact
    IEEE zeros under the psum, so this is bitwise ``full[idx]``.  Used
    for the small gathers (elite genomes / elite posteriors)."""
    if axis_name is None:
        return loc[idx]
    return jax.lax.psum(_masked_rows(loc, idx, axis_name), axis_name)


def _gather_to_slots(loc: jnp.ndarray, idx: jnp.ndarray,
                     axis_name: Optional[str],
                     axis_size: int = 1) -> jnp.ndarray:
    """Distributed gather: ``idx`` is the replicated, population-length
    query list (one global row index per population slot); shard s
    receives rows ``idx[s*chunk:(s+1)*chunk]`` — the parents for the
    slots it owns.

    Routed as a ring: each shard's (chunk, V) block visits every shard
    via S-1 ``ppermute`` hops, and a shard copies the rows it asked for
    as the owning block passes by.  The per-shard transient is the
    visiting block + the output — O(P/S · V) — where the previous
    psum_scatter formulation materialized a population-length masked
    buffer, O(P · V), per shard.  (A static-shape ``all_to_all`` cannot
    go below O(P·V) here: tournament winners may collide, so one shard
    can own the parents of every child slot and each (src, dst) pair
    must budget a full chunk.)  Rows are pure copies — each output slot
    is written on exactly the hop where the owner's block visits — so
    the gather stays bitwise exact; no float reduction is involved at
    all (the psum path relied on IEEE ``x + 0 == x`` for the same
    guarantee).
    """
    if axis_name is None:
        return loc[idx]
    chunk = loc.shape[0]
    me = jax.lax.axis_index(axis_name)
    my_idx = jax.lax.dynamic_slice_in_dim(idx, me * chunk, chunk)
    out = jnp.zeros((chunk,) + loc.shape[1:], loc.dtype)
    block = loc
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    for hop in range(axis_size):
        owner = (me - hop) % axis_size      # whose rows are visiting
        li = my_idx - owner * chunk
        own = (li >= 0) & (li < chunk)
        rows = block[jnp.clip(li, 0, max(chunk - 1, 0))]
        mask = own.reshape(own.shape + (1,) * (rows.ndim - own.ndim))
        out = jnp.where(mask, rows, out)
        if hop < axis_size - 1:
            block = jax.lax.ppermute(block, axis_name, perm)
    return out


def _slot_ids(chunk: int, axis_name: Optional[str]) -> jnp.ndarray:
    """Global population-row indices owned by this shard, (chunk,)."""
    base = 0 if axis_name is None else jax.lax.axis_index(axis_name) * chunk
    return base + jnp.arange(chunk)


# ------------------------------------------------------------- EA kernel
def _evolve_core(key, g_loc, fit_g_loc, b_loc, fit_b_loc, logits_loc, *,
                 n_nodes: int, n_g: int, n_b: int, e_g: int, e_b: int,
                 tournament_k: int, crossover_prob: float, mut_prob: float,
                 mut_frac: float, mut_std: float,
                 n_g_pad: Optional[int] = None,
                 n_b_pad: Optional[int] = None,
                 axis_name: Optional[str] = None,
                 axis_size: int = 1
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One EA generation over (possibly shard-local) population rows.

    ``n_g``/``n_b`` are the GLOBAL *real* sub-population sizes;
    ``n_g_pad``/``n_b_pad`` (default: equal) are the global ROW counts
    when the arrays carry masked padding slots so a non-dividing
    population can still shard (repro.distributed.population).  The
    ``*_loc`` arrays hold this shard's contiguous row block (the whole
    population when ``axis_name is None``).  Every random draw is sized
    and bounded by the REAL counts and the caller feeds padding slots
    ``-inf`` fitness, so padded rows are never parents, mates or elites
    and the real-row trajectory is bit-identical to the unpadded run;
    padding slots receive throwaway children (same clipped-index trick
    the sharded path already used for elite slots).  See the module
    docstring for the replicated-randomness / shard-local-work split
    that makes the result independent of the shard count.
    """
    n_g_pad = n_g if n_g_pad is None else n_g_pad
    n_b_pad = n_b if n_b_pad is None else n_b_pad
    keys = jax.random.split(key, 12)
    ax = axis_name
    # one fitness ranking shared by elite retention AND cross-type
    # seeding, so elite rows and elite_logits can never desynchronize
    fit_g = _all_gather(fit_g_loc, ax) if n_g else fit_g_loc
    order_g = jnp.argsort(-fit_g) if n_g else None

    # ---- GNN slots: elites + tournament/crossover/mutation children
    new_g = g_loc
    if n_g:
        elites = _gather_rows(g_loc, order_g[:e_g], ax)       # (e_g, V)
        slots = _slot_ids(g_loc.shape[0], ax)                 # global rows
        n_child = n_g - e_g
        plain = ax is None and n_g_pad == n_g   # unpadded single device
        if n_child:
            # replicated draws — identical on every shard, sized by the
            # REAL population so padding cannot perturb the stream
            parent_idx = tournament_indices(
                keys[0], fit_g, n_child, tournament_k, n_pool=n_g)
            mate_idx = jax.random.randint(keys[1], (n_child,), 0, e_g)
            ck = jax.random.split(keys[2], n_child)
            gate_x = jax.random.uniform(keys[3], (n_child,)) < crossover_prob
            mk = jax.random.split(keys[4], n_child)
            gate_m = jax.random.uniform(keys[5], (n_child,)) < mut_prob
            # child construction: the plain path builds exactly the
            # n_child children (PR 1 shapes); sharded/padded builds one
            # row per owned slot — elite and padding slots compute a
            # throwaway child (uniform chunk shapes), discarded or dead
            # by the select below.  The per-child math is row-
            # independent and keyed by child index, so both layouts are
            # bitwise identical on real rows.  The parent query list is
            # replicated and population-length so the ring gather can
            # route each parent row to the shard that owns the child
            # slot.
            if plain:
                c = jnp.arange(n_child)
                parents = g_loc[parent_idx]                   # (n_child, V)
            else:
                c = jnp.clip(slots - e_g, 0, n_child - 1)
                c_all = jnp.clip(jnp.arange(n_g_pad) - e_g, 0, n_child - 1)
                parents = _gather_to_slots(
                    g_loc, parent_idx[c_all], ax, axis_size)  # (chunk, V)
            mates = elites[mate_idx[c]]
            crossed = jax.vmap(single_point_crossover)(ck[c], mates, parents)
            children = jnp.where(gate_x[c][:, None], crossed, parents)
            mutated = jax.vmap(lambda k_, g_: mutate_gnn(
                k_, g_, frac=mut_frac, std=mut_std))(mk[c], children)
            children = jnp.where(gate_m[c][:, None], mutated, children)
            new_g = (jnp.concatenate([elites, children]) if plain
                     else jnp.where((slots < e_g)[:, None],
                                    elites[jnp.clip(slots, 0, e_g - 1)],
                                    children))
        else:
            new_g = elites[jnp.clip(slots, 0, max(e_g - 1, 0))]

    # ---- Boltzmann slots: mates drawn from the global elite pool; a GNN
    # mate re-seeds the child from its posterior (Alg 2 lines 16-18)
    new_b = b_loc
    if n_b:
        fit_b = _all_gather(fit_b_loc, ax)
        order_b = jnp.argsort(-fit_b)
        elites_b = _gather_rows(b_loc, order_b[:e_b], ax) if e_b else b_loc[:0]
        slots = _slot_ids(b_loc.shape[0], ax)
        n_child = n_b - e_b
        plain = ax is None and n_b_pad == n_b
        if n_child:
            parent_idx = tournament_indices(
                keys[6], fit_b, n_child, tournament_k, n_pool=n_b)
            n_elite_pool = e_g + e_b if (n_g and e_g) else e_b
            if plain:
                c = jnp.arange(n_child)
                parents = b_loc[parent_idx]                   # (n_child, F)
            else:
                c = jnp.clip(slots - e_b, 0, n_child - 1)
                c_all = jnp.clip(jnp.arange(n_b_pad) - e_b, 0, n_child - 1)
                parents = _gather_to_slots(
                    b_loc, parent_idx[c_all], ax, axis_size)  # (chunk, F)
            children = parents
            if n_elite_pool:
                mate_idx = jax.random.randint(
                    keys[7], (n_child,), 0, n_elite_pool)
                ck = jax.random.split(keys[8], n_child)
                gate_x = (jax.random.uniform(keys[9], (n_child,))
                          < crossover_prob)
                if n_g and e_g:
                    elite_logits = _gather_rows(
                        logits_loc, order_g[:e_g], ax)        # (e_g, N, 2, 3)

                    def cross_one(k, mi, child):
                        ks, kc = jax.random.split(k)
                        seeded = bz.to_flat(*bz.seed_from_logits(
                            elite_logits[jnp.clip(mi, 0, e_g - 1)], ks))
                        bz_mate = (elites_b[jnp.clip(mi - e_g,
                                                     0, max(e_b - 1, 0))]
                                   if e_b else child)
                        crossed = single_point_crossover(kc, bz_mate, child)
                        return jnp.where(mi < e_g, seeded, crossed)
                else:
                    def cross_one(k, mi, child):
                        return single_point_crossover(k, elites_b[mi], child)
                crossed = jax.vmap(cross_one)(ck[c], mate_idx[c], parents)
                children = jnp.where(gate_x[c][:, None], crossed, parents)
            mk = jax.random.split(keys[10], n_child)
            gate_m = jax.random.uniform(keys[11], (n_child,)) < mut_prob
            mutated = jax.vmap(lambda k_, g_: mutate_boltz(
                k_, g_, n_nodes=n_nodes, frac=mut_frac))(mk[c], children)
            children = jnp.where(gate_m[c][:, None], mutated, children)
            if plain:
                new_b = (jnp.concatenate([elites_b, children])
                         if e_b else children)
            else:
                new_b = (jnp.where((slots < e_b)[:, None],
                                   elites_b[jnp.clip(slots, 0, e_b - 1)],
                                   children) if e_b else children)
        else:
            new_b = elites_b[jnp.clip(slots, 0, max(e_b - 1, 0))]

    return new_g, new_b


def evolve(key, gnn_pop, fit_g, bz_pop, fit_b, gnn_logits, *,
           n_nodes: int, e_g: int, e_b: int, tournament_k: int,
           crossover_prob: float, mut_prob: float, mut_frac: float,
           mut_std: float, n_g: Optional[int] = None,
           n_b: Optional[int] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One EA generation, entirely on device (single-device path).

    gnn_pop (n_g, V) flat GNN params; bz_pop (n_b, F) flat Boltzmann
    genomes; fit_* their fitnesses; gnn_logits (n_g, N, 2, 3) this
    generation's GNN posteriors (for cross-type seeding).  ``n_g`` /
    ``n_b`` give the REAL sub-population sizes when the arrays carry
    masked padding rows (fitness -inf, see
    repro.distributed.population); default: every row is real.  Returns
    the next (gnn_pop, bz_pop) with elites in the leading rows, sorted
    by fitness (row 0 = best); padding rows hold throwaway children.
    """
    return _evolve_core(
        key, gnn_pop, fit_g, bz_pop, fit_b, gnn_logits,
        n_nodes=n_nodes,
        n_g=gnn_pop.shape[0] if n_g is None else n_g,
        n_b=bz_pop.shape[0] if n_b is None else n_b,
        n_g_pad=gnn_pop.shape[0], n_b_pad=bz_pop.shape[0],
        e_g=e_g, e_b=e_b, tournament_k=tournament_k,
        crossover_prob=crossover_prob, mut_prob=mut_prob,
        mut_frac=mut_frac, mut_std=mut_std, axis_name=None)


def evolve_sharded(mesh, key, gnn_pop, fit_g, bz_pop, fit_b, gnn_logits, *,
                   n_nodes: int, e_g: int, e_b: int, tournament_k: int,
                   crossover_prob: float, mut_prob: float, mut_frac: float,
                   mut_std: float, n_g: Optional[int] = None,
                   n_b: Optional[int] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``evolve`` with the population row-sharded over mesh axis "pop".

    The populations, fitness vectors and logits are sharded on their
    leading axis; the key is replicated.  Both sub-population ROW
    counts (padding included) must divide the mesh's "pop" axis size
    (checked here — a ragged split would silently desynchronize
    `_slot_ids`); non-dividing REAL sizes are handled upstream by
    padding the populations (repro.distributed.population) and passing
    the real sizes via ``n_g``/``n_b``.  Bitwise equal to ``evolve`` on
    real rows for any valid shard count.
    """
    n_g_pad, n_b_pad = gnn_pop.shape[0], bz_pop.shape[0]
    n_shards = mesh.shape[POP_AXIS]
    if (n_g_pad % n_shards) or (n_b_pad % n_shards):
        raise ValueError(
            f"population rows (n_g={n_g_pad}, n_b={n_b_pad}) not "
            f"divisible by mesh '{POP_AXIS}' axis ({n_shards}); pad the "
            f"populations (repro.distributed.population does this for "
            f"you) or disable sharding (REPRO_POP_SHARDS=1)")
    pop = PartitionSpec(POP_AXIS)
    rep = PartitionSpec()
    fn = partial(_evolve_core, n_nodes=n_nodes,
                 n_g=n_g_pad if n_g is None else n_g,
                 n_b=n_b_pad if n_b is None else n_b,
                 n_g_pad=n_g_pad, n_b_pad=n_b_pad,
                 e_g=e_g, e_b=e_b, tournament_k=tournament_k,
                 crossover_prob=crossover_prob, mut_prob=mut_prob,
                 mut_frac=mut_frac, mut_std=mut_std, axis_name=POP_AXIS,
                 axis_size=n_shards)
    sharded = jax.shard_map(fn, mesh=mesh,
                            in_specs=(rep, pop, pop, pop, pop, pop),
                            out_specs=(pop, pop), check_vma=False)
    return sharded(key, gnn_pop, fit_g, bz_pop, fit_b, gnn_logits)

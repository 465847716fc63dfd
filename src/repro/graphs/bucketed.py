"""Size-bucketed zoo IR: K ``GraphBatch``es, each padded only to its own
bucket's ``(N_max_k, W_max_k)``, instead of one batch padded to the
zoo-wide maxima.

The flat ``GraphBatch`` pays the padding tax twice: every graph runs
``N_max`` rectify scan steps against the batch-wide ``W_max`` ring, and
every GNN forward/critic attention tensor is ``(N_max, N_max)`` — so a
57-node ResNet batched next to the 1043-node ``moe_transformer`` runs
~15x more scan work than it needs.  ``BucketedZoo`` groups graphs into
size classes and pads each class only to its own maxima; consumers
(memsim.batch, core.gnn, core.egrl, core.sac) run one jitted call per
bucket — K is small and static, so retracing is bounded by K — and
gather per-graph results back to zoo order through the stable
``graph_bucket``/``graph_slot`` index maps.

Bucketing policy (``REPRO_ZOO_BUCKETS`` env var, or the ``buckets``
argument of ``build_bucketed_zoo`` / ``ZooEGRL``; resolved fail-loud via
``repro.utils.envpolicy``):

- ``"auto"`` (default): geometric octave bands anchored at the largest
  graph — graph n lands in band ``floor(log2(n_max / n))``, so graphs
  within a factor of 2 of each other share a bucket and per-graph
  padding waste is < 50% by construction.  Anchoring at the max (not at
  ``floor(log2 n)``) keeps near-equal sizes (e.g. 1010 and 1043) in ONE
  bucket.
- an integer K: split ``[n_min, n_max]`` into K geometric intervals
  (``K=1`` == ``"off"``).  Empty buckets are dropped, so the effective
  count is <= K.
- ``"off"``: a single bucket — byte-identical arrays to the flat
  ``build_graph_batch`` path, which every single-bucket trajectory
  guarantee in the drivers rests on.

Assignment is a pure function of the (ordered) node counts and the
policy — deterministic across runs and processes.  Buckets are ordered
by ascending ``N_max_k``; within a bucket, graphs keep their zoo order,
so ``graph_slot`` is monotone per bucket.

PRNG discipline for per-bucket sampling (``bucket_keys``): a K==1 zoo
consumes the caller's key UNCHANGED, so single-bucket trajectories are
bit-identical to the flat-path ones; K>1 splits the key once per bucket.

``BucketedZoo`` is a registered pytree (buckets are the children, the
index maps are static metadata), so it can be passed straight into
jitted functions, though consumers normally jit per bucket.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.graphs.batch import GraphBatch, build_graph_batch
from repro.graphs.graph import WorkloadGraph
from repro.utils.envpolicy import env_policy


def resolve_bucket_policy(override: Union[str, int, None] = None
                          ) -> Union[str, int]:
    """``REPRO_ZOO_BUCKETS`` -> "auto" | "off" | "autotune" | int >= 1,
    fail-loud.  "autotune" picks K from a measured per-bucket time model
    (distributed/dispatch.py) and is resolved by ``build_bucketed_zoo``
    — it needs the graphs, not just their sizes."""
    return env_policy("REPRO_ZOO_BUCKETS",
                      choices=("auto", "off", "autotune"),
                      default="auto", override=override, int_ok=True)


def assign_buckets(sizes: Sequence[int],
                   policy: Union[str, int, None] = None) -> List[int]:
    """Bucket id per graph (ids dense, 0..K-1, ascending bucket size).

    Deterministic: a pure function of the node-count sequence and the
    resolved policy (see the module docstring for the band formulas).
    """
    policy = resolve_bucket_policy(policy)
    if policy == "autotune":
        raise ValueError(
            "REPRO_ZOO_BUCKETS=autotune needs the graphs (it measures "
            "per-bucket times) — call build_bucketed_zoo, which resolves "
            "autotune to a concrete K before assigning")
    n = len(sizes)
    assert n > 0, "empty zoo"
    if policy == "off" or policy == 1 or n == 1 or min(sizes) == max(sizes):
        return [0] * n
    top = max(sizes)
    if policy == "auto":
        # octave bands anchored at the largest graph; band 0 = largest
        bands = [int(math.floor(math.log2(top / s))) for s in sizes]
    else:
        k = int(policy)
        lo = min(sizes)
        span = math.log(top) - math.log(lo)
        bands = [min(k - 1, int(k * (math.log(top) - math.log(s)) / span))
                 for s in sizes]
    # drop empty bands, relabel ascending-size (band 0 holds the largest)
    remap = {b: i for i, b in enumerate(sorted(set(bands), reverse=True))}
    return [remap[b] for b in bands]


def bucket_keys(key: jnp.ndarray, n_buckets: int) -> List[jnp.ndarray]:
    """One PRNG key per bucket.  K == 1 returns the key UNCHANGED (not a
    split), so single-bucket consumers draw exactly the flat path's
    stream — the bit-identity contract of core/egrl.py and core/sac.py.
    """
    if n_buckets == 1:
        return [key]
    return list(jax.random.split(key, n_buckets))


def bucket_keys_batch(keys: jnp.ndarray, n_buckets: int) -> List[jnp.ndarray]:
    """``bucket_keys`` over a stacked (P, 2) key array: K arrays of
    (P, 2), the flat array itself when K == 1."""
    if n_buckets == 1:
        return [keys]
    split = jax.vmap(lambda k: jax.random.split(k, n_buckets))(keys)
    return [split[:, k] for k in range(n_buckets)]


@partial(jax.jit, static_argnames=("perm",))
def _gather_zoo(per_bucket, perm):
    """``BucketedZoo.gather_zoo``'s program: leaf by leaf, concatenate
    the buckets along the graph axis and take ``perm``."""
    idx = np.asarray(perm, np.int32)
    return jax.tree.map(
        lambda *xs: jnp.take(jnp.concatenate(xs, axis=-1), idx, axis=-1),
        *per_bucket)


@dataclasses.dataclass(frozen=True)
class BucketedZoo:
    """K per-size-class GraphBatches + zoo-order index maps."""
    buckets: Tuple[GraphBatch, ...]
    graph_bucket: Tuple[int, ...]   # zoo index -> bucket id
    graph_slot: Tuple[int, ...]     # zoo index -> row inside its bucket
    names: Tuple[str, ...]          # zoo order

    # ------------------------------------------------------- geometry
    @property
    def n_graphs(self) -> int:
        return len(self.names)

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def n_features(self) -> int:
        return self.buckets[0].n_features

    @property
    def bucket_sizes(self) -> Tuple[int, ...]:
        """Graph count G_k per bucket."""
        return tuple(b.n_graphs for b in self.buckets)

    @property
    def node_slots(self) -> Tuple[int, ...]:
        """Padded node width per ZOO graph: its bucket's N_max_k."""
        return tuple(self.buckets[b].n_max for b in self.graph_bucket)

    @property
    def n_eff(self) -> int:
        """Total padded node slots sum_k(G_k * N_max_k) — the Boltzmann
        genome grid, laid out bucket-major (bucket 0's graphs first)."""
        return sum(b.n_graphs * b.n_max for b in self.buckets)

    def real_sizes(self) -> Tuple[int, ...]:
        """Real node count per zoo graph (one host sync per bucket)."""
        per = [np.asarray(b.n_nodes) for b in self.buckets]
        return tuple(int(per[b][s]) for b, s in
                     zip(self.graph_bucket, self.graph_slot))

    def pad_waste_frac(self) -> float:
        """Fraction of padded node slots that are padding (the tax the
        bucketing removes; 0.0 = every slot is a real node)."""
        real = sum(float(np.asarray(b.n_nodes).sum()) for b in self.buckets)
        slots = sum(b.n_graphs * b.n_max for b in self.buckets)
        return 1.0 - real / slots

    # ---------------------------------------------- zoo-order round-trip
    def zoo_perm(self) -> np.ndarray:
        """(G,) int32: position of zoo graph i in the bucket-major
        concatenation (bucket 0's slots, then bucket 1's, ...)."""
        offs = np.concatenate(
            [[0], np.cumsum([b.n_graphs for b in self.buckets])])
        return np.asarray([offs[b] + s for b, s in
                           zip(self.graph_bucket, self.graph_slot)], np.int32)

    def gather_zoo(self, per_bucket: Sequence):
        """Per-bucket (..., G_k) arrays -> one (..., G) array in ZOO
        order; or per-bucket pytrees of such arrays (a dict of results)
        -> one pytree, every leaf gathered in the same program.  A
        concat + exact gather: values are bit-identical, and a
        single-bucket zoo reduces to an identity permutation.  The
        permutation is a constant of the jitted program, so a call is
        one launch and no upload."""
        return _gather_zoo(tuple(per_bucket),
                           tuple(int(i) for i in self.zoo_perm()))

    def split_zoo_mappings(self, maps: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
        """Flat zoo-order mappings (..., G, N_max, 2) -> per-bucket
        (..., G_k, N_max_k, 2) slices (the inverse of evaluating the
        same rows through the flat GraphBatch)."""
        out = []
        for k, b in enumerate(self.buckets):
            ids = [i for i in range(self.n_graphs)
                   if self.graph_bucket[i] == k]    # slot order == zoo order
            out.append(jnp.take(maps, jnp.asarray(ids, jnp.int32),
                                axis=-3)[..., :b.n_max, :])
        return tuple(out)

    @classmethod
    def from_batch(cls, gb: GraphBatch) -> "BucketedZoo":
        """Wrap an existing flat GraphBatch as a single-bucket zoo (the
        arrays are shared, not copied — K=1 consumers see the exact flat
        path)."""
        g = gb.n_graphs
        return cls(buckets=(gb,), graph_bucket=(0,) * g,
                   graph_slot=tuple(range(g)), names=gb.names)


jax.tree_util.register_dataclass(
    BucketedZoo, data_fields=["buckets"],
    meta_fields=["graph_bucket", "graph_slot", "names"])


def build_bucketed_zoo(graphs: Sequence[WorkloadGraph],
                       buckets: Union[str, int, None] = None) -> BucketedZoo:
    """Bucket ``graphs`` by node count (policy: ``buckets`` argument,
    else ``REPRO_ZOO_BUCKETS``) and build one GraphBatch per bucket,
    each padded only to its own (N_max_k, W_max_k).  The "autotune"
    policy measures a per-bucket time model first (lazy import — the
    dispatch module imports this one) and resolves to the K whose
    predicted makespan over the visible devices is smallest."""
    assert graphs, "empty zoo"
    policy = resolve_bucket_policy(buckets)
    if policy == "autotune":
        from repro.distributed.dispatch import autotune_bucket_k
        policy = autotune_bucket_k(graphs)
    assign = assign_buckets([g.n for g in graphs], policy)
    n_buckets = max(assign) + 1
    per_bucket = [[g for g, a in zip(graphs, assign) if a == k]
                  for k in range(n_buckets)]
    slots, counters = [], [0] * n_buckets
    for a in assign:
        slots.append(counters[a])
        counters[a] += 1
    return BucketedZoo(
        buckets=tuple(build_graph_batch(gs) for gs in per_bucket),
        graph_bucket=tuple(assign),
        graph_slot=tuple(slots),
        names=tuple(g.name for g in graphs))

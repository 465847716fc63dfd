"""Batched (multi-graph) simulator path: rectify / latency / evaluate a
mapping — or a whole stacked population of mappings — against every
workload in a ``GraphBatch`` in ONE jitted device call.

The batch axis is a plain ``vmap`` over the stacked, padded ``SimGraph``
(see ``repro.graphs.batch`` for the padding discipline); no masking is
needed inside the rectify scan because padding steps are IEEE
identities.  Every per-graph number this module produces is bit-exact
against the single-graph ``repro.memsim.simulator`` path and the numpy
oracle (``tests/test_graph_batch.py`` sweeps the whole zoo, a ragged
mixed-size batch, and garbage-filled padding slots).

``evaluate_population_zoo`` accepts ``(P, G, N_max, 2)`` mappings with a
possibly mesh-sharded leading population axis: per-mapping work is
row-independent, so a ``("pop",)`` NamedSharding partitions the call
shard-locally under auto-SPMD exactly like the single-graph
``evaluate_population`` (PR 2).

Bucketed path (PR 5): the ``*_bucketed`` functions run the SAME jitted
per-batch programs once per size bucket of a ``BucketedZoo`` — each
bucket pays only its own ``(N_max_k, W_max_k)`` scan cost instead of the
zoo-wide maxima — and gather per-graph scalars back to zoo order through
the zoo's index maps.  Per-graph numbers are bit-exact against the flat
``GraphBatch`` path AND the numpy oracle: the rectify scan's padding
steps are IEEE identities for ANY (N_max, W_max) >= the graph's own
sizes (a graph's ring pushes/pops touch the same credits in the same
order regardless of ring width), eps divides by the host-precomputed
``total_bytes``, and latency reduces left-to-right — so re-padding a
graph to its smaller bucket changes nothing bitwise
(tests/test_bucketed_zoo.py sweeps the whole zoo).

The zoo driver (``core.egrl.ZooEGRL``) joins a generation's population
parts (GNN, Boltzmann, PG rows) and scores them in ONE call per bucket:
a bucket's time is its scans' sequential steps, nearly whatever the row
count, so a call per part paid every scan once per part.  The counter
``memsim.scan_calls`` counts the per-bucket simulator programs this
path launches.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.graphs.batch import GraphBatch
from repro.graphs.bucketed import BucketedZoo
from repro.memsim.simulator import _rectify_scan, latency


def rectify_zoo(gb: GraphBatch, mappings: jnp.ndarray):
    """mappings (G, N_max, 2) int32 -> (rectified (G, N_max, 2), eps (G,)).

    Padding rows of the rectified output are forced to 0 (HBM) so the
    result is a pure function of the real nodes — garbage in padding
    slots of ``mappings`` can neither change eps nor leak out.
    """
    out, moved = jax.vmap(_rectify_scan)(gb.sim, mappings)
    eps = moved / jnp.maximum(gb.sim.total_bytes, 1.0)
    out = jnp.where(gb.node_mask[..., None] > 0, out, 0)
    return out, eps


def latency_zoo(gb: GraphBatch, mappings: jnp.ndarray) -> jnp.ndarray:
    """Masked roofline latency per graph: (G, N_max, 2) -> (G,)."""
    return jax.vmap(latency)(gb.sim, mappings, gb.node_mask)


@partial(jax.jit, static_argnames=("reward_scale",))
def evaluate_zoo(gb: GraphBatch, mapping: jnp.ndarray,
                 reward_scale: float = 5.0):
    """Algorithm-1 reward of one mapping per graph: (G, N_max, 2) ->
    dict of (G,) arrays (+ the rectified (G, N_max, 2) mappings)."""
    rect, eps = rectify_zoo(gb, mapping)
    lat = latency_zoo(gb, rect)
    valid = eps <= 0.0
    speedup = gb.ref_latency / lat
    reward = jnp.where(valid, reward_scale * speedup, -eps)
    return {"reward": reward, "eps": eps, "latency": lat,
            "speedup": jnp.where(valid, speedup, 0.0), "valid": valid,
            "rectified": rect}


@partial(jax.jit, static_argnames=("reward_scale",))
def evaluate_population_zoo(gb: GraphBatch, mappings: jnp.ndarray,
                            reward_scale: float = 5.0):
    """Zoo-wide population evaluation in one device call.

    mappings (P, G, N_max, 2) -> dict of (P, G) arrays.  The population
    axis may carry a ("pop",) NamedSharding — rows are independent, so
    the call partitions shard-locally under auto-SPMD.
    """
    return jax.vmap(lambda m: evaluate_zoo(gb, m, reward_scale))(mappings)


# ------------------------------------------------------- bucketed path
# the per-graph results gathered back to zoo order, in one program
SCALARS = ("reward", "eps", "latency", "speedup", "valid")


def rectify_bucketed(bz: BucketedZoo, mappings: Sequence[jnp.ndarray]):
    """Per-bucket mappings [(G_k, N_max_k, 2), ...] -> (per-bucket
    rectified tuple, eps (G,) in ZOO order)."""
    rects, epss = [], []
    for gb, m in zip(bz.buckets, mappings):
        rect, eps = rectify_zoo(gb, m)
        rects.append(rect)
        epss.append(eps)
    return tuple(rects), bz.gather_zoo(epss)


def latency_bucketed(bz: BucketedZoo,
                     mappings: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Masked roofline latency per graph, zoo order: [(G_k, N_max_k, 2),
    ...] -> (G,)."""
    return bz.gather_zoo([latency_zoo(gb, m)
                          for gb, m in zip(bz.buckets, mappings)])


def evaluate_bucketed(bz: BucketedZoo, mappings: Sequence[jnp.ndarray],
                      reward_scale: float = 5.0):
    """``evaluate_zoo`` per bucket: per-bucket (G_k, N_max_k, 2)
    mappings -> dict of (G,) zoo-order scalars + per-bucket
    ``rectified`` tuple."""
    per = [evaluate_zoo(gb, m, reward_scale)
           for gb, m in zip(bz.buckets, mappings)]
    out = bz.gather_zoo([{k: r[k] for k in SCALARS} for r in per])
    out["rectified"] = tuple(r["rectified"] for r in per)
    return out


def evaluate_population_bucketed(bz: BucketedZoo,
                                 mappings: Sequence[jnp.ndarray],
                                 reward_scale: float = 5.0):
    """Zoo-wide population evaluation, one jitted call PER BUCKET.

    mappings: per-bucket (P, G_k, N_max_k, 2) stacks -> dict of (P, G)
    zoo-order arrays (+ per-bucket ``rectified``).  Each bucket call is
    the cached ``evaluate_population_zoo`` executable for that bucket's
    shape (K executables total, K static), then one gather program for
    the five per-graph results; the population axis keeps any ("pop",)
    sharding — the gather permutes only the trailing graph axis.
    Scalars are bit-exact vs evaluating the same rows through the flat
    GraphBatch (see module docstring).  Each bucket call counts once in
    ``memsim.scan_calls``."""
    assert len(mappings) == bz.n_buckets, (len(mappings), bz.n_buckets)
    per = [evaluate_population_zoo(gb, m, reward_scale)
           for gb, m in zip(bz.buckets, mappings)]
    obs.counter("memsim.scan_calls").inc(len(per))
    out = bz.gather_zoo([{k: r[k] for k in SCALARS} for r in per])
    out["rectified"] = tuple(r["rectified"] for r in per)
    return out


def aggregate_rewards(rewards: jnp.ndarray, mode: str) -> jnp.ndarray:
    """Fold per-graph rewards (..., G) into one fitness scalar per row.

    ``mean``: average case across the zoo.  ``worst``: robust/minimax —
    the fitness is the weakest graph's reward, so evolution cannot trade
    one workload off against another.
    """
    if mode == "mean":
        return jnp.mean(rewards, axis=-1)
    if mode == "worst":
        return jnp.min(rewards, axis=-1)
    raise ValueError(f"unknown fitness aggregation {mode!r}; "
                     f"use 'mean' or 'worst'")

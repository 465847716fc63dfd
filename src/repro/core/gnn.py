"""Graph U-Net policy (Gao & Ji 2019) in pure JAX, per the paper's §3.2:
bidirectional graph convolutions + graph attention, hidden 128, depth 4,
4 attention heads; per-node output = two 3-way categorical sub-actions
(weight tier, activation tier).

The adjacency is dense (graphs are <=1k nodes), symmetrized + self-loops.
gPool keeps the top-k nodes by a learned score (static k per level), and
gUnpool scatters back with skip connections — the U-shape of the paper's
policy. All functions are shape-static per workload, so population forward
passes vmap over stacked parameter pytrees (one device call per size
bucket and generation, see core/egrl.py).

GAT backends: the attention+aggregate inner op of ``_gat`` has three
implementations selected by the ``backend`` argument (default: the
``REPRO_GAT_BACKEND`` env var, default "auto"), ALL differentiable —
training and inference share one dispatch:

- ``"chunked"`` — pure-XLA online-softmax scan over neighbor blocks
  with a recompute-in-backward ``custom_vjp``
  (repro.kernels.gat_mp.chunked); peak attention transient (N, C, H).
  The path CPU/GPU training actually uses.
- ``"pallas"`` — the fused VMEM-resident kernel pair in
  repro.kernels.gat_mp (forward emits softmax residuals, backward
  recomputes attention block-wise; wrapped in ``custom_vjp`` by
  ops.py).  Compiled for TPU (tests/test_tpu_compile.py compiles it
  for a described v5e, chip_smoke.py runs it on the chip);
  ``interpret`` mode elsewhere (slow — for parity testing only, see
  tests/test_gat_backend.py).
- ``"jnp"``  — dense (N, N, H) score materialization in plain jnp.
  Opt-in only (parity oracle / tiny graphs): no default path selects it.
- ``"auto"`` — measurement-driven: a one-time per-(N, D, H, dtype)
  micro-benchmark (core/gat_tune.py) times the non-materializing
  candidates fwd and fwd+bwd and caches the winner per process.  The
  ``gat`` section of benchmarks/BENCH_inner_loop.json records the same
  timings (``bench_gat``).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import gat_tune
from repro.utils.envpolicy import env_policy
from repro.utils.params import ParamDef, init_params

HIDDEN = 128
DEPTH = 4
HEADS = 4
N_SUB = 2    # weight / activation sub-actions
N_TIER = 3

GAT_BACKENDS = ("auto", "jnp", "chunked", "pallas")


def resolve_backend(backend: Optional[str] = None, *, n: Optional[int] = None,
                    d: int = HIDDEN, heads: int = HEADS,
                    dtype=jnp.float32) -> str:
    """Resolve a backend request to a concrete one ("jnp" | "chunked" |
    "pallas").  ``auto`` with a shape autotunes (core/gat_tune.py);
    without one it falls back to the platform's non-materializing
    default ("pallas" compiled on TPU, "chunked" elsewhere)."""
    b = env_policy("REPRO_GAT_BACKEND", choices=GAT_BACKENDS,
                   default="auto", override=backend)
    if b == "auto":
        if n is None:
            return "pallas" if jax.default_backend() == "tpu" else "chunked"
        return gat_tune.autotune(n, d, heads, dtype).backend
    return b


def _gat_defs(d_in, d_out, heads=HEADS):
    return {
        "w": ParamDef((d_in, d_out), (None, None), "scaled"),
        "a_src": ParamDef((heads, d_out // heads), (None, None), "scaled"),
        "a_dst": ParamDef((heads, d_out // heads), (None, None), "scaled"),
        "b": ParamDef((d_out,), (None,), "zeros"),
    }


def gnn_defs(n_features: int, hidden: int = HIDDEN):
    d = {
        "inp": ParamDef((n_features, hidden), (None, None), "scaled"),
        "pool1": ParamDef((hidden,), (None,), "scaled"),
        "pool2": ParamDef((hidden,), (None,), "scaled"),
        "out1": ParamDef((hidden, hidden), (None, None), "scaled"),
        "out_b1": ParamDef((hidden,), (None,), "zeros"),
        "out2": ParamDef((hidden, N_SUB * N_TIER), (None, None), "scaled"),
    }
    for i in range(DEPTH):
        d[f"gat{i}"] = _gat_defs(hidden, hidden)
    return d


def init_gnn(key, n_features: int):
    return init_params(gnn_defs(n_features), key)


def _gat(p, h, adj_mask, backend: Optional[str] = None):
    """Multi-head graph attention. h (N,D), adj_mask (N,N) bool."""
    N, D = h.shape
    hd = D // HEADS
    z = h @ p["w"]                                   # (N, D)
    zh = z.reshape(N, HEADS, hd)
    e_src = jnp.einsum("nhd,hd->nh", zh, p["a_src"])  # (N, H)
    e_dst = jnp.einsum("nhd,hd->nh", zh, p["a_dst"])
    b = resolve_backend(backend, n=N, d=D, dtype=z.dtype)
    if b == "pallas":
        # fused kernel pair: no dense (N, N, H) attention materialization
        from repro.kernels.gat_mp.ops import gat_mp
        out = gat_mp(z, e_src, e_dst, adj_mask.astype(z.dtype), heads=HEADS,
                     interpret=jax.default_backend() != "tpu")
    elif b == "chunked":
        # pure-XLA custom_vjp: (N, C, H) transients, recompute-in-backward
        from repro.kernels.gat_mp.ops import gat_mp_chunked
        out = gat_mp_chunked(z, e_src, e_dst, adj_mask.astype(z.dtype),
                             heads=HEADS,
                             chunk=gat_tune.chunk_for(N, D, HEADS, z.dtype))
    else:
        e = jax.nn.leaky_relu(e_src[:, None, :] + e_dst[None, :, :], 0.2)
        e = jnp.where(adj_mask[:, :, None], e, -1e30)  # (N, N, H)
        alpha = jax.nn.softmax(e, axis=1)             # attend over neighbors j
        out = jnp.einsum("njh,jhd->nhd", alpha, zh).reshape(N, D)
    return jax.nn.elu(out + p["b"]) + h               # residual


def _pool(score_w, h, adj, k):
    """gPool: keep top-k nodes by learned score. Returns (h_k, adj_k, idx)."""
    score = jnp.tanh(h @ score_w / (jnp.linalg.norm(score_w) + 1e-6))  # (N,)
    val, idx = jax.lax.top_k(score, k)
    h_k = h[idx] * val[:, None]                       # gate by score
    adj_k = adj[idx][:, idx]
    return h_k, adj_k, idx


def _unpool(h_small, idx, n, h_skip):
    out = jnp.zeros((n, h_small.shape[1]), h_small.dtype)
    out = out.at[idx].set(h_small)
    return out + h_skip


def gnn_forward(p, feats, adj, backend: Optional[str] = None):
    """feats (N,F), adj (N,N) row-normalized with self loops -> (N,2,3)."""
    N = feats.shape[0]
    mask = adj > 0
    k1, k2 = max(2, N // 2), max(2, N // 4)
    h = jnp.tanh(feats @ p["inp"])
    h = _gat(p["gat0"], h, mask, backend)             # level 0
    h1, a1, i1 = _pool(p["pool1"], h, adj, k1)        # down 1
    h1 = _gat(p["gat1"], h1, a1 > 0, backend)
    h2, a2, i2 = _pool(p["pool2"], h1, a1, k2)        # down 2 (bottleneck)
    h2 = _gat(p["gat2"], h2, a2 > 0, backend)
    h1u = _unpool(h2, i2, k1, h1)                     # up 1 (+skip)
    h1u = _gat(p["gat3"], h1u, a1 > 0, backend)
    hu = _unpool(h1u, i1, N, h)                       # up 2 (+skip)
    z = jax.nn.elu(hu @ p["out1"] + p["out_b1"])
    logits = (z @ p["out2"]).reshape(N, N_SUB, N_TIER)
    return logits


# ------------------------------------------------- padded multi-graph path
def _pool_masked(score_w, h, adj, live, k_shared, k_real):
    """gPool over a padded graph: top-``k_shared`` (static) slots by
    score with dead slots ranked -inf, then only the first ``k_real``
    (traced, the per-graph ``max(2, n // 2^level)``) kept live.

    Because dead slots score -inf, the first ``k_real`` selected slots
    are exactly the per-graph ``_pool`` selection (same scores, same
    index tie-break), so kept rows/gates match the unpadded forward;
    the remaining slots are zeroed and disconnected so they stay inert
    through the following GAT level.  Returns (h_k, adj_k, idx, keep).
    """
    score = jnp.tanh(h @ score_w / (jnp.linalg.norm(score_w) + 1e-6))
    score = jnp.where(live > 0, score, -jnp.inf)
    val, idx = jax.lax.top_k(score, k_shared)
    keep = ((jnp.arange(k_shared) < k_real) & jnp.isfinite(val)).astype(
        h.dtype)
    h_k = jnp.where(keep[:, None] > 0,
                    h[idx] * jnp.where(keep > 0, val, 0.0)[:, None], 0.0)
    adj_k = adj[idx][:, idx]
    adj_k = jnp.where((keep[:, None] * keep[None, :]) > 0, adj_k, 0.0)
    return h_k, adj_k, idx, keep


def gnn_forward_masked(p, feats, adj, node_mask, n, backend=None):
    """``gnn_forward`` over ONE padded graph: feats (N_max, F), adj
    (N_max, N_max) with padded rows self-loop-only, node_mask (N_max,)
    1.0 = real, n = real node count (traced).  Returns (N_max, 2, 3)
    logits with padding rows forced to 0.

    Pooling sizes are the per-graph ``max(2, n//2)`` / ``max(2, n//4)``
    emulated inside static ``N_max``-derived top-k shapes (see
    ``_pool_masked``), and every level re-masks its hidden rows, so real
    -node outputs are a function of the real subgraph only: garbage in
    padding slots cannot reach them (bitwise — the padding columns enter
    attention with exactly-zero weights).  Numerically the real rows
    match the unpadded ``gnn_forward`` to float tolerance, not bitwise:
    XLA regroups the attention-axis reductions with the padded length.
    """
    nmax = feats.shape[0]
    k1s, k2s = max(2, nmax // 2), max(2, nmax // 4)
    k1r, k2r = jnp.maximum(2, n // 2), jnp.maximum(2, n // 4)
    live = node_mask.astype(feats.dtype)
    h = jnp.tanh((feats * live[:, None]) @ p["inp"]) * live[:, None]
    h = _gat(p["gat0"], h, adj > 0, backend) * live[:, None]
    h1, a1, i1, keep1 = _pool_masked(p["pool1"], h, adj, live, k1s, k1r)
    h1 = _gat(p["gat1"], h1, a1 > 0, backend) * keep1[:, None]
    h2, a2, i2, keep2 = _pool_masked(p["pool2"], h1, a1, keep1, k2s, k2r)
    h2 = _gat(p["gat2"], h2, a2 > 0, backend) * keep2[:, None]
    h1u = _unpool(h2, i2, k1s, h1)
    h1u = _gat(p["gat3"], h1u, a1 > 0, backend) * keep1[:, None]
    hu = _unpool(h1u, i1, nmax, h)
    z = jax.nn.elu(hu @ p["out1"] + p["out_b1"])
    logits = (z @ p["out2"]).reshape(nmax, N_SUB, N_TIER)
    return jnp.where(live[:, None, None] > 0, logits, 0.0)


def gnn_forward_zoo(p, feats, adj, node_mask, n_nodes, backend=None):
    """Batched forward over a GraphBatch: feats (G, N_max, F) ->
    (G, N_max, 2, 3) logits, one vmapped call for the whole zoo."""
    return jax.vmap(lambda f, a, m, n: gnn_forward_masked(
        p, f, a, m, n, backend))(feats, adj, node_mask, n_nodes)


def population_logits_zoo(template, feats, adj, node_mask, n_nodes,
                          pop_matrix, backend=None):
    """Zoo-wide stacked-population forward: (P, V) flat params ->
    (P, G, N_max, 2, 3).  The leading axis is a pure vmap, so a
    ``("pop",)``-sharded ``pop_matrix`` runs shard by shard
    (``PopSharding.map_rows``): each device runs the forward only for
    the genome rows it owns, with no collectives; the graph axis and
    the ``template`` pytree are replicated."""
    return jax.vmap(lambda vec: gnn_forward_zoo(
        unflatten_params(template, vec), feats, adj, node_mask, n_nodes,
        backend))(pop_matrix)


def gnn_forward_bucketed(p, buckets, backend=None):
    """Zoo forward over a size-bucketed zoo: one ``gnn_forward_zoo``
    call per bucket — each padded only to its own N_max_k, so the dense
    attention work shrinks to bucket size.  ``buckets`` is any sequence
    of GraphBatch-shaped batches (e.g. ``BucketedZoo.buckets``); returns
    a tuple of (G_k, N_max_k, 2, 3) logits.  Under jit each bucket shape
    traces once — K executables total, K small and static."""
    return tuple(gnn_forward_zoo(p, b.feats, b.adj, b.node_mask, b.n_nodes,
                                 backend) for b in buckets)


def population_logits_bucketed(template, buckets, pop_matrix, backend=None):
    """Stacked-population forward per bucket: (P, V) flat params ->
    tuple of (P, G_k, N_max_k, 2, 3).  Each per-bucket call is the same
    pure vmap as ``population_logits_zoo``, so a ("pop",)-sharded
    ``pop_matrix`` still runs shard by shard —
    bucketing composes with population sharding bucket by bucket."""
    return tuple(population_logits_zoo(template, b.feats, b.adj, b.node_mask,
                                       b.n_nodes, pop_matrix, backend)
                 for b in buckets)


def greedy_actions(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (N, 2)


def sample_actions(key, logits):
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def log_prob(logits, actions):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(lp, actions[..., None], axis=-1)[..., 0].sum()


def entropy_masked(logits, node_mask):
    """Mean per-node entropy (Appendix D averages over nodes) over the
    REAL rows of one padded graph: logits (N_max, 2, 3), node_mask
    (N_max,) 1.0 = real.  Padding rows are excluded from both the sum
    and the divisor, so with no padding this is the plain mean."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    ent = -(jnp.exp(lp) * lp).sum(-1)                  # (N_max, N_SUB)
    live = node_mask.astype(ent.dtype)
    return (ent * live[:, None]).sum() / jnp.maximum(
        live.sum() * ent.shape[-1], 1.0)


# ------------------------------------------------------- flat param helpers
def flatten_params(p) -> jnp.ndarray:
    leaves = jax.tree.leaves(p)
    return jnp.concatenate([x.reshape(-1) for x in leaves])


def unflatten_params(template, vec):
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for x in leaves:
        n = math.prod(x.shape)
        out.append(vec[off:off + n].reshape(x.shape).astype(x.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)

"""Pallas TPU kernel pair for fused GAT message passing (the EGRL
policy's hot op), differentiable end-to-end via the ``jax.custom_vjp``
wrapper in ``ops.py``.

Forward: per destination-node block, compute masked attention scores
against ALL nodes, softmax over neighbors and aggregate — one
VMEM-resident fusion instead of four HBM round-trips (scores / mask /
softmax / matmul).  Flash-attention style, it also emits the per-row
softmax residuals (running max ``m`` and denominator ``l``) so the
backward never needs the ``(N, N, H)`` probability tensor.

Backward: a second kernel over the same destination-node grid that
recomputes each block's attention weights in VMEM from ``(m, l)`` and
accumulates grads w.r.t. ``z`` / ``e_src`` / ``e_dst`` (``adj`` is
non-differentiable).  The ``dz`` / ``de_dst`` outputs use a constant
block index, so the sequential TPU grid revisits one VMEM buffer and
accumulates across destination blocks.

TPU layout: every score tile is a 2-D ``(bn, N)`` array with N on the
128-wide lane axis, one per head in a static loop — a ``(bn, N, H)``
tile would pad H=4 to 128 lanes.  No value is reshaped across the lane
axis: each head's aggregate is one ``(bn, N) x (N, D)`` matmul over all
D columns of z, of which only the head's own ``hd`` columns are kept
(a lane-masked select), so the MXU work equals an ``hd``-wide matmul
padded to the MXU width.  ``e_dst`` arrives head-major ``(H, N)`` so
each head's row is a sublane slice, and ``de_dst`` leaves the same way.

Workload graphs are <= ~1k nodes, so the full (N, D) node-feature
tensor (~0.5 MB at N=1024, D=128) sits in VMEM; the grid tiles only the
destination nodes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _head_lanes(bn: int, d: int, heads: int, h: int):
    """(bn, D) mask of the lanes that belong to head ``h``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (bn, d), 1)
    return lane // (d // heads) == h


def _scores(e_src, e_dst_t, adj, h: int):
    """Head ``h``'s pre-activation and masked leaky-relu scores, (bn, N)."""
    pre = e_src[:, h:h + 1] + e_dst_t[h:h + 1, :]
    s = jnp.where(pre >= 0, pre, 0.2 * pre)
    return pre, jnp.where(adj > 0, s, NEG_INF)


def _fwd_kernel(z_ref, esrc_ref, edstt_ref, adj_ref, o_ref, m_ref, l_ref, *,
                heads: int):
    z = z_ref[...]                        # (N, D) all nodes
    e_dst_t = edstt_ref[...]              # (H, N)
    e_src = esrc_ref[...]                 # (bn, H) this block's nodes
    adj = adj_ref[...]                    # (bn, N)
    bn, D = o_ref.shape
    out = jnp.zeros((bn, D), jnp.float32)
    for h in range(heads):
        _, s = _scores(e_src, e_dst_t, adj, h)
        m = s.max(axis=1, keepdims=True)                # (bn, 1)
        p = jnp.exp(s - m)
        l = p.sum(axis=1, keepdims=True)                # (bn, 1)
        p = p / jnp.maximum(l, 1e-30)
        agg = jnp.dot(p, z, preferred_element_type=jnp.float32)  # (bn, D)
        out = jnp.where(_head_lanes(bn, D, heads, h), agg, out)
        m_ref[:, h:h + 1] = m.astype(m_ref.dtype)
        l_ref[:, h:h + 1] = l.astype(l_ref.dtype)
    o_ref[...] = out.astype(o_ref.dtype)


def gat_mp_pallas(z, e_src, e_dst, adj, *, heads: int, block: int = 128,
                  interpret: bool = True):
    """z (N, D); e_src/e_dst (N, H); adj (N, N) -> (aggregated (N, D),
    softmax residuals m (N, H), l (N, H)).

    N is padded to a multiple of `block` by the ops.py wrapper.
    """
    N, D = z.shape
    bn = min(block, N)
    assert N % bn == 0
    kern = functools.partial(_fwd_kernel, heads=heads)
    return pl.pallas_call(
        kern,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((N, D), lambda i: (0, 0)),
            pl.BlockSpec((bn, heads), lambda i: (i, 0)),
            pl.BlockSpec((heads, N), lambda i: (0, 0)),
            pl.BlockSpec((bn, N), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, heads), lambda i: (i, 0)),
            pl.BlockSpec((bn, heads), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), z.dtype),
            jax.ShapeDtypeStruct((N, heads), jnp.float32),
            jax.ShapeDtypeStruct((N, heads), jnp.float32),
        ],
        interpret=interpret,
    )(z, e_src, e_dst.T, adj)


def _bwd_kernel(z_ref, esrc_ref, edstt_ref, adj_ref, m_ref, l_ref, o_ref,
                g_ref, dz_ref, desrc_ref, dedstt_ref, *, heads: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        # dz / de_dst blocks revisit the same VMEM buffer every grid step
        dz_ref[...] = jnp.zeros_like(dz_ref)
        dedstt_ref[...] = jnp.zeros_like(dedstt_ref)

    z = z_ref[...]                        # (N, D)
    e_src = esrc_ref[...]                 # (bn, H)
    e_dst_t = edstt_ref[...]              # (H, N)
    adj = adj_ref[...]                    # (bn, N)
    m = m_ref[...]                        # (bn, H)
    l = jnp.maximum(l_ref[...], 1e-30)
    g = g_ref[...].astype(jnp.float32)    # (bn, D)
    go = g * o_ref[...].astype(jnp.float32)
    bn, D = g.shape
    for h in range(heads):
        pre, s = _scores(e_src, e_dst_t, adj, h)
        p = jnp.exp(s - m[:, h:h + 1]) / l[:, h:h + 1]  # alpha (bn, N)
        lanes = _head_lanes(bn, D, heads, h)
        gh = jnp.where(lanes, g, 0.0)                   # g_i, head h only
        # dz_j += sum_i alpha_ij g_i : (bn, N)^T x (bn, D) -> (N, D)
        dz_ref[...] += jax.lax.dot_general(
            p, gh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dz_ref.dtype)
        # dalpha_ij = g_i . z_j over head h's lanes : (bn, D) x (N, D)^T
        dalpha = jax.lax.dot_general(
            gh, z, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bn, N)
        drow = jnp.where(lanes, go, 0.0).sum(axis=1, keepdims=True)
        ds = p * (dalpha - drow)
        dpre = jnp.where(pre >= 0, ds, 0.2 * ds)
        dpre = jnp.where(adj > 0, dpre, 0.0)
        desrc_ref[:, h:h + 1] = dpre.sum(axis=1, keepdims=True).astype(
            desrc_ref.dtype)
        dedstt_ref[h:h + 1, :] += dpre.sum(axis=0, keepdims=True).astype(
            dedstt_ref.dtype)


def gat_mp_bwd_pallas(z, e_src, e_dst, adj, m, l, o, g, *, heads: int,
                      block: int = 128, interpret: bool = True):
    """Backward kernel: recompute attention block-wise from the (m, l)
    residuals and return (dz, de_src, de_dst).  Shapes as the forward;
    o/g are the forward output and its cotangent, both (N, D)."""
    N, D = z.shape
    bn = min(block, N)
    assert N % bn == 0
    kern = functools.partial(_bwd_kernel, heads=heads)
    dz, de_src, de_dst_t = pl.pallas_call(
        kern,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((N, D), lambda i: (0, 0)),
            pl.BlockSpec((bn, heads), lambda i: (i, 0)),
            pl.BlockSpec((heads, N), lambda i: (0, 0)),
            pl.BlockSpec((bn, N), lambda i: (i, 0)),
            pl.BlockSpec((bn, heads), lambda i: (i, 0)),
            pl.BlockSpec((bn, heads), lambda i: (i, 0)),
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((N, D), lambda i: (0, 0)),
            pl.BlockSpec((bn, heads), lambda i: (i, 0)),
            pl.BlockSpec((heads, N), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), z.dtype),
            jax.ShapeDtypeStruct((N, heads), e_src.dtype),
            jax.ShapeDtypeStruct((heads, N), e_dst.dtype),
        ],
        interpret=interpret,
    )(z, e_src, e_dst.T, adj, m, l, o, g)
    return dz, de_src, de_dst_t.T

"""Differentiable public wrappers for the fused GAT op.

Two ``jax.custom_vjp`` pairs share one contract (z (N, D), e_src/e_dst
(N, H), float adj (N, N) -> aggregated (N, D); grads w.r.t. z/e_src/
e_dst, ``adj`` non-diff):

- ``gat_mp`` — the Pallas kernel pair in ``gat_mp.py`` (forward emits
  per-row softmax residuals; backward recomputes attention block-wise in
  VMEM).  Compiled for TPU (tests/test_tpu_compile.py); interpret mode
  elsewhere (parity only).
- ``gat_mp_chunked`` — the pure-XLA online-softmax scan in
  ``chunked.py`` (recompute-in-backward), the training path CPU/GPU
  actually use.

Neither materializes an ``(N, N, H)`` attention tensor outside kernel
VMEM blocks; ``tests/test_gat_backend.py`` asserts both gradient parity
against ``jax.grad`` through the dense jnp path and the absence of the
dense intermediate from the default training jaxpr.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gat_mp.chunked import gat_chunked_bwd, gat_chunked_fwd
from repro.kernels.gat_mp.gat_mp import gat_mp_bwd_pallas, gat_mp_pallas


def _pad_graph(z, e_src, e_dst, adj, mult: int):
    """Pad N up to a multiple of ``mult``; padded rows get a self-loop so
    their softmax stays well-defined (their outputs are sliced off, and
    zero cotangents make their backward contributions exact zeros)."""
    N = z.shape[0]
    pad = (-N) % mult
    if not pad:
        return z, e_src, e_dst, adj
    z = jnp.pad(z, ((0, pad), (0, 0)))
    e_src = jnp.pad(e_src, ((0, pad), (0, 0)))
    e_dst = jnp.pad(e_dst, ((0, pad), (0, 0)))
    adj = jnp.pad(adj, ((0, pad), (0, pad)))
    adj = adj.at[jnp.arange(N, N + pad), jnp.arange(N, N + pad)].set(1.0)
    return z, e_src, e_dst, adj


# ------------------------------------------------------- fused Pallas pair
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused(heads, block, interpret, z, e_src, e_dst, adj):
    out, _, _ = _fused_call(heads, block, interpret, z, e_src, e_dst, adj)
    return out


def _fused_call(heads, block, interpret, z, e_src, e_dst, adj):
    N = z.shape[0]
    zp, ep, dp, ap = _pad_graph(z, e_src, e_dst, adj, block)
    o, m, l = gat_mp_pallas(zp, ep, dp, ap, heads=heads, block=block,
                            interpret=interpret)
    return o[:N], m[:N], l[:N]


def _fused_fwd(heads, block, interpret, z, e_src, e_dst, adj):
    out, m, l = _fused_call(heads, block, interpret, z, e_src, e_dst, adj)
    return out, (z, e_src, e_dst, adj, out, m, l)


def _fused_bwd(heads, block, interpret, res, g):
    z, e_src, e_dst, adj, out, m, l = res
    N = z.shape[0]
    pad = (-N) % block
    zp, ep, dp, ap = _pad_graph(z, e_src, e_dst, adj, block)
    # padded rows re-enter with exactly the residuals the forward kernel
    # computed for them (self-loop only: m = 0, l = 1), and zero
    # cotangents keep their contributions at exact zeros
    mp = jnp.pad(m, ((0, pad), (0, 0)))
    lp = jnp.pad(l, ((0, pad), (0, 0)), constant_values=1.0)
    op = jnp.pad(out, ((0, pad), (0, 0)))
    gp = jnp.pad(g, ((0, pad), (0, 0)))
    dz, des, ded = gat_mp_bwd_pallas(zp, ep, dp, ap, mp, lp, op, gp,
                                     heads=heads, block=block,
                                     interpret=interpret)
    return dz[:N], des[:N], ded[:N], jnp.zeros_like(adj)


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "block", "interpret"))
def gat_mp(z, e_src, e_dst, adj, *, heads: int, block: int = 128,
           interpret: bool = True):
    """Fused Pallas GAT attention, differentiable w.r.t. z/e_src/e_dst.

    z (N, D); e_src/e_dst (N, H); adj (N, N) float -> aggregated (N, D).
    """
    return _fused(heads, block, interpret, z, e_src, e_dst, adj)


# -------------------------------------------------- chunked pure-XLA pair
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunked(heads, chunk, z, e_src, e_dst, adj):
    out, _ = gat_chunked_fwd(z, e_src, e_dst, adj, heads=heads, chunk=chunk)
    return out


def _chunked_fwd(heads, chunk, z, e_src, e_dst, adj):
    out, lse = gat_chunked_fwd(z, e_src, e_dst, adj, heads=heads,
                               chunk=chunk)
    return out, (z, e_src, e_dst, adj, out, lse)


def _chunked_bwd(heads, chunk, res, g):
    z, e_src, e_dst, adj, out, lse = res
    dz, des, ded = gat_chunked_bwd(z, e_src, e_dst, adj, out, lse, g,
                                   heads=heads, chunk=chunk)
    return dz, des, ded, jnp.zeros_like(adj)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "chunk"))
def gat_mp_chunked(z, e_src, e_dst, adj, *, heads: int, chunk: int = 128):
    """Chunked pure-XLA GAT attention (online softmax over neighbor
    blocks, recompute-in-backward), differentiable w.r.t. z/e_src/e_dst.

    z (N, D); e_src/e_dst (N, H); adj (N, N) float -> aggregated (N, D).
    """
    return _chunked(heads, min(chunk, z.shape[0]), z, e_src, e_dst, adj)

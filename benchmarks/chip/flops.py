"""Operation and byte counts of one EGRL generation, from shapes alone.

Everything here is counted at the graphs' REAL node counts: the rows a
size bucket pads a graph with, and the block padding of the GAT kernel,
are not work.  Recomputation (the kernel backward re-deriving the
attention weights) is counted only where it is the algorithm's own
work, as in ``gat_bwd_work``'s softmax terms.

Counted: matrix products, the GAT attention (scores, softmax and
aggregation over all N x N pairs of the dense adjacency, which is what
the policy computes), and the top-k pooling scores.  Not counted:
elementwise activations, the Boltzmann and categorical sampling, the
rectify/latency scans and the EA step, which do no model arithmetic.

The policy widths are the paper's (section 3.2): hidden 128, 4 heads,
Graph U-Net depth 4 (GAT levels at n, n/2, n/4, n/2), two 3-way
sub-actions per node.  The double-Q critic is the input projection, two
GAT levels over the graph and two small heads.
"""
from __future__ import annotations

from typing import Callable, Iterable, List, Sequence, Tuple

HIDDEN = 128
HEADS = 4
N_ACT = 6          # two sub-actions x three tiers
F32 = 4            # bytes


def pool_sizes(n: int) -> Tuple[int, int]:
    """Node counts kept by the two gPool levels of an n-node graph."""
    return max(2, n // 2), max(2, n // 4)


def gnn_gat_levels(n: int) -> List[int]:
    """Node count of each of the policy's four GAT levels."""
    n1, n2 = pool_sizes(n)
    return [n, n1, n2, n1]


def gat_flops(n: int, d: int = HIDDEN, h: int = HEADS) -> float:
    """One multi-head GAT layer forward on n nodes (dense attention)."""
    proj = 2.0 * n * d * d + 2.0 * 2 * n * d          # z = hW; e_src, e_dst
    return proj + gat_attention_flops(n, d, h)


def gat_attention_flops(n: int, d: int = HIDDEN, h: int = HEADS) -> float:
    """Scores, softmax and aggregation over the n x n pairs: the work of
    the fused kernel's forward (6 elementwise ops per score: add,
    leaky-relu, mask, max, exp, sum; 1 normalisation; 2 per
    multiply-add of the aggregation)."""
    return 7.0 * n * n * h + 2.0 * n * n * d


def gnn_forward_flops(n: int, n_features: int, d: int = HIDDEN,
                      h: int = HEADS) -> float:
    """Graph U-Net policy forward on one n-node graph."""
    n1, _ = pool_sizes(n)
    total = 2.0 * n * n_features * d                   # input projection
    total += sum(gat_flops(m, d, h) for m in gnn_gat_levels(n))
    total += 2.0 * n * d + 2.0 * n1 * d                # pooling scores
    total += 2.0 * n * d * d + 2.0 * n * d * N_ACT     # output MLP
    return total


def critic_forward_flops(n: int, n_features: int, d: int = HIDDEN,
                         h: int = HEADS) -> float:
    """Double-Q critic forward on one n-node graph and one action."""
    total = 2.0 * n * (n_features + N_ACT) * d
    total += 2 * gat_flops(n, d, h)
    total += 2 * (2.0 * d * d + 2.0 * d)               # two Q heads
    return total


def sac_step_flops(sizes: Sequence[int], n_features: int, batch: int,
                   d: int = HIDDEN, h: int = HEADS) -> float:
    """One zoo-wide SAC gradient step: the critic loss over ``batch``
    actions of every graph and the actor loss through the critic, each
    forward and backward (backward = 2 x forward)."""
    critic = sum(batch * critic_forward_flops(n, n_features, d, h)
                 for n in sizes)
    actor = sum(gnn_forward_flops(n, n_features, d, h)
                + critic_forward_flops(n, n_features, d, h) for n in sizes)
    return 3.0 * (critic + actor)


def generation_flops(sizes: Sequence[int], n_features: int, *,
                     gnn_rows: int, pg_rows: int, sac_steps: int,
                     batch: int, d: int = HIDDEN, h: int = HEADS) -> float:
    """Model FLOPs of one generation: the policy forward of every real
    GNN genome row and PG rollout row on every graph, plus ``sac_steps``
    SAC gradient steps (0 in EA-only mode)."""
    fwd = sum(gnn_forward_flops(n, n_features, d, h) for n in sizes)
    total = (gnn_rows + pg_rows) * fwd
    if sac_steps:
        total += sac_steps * sac_step_flops(sizes, n_features, batch, d, h)
    return total


# ------------------------------------------------------ GAT kernel work
def gat_fwd_work(n: int, d: int = HIDDEN, h: int = HEADS
                 ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the fused GAT forward on n real nodes: reads
    the dense f32 adjacency, z, e_src, e_dst; writes the aggregate and
    the per-row softmax residuals (m, l)."""
    flops = gat_attention_flops(n, d, h)
    nbytes = F32 * (n * n + n * d + 2 * n * h + n * d + 2 * n * h)
    return flops, nbytes


def gat_bwd_work(n: int, d: int = HIDDEN, h: int = HEADS
                 ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the fused GAT backward on n real nodes:
    recomputes the attention weights (7 ops per score), forms dz = P^T g
    and dP = g z^T (2 x 2 n^2 d) and the score gradients (4 ops per
    score); reads adj, z, e_src, e_dst, m, l, out, g; writes dz, de_src,
    de_dst."""
    flops = 7.0 * n * n * h + 4.0 * n * n * d + 4.0 * n * n * h
    nbytes = F32 * (n * n + 3 * n * d + 4 * n * h        # reads
                    + n * d + 2 * n * h)                  # writes
    return flops, nbytes


def gat_kernel_calls(buckets: Iterable[Tuple[int, Sequence[int]]], *,
                     gnn_rows: int, pg_rows: int, sac_steps: int,
                     batch: int, backend_of: Callable[[int], str]
                     ) -> List[Tuple[int, str, int]]:
    """GAT calls of one generation that run on the fused kernel pair, as
    (real node count, "fwd" | "bwd", number of calls).

    ``buckets`` lists (padded node count, real node counts of the
    bucket's graphs).  A GAT level runs at its bucket's padded size and
    ``backend_of(padded size)`` says which backend the program chose for
    that shape; only "pallas" levels are counted, each at the graph's
    real size, since padding is not work."""
    calls: dict = {}

    def add(n, kind, count):
        if count:
            calls[(n, kind)] = calls.get((n, kind), 0) + count

    for n_pad, reals in buckets:
        pad_levels = gnn_gat_levels(n_pad)
        for n in reals:
            for pad, real in zip(pad_levels, gnn_gat_levels(n)):
                if backend_of(pad) != "pallas":
                    continue
                # population + PG rollout forwards; the actor loss of
                # every SAC step (forward and backward)
                add(real, "fwd", gnn_rows + pg_rows + sac_steps)
                add(real, "bwd", sac_steps)
            if backend_of(n_pad) == "pallas" and sac_steps:
                # critic: two GAT levels at the full graph, over the
                # batch (critic loss) and once more in the actor loss
                add(n, "fwd", 2 * sac_steps * (batch + 1))
                add(n, "bwd", 2 * sac_steps * (batch + 1))
    return [(n, kind, c) for (n, kind), c in sorted(calls.items())]


def kernel_least_time_s(calls: Iterable[Tuple[int, str, int]],
                        peak_flops: float, peak_bytes_per_s: float,
                        d: int = HIDDEN, h: int = HEADS
                        ) -> Tuple[float, float, float]:
    """(least seconds, FLOPs, bytes) of a list of kernel calls: per call
    the larger of FLOPs over peak and bytes over bandwidth, summed."""
    least = flops = nbytes = 0.0
    for n, kind, count in calls:
        f, b = (gat_fwd_work if kind == "fwd" else gat_bwd_work)(n, d, h)
        least += count * max(f / peak_flops, b / peak_bytes_per_s)
        flops += count * f
        nbytes += count * b
    return least, flops, nbytes

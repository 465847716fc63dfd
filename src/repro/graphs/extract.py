"""Extract a placement WorkloadGraph from any assigned architecture config
at a given run shape — the bridge that makes the paper's technique a
first-class framework feature (--arch x --shape => EGRL placement plan).

Semantics per shape kind:
- train / prefill: one forward over (B, S) tokens; activations are
  (B, S, ...) tensors.
- decode: one token step; activations are (B, 1, ...) but each attention
  layer gains a KV-CACHE node — a large placeable tensor read in full every
  step (the dominant decode placement decision).  A windowed layer's
  cache holds its last ``window`` tokens, a full layer's all S.

Each layer's attention takes its own kind (``ModelConfig.attn_kind``):
query and kv heads, q/k and v widths, window, sink logits.  Weight-tied
blocks (zamba2 shared attention) carry their bytes on the first
application only.  See docs/architecture.md, "Graph extraction".

What one chip holds comes in two ways:
- a configuration that states a ``Deployment`` for the shape: the nodes
  are built at the chip's own head, kv-head, width, expert and vocabulary
  counts, for the sequences it serves, and a graph is one pipeline
  stage.
- otherwise the uniform SPMD divisor of ``extract_graph``'s mesh
  arguments over the whole model.

Either way the rules are one set: FLOPs are the chip's, for the
sequences it serves; an MoE layer's bank holds the chip's experts, and a
step streams each of them with the chance ``1 - (1 - top_k/E)^T`` that
one of the T tokens its expert group routes reaches it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

from repro import obs
from repro.configs.base import AttnKind, Deployment, ModelConfig, ShapeCfg
from repro.graphs.graph import Node, WorkloadGraph


@dataclasses.dataclass(frozen=True)
class _Share:
    """What the built nodes hold of each layer.  ``tp`` divides query
    heads, kv-head groups, dense FFN width and vocabulary, ``ep`` routed
    experts; ``batch`` is the sequences on the nodes, whose FLOPs they
    count; ``expert_tokens`` the tokens a step routes over the experts'
    group, which reach the held experts."""
    batch: int
    expert_tokens: int
    tp: int = 1
    ep: int = 1

    def heads(self, kind: AttnKind) -> Tuple[int, int]:
        """(query heads, kv heads) held: kv heads are replicated where
        the tensor-parallel ways exceed them."""
        if kind.n_heads % self.tp:
            raise ValueError(f"{kind.n_heads} query heads over tp={self.tp}")
        if kind.n_kv_heads >= self.tp and kind.n_kv_heads % self.tp:
            raise ValueError(f"{kind.n_kv_heads} kv heads over tp={self.tp}")
        return kind.n_heads // self.tp, math.ceil(kind.n_kv_heads / self.tp)


def _part(n: int, ways: int) -> int:
    """One of ``ways`` equal parts of ``n``."""
    if n % ways:
        raise ValueError(f"{n} does not divide {ways} ways")
    return n // ways


class _B:
    def __init__(self):
        self.nodes: List[Node] = []
        self.edges: List[Tuple[int, int]] = []
        self.window_caches: List[int] = []

    def add(self, node: Node, srcs) -> int:
        """Append ``node`` fed by ``srcs`` (None: from outside the graph)."""
        i = len(self.nodes)
        self.nodes.append(node)
        for s in srcs:
            if s is not None:
                self.edges.append((s, i))
        return i


def _attn_nodes(b: _B, cfg: ModelConfig, sh: _Share, kind: AttnKind, i: int,
                S: int, decode: bool, tied_bytes: bool = True):
    D, qk, v = cfg.d_model, kind.head_dim, kind.v_head_dim
    H, K = sh.heads(kind)
    B = sh.batch
    L = min(S, kind.window) if kind.window else S
    width = H * qk + K * (qk + v)
    wq = 2.0 * D * width + (2.0 * H if kind.sink else 0.0) \
        if tied_bytes else 0.0
    wo = 2.0 * H * v * D if tied_bytes else 0.0
    s_eff = 1 if decode else S
    i = b.add(Node(op="qkv", weight_bytes=wq, ifm=(s_eff, 1, D),
                   ofm=(s_eff, 1, width),
                   flops=2.0 * B * s_eff * D * width, batch=B), [i])
    if decode:
        kvb = 2.0 * B * L * K * (qk + v)
        i = b.add(Node(op="kv_cache", weight_bytes=kvb, ifm=(L, 1, K * qk),
                       ofm=(1, 1, H * v), flops=2.0 * B * L * H * (qk + v),
                       batch=B), [i])
        if L < S:
            b.window_caches.append(i)
    else:
        i = b.add(Node(op="attn", ifm=(S, 1, H * qk), ofm=(S, 1, H * v),
                       flops=2.0 * B * S * L * H * (qk + v),
                       batch=B), [i])
    i = b.add(Node(op="o_proj", weight_bytes=wo, ifm=(s_eff, 1, H * v),
                   ofm=(s_eff, 1, D), flops=2.0 * B * s_eff * H * v * D,
                   batch=B), [i])
    return i


def _mlp_nodes(b: _B, cfg: ModelConfig, sh: _Share, i: int, S: int,
               decode: bool, tied_bytes: bool = True):
    D, F = cfg.d_model, _part(cfg.d_ff, sh.tp)
    B = sh.batch
    s_eff = 1 if decode else S
    i = b.add(Node(op="mlp", weight_bytes=2.0 * 2 * D * F if tied_bytes
                   else 0.0, ifm=(s_eff, 1, D), ofm=(s_eff, 1, F),
                   flops=4.0 * B * s_eff * D * F, batch=B), [i])
    i = b.add(Node(op="mlp", weight_bytes=2.0 * F * D if tied_bytes else 0.0,
                   ifm=(s_eff, 1, F), ofm=(s_eff, 1, D),
                   flops=2.0 * B * s_eff * F * D, batch=B), [i])
    return i


def _moe_nodes(b: _B, cfg: ModelConfig, sh: _Share, i: int, S: int,
               decode: bool):
    m = cfg.moe
    D, Fe, E, k = cfg.d_model, m.d_ff_expert, m.n_experts, m.top_k
    E_held = _part(E, sh.ep)
    B = sh.batch
    s_eff = 1 if decode else S
    # the router keeps its published width: every chip scores all E
    i = b.add(Node(op="moe_router", weight_bytes=2.0 * D * E,
                   ifm=(s_eff, 1, D), ofm=(s_eff, 1, E),
                   flops=2.0 * B * s_eff * D * E, batch=B), [i])
    tokens = sh.expert_tokens
    access = 1.0 - (1.0 - k / E) ** tokens
    # token-expert pairs that land on the held experts
    routed = tokens * k * E_held / E
    i = b.add(Node(op="expert_bank", weight_bytes=2.0 * E_held * 3 * D * Fe,
                   ifm=(s_eff, 1, D), ofm=(s_eff, 1, D),
                   flops=6.0 * D * Fe * routed, batch=B,
                   weight_access_frac=access, groups=E_held), [i])
    if m.shared_expert_ff:
        Fs = _part(m.shared_expert_ff, sh.tp)
        i = b.add(Node(op="mlp", weight_bytes=2.0 * 3 * D * Fs,
                       ifm=(s_eff, 1, D), ofm=(s_eff, 1, D),
                       flops=6.0 * B * s_eff * D * Fs, batch=B), [i])
    return i


def _ssm_nodes(b: _B, cfg: ModelConfig, i: int, S: int, B: int, decode: bool,
               tied_bytes: bool = True):
    s = cfg.ssm
    D = cfg.d_model
    d_in = D * s.expand
    H = d_in // s.head_dim
    s_eff = 1 if decode else S
    w_in = 2.0 * D * (2 * d_in + 2 * s.d_state + H) if tied_bytes else 0.0
    i = b.add(Node(op="conv1d", weight_bytes=w_in, ifm=(s_eff, 1, D),
                   ofm=(s_eff, 1, d_in),
                   flops=2.0 * B * s_eff * D * 2 * d_in,
                   kernel=(s.conv_width, 1), batch=B), [i])
    i = b.add(Node(op="ssm", ifm=(s_eff, 1, d_in), ofm=(s_eff, 1, d_in),
                   flops=6.0 * B * s_eff * H * s.head_dim * s.d_state,
                   batch=B, groups=H), [i])
    i = b.add(Node(op="o_proj", weight_bytes=2.0 * d_in * D if tied_bytes else 0.0,
                   ifm=(s_eff, 1, d_in), ofm=(s_eff, 1, D),
                   flops=2.0 * B * s_eff * d_in * D, batch=B), [i])
    return i


def extract_for(arch: str, shape_name: str) -> WorkloadGraph:
    """Resolve (arch, shape) request strings to a WorkloadGraph — the
    request-facing bridge the placement service and the CLIs share.

    ``arch`` is a registry id (repro.configs.registry) or a paper
    workload name (repro.graphs.zoo.PAPER_WORKLOADS); ``shape_name`` is
    a SHAPES key (ignored for paper workloads, which carry their own
    fixed shape).  Where the configuration states a deployment of the
    shape, the graph is the chip's share of pipeline stage 0: the
    request does not yet say which stage the chip serves.  Raises
    ``KeyError`` naming the unknown id — the fail-loud surface
    ``serving/placement_service.py`` converts into a failed
    PlacementResult.  Deterministic: the same request always yields the
    same graph (and so the same canonical hash).
    """
    from repro.configs.base import SHAPES, supports_shape
    from repro.configs.registry import ARCH_IDS, get_config
    from repro.graphs.zoo import PAPER_WORKLOADS

    if arch in PAPER_WORKLOADS:
        return PAPER_WORKLOADS[arch]()
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: "
                       f"{', '.join(tuple(ARCH_IDS) + tuple(PAPER_WORKLOADS))}")
    if shape_name not in SHAPES:
        raise KeyError(f"unknown shape {shape_name!r}; known: "
                       f"{', '.join(SHAPES)}")
    cfg = get_config(arch)
    ok, why = supports_shape(cfg, SHAPES[shape_name])
    if not ok:
        raise KeyError(f"{arch} does not support {shape_name}: {why}")
    dep = cfg.deployment
    return extract_graph(cfg, SHAPES[shape_name],
                         deployment=dep if dep and dep.shape == shape_name
                         else None)


def extract_graph(cfg: ModelConfig, shape: ShapeCfg,
                  deployment: Optional[Deployment] = None, stage: int = 0, *,
                  mesh_data: int = 16, mesh_model: int = 16) -> WorkloadGraph:
    """Graph of ONE chip's share, which EGRL places into that chip's
    HBM/CMEM/VMEM.

    With a ``deployment``: the chip's share of pipeline stage ``stage``
    (module docstring).  Without: the whole model under SPMD on a
    (mesh_data, mesh_model) mesh — the sequences divided by the batch
    sharding, weights by the tensor-parallel degree (x FSDP for
    train/prefill), KV caches and FLOPs by the tensor-parallel degree;
    every chip is identical, so one plan serves the whole mesh.

    Opens the span ``extract.graph`` with the share's bytes by kind and
    counts ``extract.graphs`` by arch (docs/observability.md)."""
    with obs.span("extract.graph", arch=cfg.name, shape=shape.name,
                  stage=stage) as sp:
        if deployment is None:
            g, window = _uniform_share(cfg, shape, mesh_data, mesh_model)
        else:
            g, window = _deployed_share(cfg, shape, deployment, stage)
        sp.set(nodes=g.n, **_bytes_by_kind(g, window))
    obs.counter("extract.graphs", arch=cfg.name).inc()
    return g


def _bytes_by_kind(g: WorkloadGraph, window_caches) -> dict:
    """The graph's bytes by kind: weights (everything but caches and
    expert banks), full caches, window caches, expert banks."""
    out = dict.fromkeys(("weight_bytes", "full_cache_bytes",
                         "window_cache_bytes", "expert_bytes"), 0.0)
    window_caches = set(window_caches)
    for i, nd in enumerate(g.nodes):
        key = ("window_cache_bytes" if i in window_caches
               else "full_cache_bytes" if nd.op == "kv_cache"
               else "expert_bytes" if nd.op == "expert_bank"
               else "weight_bytes")
        out[key] += nd.weight_bytes
    return out


def _uniform_share(cfg: ModelConfig, shape: ShapeCfg, mesh_data: int,
                   mesh_model: int):
    # a chip serves its data-parallel group's sequences, whose tokens
    # its width slice of every expert sees
    seqs = _part(shape.global_batch, min(shape.global_batch, mesh_data))
    tokens = seqs * (1 if shape.kind == "decode" else shape.seq_len)
    b = _build(cfg, shape, _Share(batch=seqs, expert_tokens=tokens), 0,
               cfg.n_layers, first=True, last=True)
    g = WorkloadGraph(f"{cfg.name}__{shape.name}", b.nodes, b.edges)
    w_div = float(mesh_model * (mesh_data if shape.kind != "decode" else 1))
    for nd in g.nodes:
        nd.weight_bytes /= (float(mesh_model) if nd.op == "kv_cache"
                            else w_div)
        nd.flops /= mesh_model
    g.validate()
    return g, b.window_caches


def _deployed_share(cfg: ModelConfig, shape: ShapeCfg, dep: Deployment,
                    stage: int):
    if dep.shape != shape.name:
        raise ValueError(f"the deployment serves {dep.shape}, not "
                         f"{shape.name}")
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"no deployment share for the {cfg.family} family")
    if not 0 <= stage < dep.pipeline_stages:
        raise ValueError(f"stage {stage} of {dep.pipeline_stages}")
    seqs = _part(shape.global_batch, dep.data_parallel)
    sh = _Share(batch=seqs, expert_tokens=dep.expert_tokens,
                tp=dep.tensor_parallel, ep=dep.expert_parallel)
    per = _part(cfg.n_layers, dep.pipeline_stages)
    b = _build(cfg, shape, sh, stage * per, (stage + 1) * per,
               first=stage == 0, last=stage == dep.pipeline_stages - 1)
    g = WorkloadGraph(f"{cfg.name}__{shape.name}__pp{stage}", b.nodes,
                      b.edges)
    g.validate()
    return g, b.window_caches


def _build(cfg: ModelConfig, shape: ShapeCfg, sh: _Share, lo: int, hi: int,
           *, first: bool, last: bool) -> _B:
    """Layers [lo, hi) at the share ``sh``, with the embedding when
    ``first`` and the head when ``last``.  A stage after the first takes
    its input from the previous stage, outside the graph."""
    b = _B()
    B, S = sh.batch, shape.seq_len
    decode = shape.kind == "decode"
    s_eff = 1 if decode else S
    D, Vp = cfg.d_model, cfg.vocab_padded
    V = _part(Vp, sh.tp)

    i = None
    if first:
        # a token's row lies in this chip's slice with chance V / Vp
        i = b.add(Node(op="embed", weight_bytes=2.0 * V * D,
                       ifm=(s_eff, 1, 1), ofm=(s_eff, 1, D),
                       flops=float(B * s_eff * D), batch=B,
                       weight_access_frac=min(1.0, s_eff * B / Vp)), [])

    if cfg.family in ("dense", "moe", "vlm"):
        for layer in range(lo, hi):
            prev = i
            i = _attn_nodes(b, cfg, sh, cfg.attn_kind(layer), i, S, decode)
            i = (_moe_nodes(b, cfg, sh, i, S, decode) if cfg.uses_moe(layer)
                 else _mlp_nodes(b, cfg, sh, i, S, decode))
            if prev is not None:
                b.edges.append((prev, i))  # residual
    elif cfg.family == "ssm":
        for layer in range(cfg.n_layers):
            prev = i
            i = _ssm_nodes(b, cfg, i, S, B, decode)
            b.edges.append((prev, i))
    elif cfg.family == "hybrid":
        k = cfg.shared_attn_every
        for layer in range(cfg.n_layers):
            prev = i
            i = _ssm_nodes(b, cfg, i, S, B, decode)
            b.edges.append((prev, i))
            if layer % k == k - 1:
                first_use = layer == k - 1
                i = _attn_nodes(b, cfg, sh, cfg.attn_kind(layer), i, S,
                                decode, tied_bytes=first_use)
                i = _mlp_nodes(b, cfg, sh, i, S, decode,
                               tied_bytes=first_use)
    elif cfg.family == "encdec":
        enc_i = i
        for layer in range(cfg.enc_layers):  # encoder always runs full length
            prev = enc_i
            enc_i = _attn_nodes(b, cfg, sh, cfg.attn_kind(layer), enc_i, S,
                                decode=False)
            enc_i = _mlp_nodes(b, cfg, sh, enc_i, S, decode=False)
            b.edges.append((prev, enc_i))
        i = enc_i
        for layer in range(cfg.dec_layers):
            prev = i
            i = _attn_nodes(b, cfg, sh, cfg.attn_kind(layer), i, S, decode)
            # cross attention reads encoder memory
            i = b.add(Node(op="cross_attn",
                           weight_bytes=2.0 * D * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim,
                           ifm=(S, 1, D), ofm=(s_eff, 1, D),
                           flops=4.0 * B * s_eff * S * D, batch=B),
                      [i, enc_i])
            i = _mlp_nodes(b, cfg, sh, i, S, decode)
            b.edges.append((prev, i))
    else:
        raise ValueError(cfg.family)

    if last:
        # a tied head reads the embedding's bytes where the embedding
        # is in the same graph
        b.add(Node(op="lm_head", weight_bytes=0.0
                   if cfg.tie_embeddings and first else 2.0 * D * V,
                   ifm=(s_eff, 1, D), ofm=(s_eff, 1, V),
                   flops=2.0 * B * s_eff * D * V, batch=B), [i])
    return b

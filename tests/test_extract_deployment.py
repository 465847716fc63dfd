"""A stated deployment through the extractor (MiMo-V2-Flash decode_32k):
each stage graph agrees node by node with the plain accounting of
``benchmarks/chip/accounting.py`` at published widths and at a smoke
size; the shares of every stage and chip add up to the uncut model; the
other registry graphs hold bitwise the tensors they held before;
extraction is spanned and counted."""
import dataclasses
import hashlib
import math
import os
import sys

import pytest

from repro import obs
from repro.configs.base import (SHAPES, AttnKind, Deployment, MoECfg,
                                ShapeCfg, supports_shape)
from repro.configs.registry import ARCH_IDS, get_config
from repro.graphs.extract import extract_for, extract_graph
from repro.graphs.zoo import WORKLOADS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

import accounting  # noqa: E402

ARCH = "mimo-v2-flash"
CONF = os.path.join(ROOT, "benchmarks", "chip", "configs",
                    "mimo_v2_flash.json")
SMOKE_PATTERN = (0, 1, 1, 0, 1, 1, 1, 0, 1, 1, 1, 0)


def _published():
    cfg = get_config(ARCH)
    return cfg, SHAPES["decode_32k"], accounting.load(CONF)


def _smoke():
    """A 12-layer, 2-stage copy at small widths in which tensor
    parallelism (4) exceeds the full layers' kv heads (2), so they are
    replicated, and a chip holds 2 of 16 experts."""
    dep = Deployment(shape="smoke", pipeline_stages=2, tensor_parallel=4,
                     expert_parallel=8, data_parallel=2, expert_tokens=16)
    cfg = get_config(ARCH).replace(
        n_layers=12, d_model=64, n_heads=8, n_kv_heads=2, head_dim=24,
        v_head_dim=16, d_ff=96, vocab_size=512,
        swa=AttnKind(8, 4, 24, 16, window=8, sink=True),
        layer_pattern=SMOKE_PATTERN,
        moe=MoECfg(n_experts=16, top_k=4, d_ff_expert=32), deployment=dep)
    conf = accounting.load(CONF)
    conf.update(
        hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
        head_dim=24, v_head_dim=16, swa_num_attention_heads=8,
        swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
        sliding_window=8, intermediate_size=96, moe_intermediate_size=32,
        num_experts_per_tok=4, hybrid_layer_pattern=list(SMOKE_PATTERN),
        moe_layer_freq=[0] + [1] * 11, num_hidden_layers=6,
        n_routed_experts=2, vocab_size=128,
        published={"num_hidden_layers": 12, "n_routed_experts": 16,
                   "vocab_size": 512},
        deployment={"pipeline_stages": 2, "tensor_parallel": 4,
                    "expert_parallel": 8, "data_parallel": 2,
                    "expert_tokens": 16},
        shape={"name": "smoke", "seq_len": 32, "global_batch": 8,
               "kind": "decode"})
    return cfg, ShapeCfg("smoke", 32, 8, "decode"), conf


SIZES = {"published": _published, "smoke": _smoke}


@pytest.mark.parametrize("stage", [0, 1])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_stage_graph_matches_accounting(size, stage):
    cfg, shape, conf = SIZES[size]()
    g = extract_graph(cfg, shape, deployment=cfg.deployment, stage=stage)
    want = accounting.stage_nodes(conf, stage)
    assert [nd.op for nd in g.nodes] == [w["op"] for w in want]
    for i, (nd, w) in enumerate(zip(g.nodes, want)):
        for got, ref in ((nd.weight_bytes, w["weight_bytes"]),
                         (nd.flops, w["flops"]),
                         (nd.weight_access_frac, w["access"]),
                         (nd.ofm_bytes, w["act_bytes"])):
            assert got == pytest.approx(ref, rel=1e-9), (i, nd.op)
    if size == "published":
        assert g.n == 121
        assert g.name == f"{ARCH}__decode_32k__pp{stage}"


def test_zoo_and_extract_for_give_the_stage_graphs():
    g0 = extract_for(ARCH, "decode_32k")
    for stage in (0, 1):
        g = WORKLOADS[f"{ARCH}__decode_32k__pp{stage}"]()
        assert g.n == 121
        if stage == 0:
            assert _digest(g) == _digest(g0)
    # a shape the deployment does not serve takes the uniform divisor
    assert extract_for(ARCH, "prefill_32k").name == f"{ARCH}__prefill_32k"


def _copies(n_kv, tp):
    """How many chips hold each kv head of a tensor-parallel group."""
    return tp * math.ceil(n_kv / tp) / n_kv


@pytest.mark.parametrize("size", sorted(SIZES))
def test_shares_sum_to_the_whole(size):
    """Over every stage and every chip of a stage, each held tensor
    counted once, the bytes of each kind equal the uncut model's (one
    stage on one chip).  Replicated kv heads and the router, which every
    chip holds whole, count once."""
    cfg, shape, _ = SIZES[size]()
    dep = cfg.deployment
    tp, ep, dp = dep.tensor_parallel, dep.expert_parallel, dep.data_parallel
    whole = extract_graph(cfg, shape, deployment=dataclasses.replace(
        dep, pipeline_stages=1, tensor_parallel=1, expert_parallel=1,
        data_parallel=1))
    got, want = {}, {}
    for nd in whole.nodes:
        want[nd.op] = want.get(nd.op, 0.0) + nd.weight_bytes
    per = cfg.n_layers // dep.pipeline_stages
    for stage in range(dep.pipeline_stages):
        g = extract_graph(cfg, shape, deployment=dep, stage=stage)
        layer = stage * per - 1
        for nd in g.nodes:
            if nd.op == "qkv":
                layer += 1
                kind = cfg.attn_kind(layer)
                kv_part = 2.0 * cfg.d_model * math.ceil(
                    kind.n_kv_heads / tp) * (kind.head_dim + kind.v_head_dim)
                c = _copies(kind.n_kv_heads, tp)
                b = tp * (nd.weight_bytes - kv_part) + tp * kv_part / c
            elif nd.op == "kv_cache":
                b = tp * dp * nd.weight_bytes / c
            elif nd.op == "expert_bank":
                b = ep * nd.weight_bytes
            elif nd.op == "moe_router":
                b = nd.weight_bytes
            else:                      # embed, o_proj, mlp, lm_head
                b = tp * nd.weight_bytes
            got[nd.op] = got.get(nd.op, 0.0) + b
    assert set(got) == set(want)
    for op in want:
        assert got[op] == pytest.approx(want[op], rel=1e-12), op
    if size == "smoke":            # the full layers' kv heads replicate
        assert _copies(cfg.n_kv_heads, tp) == 2


# SHA-256 prefixes of each registry graph (all supported shapes, no
# deployment).  TENSORS covers every field but the FLOPs and the expert
# access fraction, and was recorded on the extractor before per-layer
# attention kinds and deployments existed: a configuration that states
# no deployment divides its tensors as it did.  FULL covers every field,
# recorded once each graph's FLOPs became the chip's (for the sequences
# it serves) and an expert bank's access the chance that the step's
# tokens reach an expert, the rules of the deployed shares.
TENSORS = {
    "granite-3-8b": ("4450ff767e59d82d", "77167b872f90169b",
                     "2ef44c6259323466"),
    "llama3-405b": ("73693f4ae97cd201", "45e31703f9a4d6c1",
                    "287d44222a882f9c"),
    "qwen3-0.6b": ("a70491a5346cb19b", "f9f0641e42cd548e", "2a5b9e7952af81f0"),
    "qwen2.5-14b": ("b5ad2e198c5c2b8b", "cde4cfb250f9711e",
                    "15e2c6f1e8cea9c4"),
    "llama4-maverick-400b-a17b": ("45bc70f0d150e31a", "f9f9b5133e22da3f",
                                  "890acb6ec554488c"),
    "qwen3-moe-30b-a3b": ("1c0bb145a0fc9b77", "3bd60a7b20a9b073",
                          "3ac35e7644cc6af1"),
    "chameleon-34b": ("fbda066227558a9a", "95a072eebf03aeee",
                      "71a6a89430d76f82"),
    "mamba2-780m": ("f6fee3ec4f716bc0", "4dfffbcd903a8c22", "2739dd8c7419ef1c",
                    "aaf62a8730e784c1"),
    "zamba2-1.2b": ("6a242fe82675dfa6", "56d462514fef8f7f", "b3e70f9fdedea629",
                    "e8884626058a6e63"),
    "seamless-m4t-medium": ("fbd1cdf8cbcb4e26", "a9580bb7ea91aaa7",
                            "5a943c6c98df4dc3"),
}
FULL = {
    "granite-3-8b": ("fca15cfa56cec199", "5d41cdd6ac141c4b",
                     "97e8f86916c7ea39"),
    "llama3-405b": ("86ad36a4283175da", "16a53de1602237a2",
                    "2ce4d7b5870c2999"),
    "qwen3-0.6b": ("bfa1c3893af4ce33", "1f2d6e82f01867ec", "d0bc15d8f60a8faa"),
    "qwen2.5-14b": ("50aac5875814b1bb", "67b82327d620bff8",
                    "abc88b23a0f15a84"),
    "llama4-maverick-400b-a17b": ("d7a7cd8e193bb341", "019a6fe9f5da8398",
                                  "6ce3842794d29e1a"),
    "qwen3-moe-30b-a3b": ("4d03bf303fd53181", "cd4e41b8df722cc3",
                          "352487339390aede"),
    "chameleon-34b": ("5c8048e2d7662c9b", "5c8923c2510171ce",
                      "571325ef947fb1cd"),
    "mamba2-780m": ("61d3953ce4b4a2b6", "0b4d113a4e28cfba", "d64fcf4b10f9e03d",
                    "134ca7cb001a7b04"),
    "zamba2-1.2b": ("36cca06e58dd3d49", "e7839e78d9d2705a", "18a2e8a3f00a816f",
                    "f7164681280c4016"),
    "seamless-m4t-medium": ("caae4bce16b414ce", "2e2e3e67ebd6072c",
                            "e7cc1378af7dc634"),
}


def _digest(g, skip=()) -> str:
    h = hashlib.sha256(g.name.encode())
    for nd in g.nodes:
        for f in dataclasses.fields(nd):
            if f.name not in skip:
                v = getattr(nd, f.name)
                h.update(repr(v.hex() if isinstance(v, float) else v)
                         .encode())
    h.update(repr(g.edges).encode())
    return h.hexdigest()[:16]


def test_other_registry_graphs_are_unchanged():
    """The other registry graphs hold bitwise the tensors they held, and
    their FLOPs and access fractions are the ones recorded."""
    assert set(TENSORS) == set(FULL) == set(ARCH_IDS) - {ARCH}
    for arch in TENSORS:
        cfg = get_config(arch)
        graphs = [extract_graph(cfg, s) for s in SHAPES.values()
                  if supports_shape(cfg, s)[0]]
        assert tuple(_digest(g, ("flops", "weight_access_frac"))
                     for g in graphs) == TENSORS[arch], arch
        assert tuple(_digest(g) for g in graphs) == FULL[arch], arch


def test_extraction_is_spanned_and_counted():
    cfg, shape, conf = _published()
    count = obs.counter("extract.graphs", arch=ARCH)
    before = count.value
    with obs.override(mode="mem"):
        g = extract_graph(cfg, shape, deployment=cfg.deployment, stage=1)
        spans = [e for e in obs.events()
                 if e["type"] == "span" and e["name"] == "extract.graph"]
    assert count.value == before + 1
    assert len(spans) == 1
    attrs = spans[0]["attrs"]
    assert {k: attrs[k] for k in ("arch", "shape", "stage", "nodes")} == {
        "arch": ARCH, "shape": "decode_32k", "stage": 1, "nodes": g.n}
    want = accounting.totals(accounting.stage_nodes(conf, 1))
    assert {k: attrs[k] for k in accounting.KINDS} == pytest.approx(want)
    assert want["window_cache_bytes"] == 20 * 2 * 16 * 128 * 2 * 320

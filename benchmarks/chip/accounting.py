"""Plain byte and FLOP accounting of one chip's share of a stated decode
deployment, read from a configuration file's published keys (the
source's config.json names) and its ``deployment`` and ``shape``.

It is the configuration's reference for the graphs the program extracts
(``graphs/extract.py``) and imports nothing of the program.  For each
pipeline stage it lists the nodes in the order a decode step runs them,
each with its weight bytes, FLOPs, streamed fraction and output bytes:

- the embedding (first stage): the chip's ``vocab_size`` rows; a token's
  row lies in the slice with chance slice / published vocabulary;
- per layer, attention of the layer's kind (``hybrid_layer_pattern``: 0
  full, 1 sliding window): the q/k/v projection of the chip's query
  heads (heads / tp) and kv heads (ceil(kv heads / tp)), the sink logits
  of a sliding layer, the cache of ``min(seq_len, window)`` tokens of
  the chip's kv heads and sequences, the output projection;
- per layer, the FFN (``moe_layer_freq``): a dense FFN of
  intermediate_size / tp, or the router of the published expert count
  and the chip's ``n_routed_experts`` experts, each reached by one of the
  expert group's T tokens with chance 1 - (1 - k / E)^T;
- the head (last stage).

Every size is bfloat16 (2 bytes).  FLOPs count multiply-adds twice, for
the chip's sequences; an expert bank's for the token-expert pairs that
land on the chip's experts (T x k x held / E).
"""
from __future__ import annotations

import json
import math
from typing import Dict, List

KINDS = ("weight_bytes", "full_cache_bytes", "window_cache_bytes",
         "expert_bytes")


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _node(op, weight, flops, out_width, seqs, access=1.0, kind=None):
    return {"op": op, "weight_bytes": float(weight), "flops": float(flops),
            "access": float(access), "act_bytes": 2.0 * out_width * seqs,
            "kind": kind or ("full_cache_bytes" if op == "kv_cache"
                             else "expert_bytes" if op == "expert_bank"
                             else "weight_bytes")}


def _heads(conf: dict, sliding: bool, tp: int):
    """(query heads, kv heads, q/k width, v width, window, sink) held."""
    p = "swa_" if sliding else ""
    h = conf[p + "num_attention_heads"]
    kv = conf[p + "num_key_value_heads"]
    qk = conf["swa_head_dim" if sliding else "head_dim"]
    v = conf["swa_v_head_dim" if sliding else "v_head_dim"]
    window = conf["sliding_window"] if sliding else 0
    sink = conf["add_swa_attention_sink_bias" if sliding
                else "add_full_attention_sink_bias"]
    return h // tp, math.ceil(kv / tp), qk, v, window, bool(sink)


def stage_nodes(conf: dict, stage: int) -> List[Dict]:
    """The nodes of one chip's share of pipeline stage ``stage``."""
    dep, shape, pub = conf["deployment"], conf["shape"], conf["published"]
    tp, T = dep["tensor_parallel"], dep["expert_tokens"]
    seqs = shape["global_batch"] // dep["data_parallel"]
    S = shape["seq_len"]
    D = conf["hidden_size"]
    per = conf["num_hidden_layers"]
    vocab = conf["vocab_size"]                 # the chip's slice
    E, k = pub["n_routed_experts"], conf["num_experts_per_tok"]
    held = conf["n_routed_experts"]
    Fe = conf["moe_intermediate_size"]
    out = []
    if stage == 0:
        out.append(_node("embed", 2 * vocab * D, seqs * D, D, seqs,
                         access=min(1.0, seqs / pub["vocab_size"])))
    for layer in range(stage * per, (stage + 1) * per):
        sliding = conf["hybrid_layer_pattern"][layer] == 1
        h, kv, qk, v, window, sink = _heads(conf, sliding, tp)
        qkv = h * qk + kv * (qk + v)
        out.append(_node("qkv", 2 * D * qkv + (2 * h if sink else 0),
                         2 * seqs * D * qkv, qkv, seqs))
        L = min(S, window) if window else S
        out.append(_node("kv_cache", 2 * seqs * L * kv * (qk + v),
                         2 * seqs * L * h * (qk + v), h * v, seqs,
                         kind="window_cache_bytes" if L < S else None))
        out.append(_node("o_proj", 2 * h * v * D, 2 * seqs * h * v * D, D,
                         seqs))
        if conf["moe_layer_freq"][layer]:
            out.append(_node("moe_router", 2 * D * E, 2 * seqs * D * E, E,
                             seqs))
            out.append(_node("expert_bank", 2 * held * 3 * D * Fe,
                             6 * D * Fe * T * k * held / E, D, seqs,
                             access=1 - (1 - k / E) ** T))
        else:
            F = conf["intermediate_size"] // tp
            out.append(_node("mlp", 2 * 2 * D * F, 4 * seqs * D * F, F, seqs))
            out.append(_node("mlp", 2 * F * D, 2 * seqs * F * D, D, seqs))
    if stage == dep["pipeline_stages"] - 1:
        out.append(_node("lm_head", 2 * D * vocab, 2 * seqs * D * vocab,
                         vocab, seqs))
    return out


def totals(nodes: List[Dict]) -> Dict[str, float]:
    """A stage's weight bytes by kind."""
    out = dict.fromkeys(KINDS, 0.0)
    for nd in nodes:
        out[nd["kind"]] += nd["weight_bytes"]
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config", help="configuration JSON, e.g. "
                    "benchmarks/chip/configs/mimo_v2_flash.json")
    args = ap.parse_args(argv)
    conf = load(args.config)
    for s in range(conf["deployment"]["pipeline_stages"]):
        nodes = stage_nodes(conf, s)
        print(json.dumps({"stage": s, "nodes": len(nodes), **totals(nodes)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

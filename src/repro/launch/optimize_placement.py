"""EGRL placement entry point: --arch x --shape -> placement plan JSON.

The plan records per-op (weight tier, activation tier), expected latency
vs the heuristic compiler, and derived knobs the rest of the framework
consumes (training/remat.py maps activation tiers to a remat policy;
serving reports the plan's expected decode latency).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS
from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.graphs.extract import extract_for
from repro.graphs.zoo import PAPER_WORKLOADS
from repro.launch.compile_cache import enable_compile_cache
from repro.memsim import tiers as T
from repro.memsim.compiler import compiler_reference
from repro.memsim.simulator import build_sim_graph, evaluate
from repro.obs.log import get_logger
import jax.numpy as jnp

_log = get_logger("optimize_placement")


def make_graph(arch: str, shape_name: str):
    return extract_for(arch, shape_name)


def plan_from_mapping(graph, mapping: np.ndarray, meta: dict) -> dict:
    tiers = [t.name for t in T.TIERS]
    ops = []
    for i, nd in enumerate(graph.nodes):
        ops.append({
            "index": i, "op": nd.op,
            "weight_tier": tiers[int(mapping[i, 0])],
            "act_tier": tiers[int(mapping[i, 1])],
            "weight_bytes": nd.weight_bytes, "act_bytes": nd.ofm_bytes,
        })
    # framework knobs: fraction of activations the plan wants resident
    resident = np.mean(mapping[:, 1] != T.HBM_IDX)
    remat = "none" if resident > 0.85 else ("dots" if resident > 0.4 else "full")
    return {**meta, "ops": ops,
            "derived": {"act_resident_frac": float(resident),
                        "suggested_remat": remat}}


def optimize(arch: str, shape_name: str, steps: int, mode: str = "egrl",
             seed: int = 0, log=_log.info):
    g = make_graph(arch, shape_name)
    algo = ZooEGRL([g], EGRLConfig(total_steps=steps, seed=seed), mode=mode)
    algo.train(log=log)
    mapping = algo.best_mapping[0]
    sg = build_sim_graph(g)
    _, clat = compiler_reference(g)
    res = evaluate(sg, jnp.asarray(mapping), jnp.float32(clat))
    meta = {
        "arch": arch, "shape": shape_name, "graph_nodes": g.n,
        "mode": mode, "env_steps": algo.steps,
        "speedup_vs_compiler": float(res["speedup"]),
        "latency_ms": float(res["latency"]) * 1e3,
        "compiler_latency_ms": clat * 1e3,
    }
    return plan_from_mapping(g, mapping, meta), algo


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=list(ARCH_IDS) + list(PAPER_WORKLOADS))
    ap.add_argument("--shape", default="decode_32k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--mode", default="egrl", choices=["egrl", "ea", "pg"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="experiments/plans")
    args = ap.parse_args()
    enable_compile_cache()

    plan, _ = optimize(args.arch, args.shape, args.steps, args.mode, args.seed)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.arch}__{args.shape}.json")
    with open(path, "w") as f:
        json.dump(plan, f, indent=1)
    _log.info(f"speedup vs compiler: {plan['speedup_vs_compiler']:.3f} "
              f"({plan['compiler_latency_ms']:.3f} -> {plan['latency_ms']:.3f} ms)")
    _log.info(f"plan written to {path}")


if __name__ == "__main__":
    main()

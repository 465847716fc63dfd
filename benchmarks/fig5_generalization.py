"""Figure 5 reproduction: zero-shot transfer of the GNN policy — train on
one workload, evaluate (no fine-tuning) on the others.

The evaluation leg runs through the bucketed zoo path
(``evaluate_gnn_zoo``): all destination workloads are stacked into one
size-bucketed ``BucketedZoo`` (one padded batch per size class, policy
``REPRO_ZOO_BUCKETS``) and scored in one device call per bucket per
trained policy.  Each source trains as a one-graph ``ZooEGRL``."""
from __future__ import annotations

import json
import os

from repro.core.egrl import EGRLConfig, ZooEGRL, evaluate_gnn_zoo
from repro.graphs.bucketed import build_bucketed_zoo
from repro.graphs.zoo import PAPER_WORKLOADS


def run(steps: int = 1000, train_on=("bert", "resnet50"),
        outdir: str = "experiments/fig5", seed: int = 0, log=print):
    os.makedirs(outdir, exist_ok=True)
    # one bucketed zoo of the whole sweep grid, reused for every source
    batch = build_bucketed_zoo([f() for f in PAPER_WORKLOADS.values()])
    rows = []
    for src in train_on:
        algo = ZooEGRL([PAPER_WORKLOADS[src]()],
                       EGRLConfig(total_steps=steps, seed=seed), mode="egrl")
        algo.train()
        vec = algo.best_gnn_vec()
        src_speedup = algo.best_reward[0] / algo.cfg.reward_scale
        zero_shot = evaluate_gnn_zoo(None, vec, seed=seed, batch=batch)
        for dst in PAPER_WORKLOADS:
            # the source graph reports its trained (not zero-shot) speedup
            sp = src_speedup if dst == src else zero_shot[dst]
            rows.append({"train": src, "eval": dst, "speedup": sp})
            if log:
                log(f"fig5,{src}->{dst},{sp:.3f}")
    with open(os.path.join(outdir, f"fig5_{steps}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=2000)
    a = ap.parse_args()
    run(a.steps)

"""Figure 4 reproduction: speedup vs the native compiler for EGRL / EA /
PG / Greedy-DP on ResNet-50, ResNet-101 and BERT, n seeds, iteration
budget counted cumulatively across the population (as in §4 Metrics)."""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.graphs.zoo import PAPER_WORKLOADS
from repro.memsim.compiler import greedy_dp, compiler_reference
from repro.memsim.simulator import build_sim_graph, evaluate
import jax.numpy as jnp

AGENTS = ("egrl", "ea", "pg", "greedy-dp")


def run_agent(workload: str, agent: str, steps: int, seed: int):
    g = PAPER_WORKLOADS[workload]()
    t0 = time.time()
    if agent == "greedy-dp":
        mapping, history = greedy_dp(g, passes=max(1, steps // (9 * g.n)),
                                     budget=steps)
        sg = build_sim_graph(g)
        _, ref = compiler_reference(g)
        res = evaluate(sg, jnp.asarray(mapping), jnp.float32(ref))
        speedup = float(res["speedup"])
        curve = [(i, r / 5.0) for i, r in history]
    else:
        algo = ZooEGRL([g], EGRLConfig(total_steps=steps, seed=seed),
                       mode=agent)
        algo.train()
        scale = algo.cfg.reward_scale
        speedup = max(float(algo.best_reward[0]), 0.0) / scale
        curve = [(h["steps"], max(h["best_reward_per_graph"][g.name], 0.0)
                  / scale) for h in algo.history]
    return {"workload": workload, "agent": agent, "seed": seed,
            "steps": steps, "speedup": speedup, "curve": curve,
            "wall_s": round(time.time() - t0, 1)}


def run(steps: int = 1000, seeds=(0,), workloads=None, agents=AGENTS,
        outdir: str = "experiments/fig4", log=print):
    os.makedirs(outdir, exist_ok=True)
    rows = []
    for w in (workloads or PAPER_WORKLOADS):
        for agent in agents:
            per_seed = []
            for s in seeds:
                r = run_agent(w, agent, steps, s)
                per_seed.append(r["speedup"])
                rows.append(r)
                if log:
                    log(f"fig4,{w},{agent},seed{s},{r['speedup']:.3f},"
                        f"{r['wall_s']}s")
            mu, sd = float(np.mean(per_seed)), float(np.std(per_seed))
            rows.append({"workload": w, "agent": agent, "seed": "mean",
                         "speedup": mu, "std": sd, "steps": steps})
            if log:
                log(f"fig4,{w},{agent},mean,{mu:.3f}+-{sd:.3f}")
    with open(os.path.join(outdir, f"fig4_{steps}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4000)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--workloads", nargs="*", default=None)
    ap.add_argument("--out", default="experiments/fig4")
    a = ap.parse_args()
    run(a.steps, tuple(range(a.seeds)), a.workloads, outdir=a.out)

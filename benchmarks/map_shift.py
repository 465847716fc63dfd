"""Figure 7 reproduction: transition matrices showing how EGRL
re-distributes tensors relative to the compiler's mapping."""
from __future__ import annotations

import json
import os

import numpy as np

from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.graphs.zoo import PAPER_WORKLOADS
from repro.memsim import tiers as T
from repro.memsim.compiler import compiler_reference


def transition_matrix(cmap: np.ndarray, emap: np.ndarray, bytes_: np.ndarray):
    """(3,3) row-normalized byte flow: row=compiler tier, col=EGRL tier."""
    m = np.zeros((3, 3))
    for c, e, b in zip(cmap, emap, bytes_):
        m[c, e] += b
    return m / np.maximum(m.sum(1, keepdims=True), 1e-9)


def run(steps: int = 1000, workloads=("resnet50",), seed: int = 0,
        outdir: str = "experiments/fig7", log=print):
    os.makedirs(outdir, exist_ok=True)
    out = {}
    for w in workloads:
        g = PAPER_WORKLOADS[w]()
        cmap, _ = compiler_reference(g)
        algo = ZooEGRL([g], EGRLConfig(total_steps=steps, seed=seed),
                       mode="egrl")
        algo.train()
        emap = algo.best_mapping[0]
        wb = np.array([nd.weight_bytes for nd in g.nodes])
        ab = np.array([nd.ofm_bytes for nd in g.nodes])
        tw = transition_matrix(cmap[:, 0], emap[:, 0], wb)
        ta = transition_matrix(cmap[:, 1], emap[:, 1], ab)
        out[w] = {"weights": tw.tolist(), "acts": ta.tolist(),
                  "speedup": algo.best_reward[0] / algo.cfg.reward_scale}
        if log:
            names = [t.name for t in T.TIERS]
            log(f"fig7,{w},speedup,{out[w]['speedup']:.3f}")
            for kind, mat in (("weights", tw), ("acts", ta)):
                for i, row in enumerate(mat):
                    log(f"fig7,{w},{kind},{names[i]}->"
                        + ",".join(f"{names[j]}:{row[j]:.2f}" for j in range(3)))
    with open(os.path.join(outdir, f"fig7_{steps}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    run()

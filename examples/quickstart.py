"""Quickstart: the paper's technique end to end in ~a minute on CPU.

Builds the ResNet-50 workload graph (57 nodes, as in §4), runs a short
EGRL search against the TPU memory-tier simulator, and prints the found
placement's speedup over the heuristic compiler.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.graphs.zoo import resnet50
from repro.launch.compile_cache import enable_compile_cache
from repro.memsim import tiers as T

enable_compile_cache()
graph = resnet50()
print(f"workload: {graph.name}, {graph.n} nodes "
      f"(action space 3^{2 * graph.n} ~ 10^{int(2 * graph.n * 0.477)})")

algo = ZooEGRL([graph], EGRLConfig(total_steps=400, seed=0), mode="egrl")
algo.train(log=print)

print(f"\nbest speedup vs compiler: "
      f"{algo.best_reward[0] / algo.cfg.reward_scale:.3f}x")
tiers = [t.name for t in T.TIERS]
w = algo.best_mapping[0][:, 0]
a = algo.best_mapping[0][:, 1]
for k in range(3):
    print(f"  {tiers[k]:5s}: {int((w == k).sum()):3d} weight tensors, "
          f"{int((a == k).sum()):3d} activation tensors")

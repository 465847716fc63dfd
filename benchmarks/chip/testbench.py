"""A one-bucket, small-population cell for the CPU tests: two small zoo
graphs (resnet50, 57 nodes; mobilenet_v2, 65) in one size bucket, the
policy at its full width, a population of 6 and a SAC batch of 4.  It is
written as files under a temporary directory laid out like the
benchmark, so the tests find it by name exactly as a run does."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))


# Limits for the fault tests: on the CPU the program computes in full
# float32 and reads about 1e-7 on every number, so the chip's limits
# (set where the program takes bfloat16 passes) are far above what a
# CPU run can show; these sit a hundred times above the CPU readings.
CPU_LIMITS = {"logits_gap_p90": 1e-5, "reward_gap": 1e-5, "ea_gap": 1e-6,
              "sac_chain_gap": 0.0, "critic_loss_gap": 1e-4,
              "param_change_gap": 1e-4, "actor_change_gap": 1e-4,
              "critic_change_gap": 1e-4}


def make(root: str, cells_limits: str = "paper_zoo.egrl",
         limits: dict = None) -> str:
    """Write the small benchmark under ``root``; returns the directory
    that plays ``benchmarks/chip`` (configs, traffic, limits, metrics).
    Its cells use the limits of ``cells_limits`` in this benchmark, or
    ``limits`` where given."""
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = os.path.join(root, "benchmarks", "chip")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(base, d), exist_ok=True)
    shutil.copytree(os.path.join(HERE, "metrics"),
                    os.path.join(base, "metrics"), dirs_exist_ok=True)
    with open(os.path.join(HERE, "configs", "paper_zoo.json")) as f:
        conf = json.load(f)
    conf.update(name="small", graphs={"resnet50": 57, "mobilenet_v2": 65},
                expected_buckets=[[65, 5]], gat_backend="chunked")
    conf["egrl"].update(pop_size=6, elites=2)
    conf["sac"].update(batch=4)
    bench["configs"] = [{"name": "small", "source": conf["source"],
                         "file": "benchmarks/chip/configs/small.json",
                         "reduced": [], "why": "CPU tests"}]
    bench["workloads"] = [
        {"name": f"small.{t}", "config": "small", "traffic": t, "chips": 1,
         "why": "CPU tests"} for t in ("egrl", "ea")]
    for m in bench["per_layer"]:
        m["workloads"] = ["small.egrl", "small.ea"]
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    _dump(os.path.join(base, "configs", "small.json"), conf)
    with open(os.path.join(HERE, "traffic", "egrl.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "limits", cells_limits + ".json")) as f:
        chip_limits = json.load(f)["limits"]
    sac = ("sac_chain_gap", "critic_loss_gap", "param_change_gap",
           "actor_change_gap", "critic_change_gap")
    for t in ("egrl", "ea"):
        _dump(os.path.join(base, "traffic", t + ".json"),
              dict(traffic, mode=t))
        lim = chip_limits if limits is None else limits
        _dump(os.path.join(base, "limits", f"small.{t}.json"),
              {"limits": {k: v for k, v in lim.items()
                          if t == "egrl" or k not in sac}})
    return base


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)

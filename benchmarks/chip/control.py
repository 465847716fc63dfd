#!/usr/bin/env python3
"""Readings of the correctness check over several seeds in one process:
the program's own numbers (the lower readings) and, on the seeds given
with ``--variant-seeds``, the numbers of the control (the reference in
bfloat16 in the program's place) and of the faults the check must catch
(see ``correct.VARIANTS``).  The limits in ``limits/<cell>.json`` are set
from these readings; the benchmark's own runs do not run this.

    python3 benchmarks/chip/control.py --workload paper_zoo.egrl \
        --seeds 11,12,13,14 --variant-seeds 11,12,13 --seconds 5 \
        --out control_paper_zoo.egrl.jsonl

Needs the chip, like run.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import correct  # noqa: E402
import run  # noqa: E402


def readings(cell, seeds, variant_seeds, seconds, require_tpu=True,
             fault=None):
    """One record per seed: the program's numbers, and those of every
    variant on the seeds in ``variant_seeds``."""
    out = []
    for seed in seeds:
        variants = (correct.VARIANTS if seed in variant_seeds
                    else ("program",))
        res = run.run(cell, seed, seconds, False, require_tpu=require_tpu,
                      fault=fault, variants=variants)
        out.append({"seed": seed, "correct": res["correct"],
                    "program": {k: c["value"]
                                for k, c in res["checks"].items()},
                    "variants": res.get("variants", {}),
                    "generation_ms": res["metrics"]["generation_ms"]["value"],
                    "detail": res.get("detail", {})})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    root = cells.find_root()
    sys.path.insert(1, os.path.join(root, "src"))
    cell = cells.Cell(root, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    vseeds = {int(s) for s in args.variant_seeds.split(",") if s}
    try:
        recs = readings(cell, seeds, vseeds, args.seconds)
    except run.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    names = list(recs[0]["program"])
    lower = {k: max(r["program"][k] for r in recs) for k in names}
    print("lower", json.dumps(lower))
    for v in correct.VARIANTS[1:]:
        got = [r["variants"][v] for r in recs if v in r["variants"]]
        if got:
            print(v, json.dumps({k: min(g[k] for g in got) for k in names
                                 if k in got[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

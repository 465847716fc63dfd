#!/usr/bin/env python3
"""Smoke run of the placement optimizer on a TPU: the main path once,
through the entry points a user calls, at the paper's full width.

    python chip_smoke.py               # one chip: phases 1-4
    python chip_smoke.py --four-chips  # four chips: phase 1 and 5 only

Phases, one result line each:

1. devices   — platform, kind and count; anything but a TPU fails.
2. kernel    — the Pallas GAT pair compiled (not interpreted), forward
               and gradient at N in {57, 388, 1043}, against the jnp
               oracle at highest matmul precision; the autotuner's
               choices; ``tpu_custom_call`` in the population forward.
3. training  — ``train_zoo`` in "egrl" mode (GNN population + Boltzmann
               + ZooSAC) over resnet50/resnet101/bert/moe_transformer
               for a few generations; every reward finite; each graph's
               best mapping rectified on the device equals the numpy
               oracle, and re-evaluated on the device gives the reward
               training recorded.
4. service   — registry requests through ``serve``: no failure, no
               fault, never slower than the compiler, misses refined on
               the device, repeats answered from the cache.
5. four chips — the same zoo serially on device 0, with the population
               sharded over four chips, and with buckets dispatched
               across them; trajectories compared with the serial one.

The last line of standard output is one JSON object naming the device;
any failed check exits non-zero before it.  Weights and inputs are
random from fixed seeds.  The XLA compile cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` here.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ZOO = ("resnet50", "resnet101", "bert", "moe_transformer")
KERNEL_SIZES = (57, 388, 1043)
KERNEL_TOL = 2e-2      # max |err| / max |ref|, kernel at default precision
GENERATIONS = 4
SERVE_PAIRS = (("seamless-m4t-medium", "train_4k"),
               ("qwen3-0.6b", "decode_32k"),
               ("granite-3-8b", "prefill_32k"))


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def say(tag: str, **fields) -> None:
    print(f"{tag}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ------------------------------------------------------------- 1. devices
def phase_devices(want: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    say("devices", platform=d.platform, kind=repr(d.device_kind),
        count=len(devs))
    check(d.platform == "tpu", f"no TPU: JAX found {d.platform}")
    check(len(devs) >= want, f"{want} chips needed, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -------------------------------------------------------------- 2. kernel
def _kernel_inputs(n: int, seed: int):
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((n, 128), np.float32)
    es = rng.standard_normal((n, 4), np.float32)
    ed = rng.standard_normal((n, 4), np.float32)
    adj = rng.random((n, n)) < min(1.0, 8.0 / n)       # ~8 neighbours/row
    adj = np.maximum(np.maximum(adj, adj.T), np.eye(n, dtype=bool))
    w = rng.standard_normal((n, 128), np.float32)
    return [jnp.asarray(x, jnp.float32) for x in (z, es, ed, adj, w)]


def phase_kernel():
    import jax
    import jax.numpy as jnp
    from repro.core import gat_tune, gnn
    from repro.graphs.bucketed import build_bucketed_zoo
    from repro.graphs.zoo import WORKLOADS
    from repro.kernels.gat_mp.ops import gat_mp
    from repro.kernels.gat_mp.ref import gat_mp_ref

    def value_and_grads(op):
        def loss(z, es, ed, adj, w):
            return (op(z, es, ed, adj, heads=4) * w).sum()
        fwd = jax.jit(lambda z, es, ed, adj, w: op(z, es, ed, adj, heads=4))
        return fwd, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    kfwd, kgrad = value_and_grads(
        lambda *a, **k: gat_mp(*a, interpret=False, **k))
    rfwd, rgrad = value_and_grads(gat_mp_ref)
    worst = {}
    for n in KERNEL_SIZES:
        args = _kernel_inputs(n, seed=n)
        got = [kfwd(*args), *kgrad(*args)]
        with jax.default_matmul_precision("highest"):
            ref = [rfwd(*args), *rgrad(*args)]
        errs = {}
        for name, a, b in zip(("out", "dz", "de_src", "de_dst"), got, ref):
            check(bool(jnp.isfinite(a).all()), f"kernel {name} not finite "
                                               f"at N={n}")
            errs[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        worst[n] = max(errs.values())
        say("kernel.parity", n=n, tol=KERNEL_TOL,
            **{k: f"{v:.3e}" for k, v in errs.items()})
        check(worst[n] <= KERNEL_TOL,
              f"kernel error {worst[n]:.3e} > {KERNEL_TOL} at N={n}")

    for n in KERNEL_SIZES:
        t = gat_tune.autotune(n, gnn.HIDDEN, gnn.HEADS, jnp.float32,
                              force_time=True)
        say("kernel.autotune", n=n,
            chosen=f"{t.backend}{t.chunk or ''}",
            timings_us=json.dumps(t.timings, separators=(",", ":")))

    # the population forward over the zoo's largest bucket, kernel forced
    zoo = build_bucketed_zoo([WORKLOADS[name]() for name in ZOO])
    b = zoo.buckets[-1]
    tpl = gnn.init_gnn(jax.random.PRNGKey(0), zoo.n_features)
    vec = gnn.flatten_params(tpl)
    pop = jnp.broadcast_to(vec, (16, vec.shape[0]))
    text = jax.jit(gnn.population_logits_zoo,
                   static_argnames=("backend",)).lower(
        tpl, b.feats, b.adj, b.node_mask, b.n_nodes, pop,
        backend="pallas").compile().as_text()
    calls = text.count("tpu_custom_call")
    check(calls > 0, "no tpu_custom_call in the population forward")
    say("kernel", ok=True, max_rel_err=f"{max(worst.values()):.3e}",
        tol=KERNEL_TOL, pop_forward_n_max=b.n_max,
        pop_forward_tpu_custom_calls=calls)


# ------------------------------------------------------------ 3. training
def phase_training():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import obs
    from repro.core.egrl import EGRLConfig
    from repro.graphs.zoo import WORKLOADS
    from repro.launch.train_zoo import train_zoo
    from repro.memsim.compiler import compiler_reference
    from repro.memsim.reference import rectify_np
    from repro.memsim.simulator import build_sim_graph, evaluate, rectify

    cfg = EGRLConfig()
    # one env step per (genome, graph) and generation; the PG member
    # adds pg_rollouts rows
    steps = GENERATIONS * (cfg.pop_size + cfg.pg_rollouts) * len(ZOO)
    with obs.override(mode="mem"):
        report, algo = train_zoo(list(ZOO), steps=steps, mode="egrl",
                                 log=None)
        gen_ms = [e["dur_ms"] for e in obs.events()
                  if e.get("type") == "span" and e["name"] == "generation"]
    say("training.smoke_time_not_a_benchmark",
        generation_wall_ms=[round(x, 1) for x in gen_ms])
    check(len(algo.history) >= 3, f"{len(algo.history)} generations ran")
    for rec in algo.history:
        # mean over every (genome, graph) reward: finite iff all are
        check(math.isfinite(rec["gen_mean_fitness"])
              and math.isfinite(rec["gen_best_fitness"]),
              f"non-finite reward at step {rec['steps']}")

    scale = algo.cfg.reward_scale
    rect_fn = jax.jit(rectify)
    speedups = {}
    for gi, name in enumerate(algo.zoo.names):
        g = WORKLOADS[name]()
        m = np.asarray(algo.best_mapping[gi], np.int32)
        check(m.shape == (g.n, 2), f"{name}: best mapping {m.shape}")
        sg = build_sim_graph(g)
        r_dev, eps_dev = rect_fn(sg, jnp.asarray(m))
        r_np, eps_np = rectify_np(sg, m)
        check(bool((np.asarray(r_dev) == r_np).all())
              and np.float32(eps_dev) == eps_np,
              f"{name}: device rectify differs from rectify_np")
        _, clat = compiler_reference(g)
        res = evaluate(sg, jnp.asarray(m), jnp.float32(clat),
                       reward_scale=scale)
        best = float(algo.best_reward[gi])
        sp = float(res["speedup"])
        check(math.isfinite(best), f"{name}: best reward {best}")
        if best > 0:                   # valid mapping: reward = scale * sp
            check(abs(sp - best / scale) <= 1e-6 * best / scale,
                  f"{name}: re-evaluated speedup {sp} != {best / scale}")
        else:                          # invalid: reward = -eps
            check(float(res["reward"]) == best,
                  f"{name}: re-evaluated reward {float(res['reward'])} "
                  f"!= {best}")
        speedups[name] = sp
        say("training.graph", name=name, nodes=g.n,
            rectify_matches_numpy=True,
            recorded_reward=repr(best),
            reevaluated_reward=repr(float(res["reward"])),
            speedup=repr(sp))
    say("training", ok=True, generations=len(algo.history),
        env_steps=algo.steps,
        buckets=[b["n_max"] for b in report["buckets"]],
        best_fitness=repr(float(algo.best_fitness)),
        speedups=json.dumps({k: round(v, 4) for k, v in speedups.items()},
                            separators=(",", ":")))


# ------------------------------------------------------------- 4. service
def phase_service():
    from repro.launch.serve_placements import serve
    from repro.serving.placement_service import PlacementRequest

    first = [PlacementRequest(i, a, s) for i, (a, s) in enumerate(SERVE_PAIRS)]
    res1, summary, svc = serve(first, seed=0, log=None)
    calls = svc.stats()["evaluator_calls"]
    repeats = [PlacementRequest(len(first) + i, a, s)
               for i, (a, s) in enumerate(SERVE_PAIRS)]
    res2 = svc.run(repeats)
    stats = svc.stats()
    results = res1 + res2
    for r in sorted(results, key=lambda r: r.request_id):
        say("service.result", id=r.request_id, arch=r.arch, shape=r.shape,
            status=r.status, hit=r.cache_hit, nn_hit=r.nn_hit,
            source=r.source, speedup=repr(r.speedup),
            wall_ms=round(r.wall_ms, 1))
    failed = sum(not r.ok for r in results)
    check(failed == 0, f"{failed} failed results: "
                       f"{[r.error for r in results if not r.ok]}")
    check(stats["faults"] == 0, f"faults={stats['faults']}")
    check(all(r.speedup >= 1.0 for r in results),
          "a result is slower than the compiler")
    check(calls > 0, "no miss was refined on the device")
    check(len(res2) == len(repeats) and all(r.cache_hit for r in res2),
          "repeated requests were not cache hits")
    say("service", ok=True, requests=len(results), failed=failed,
        faults=stats["faults"], evaluator_calls=calls,
        first_pass_misses=summary["cache_misses"],
        first_pass_nn_hits=summary["nn_hits"],
        repeat_hits=sum(r.cache_hit for r in res2),
        min_speedup=repr(min(r.speedup for r in results)))


# ---------------------------------------------------------- 5. four chips
def phase_four_chips():
    import jax
    import numpy as np
    from repro.core.egrl import EGRLConfig, ZooEGRL
    from repro.graphs.zoo import WORKLOADS

    graphs = [WORKLOADS[name]() for name in ZOO]
    runs = {"serial": dict(pop_shards="off", dispatch="off"),
            "pop_sharded": dict(pop_shards="auto"),
            "dispatched": dict(pop_shards="off", dispatch="async")}
    traj, placement = {}, {}
    for label, kw in runs.items():
        algo = ZooEGRL(graphs, EGRLConfig(seed=0), mode="egrl", **kw)
        t0 = time.perf_counter()
        traj[label] = [(r["gen_best_fitness"], r["best_fitness"])
                       for r in (algo.generation()
                                 for _ in range(GENERATIONS))]
        wall = time.perf_counter() - t0
        ids = sorted(d.id for d in algo.gnn_pop.devices())
        if algo.dispatch is not None:
            buckets = algo.dispatch.device_map()
        else:
            buckets = {k: ids for k in range(algo.zoo.n_buckets)}
        placement[label] = (ids, buckets)
        say("four_chips.run", run=label,
            pop_devices=ids, bucket_devices=json.dumps(buckets),
            bucket_n_max=[b.n_max for b in algo.zoo.buckets],
            smoke_wall_s_not_a_benchmark=round(wall, 2),
            elite_fitness=[repr(b) for b, _ in traj[label]])
    base = np.asarray(traj["serial"])
    for label in ("pop_sharded", "dispatched"):
        t = np.asarray(traj[label])
        say("four_chips.compare", run=label,
            bit_identical=bool((t == base).all()),
            max_abs_diff=repr(float(np.abs(t - base).max())))
    check(placement["serial"][0] == [jax.devices()[0].id],
          "serial baseline left device 0")
    check(len(placement["pop_sharded"][0]) == 4,
          "population not sharded over 4 chips")
    spread = {d for d in placement["dispatched"][1].values()}
    check(len(spread) > 1, "dispatched buckets all on one device")
    say("four_chips", ok=True)


# ------------------------------------------------------------------ main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip comparison (phase 5)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "writes": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["writes"] += 1

    jax.monitoring.register_event_listener(on_event)

    t0 = time.perf_counter()
    try:
        device = phase_devices(4 if args.four_chips else 1)
        phases = ((phase_four_chips,) if args.four_chips
                  else (phase_kernel, phase_training, phase_service))
        for phase in phases:
            t = time.perf_counter()
            phase()
            say("phase_time", phase=phase.__name__[6:],
                wall_s=round(time.perf_counter() - t, 1))
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say("compile_cache", dir=cache_dir, hits=cache["hits"],
        writes=cache["writes"])
    say("total", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

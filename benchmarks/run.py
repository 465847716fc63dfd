"""Benchmark harness entry: one function per paper table/figure, plus the
inner-loop microbenchmarks gating perf PRs.  Prints ``name,value,derived``
CSV.  BENCH_STEPS / BENCH_SEEDS env vars control the budget (defaults
keep a full run ~20-30 min on this CPU container; the full-budget numbers
in EXPERIMENTS.md come from the background runs under experiments/).

Select benches by name: ``python benchmarks/run.py [simulator rectify
generation fig4 ...]`` (no args = all).  ``rectify`` + ``generation``
also write machine-readable numbers to BENCH_inner_loop.json next to
this file, so the perf trajectory of the EGRL inner loop is tracked
across PRs."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

STEPS = int(os.environ.get("BENCH_STEPS", "800"))
SEEDS = int(os.environ.get("BENCH_SEEDS", "1"))
# BENCH_JSON redirects the machine-readable output (smoke runs point it
# at a temp file so reduced-budget timings never clobber the tracked
# trajectory numbers)
_JSON_PATH = os.environ.get("BENCH_JSON", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_inner_loop.json"))


def _update_json(section: str, payload: dict, merge: bool = False) -> None:
    """Rewrite one section of BENCH_inner_loop.json atomically.  With
    ``merge=True`` the payload's keys are merged into the existing
    section instead of replacing it — used by bench steps that annotate
    a section another bench owns (bench_zoo_sac -> generation)."""
    data = {}
    try:
        with open(_JSON_PATH) as f:
            data = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        pass   # first run, or a truncated file from an interrupted one
    if merge and isinstance(data.get(section), dict):
        data[section] = {**data[section], **payload}
    else:
        data[section] = payload
    tmp = _JSON_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, _JSON_PATH)   # atomic: no torn writes on Ctrl-C


def _time_evaluate(g, pop: int, reps: int) -> float:
    """us/rollout of the vmapped pop-evaluation on graph g (warm cache)."""
    import jax
    import jax.numpy as jnp
    from repro.memsim.simulator import build_sim_graph, evaluate_population
    from repro.memsim.compiler import compiler_reference

    sg = build_sim_graph(g)
    _, ref = compiler_reference(g)
    maps = jax.random.randint(jax.random.PRNGKey(0), (pop, g.n, 2), 0, 3)
    r = evaluate_population(sg, maps, jnp.float32(ref))
    jax.block_until_ready(r["reward"])
    t0 = time.perf_counter()
    for _ in range(reps):
        r = evaluate_population(sg, maps, jnp.float32(ref))
        jax.block_until_ready(r["reward"])
    return (time.perf_counter() - t0) / reps / pop * 1e6


def bench_simulator() -> None:
    """Microbenchmark: vmapped population evaluation (the inner loop)."""
    from repro.graphs.zoo import resnet50, bert

    for g in (resnet50(), bert()):
        us = _time_evaluate(g, pop=64, reps=5)
        print(f"simulator_rollout_{g.name},{us:.1f},us_per_rollout_pop64")


def bench_rectify() -> None:
    """Inner-loop gate: vmapped rectify+latency+reward per rollout, and
    rectify in isolation, on every zoo graph.  Writes
    BENCH_inner_loop.json (us_per_rollout at pop 64)."""
    import jax
    from repro.graphs.zoo import resnet50, resnet101, bert
    from repro.memsim.simulator import build_sim_graph, rectify

    pop, reps = 64, 20
    payload = {"pop": pop}
    for g in (resnet50(), resnet101(), bert()):
        sg = build_sim_graph(g)
        us_eval = _time_evaluate(g, pop=pop, reps=reps)
        maps = jax.random.randint(jax.random.PRNGKey(0), (pop, g.n, 2), 0, 3)
        rect = jax.jit(jax.vmap(lambda m: rectify(sg, m)))
        jax.block_until_ready(rect(maps))
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(rect(maps))
        us_rect = (time.perf_counter() - t0) / reps / pop * 1e6

        print(f"rectify_{g.name},{us_rect:.1f},us_per_rollout_pop{pop}")
        print(f"evaluate_{g.name},{us_eval:.1f},us_per_rollout_pop{pop}")
        payload[g.name] = {"rectify_us_per_rollout": round(us_rect, 2),
                           "evaluate_us_per_rollout": round(us_eval, 2)}
    _update_json("rectify", payload)


def bench_zoo_eval() -> None:
    """Workload-batch gate: zoo-wide pop-64 evaluation — every graph in
    the registry (including both 1k+-node synthetics) scored over (a)
    ONE flat padded GraphBatch, (b) the size-bucketed BucketedZoo (one
    jitted call per bucket, each padded only to its own size class) and
    (c) the per-graph evaluate_population loop, all on the same
    mappings.  Writes the zoo_eval section of BENCH_inner_loop.json
    (us/rollout, batch + bucket geometry, and the pad_waste_frac gauge
    — the padded-slot fraction the bucketing removes)."""
    import jax
    import jax.numpy as jnp
    from repro.graphs.batch import build_graph_batch
    from repro.graphs.bucketed import BucketedZoo, build_bucketed_zoo
    from repro.graphs.zoo import WORKLOADS
    from repro.memsim.batch import (evaluate_population_bucketed,
                                    evaluate_population_zoo)
    from repro.memsim.simulator import build_sim_graph, evaluate_population

    pop = 64
    reps = max(3, min(10, STEPS // 80))    # BENCH_STEPS scales the loop
    graphs = [f() for f in WORKLOADS.values()]
    assert sum(g.n >= 1000 for g in graphs) >= 2
    assert sum(g.n < 200 for g in graphs) >= 2   # small size classes exist
    gb = build_graph_batch(graphs)
    rollouts = pop * gb.n_graphs
    maps = jax.random.randint(jax.random.PRNGKey(0),
                              (pop, gb.n_graphs, gb.n_max, 2), 0, 3)
    r = evaluate_population_zoo(gb, maps)
    jax.block_until_ready(r["reward"])
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(evaluate_population_zoo(gb, maps)["reward"])
    us_zoo = (time.perf_counter() - t0) / reps / rollouts * 1e6

    # bucketed path on the SAME mappings (bit-exact per-graph scalars)
    bz = build_bucketed_zoo(graphs)
    assert bz.n_buckets >= 2, "mixed-size zoo should bucket"
    bmaps = bz.split_zoo_mappings(maps)
    jax.block_until_ready(
        evaluate_population_bucketed(bz, bmaps)["reward"])
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(
            evaluate_population_bucketed(bz, bmaps)["reward"])
    us_bucketed = (time.perf_counter() - t0) / reps / rollouts * 1e6

    # per-graph loop on the same mappings (the path the batch replaces),
    # scored against the same reference latencies the batch holds
    singles = []
    for i, g in enumerate(graphs):
        sg = build_sim_graph(g)
        singles.append((sg, jnp.float32(gb.ref_latency[i]),
                        maps[:, i, :g.n]))
    for sg, ref, m in singles:
        jax.block_until_ready(evaluate_population(sg, m, ref)["reward"])
    t0 = time.perf_counter()
    for _ in range(reps):
        for sg, ref, m in singles:
            jax.block_until_ready(evaluate_population(sg, m, ref)["reward"])
    us_loop = (time.perf_counter() - t0) / reps / rollouts * 1e6

    waste_flat = BucketedZoo.from_batch(gb).pad_waste_frac()
    waste_bucketed = bz.pad_waste_frac()
    print(f"zoo_eval_batched,{us_zoo:.1f},us_per_rollout_pop{pop}"
          f"_graphs{gb.n_graphs}")
    print(f"zoo_eval_bucketed,{us_bucketed:.1f},us_per_rollout_pop{pop}"
          f"_buckets{bz.n_buckets}")
    print(f"zoo_eval_pergraph_loop,{us_loop:.1f},us_per_rollout_pop{pop}"
          f"_graphs{gb.n_graphs}")
    print(f"zoo_eval_pad_waste,{waste_bucketed:.4f},"
          f"frac_vs_flat_{waste_flat:.4f}")
    _update_json("zoo_eval", {
        "pop": pop,
        "graphs": {g.name: g.n for g in graphs},
        "n_max": gb.n_max,
        "rollouts_per_call": rollouts,
        "batched_us_per_rollout": round(us_zoo, 2),
        "bucketed_us_per_rollout": round(us_bucketed, 2),
        "pergraph_loop_us_per_rollout": round(us_loop, 2),
        "pad_waste_frac": {"flat": round(waste_flat, 4),
                           "bucketed": round(waste_bucketed, 4)},
        "buckets": {
            f"bucket{k}": {"n_max": b.n_max, "w_max": b.w_max,
                           "graphs": list(b.names)}
            for k, b in enumerate(bz.buckets)},
    })


def bench_generation() -> None:
    """Inner-loop gate: ms per EGRL generation (pop 20), EA-only (the
    device-resident EA path) and full EGRL (adds SAC updates)."""
    from repro.core.egrl import EGRLConfig, ZooEGRL
    from repro.graphs.zoo import resnet50, bert

    reps = max(3, min(10, STEPS // 80))
    payload = {"pop": 20}
    for gf in (resnet50, bert):
        g = gf()
        row = {}
        for mode in ("ea", "egrl"):
            algo = ZooEGRL([g], EGRLConfig(seed=0), mode=mode)
            for _ in range(2):
                algo.generation()          # compile + warmup
            t0 = time.perf_counter()
            for _ in range(reps):
                algo.generation()
            ms = (time.perf_counter() - t0) / reps * 1e3
            print(f"generation_{mode}_{g.name},{ms:.1f},ms_per_generation")
            row[f"{mode}_ms_per_generation"] = round(ms, 2)
        payload[g.name] = row
    # merge: a standalone `run.py generation` refresh must not delete
    # the zoo_sac keys bench_zoo_sac merged into this section (the
    # bench-check gate requires them)
    _update_json("generation", payload, merge=True)


def bench_zoo_sac() -> None:
    """Zoo-SAC gate: ms per zoo-wide batched SAC update call — ZooSAC
    trains against all three paper workloads at once, one jitted
    update_scan per call (`steps` gradient steps, each on a (G, B)
    replay batch spanning the zoo).  Merges ``zoo_sac_ms`` (+ a
    ``zoo_sac`` detail row) into the ``generation`` section of
    BENCH_inner_loop.json so the SAC cost trajectory sits next to the
    per-graph ``egrl_ms_per_generation`` it amortizes."""
    from repro.core.egrl import EGRLConfig, ZooEGRL
    from repro.graphs.zoo import bert, resnet50, resnet101

    reps = max(3, min(10, STEPS // 80))
    # pop 8 keeps one update call (pop+1 gradient steps over the padded
    # (G, B, N_max=bert) grid) a few seconds on the CPU container while
    # still covering the full three-graph paper zoo
    cfg = EGRLConfig(pop_size=8, seed=0)
    graphs = [resnet50(), resnet101(), bert()]
    algo = ZooEGRL(graphs, cfg, mode="egrl")
    steps = cfg.pop_size + cfg.pg_rollouts     # rollout rows per generation
    # warmup: fill the bank until the first learner update has run (and
    # compiled the scan) — sac.batch transitions need ceil(batch/steps)
    # generations
    for _ in range(8):
        rec = algo.generation()
        if "critic_loss" in rec:
            break
    assert "critic_loss" in rec, "bank never warmed up"

    t0 = time.perf_counter()
    gen_reps = max(2, reps // 2)
    for _ in range(gen_reps):
        algo.generation()          # full hybrid generation (incl. update)
    gen_ms = (time.perf_counter() - t0) / gen_reps * 1e3

    t0 = time.perf_counter()
    for _ in range(reps):
        algo.learner.update(algo.bank, steps)   # the batched learner alone
    ms = (time.perf_counter() - t0) / reps * 1e3

    print(f"zoo_sac_update,{ms:.1f},ms_per_update_call_steps{steps}"
          f"_graphs{algo.n_graphs}")
    print(f"generation_egrl_zoo,{gen_ms:.1f},ms_per_generation"
          f"_graphs{algo.n_graphs}")
    _update_json("generation", {
        "zoo_sac_ms": round(ms, 2),
        "zoo_sac": {
            "pop": cfg.pop_size,
            "graphs": {g.name: g.n for g in graphs},
            "update_steps_per_call": steps,
            "sac_batch": algo.cfg.sac.batch,
            "egrl_zoo_ms_per_generation": round(gen_ms, 2),
        },
    }, merge=True)


def bench_gat() -> None:
    """GAT backend gate: per-shape fwd and fwd+bwd timings of every
    non-materializing backend candidate (the autotune set of
    core/gat_tune.py) plus the dense jnp oracle, at the GNN's training
    width (hidden 128, 4 heads) over the distinct zoo graph sizes.
    Writes the ``gat`` section of BENCH_inner_loop.json: which backend
    ``auto`` resolves to per shape and the timings that justified it —
    an audit record, never a pass/fail timing gate."""
    import jax
    import jax.numpy as jnp
    from repro.core import gat_tune, gnn
    from repro.graphs.zoo import WORKLOADS

    sizes = sorted({f().n for f in WORKLOADS.values()})
    if STEPS < 200:        # smoke budget: timing dense jnp fwd+bwd on the
        dropped = [n for n in sizes if n >= 500]    # 1k-node graphs costs
        sizes = [n for n in sizes if n < 500]       # minutes on 2 CPU cores
        print(f"gat_sizes_skipped,{len(dropped)},reduced_budget_"
              f"{'_'.join(f'n{n}' for n in dropped)}")
    payload = {"hidden": gnn.HIDDEN, "heads": gnn.HEADS,
               "platform": jax.default_backend(), "shapes": {}}
    for n in sizes:
        res = gat_tune.autotune(n, gnn.HIDDEN, gnn.HEADS, jnp.float32,
                                include_dense=True, force_time=True)
        chosen = gat_tune._label(res.backend, res.chunk)
        for label, row in sorted(res.timings.items()):
            print(f"gat_{label}_n{n},{row['fwd_bwd_us']:.1f},"
                  f"us_fwd_bwd_fwd{row['fwd_us']:.1f}")
        print(f"gat_chosen_n{n},{chosen},autotuned_backend")
        payload["shapes"][f"n{n}"] = {"chosen": chosen,
                                      "candidates": res.timings}
    _update_json("gat", payload)


def _pop_sharding_child() -> None:
    """Child body for bench_pop_sharding: time EA-mode generations with
    the population sharded over every visible device, print one
    machine-readable line.  Runs in a subprocess because the host device
    count (XLA_FLAGS) is fixed at first jax init."""
    import jax
    from repro.core.egrl import EGRLConfig, ZooEGRL
    from repro.graphs.zoo import resnet50

    n_dev = len(jax.devices())
    reps = max(3, min(10, STEPS // 80))
    # pop 64 split 48/16 so every mesh size in (1, 2, 4) divides both
    cfg = EGRLConfig(pop_size=64, boltzmann_frac=0.25, elites=8, seed=0)
    algo = ZooEGRL([resnet50()], cfg, mode="ea", pop_shards=n_dev)
    for _ in range(2):
        algo.generation()              # compile + warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        algo.generation()
    ms = (time.perf_counter() - t0) / reps * 1e3
    print("POPCHILD " + json.dumps(
        {"mesh": n_dev, "shards": algo.pop_sharding.n_shards,
         "ea_ms_per_generation": round(ms, 2)}))


def bench_pop_sharding() -> None:
    """Scaling gate: EA generation time vs ("pop",) mesh size (pop 64 on
    resnet50, forced-host-device CPU meshes).  Each mesh size runs in a
    subprocess (the device count must be set before jax initializes);
    a failing child aborts the bench instead of recording partial data."""
    payload = {"pop": 64, "graph": "resnet50", "mode": "ea"}
    for n in (1, 2, 4):
        env = dict(os.environ,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
                   JAX_PLATFORMS="cpu",   # forced host devices are CPU-only
                   BENCH_POP_CHILD="1")
        out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                             env=env, capture_output=True, text=True,
                             timeout=1800)
        lines = [l for l in out.stdout.splitlines()
                 if l.startswith("POPCHILD ")]
        if out.returncode != 0 or not lines:
            raise RuntimeError(
                f"pop_sharding child (mesh={n}) failed "
                f"(exit {out.returncode}):\n{out.stderr[-2000:]}")
        row = json.loads(lines[-1][len("POPCHILD "):])
        if row["mesh"] != n or row["shards"] != n:
            raise RuntimeError(
                f"pop_sharding child saw {row['mesh']} device(s) / "
                f"{row['shards']} shard(s) instead of {n} — timings would "
                f"be recorded under the wrong mesh key")
        print(f"generation_ea_pop64_mesh{n}_resnet50,"
              f"{row['ea_ms_per_generation']},ms_per_generation")
        payload[f"mesh{n}"] = row
    _update_json("pop_sharding", payload)


def _bucket_dispatch_child() -> None:
    """Child body for bench_bucket_dispatch: serial vs async bucket
    dispatch on a forced multi-device CPU mesh (the device count is
    fixed at first jax init, hence the subprocess).  Prints one
    machine-readable DISPATCHCHILD line with the per-bucket time
    breakdown, the serial/async pipeline times, the end-to-end
    generation times, the bitwise-identity verdict, and the autotuned
    bucket K."""
    import numpy as np

    import jax
    from repro.core import gnn
    from repro.core.egrl import EGRLConfig, ZooEGRL
    from repro.distributed.dispatch import autotune_bucket_k
    from repro.graphs.bucketed import bucket_keys_batch
    from repro.graphs.zoo import WORKLOADS, bert, resnet50, tiny_gpt
    from repro.memsim.batch import evaluate_population_bucketed

    n_dev = len(jax.devices())
    reps = max(2, min(6, STEPS // 160))
    if STEPS >= 200:
        graphs = [f() for f in WORKLOADS.values()]   # full registry zoo
    else:
        graphs = [resnet50(), bert(), tiny_gpt()]    # smoke: 3 classes
    cfg = EGRLConfig(pop_size=8, boltzmann_frac=0.25, elites=2, seed=0)
    serial = ZooEGRL(graphs, cfg, mode="ea", pop_shards="off",
                     dispatch="off")
    asyncd = ZooEGRL(graphs, cfg, mode="ea", pop_shards="off",
                     dispatch="async")
    assert serial.dispatch is None and asyncd.dispatch is not None

    # warmup compiles both paths AND checks per-generation bit-identity
    for _ in range(2):
        rs, ra = serial.generation(), asyncd.generation()
        assert rs["best_fitness"] == ra["best_fitness"]

    t0 = time.perf_counter()
    for _ in range(reps):
        serial.generation()
    serial_gen_ms = (time.perf_counter() - t0) / reps * 1e3
    t0 = time.perf_counter()
    for _ in range(reps):
        asyncd.generation()
    async_gen_ms = (time.perf_counter() - t0) / reps * 1e3
    # equal generation counts -> the full trajectories must still agree
    bit_identical = bool(
        np.array_equal(serial.best_reward, asyncd.best_reward)
        and all(np.array_equal(ms, ma) for ms, ma in
                zip(serial.best_mapping, asyncd.best_mapping)))

    # rollout+evaluate pipeline in isolation, one block at the end:
    # serial issues all K bucket chains on ONE device, async fans them
    # out — the structural claim the gate checks
    dsp = asyncd.dispatch
    pop = asyncd.gnn_pop
    keys = jax.random.split(jax.random.PRNGKey(1), pop.shape[0])

    bkeys = bucket_keys_batch(keys, serial.zoo.n_buckets)
    sample = jax.jit(jax.vmap(gnn.sample_actions))

    def async_pipe():
        lg = dsp.forward(pop)
        maps = dsp.sample(bkeys, lg)
        jax.block_until_ready(dsp.evaluate(maps, cfg.reward_scale)["reward"])

    def serial_pipe():
        lgs = [f(serial.gnn_pop) for f in serial._pop_logits]
        maps = tuple(sample(kc, lg) for kc, lg in zip(bkeys, lgs))
        jax.block_until_ready(evaluate_population_bucketed(
            serial.zoo, maps, cfg.reward_scale)["reward"])

    async_pipe()
    serial_pipe()                            # warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        async_pipe()
    async_ms = (time.perf_counter() - t0) / reps * 1e3
    t0 = time.perf_counter()
    for _ in range(reps):
        serial_pipe()
    serial_ms = (time.perf_counter() - t0) / reps * 1e3

    per_bucket = dsp.measure(pop, reward_scale=cfg.reward_scale,
                             reps=reps)
    k = autotune_bucket_k(graphs, pop=4, reps=1)
    print("DISPATCHCHILD " + json.dumps({
        "mesh": n_dev,
        "buckets": asyncd.zoo.n_buckets,
        "graphs": len(graphs),
        "pop": cfg.pop_size,
        "reps": reps,
        # smoke rows (3 tiny graphs) are schema-gated only: per-bucket
        # compute is so small that cross-device staging, not overlap,
        # decides the pipeline relation — bench_check gates the timing
        # RELATIONS on full-size rows (the tracked JSON)
        "smoke": STEPS < 200,
        "device_map": {f"bucket{b}": d
                       for b, d in dsp.device_map().items()},
        "per_bucket_ms": {f"bucket{b}": round(v, 3)
                          for b, v in sorted(per_bucket.items())},
        "per_bucket_sum_ms": round(sum(per_bucket.values()), 3),
        "serial_ms": round(serial_ms, 3),
        "async_ms": round(async_ms, 3),
        "serial_gen_ms": round(serial_gen_ms, 3),
        "async_gen_ms": round(async_gen_ms, 3),
        "bit_identical": bit_identical,
        "autotuned_k": k,
    }))


def _multi_slot_probe(seed: int = 0) -> dict:
    """Multi-slot pool SLO (``slots="thread:2"``): two queued size
    classes refine CONCURRENTLY — both slots' spans land in the gated
    taxonomy with per-slot attribution, everything drains, and nothing
    fails.  bench_check gates the structure (slots_used == 2, both
    classes dispatched+drained, failed == 0), never timings."""
    from repro import obs
    from repro.graphs.extract import extract_for
    from repro.serving.placement_service import (PlacementRequest,
                                                 PlacementService)

    shape = "decode_32k"
    archs = ["seamless-m4t-medium", "qwen3-0.6b"]   # classes 128 + 256
    with obs.override(mode="mem"):
        svc = PlacementService(seed=seed, slots="thread:2", budget=2,
                               nn="off")
        for i, a in enumerate(archs):
            assert svc.submit(PlacementRequest(i, a, shape),
                              graph=extract_for(a, shape)) is None
        t0 = time.perf_counter()
        drained = svc.run_until_drained()
        wall_ms = (time.perf_counter() - t0) * 1e3
        stats = svc.stats()
        events = obs.events()
    assert len(drained) == len(archs) and all(r.ok for r in drained)
    disp = [e for e in events if e["name"] == "slot_dispatch"]
    drains = [e for e in events if e["name"] == "slot_drain"]
    classes = sorted(e["attrs"]["n_class"] for e in disp)
    return {
        "slots": "thread:2",
        "n_slots": svc.n_slots,
        "classes": classes,
        "slots_used": len({e["attrs"]["slot"] for e in disp}),
        "slots_drained": len({e["attrs"]["slot"] for e in drains}),
        "drain_wall_ms": round(wall_ms, 3),
        "served": stats["served"],
        "failed": stats["failed"],
        "span_names": sorted({e["name"] for e in events}),
    }


def bench_bucket_dispatch() -> None:
    """Bucket-dispatch gate (PR 10): serial-vs-async generation and
    pipeline times plus the per-bucket breakdown on a forced-8-device
    CPU mesh (subprocess — the device count is fixed at first jax
    init), and the multi-slot placement-service probe (``thread:2``).
    Writes the ``bucket_dispatch`` section of BENCH_inner_loop.json;
    tools/bench_check.py gates STRUCTURE only — async pipeline <
    sum-of-blocked-buckets, the per-bucket sum within a loose factor of
    the serial pipeline, bitwise-identical rewards, multi-slot
    failed == 0 — never absolute timings."""
    n = 8
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               JAX_PLATFORMS="cpu",   # forced host devices are CPU-only
               BENCH_DISPATCH_CHILD="1")
    for k in ("REPRO_POP_SHARDS", "REPRO_MODEL_SHARDS",
              "REPRO_BUCKET_DISPATCH", "REPRO_ZOO_BUCKETS"):
        env.pop(k, None)
    out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env, capture_output=True, text=True,
                         timeout=1800)
    lines = [l for l in out.stdout.splitlines()
             if l.startswith("DISPATCHCHILD ")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"bucket_dispatch child (mesh={n}) failed "
            f"(exit {out.returncode}):\n{out.stderr[-2000:]}")
    row = json.loads(lines[-1][len("DISPATCHCHILD "):])
    if row["mesh"] != n:
        raise RuntimeError(
            f"bucket_dispatch child saw {row['mesh']} device(s) instead "
            f"of {n} — timings would be recorded under the wrong mesh")
    if not row["bit_identical"]:
        raise RuntimeError(
            "async dispatch diverged from the serial trajectory — "
            "refusing to record timings for a wrong result")
    row["multi_slot"] = _multi_slot_probe(seed=0)

    print(f"dispatch_async_pipeline,{row['async_ms']},"
          f"ms_serial_{row['serial_ms']}_buckets{row['buckets']}")
    print(f"dispatch_bucket_sum,{row['per_bucket_sum_ms']},"
          f"ms_blocked_per_bucket_mesh{row['mesh']}")
    print(f"dispatch_generation_async,{row['async_gen_ms']},"
          f"ms_serial_{row['serial_gen_ms']}")
    print(f"dispatch_bit_identical,{int(row['bit_identical'])},"
          f"rewards_and_mappings")
    print(f"dispatch_autotuned_k,{row['autotuned_k']},"
          f"buckets_octave_{row['buckets']}")
    ms = row["multi_slot"]
    print(f"dispatch_multi_slot,{ms['slots_used']},"
          f"classes_{'_'.join(map(str, ms['classes']))}"
          f"_failed{ms['failed']}")
    _update_json("bucket_dispatch", row)


def _obs_overhead(svc, results, reps: int = 25) -> dict:
    """Hit-path tracing tax: replay one cached (arch, shape) through the
    warmed service ``reps`` times each with tracing off and with the
    full jsonl sink on (alternating, so drift hits both arms), and
    report the p50 pair + relative overhead.  bench_check gates
    ``overhead_frac`` structurally (< 0.2), never the absolute times."""
    import tempfile

    import numpy as np

    from repro import obs
    from repro.serving.placement_service import PlacementRequest

    hit = next(r for r in results if r.ok)
    on, off = [], []
    fd, tmp = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        rid = 10 ** 6                      # clear of the stream's ids
        for _ in range(reps):
            for bucket, kw in ((off, {"mode": "off"}),
                               (on, {"mode": "jsonl", "path": tmp})):
                with obs.override(**kw):
                    t0 = time.perf_counter()
                    r = svc.submit(PlacementRequest(rid, hit.arch, hit.shape))
                    bucket.append((time.perf_counter() - t0) * 1e3)
                assert r is not None and r.cache_hit, \
                    "overhead probe must stay on the cache-hit path"
                rid += 1
    finally:
        os.unlink(tmp)
    p50_on = float(np.percentile(on, 50))
    p50_off = float(np.percentile(off, 50))
    return {"hit_p50_obs_on_ms": round(p50_on, 4),
            "hit_p50_obs_off_ms": round(p50_off, 4),
            "overhead_frac": round(p50_on / max(p50_off, 1e-9) - 1.0, 4),
            "reps": reps, "mode_on": "jsonl"}


def _variant(g, scale: float, only_node=None):
    """A same-size-class copy of ``g`` with ``weight_bytes`` scaled on
    one node (``only_node``, the nearest-neighbor probe: most WL sketch
    slots survive) or on EVERY node (a cold miss: all labels change, so
    the sketch shares ~no slots with the original)."""
    import dataclasses
    return dataclasses.replace(g, nodes=tuple(
        dataclasses.replace(nd, weight_bytes=nd.weight_bytes * scale + 1.0)
        if (only_node is None or i == only_node) else nd
        for i, nd in enumerate(g.nodes)))


def _concurrent_probe(seed: int = 0) -> dict:
    """Concurrent-load serve mode: measure the cache-hit path p99
    DURING an in-flight miss batch (``slots=thread``), plus the
    nearest-neighbor and restart-from-persisted-cache SLOs.
    tools/bench_check.py gates only structural relations on this dict
    (hit p99 during a miss < the miss batch itself, neighbor speedup
    >= 1, a restarted service answers without the evaluator) — never
    absolute timings."""
    import tempfile

    import numpy as np

    from repro.graphs.extract import extract_for
    from repro.memsim.compiler import compiler_reference
    from repro.serving.placement_service import (PlacementRequest,
                                                 PlacementService)

    archs = ["qwen3-0.6b", "mamba2-780m", "zamba2-1.2b", "granite-3-8b"]
    shape = "decode_32k"
    graphs = {a: extract_for(a, shape) for a in archs}

    svc = PlacementService(seed=seed, slots="thread", budget=8, nn="off")
    warm = svc.run([PlacementRequest(i, a, shape)
                    for i, a in enumerate(archs)])
    assert all(r.ok for r in warm), "warm-up must serve cleanly"

    # idle baseline: the hit path with nothing in flight
    rid = 10 ** 6
    idle = []
    for _ in range(30):
        r = svc.submit(PlacementRequest(rid, archs[0], shape))
        assert r is not None and r.cache_hit
        idle.append(r.wall_ms)
        rid += 1
    idle_p50 = float(np.percentile(idle, 50))

    # miss batch in flight: submit batch_max cold variants (every node
    # rescaled -> new hash, no near neighbor), dispatch, and hammer the
    # hit path until the worker finishes.  If the batch lands before we
    # collect a stable sample, escalate the budget and retry.
    during, miss_batch_ms, attempt = [], 0.0, 0
    while attempt < 3:
        attempt += 1
        svc.budget = 8 * (2 ** attempt)
        cold = [_variant(graphs[a], 1.25 + 0.125 * (10 * attempt + j))
                for j, a in enumerate(archs)]
        t_batch = time.perf_counter()
        for g in cold:
            assert svc.submit(PlacementRequest(rid, "cold", shape),
                              graph=g) is None, "cold variant must miss"
            rid += 1
        svc.tick()                         # dispatch the slot
        during = []
        while svc._slot is not None and not svc._slot.finished \
                and len(during) < 400:
            r = svc.submit(PlacementRequest(rid, archs[0], shape))
            assert r is not None and r.cache_hit, \
                "hit path must keep streaming during refinement"
            during.append(r.wall_ms)
            rid += 1
            time.sleep(0.002)
        drained = svc.run_until_drained()
        miss_batch_ms = (time.perf_counter() - t_batch) * 1e3
        assert all(r.ok for r in drained), "miss batch must serve"
        if len(during) >= 5:
            break
    assert during, "no hit landed during the in-flight miss batch"

    # nearest-neighbor SLO: warm an egrl-sourced entry (escalating the
    # budget until refinement beats the compiler), then serve a
    # one-node-perturbed variant — it must come back ``neighbor``
    # sourced, never worse than the compiler, and cheaper than a cold
    # miss at the same budget.
    nn = {}
    persist_dir = tempfile.mkdtemp(prefix="serve_persist_")
    for nn_budget in (8, 16, 32, 64):
        svc2 = PlacementService(seed=seed, budget=nn_budget)
        base = svc2.run([PlacementRequest(0, archs[0], shape)])[0]
        if base.source != "egrl":
            continue
        g = graphs[archs[0]]
        # pre-warm the rescore executable so the timed neighbor hit
        # measures the steady state, not the one-off jit compile
        svc2._rescore_neighbor(g, compiler_reference(g)[0])
        near = _variant(g, 1.001, only_node=g.n // 2)
        r = svc2.submit(PlacementRequest(1, "near", shape), graph=near)
        assert r is not None and r.nn_hit and r.source == "neighbor", \
            "near variant must serve from the neighbor cache"
        nn = {"nn_budget": nn_budget, "nn_hit_ms": round(r.wall_ms, 3),
              "nn_speedup": round(r.speedup, 4)}
        # cold miss at the SAME budget on the warmed service
        cold_g = _variant(g, 3.5)
        miss = svc2.submit(PlacementRequest(3, "cold", shape),
                           graph=cold_g)
        assert miss is None
        t0 = time.perf_counter()
        svc2.run_until_drained()
        nn["cold_miss_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        svc2.persist_dir = persist_dir   # attached only on success: an
        svc2.persist()                   # escalation retry must refine
        break                            # fresh, not reload a cold cache
    assert nn, "no budget produced an egrl-sourced neighbor seed"

    # restart from the persisted cache: previously-seen graphs answer
    # without touching the evaluator
    svc3 = PlacementService(seed=seed, persist=persist_dir)
    r = svc3.submit(PlacementRequest(0, archs[0], shape))
    restart_hits = int(r is not None and r.cache_hit
                       and svc3.evaluator_calls == 0)
    assert restart_hits == 1, \
        "restarted service must answer seen graphs from the cache"
    import shutil
    shutil.rmtree(persist_dir, ignore_errors=True)

    p99_during = float(np.percentile(during, 99))
    return {
        "slots": "thread",
        "idle_hit_p50_ms": round(idle_p50, 4),
        "hit_p50_during_miss_ms": round(
            float(np.percentile(during, 50)), 4),
        "hit_p99_during_miss_ms": round(p99_during, 4),
        "hits_during_miss": len(during),
        "miss_batch_ms": round(miss_batch_ms, 3),
        "miss_distinct": len(archs),
        "budget": svc.budget,
        "hit_p99_over_idle_p50": round(p99_during / max(idle_p50, 1e-9),
                                       3),
        **nn,
        "restart_hits": restart_hits,
    }


def bench_serve() -> None:
    """Serving gate: placement-as-a-service SLOs over a seeded synthetic
    request stream (launch/serve_placements.py) — p50/p99
    time-to-placement split by cache hit/miss, placements/sec, cache
    hit rate, placement quality, and the hit-path tracing overhead
    (obs on vs off on the warmed service) — plus the concurrent-load
    mode (``_concurrent_probe``): hit-path p99 DURING an in-flight
    miss batch, neighbor-cache and persisted-restart SLOs.  Writes the
    ``serve`` section of BENCH_inner_loop.json; tools/bench_check.py
    gates its SHAPE (and the hit-p50 <= miss-p50 relation plus the
    obs-overhead bound), never absolute timings.  The smoke budget
    (BENCH_STEPS < 200) trims the stream and pins the catalog to one
    canonical size class so the run stays in seconds."""
    from repro.launch.serve_placements import serve, synthetic_stream

    if STEPS >= 200:
        n_req, archs = 50, None            # the full registry catalog
    else:
        n_req = 12
        archs = ["qwen3-0.6b", "mamba2-780m", "zamba2-1.2b",
                 "granite-3-8b", "qwen2.5-14b"]
    reqs = synthetic_stream(n_req, seed=0, archs=archs)
    results, summary, svc = serve(reqs, seed=0, log=None)
    assert len({r.arch for r in reqs}) >= 5, "stream must span >=5 archs"
    assert summary["failed"] == 0, "synthetic catalog must serve cleanly"
    summary["obs_overhead"] = _obs_overhead(svc, results)
    summary["concurrent"] = _concurrent_probe(seed=0)

    print(f"serve_requests,{summary['requests']},"
          f"archs{summary['archs']}_budget{summary['budget']}")
    print(f"serve_hit_rate,{summary['hit_rate']},"
          f"hits{summary['cache_hits']}_misses{summary['cache_misses']}")
    print(f"serve_hit_p50,{summary['hit_p50_ms']},"
          f"ms_p99_{summary['hit_p99_ms']}")
    print(f"serve_miss_p50,{summary['miss_p50_ms']},"
          f"ms_p99_{summary['miss_p99_ms']}")
    print(f"serve_throughput,{summary['placements_per_sec']},"
          f"placements_per_sec")
    print(f"serve_mean_speedup,{summary['mean_speedup']},"
          f"egrl_frac_{summary['egrl_frac']}")
    ov = summary["obs_overhead"]
    print(f"serve_obs_overhead,{ov['overhead_frac']},"
          f"hit_p50_on{ov['hit_p50_obs_on_ms']}_off{ov['hit_p50_obs_off_ms']}")
    cc = summary["concurrent"]
    print(f"serve_hit_p99_during_miss,{cc['hit_p99_during_miss_ms']},"
          f"ms_idle_p50_{cc['idle_hit_p50_ms']}"
          f"_x{cc['hit_p99_over_idle_p50']}")
    print(f"serve_miss_batch,{cc['miss_batch_ms']},"
          f"ms_hits_streamed_{cc['hits_during_miss']}")
    print(f"serve_nn_hit,{cc['nn_hit_ms']},"
          f"ms_speedup_{cc['nn_speedup']}_cold_{cc['cold_miss_ms']}")
    print(f"serve_restart_hits,{cc['restart_hits']},from_persisted_cache")
    _update_json("serve", summary)


def bench_fig4() -> None:
    from fig4_speedup import run as fig4
    fig4(steps=STEPS, seeds=tuple(range(SEEDS)), log=lambda m: print(m))


def bench_fig5() -> None:
    from fig5_generalization import run as fig5
    fig5(steps=STEPS, log=lambda m: print(m))


def bench_fig7() -> None:
    from map_shift import run as fig7
    fig7(steps=STEPS, log=lambda m: print(m))


def bench_arch_placement() -> None:
    """Beyond-paper: EGRL placement on assigned-architecture graphs."""
    from repro.launch.optimize_placement import optimize
    for arch, shape in (("granite-3-8b", "decode_32k"),
                        ("qwen3-moe-30b-a3b", "decode_32k"),
                        ("mamba2-780m", "long_500k")):
        plan, _ = optimize(arch, shape, steps=min(STEPS, 600), log=None)
        print(f"placement_{arch}_{shape},{plan['speedup_vs_compiler']:.3f},"
              f"speedup_vs_compiler")


def bench_roofline() -> None:
    from roofline import load
    rows = load("experiments/dryrun")
    if not rows:
        print("roofline,skipped,run launch/dryrun.py first")
        return
    for r in rows:
        if r["mesh"] == "16x16":
            print(f"roofline_{r['arch']}_{r['shape']},"
                  f"{r['roofline_fraction']:.3f},dominant={r['dominant']}")


BENCHES = {
    "simulator": bench_simulator,
    "rectify": bench_rectify,
    "zoo_eval": bench_zoo_eval,
    "generation": bench_generation,
    "zoo_sac": bench_zoo_sac,
    "gat": bench_gat,
    "pop_sharding": bench_pop_sharding,
    "serve": bench_serve,
    "bucket_dispatch": bench_bucket_dispatch,
    "fig4": bench_fig4,
    "fig5": bench_fig5,
    "fig7": bench_fig7,
    "arch_placement": bench_arch_placement,
    "roofline": bench_roofline,
}
# "inner_loop" = the fast microbenchmark set used by benchmarks/smoke.sh.
# generation and zoo_sac both merge into the shared "generation"
# section, so either can be refreshed standalone.
GROUPS = {"inner_loop": ("rectify", "zoo_eval", "generation", "zoo_sac",
                         "gat", "pop_sharding", "serve",
                         "bucket_dispatch")}


def main(argv=None) -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if os.environ.get("BENCH_POP_CHILD"):
        _pop_sharding_child()
        return
    if os.environ.get("BENCH_DISPATCH_CHILD"):
        _bucket_dispatch_child()
        return
    argv = sys.argv[1:] if argv is None else argv
    names = []
    for a in argv:
        names += list(GROUPS.get(a, (a,)))
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        sys.exit(f"unknown bench(es) {unknown}; "
                 f"choose from {sorted(BENCHES) + sorted(GROUPS)}")
    t0 = time.time()
    print("name,value,derived")
    # every requested bench runs; a raising step is reported and turned
    # into a non-zero exit instead of silently truncating the run (and
    # with it BENCH_inner_loop.json)
    failed = []
    for name in (names or list(BENCHES)):
        try:
            BENCHES[name]()
        except Exception:
            traceback.print_exc()
            print(f"{name},FAILED,see_traceback_on_stderr")
            failed.append(name)
    print(f"total_wall_s,{time.time() - t0:.0f},")
    if failed:
        sys.exit(f"bench step(s) failed: {failed} — recorded sections in "
                 f"{_JSON_PATH} are partial for this run")


if __name__ == "__main__":
    main()

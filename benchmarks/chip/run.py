#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload paper_zoo.egrl --seed 7 \
        --seconds 30 --trace 0

A run builds the cell's ``ZooEGRL`` the way ``launch/train_zoo.py`` does,
installs initial weights made from ``--seed`` on the device, warms up
until the SAC learner has updated and a generation ran without
compiling, then times back-to-back ``generation()`` calls for
``--seconds`` (``--trace 0``) or traces a few generations with the
profiler (``--trace 1``).  After the window it checks what the timed
path produced against ``reference.py`` (see ``correct.py``).

Earlier lines of standard output break the set-up down; the last line
is the result as one JSON object.  The numbers compared, each beside
its limit, are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402


class NoChip(RuntimeError):
    pass


def say(tag: str, **fields) -> None:
    print(f"{tag}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), caching every program so
    that only a checkout's first run of a cell compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations and persistent-cache hits and misses, from
    ``jax.monitoring`` events (one per process: listeners stay
    registered)."""

    _one = None

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.n = {self.COMPILE: 0, self.HIT: 0, self.MISS: 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, name, **_):
        if name in self.n:
            self.n[name] += 1

    def _dur(self, name, secs, **_):
        if name == self.COMPILE:
            self.n[name] += 1
            self.compile_s += secs

    @property
    def compiles(self) -> int:
        return self.n[self.COMPILE]


def _unflatten_like(tree, vec):
    import jax
    leaves, treedef = jax.tree.flatten(tree)
    out, off = [], 0
    for x in leaves:
        out.append(vec[off:off + x.size].reshape(x.shape).astype(x.dtype))
        off += x.size
    if off != vec.shape[0]:
        raise ValueError(f"{vec.shape[0]} weights for a tree of {off}")
    return jax.tree.unflatten(treedef, out)


def build(cell, seed: int):
    """The cell's ZooEGRL with initial weights made from the seed."""
    import jax
    import reference as ref
    from repro.core import gnn
    from repro.core.egrl import EGRLConfig, ZooEGRL
    from repro.core.sac import SACConfig
    from repro.graphs.zoo import WORKLOADS

    conf, traffic = cell.config, cell.traffic
    pol = conf["policy"]
    if (gnn.HIDDEN, gnn.HEADS, gnn.DEPTH) != (pol["hidden"], pol["heads"],
                                               pol["depth"]):
        raise ValueError(f"policy widths {pol} differ from the program's "
                         f"{(gnn.HIDDEN, gnn.HEADS, gnn.DEPTH)}")
    graphs = {name: WORKLOADS[name]() for name in conf["graphs"]}
    for name, n in conf["graphs"].items():
        if graphs[name].n != n:
            raise ValueError(f"{name} has {graphs[name].n} nodes, the "
                             f"configuration states {n}")
    # the GAT backend the configuration states, through the program's
    # own option (read when each GAT shape is first traced)
    os.environ["REPRO_GAT_BACKEND"] = conf["gat_backend"]
    cfg = EGRLConfig(**conf["egrl"], sac=SACConfig(**conf["sac"]),
                     seed=seed % 2 ** 31)
    algo = ZooEGRL(list(graphs.values()), cfg, mode=traffic["mode"],
                   fitness_agg=traffic["fitness_agg"],
                   buckets=conf["buckets"])
    zoo = algo.zoo
    got = [[b.n_max, b.w_max] for b in zoo.buckets]
    if got != conf["expected_buckets"]:
        raise ValueError(f"size buckets {got} differ from the configured "
                         f"{conf['expected_buckets']}")
    if algo.n_g_pad != algo.n_g or algo.n_b_pad != algo.n_b:
        raise ValueError("a padded (sharded) population is not a one-chip "
                         "configuration")
    gpop, bpop, actor, critic = ref.make_weights(
        seed % 2 ** 31, n_features=zoo.n_features, n_gnn=algo.n_g,
        n_bz=algo.n_b, bz_nodes=algo.n_eff)
    if gpop.shape != algo.gnn_pop.shape or bpop.shape != algo.bz_pop.shape:
        raise ValueError(f"genome shapes {gpop.shape}/{bpop.shape} differ "
                         f"from the program's {algo.gnn_pop.shape}/"
                         f"{algo.bz_pop.shape}")
    algo.gnn_pop, algo.bz_pop = gpop, bpop
    if algo.learner is not None:
        algo.learner.actor = _unflatten_like(algo.learner.actor, actor)
        algo.learner.critic = _unflatten_like(algo.learner.critic, critic)
    jax.block_until_ready((algo.gnn_pop, algo.bz_pop))
    return algo, [graphs[n] for n in zoo.names]


def _finite(rec) -> bool:
    return math.isfinite(rec["gen_mean_fitness"]) and math.isfinite(
        rec["gen_best_fitness"])


def trace_context(cell, algo, reduced, generations, peaks):
    """What the per-layer metric readers read (see metrics/)."""
    import flops
    from repro.core import gnn
    cfg, zoo = algo.cfg, algo.zoo
    sizes = list(zoo.real_sizes())
    sac_steps = (algo.n_g + algo.n_b + cfg.pg_rollouts
                 if algo.learner is not None else 0)
    pg_rows = cfg.pg_rollouts if algo.learner is not None else 0
    buckets = [(b.n_max, [sizes[i] for i in range(zoo.n_graphs)
                          if zoo.graph_bucket[i] == k])
               for k, b in enumerate(zoo.buckets)]

    class Ctx:
        pass

    ctx = Ctx()
    ctx.reduced = reduced
    ctx.generations = generations
    ctx.peaks = peaks
    ctx.gen_flops = flops.generation_flops(
        sizes, zoo.n_features, gnn_rows=algo.n_g, pg_rows=pg_rows,
        sac_steps=sac_steps, batch=cfg.sac.batch)
    ctx.gat_calls = flops.gat_kernel_calls(
        buckets, gnn_rows=algo.n_g, pg_rows=pg_rows, sac_steps=sac_steps,
        batch=cfg.sac.batch,
        backend_of=lambda n: gnn.resolve_backend(None, n=n))
    return ctx


def run(cell, seed: int, seconds: float, trace: bool, **kw) -> dict:
    """One run of ``cell`` (see ``_run``), leaving the process's
    environment as it found it."""
    prev = os.environ.get("REPRO_GAT_BACKEND")
    try:
        return _run(cell, seed, seconds, trace, **kw)
    finally:
        if prev is None:
            os.environ.pop("REPRO_GAT_BACKEND", None)
        else:
            os.environ["REPRO_GAT_BACKEND"] = prev


def _run(cell, seed: int, seconds: float, trace: bool, *,
         require_tpu: bool = True, fault=None, variants=(),
         cache: bool = True) -> dict:
    """One run of ``cell``.  ``fault`` (tests only) breaks the built
    program before the run; ``variants`` (the control script) adds the
    readings of those correct.VARIANTS, and every number of the
    program's own (the limits compare only some), under
    ``result["variants"]`` (``("program",)`` adds only the program's); ``cache`` False (tests) leaves JAX's
    compilation cache settings alone."""
    import jax
    cache = enable_compile_cache(cell.root) if cache else None
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d0.platform}")
    if require_tpu and len(devs) < cell.chips:
        raise NoChip(f"{cell.chips} chips needed, {len(devs)} found")
    import correct
    import peaks as peak_table
    import trace_reduce
    from repro.core import egrl as egrl_mod
    peaks = peak_table.peaks_for(d0.device_kind) if require_tpu else None
    counter = CompileCounter.get()
    c_start = dict(counter.n)
    t_init = time.perf_counter()
    say("device", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(devs), jax=jax.__version__, compile_cache=cache)

    algo, graphs = build(cell, seed)
    if fault is not None:
        fault(algo)
    t_build = time.perf_counter()
    traffic = cell.traffic
    tap = correct.Tap(algo, egrl_mod, keep=traffic["check_generations"],
                      seed=seed)

    # ---- warm-up: until the learner has updated and a generation ran
    # without compiling
    warm = []
    while True:
        c0, t = counter.compiles, time.perf_counter()
        tap.start()
        algo.generation()
        tap.finish(sample=False)
        warm.append((round(time.perf_counter() - t, 3),
                     counter.compiles - c0))
        learned = algo.learner is None or tap.updates > 0
        if (learned and warm[-1][1] == 0) or \
                len(warm) >= traffic["max_warmup_generations"]:
            break
    t_warm = time.perf_counter()
    say("setup", jax_init_s=round(t_init - T0, 3),
        build_s=round(t_build - t_init, 3),
        warmup_s=round(t_warm - t_build, 3),
        warmup_generations=json.dumps(warm, separators=(",", ":")),
        compiles=counter.compiles - c_start[counter.COMPILE],
        cache_hits=counter.n[counter.HIT] - c_start[counter.HIT],
        cache_misses=counter.n[counter.MISS] - c_start[counter.MISS])

    # ---- the window
    compiles0 = counter.compiles
    attempted = failed = 0
    trace_dir = tempfile.mkdtemp(prefix="egrl_bench_trace_") if trace \
        else None
    t_start = time.perf_counter()
    setup_s = t_start - T0
    if trace:
        jax.profiler.start_trace(trace_dir)
    while True:
        tap.start()
        try:
            if trace:
                with jax.profiler.TraceAnnotation(trace_reduce.ANNOTATION):
                    rec = algo.generation()
            else:
                rec = algo.generation()
            failed += 0 if _finite(rec) else 1
        except Exception as e:             # counted, and the run goes on
            failed += 1
            say("generation_failed", error=repr(e)[:300])
        tap.finish(sample=True)
        attempted += 1
        if trace and attempted >= traffic["trace_generations"]:
            break
        if not trace and time.perf_counter() - t_start >= seconds:
            break
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_compiles = counter.compiles - compiles0
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:cell.chips])
    say("window", generations=attempted, failed=failed,
        seconds=round(t_end - t_start, 6), compiles=window_compiles,
        memory_peak_bytes=mem)
    best = {name: float(max(algo.best_reward[i], 0.0)) / algo.cfg.reward_scale
            for i, name in enumerate(algo.zoo.names)}
    say("best_speedup", **{k: round(v, 4) for k, v in best.items()})

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": False, "attempted": attempted, "failed": failed}
    breakdown = None
    if trace:
        reduced = trace_reduce.Reduced(trace_reduce.load_dir(trace_dir))
        ctx = trace_context(cell, algo, reduced, attempted, peaks)
        metrics = cells.read_metrics(cell, ctx)
        missing = cells.missing_metrics(cell, metrics)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        breakdown = reduced.breakdown()
        say("trace", modules=json.dumps(
            {k: round(v, 6) for k, v in sorted(
                reduced.module_s().items(), key=lambda kv: -kv[1])[:12]},
            separators=(",", ":")))
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        missing = []
        metrics = {
            "generation_ms": {"value": (t_end - t_start) * 1e3 / attempted,
                              "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    # ---- the check, once the program's state is freed
    inp = correct.Inputs(graphs, algo)
    tap.to_host()
    tap.close()
    del algo
    gc.collect()
    t_check = time.perf_counter()
    detail = {v: {} for v in ("program",) + tuple(variants)}
    numbers = correct.readings(inp, tap, detail=detail["program"])
    extra = {v: correct.readings(inp, tap, v, detail[v]) for v in variants
             if v != "program"}
    limits = cell.limits["limits"]
    ok = correct.verdict(numbers, limits) and failed == 0
    say("check", seconds=round(time.perf_counter() - t_check, 3),
        window_compiles=window_compiles,
        detail=json.dumps(detail, separators=(",", ":")))
    # a number that is missing or not finite (a tap that caught nothing)
    # reads null: the line stays JSON, and the verdict is already false
    checks = {}
    for k, lim in limits.items():
        v = numbers.get(k, math.inf)
        checks[k] = {"value": v if math.isfinite(v) else None, "limit": lim}
    result.update(correct=ok, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    if variants:
        result["variants"] = {"program": numbers, **extra}
        result["detail"] = detail
    if missing:
        result["missing_metrics"] = missing
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = cells.find_root()
    sys.path.insert(1, os.path.join(root, "src"))
    cell = cells.Cell(root, args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr, flush=True)
        return 3
    missing = result.pop("missing_metrics", [])
    if missing:
        # a per-layer metric of the cell found nothing in the trace: the
        # program it reads was renamed or fused away, so the run fails
        print(f"error: per-layer metrics read nothing: {missing}",
              file=sys.stderr, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 4 if missing else 0


if __name__ == "__main__":
    sys.exit(main())

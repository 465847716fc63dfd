"""The program-dispatch layer of a ``ZooEGRL`` generation: what one warm
generation launches, and the trajectory it leaves.

A generation's host-side glue (key splits, the per-bucket sampler
slices, the seeding grid, the zoo-order gathers, the actor flatten of
the migration) runs inside a few jitted programs, so each ``obs/`` span
issues only its real programs.  The glue only moves data and splits
keys, so the values are the eager sequence's bit for bit: three
generations of a three-bucket ``egrl`` zoo must reproduce the rewards,
populations, actor and both key streams recorded from the eager code.

Launches are counted in a ``jax.profiler`` trace of one warm
generation on the CPU: ``PjRtCpuExecutable::Execute`` events are
program executions, ``DevicePutWithSharding`` events host-to-device
uploads of arrays and ``DevicePut`` events puts of any argument (a
Python scalar too), each attributed to the innermost ``obs/`` span open
when it started (the spans the benchmark's idle readers attribute
device idle time to).
"""
import collections
import glob
import hashlib
import os

import numpy as np
import pytest

import jax

from repro import obs
from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.core.sac import SACConfig
from repro.graphs.graph import WorkloadGraph
from repro.graphs.zoo import tiny_gpt

EXECUTE = "PjRtCpuExecutable::Execute"
UPLOAD = "DevicePutWithSharding"
PUT = "DevicePut"

# Recorded from the eager glue: seed 3, after three generations.
# Arrays are the first 16 hex digits of the SHA-256 of their bytes.
RECORDED = {
    "gnn_pop": "7a439f5a5ffa0d69 float32(4, 87040)",
    "bz_pop": "170bb51a591269e3 float32(2, 776)",
    "actor": "a54de5d5e91d21b2 float32(87040,)",
    "key": [792740276, 531662773],
    "learner_key": [223976215, 2873411932],
    "best_reward": [5.04573917388916, 5.015334129333496,
                    5.003952503204346],
    "gen_mean_fitness": [4.96389102935791, 4.975818634033203,
                         4.958428859710693],
}

# Per-span ceilings of one warm generation.  The real programs: three
# bucket forwards, the key program and the sampler (rollout.gnn); one
# Boltzmann and one PG sampler; the join of the parts' rollout rows, one
# simulator call per bucket over all of them and one zoo-order gather
# (evaluate); the fitness means; the EA step; the migration.  sac.upload
# (replay batch copies and noise) is the replay layer's, counted in the
# total only.
SPAN_CEILINGS = {"rollout.gnn": 5, "rollout.boltzmann": 1,
                 "rollout.pg": 1, "evaluate": 5, "fitness": 1,
                 "evolve": 1, "migrate": 1, "sac.scan": 1,
                 "generation": 0, "host_sync": 0, "bookkeeping": 0,
                 "replay.insert": 0}
TOTAL_CEILING = 33
UPLOAD_CEILING = 6


def _zoo_graphs():
    """14, 25 and 58 nodes: three size buckets, small enough to train
    in seconds on a CPU.  At 64 nodes or fewer every GAT shape has a
    single chunked lowering (``gat_tune``), so no timed choice, here or
    cached by another test in the process, can change the bits."""
    out = []
    for layers in (1, 2, 5):
        g = tiny_gpt(seq=16, layers=layers, d=64, heads=1)
        out.append(WorkloadGraph(f"gpt{layers}", g.nodes, g.edges))
    return out


def _digest(x):
    a = np.ascontiguousarray(np.asarray(x))
    return (hashlib.sha256(a.tobytes()).hexdigest()[:16]
            + f" {a.dtype}{a.shape}")


def launches_by_span(planes):
    """{event name: Counter of events by the innermost ``obs/`` span
    open at the event's start} for ``EXECUTE``, ``UPLOAD`` and ``PUT``,
    on the host's python line."""
    out = {name: collections.Counter() for name in (EXECUTE, UPLOAD, PUT)}
    for p in planes:
        if p.name != "/host:CPU":
            continue
        for ln in p.lines:
            evs = list(ln.events)
            spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name[4:])
                     for e in evs if e.name.startswith("obs/")]
            for e in evs:
                if e.name not in out:
                    continue
                open_ = [s for s in spans if s[0] <= e.start_ns < s[1]]
                out[e.name][max(open_)[2] if open_ else None] += 1
    return out


@pytest.fixture(scope="module")
def zoo_run(tmp_path_factory):
    """Three generations (the recorded trajectory), then one more warm
    generation under the profiler."""
    with pytest.MonkeyPatch.context() as mp:
        # the CPU's lowering, whatever the environment asks for
        mp.setenv("REPRO_GAT_BACKEND", "chunked")
        cfg = EGRLConfig(pop_size=6, boltzmann_frac=0.34, elites=2,
                         seed=3, sac=SACConfig(batch=4))
        algo = ZooEGRL(_zoo_graphs(), cfg, mode="egrl", fitness_agg="mean",
                       buckets="auto", pop_shards="off", dispatch="off")
        assert [(b.n_graphs, b.n_max) for b in algo.zoo.buckets] == [
            (1, 14), (1, 25), (1, 58)]
        recs = [algo.generation() for _ in range(3)]
        state = {
            "gnn_pop": _digest(algo.gnn_pop),
            "bz_pop": _digest(algo.bz_pop),
            "actor": _digest(np.concatenate(
                [np.asarray(x).ravel()
                 for x in jax.tree.leaves(algo.learner.actor)])),
            "key": np.asarray(algo.key).tolist(),
            "learner_key": np.asarray(algo.learner.key).tolist(),
            "best_reward": algo.best_reward.tolist(),
            "gen_mean_fitness": [r["gen_mean_fitness"] for r in recs],
        }
        trace_dir = tmp_path_factory.mktemp("trace")
        scans = obs.counter("memsim.scan_calls")
        before = scans.value
        jax.profiler.start_trace(str(trace_dir))
        try:
            algo.generation()
        finally:
            jax.profiler.stop_trace()
        n_scans = scans.value - before
    from jax.profiler import ProfileData
    pb = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                   recursive=True)
    return (state, launches_by_span(ProfileData.from_file(pb[-1]).planes),
            n_scans)


def test_zoo_generation_trajectory_is_bitwise_unchanged(zoo_run):
    state, _, _ = zoo_run
    assert state == RECORDED


def test_zoo_generation_launch_count(zoo_run):
    _, events, _ = zoo_run
    execs = events[EXECUTE]
    over = {name: (execs[name], cap) for name, cap in SPAN_CEILINGS.items()
            if execs[name] > cap}
    assert not over, f"span: (executions, ceiling) {over}; all {execs}"
    assert execs["rollout.gnn"] >= 3      # the three bucket forwards
    assert execs["evaluate"] >= 3         # one simulator call per bucket
    assert sum(execs.values()) <= TOTAL_CEILING, execs
    assert sum(events[UPLOAD].values()) <= UPLOAD_CEILING, events[UPLOAD]
    # only the replay layer's batch copies put anything on the device
    for name in (UPLOAD, PUT):
        assert set(events[name]) <= {"sac.upload"}, (name, events[name])


@pytest.mark.parametrize("mode", ["egrl", "ea"])
def test_warm_generation_scans_each_bucket_once(zoo_run, mode):
    """Every part's rollout rows share one simulator call per bucket:
    ``memsim.scan_calls`` grows by the bucket count in a warm
    generation, not by parts x buckets."""
    if mode == "egrl":
        scans = zoo_run[2]
    else:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("REPRO_GAT_BACKEND", "chunked")
            cfg = EGRLConfig(pop_size=6, boltzmann_frac=0.34, elites=2,
                             seed=3)
            algo = ZooEGRL(_zoo_graphs(), cfg, mode="ea",
                           fitness_agg="mean", buckets="auto",
                           pop_shards="off", dispatch="off")
            algo.generation()
            counter = obs.counter("memsim.scan_calls")
            before = counter.value
            algo.generation()
            scans = counter.value - before
    assert scans == 3

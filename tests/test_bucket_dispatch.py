"""Bucket-parallel dispatch + 2-D (pop, model) mesh tests (PR 10):
the async per-bucket dispatcher must be a pure placement change — per
graph rewards and the whole EA trajectory stay bitwise the serial
path's — the 2-D mesh must resolve/fail-loud like the 1-D one, and the
measured-time bucket-K autotune must pick a valid assignment.

Multi-device cases run in subprocesses with XLA-forced host devices
(the main test process keeps 1 device, and the device count is fixed at
first jax init), mirroring tests/test_ea_sharding.py."""
import os
import subprocess
import sys

import pytest

import jax

from repro.distributed.dispatch import (BucketDispatcher, fit_time_model,
                                        predict_bucket_ms,
                                        resolve_dispatch_policy)
from repro.utils.envpolicy import env_policy

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 4) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    for k in ("REPRO_POP_SHARDS", "REPRO_MODEL_SHARDS",
              "REPRO_BUCKET_DISPATCH", "REPRO_ZOO_BUCKETS"):
        env.pop(k, None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------ policies
def test_dispatch_policy_fail_loud(monkeypatch):
    assert resolve_dispatch_policy() == "auto"
    assert resolve_dispatch_policy("off") == "off"
    monkeypatch.setenv("REPRO_BUCKET_DISPATCH", "sideways")
    with pytest.raises(ValueError, match="REPRO_BUCKET_DISPATCH"):
        resolve_dispatch_policy()


def test_env_policy_int_prefixes():
    """The shared resolver's "prefix:N" support (REPRO_SERVE_SLOTS=
    thread:N rides on it): normalized pass-through, fail-loud on
    malformed or below-minimum suffixes, and inert for plain values."""
    kw = dict(choices=("off", "thread"), default="off",
              int_prefixes=("thread",))
    assert env_policy("X_TEST", override="thread:4", **kw) == "thread:4"
    assert env_policy("X_TEST", override="THREAD:4", **kw) == "thread:4"
    assert env_policy("X_TEST", override="thread", **kw) == "thread"
    with pytest.raises(ValueError, match="X_TEST"):
        env_policy("X_TEST", override="thread:zero", **kw)
    with pytest.raises(ValueError, match="n >= 1"):
        env_policy("X_TEST", override="thread:0", **kw)
    with pytest.raises(ValueError, match="X_TEST"):
        env_policy("X_TEST", override="step:2", **kw)


def test_dispatcher_gating_single_device():
    """On a 1-device host "auto" stays off (nothing to overlap), an
    explicit "async" forces the dispatcher on, and "off" always wins;
    pop-sharded drivers never build one (either/or by design)."""
    from repro.core import gnn
    from repro.graphs.bucketed import build_bucketed_zoo
    from repro.graphs.zoo import bert, resnet50, tiny_gpt

    zoo = build_bucketed_zoo([resnet50(), bert(), tiny_gpt()])
    assert zoo.n_buckets >= 2
    tpl = gnn.init_gnn(jax.random.PRNGKey(0), zoo.n_features)
    if len(jax.devices()) == 1:
        assert not BucketDispatcher(zoo, tpl, policy="auto").active
    assert not BucketDispatcher(zoo, tpl, policy="off").active
    d = BucketDispatcher(zoo, tpl, policy="async")
    assert d.active
    dm = d.device_map()
    assert sorted(dm) == list(range(zoo.n_buckets))
    assert all(0 <= v < len(jax.devices()) for v in dm.values())
    # a single-bucket zoo has nothing to overlap in any policy
    single = build_bucketed_zoo([resnet50()], buckets="off")
    assert not BucketDispatcher(single, tpl, policy="async").active


def test_time_model_fit_and_predict():
    """Least-squares t = c0 + c1 * G * N^2 on clean synthetic points
    recovers the model; degenerate single-point fits stay positive."""
    pts = [(4, 64, 0.5 + 2e-6 * 4 * 64 ** 2),
           (4, 128, 0.5 + 2e-6 * 4 * 128 ** 2),
           (8, 256, 0.5 + 2e-6 * 8 * 256 ** 2)]
    c0, c1 = fit_time_model(pts)
    assert abs(c0 - 0.5) < 1e-6 and abs(c1 - 2e-6) < 1e-9
    assert abs(predict_bucket_ms((c0, c1), 4, 128)
               - pts[1][2]) < 1e-6
    c0, c1 = fit_time_model([(4, 64, 3.0)])     # degenerate: no slope
    assert predict_bucket_ms((c0, c1), 8, 128) > 0.0


# ----------------------------------------------- multi-device (forced)
def test_async_dispatch_bit_identical_and_measured():
    """The tentpole's correctness bar, on a forced 4-device CPU mesh:
    with pop sharding off, the async dispatcher's per-graph rewards and
    whole EA trajectory are BITWISE the serial per-bucket loop's, and a
    warm generation launches one simulator program per bucket; after
    ``measure()`` the LPT assignment reflects measured per-bucket times
    and the autotuned K builds a working zoo."""
    run_py("""
import numpy as np
from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.distributed.dispatch import autotune_bucket_k
from repro.graphs.bucketed import build_bucketed_zoo
from repro.graphs.zoo import bert, resnet50, tiny_gpt

graphs = [resnet50(), bert(), tiny_gpt()]
cfg = EGRLConfig(pop_size=6, boltzmann_frac=0.34, elites=2, seed=0)
serial = ZooEGRL(graphs, cfg, mode="ea", pop_shards="off",
                 dispatch="off")
asyncd = ZooEGRL(graphs, cfg, mode="ea", pop_shards="off",
                 dispatch="async")
assert serial.dispatch is None
assert asyncd.dispatch is not None and asyncd.zoo.n_buckets >= 2
dm = asyncd.dispatch.device_map()
assert sorted(dm) == list(range(asyncd.zoo.n_buckets))
for _ in range(3):
    rs, ra = serial.generation(), asyncd.generation()
    assert rs["best_fitness"] == ra["best_fitness"]
    assert rs["best_reward_per_graph"] == ra["best_reward_per_graph"]
assert np.array_equal(serial.best_reward, asyncd.best_reward)
for ms, ma in zip(serial.best_mapping, asyncd.best_mapping):
    assert np.array_equal(ms, ma)

# every part's rows share one simulator call per bucket here too
from repro import obs
scans = obs.counter("memsim.scan_calls")
before = scans.value
asyncd.generation()
assert scans.value - before == asyncd.zoo.n_buckets, scans.value - before

# measured re-balance: every bucket gets a positive ms, and the new
# assignment still covers every bucket
ms = asyncd.dispatch.measure(asyncd.gnn_pop)
assert sorted(ms) == list(range(asyncd.zoo.n_buckets))
assert all(v > 0.0 for v in ms.values())
assert sorted(asyncd.dispatch.device_map()) == sorted(dm)

k = autotune_bucket_k(graphs, pop=4, reps=1)
assert isinstance(k, int) and k >= 1
assert autotune_bucket_k(graphs, pop=4, reps=1) == k   # cached
zoo = build_bucketed_zoo(graphs, buckets="autotune")
assert 1 <= zoo.n_buckets <= len(graphs)
print("OK")
""")


def test_pop_model_mesh_2d_resolution():
    """2-D mesh plumbing on a forced 8-device host: explicit and auto
    (pop, model) factorizations, wide-layout row rounding to pop*model,
    and the over-subscription fail-loud."""
    run_py("""
import pytest
from jax.sharding import PartitionSpec
from repro.distributed.population import resolve_pop_sharding
from repro.launch.mesh import make_pop_model_mesh

s = resolve_pop_sharding(12, 4, 2, model_shards=2)
assert s.n_shards == 2 and s.model_shards == 2
assert s.mesh.shape == {"pop": 2, "model": 2}
assert s.padded(12, 4) == (12, 4)
assert s.sharding.spec == PartitionSpec("pop")
assert s.wide_sharding.spec == PartitionSpec(("pop", "model"))
s = resolve_pop_sharding(5, 3, 2, model_shards=4)   # rounds to n*m=8
assert s.padded(5, 3) == (8, 8)
# model auto claims the devices the pop axis left over
s = resolve_pop_sharding(4, 2, "auto", model_shards="auto")
assert s.n_shards == 4 and s.model_shards == 2
assert s.mesh.shape == {"pop": 4, "model": 2}
# 1-D resolution is unchanged when the model axis is off (default)
s = resolve_pop_sharding(12, 4, 4)
assert s.model_shards == 1 and s.mesh.shape == {"pop": 4}
with pytest.raises(ValueError, match="device"):
    resolve_pop_sharding(12, 4, 4, model_shards=4)   # 16 > 8
with pytest.raises(ValueError, match="device"):
    make_pop_model_mesh(4, 4)
print("OK")
""", devices=8)


def test_wide_forward_bit_identical_on_2d_mesh():
    """evolve_sharded + the wide big-bucket forward on a 2-D (2, 2)
    mesh: the whole zoo trajectory matches the single-device run bit
    for bit — the model axis is a capacity knob, not a different
    algorithm."""
    run_py("""
import numpy as np
from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.graphs.zoo import bert, resnet50, tiny_gpt

graphs = [resnet50(), bert(), tiny_gpt()]
cfg = EGRLConfig(pop_size=8, boltzmann_frac=0.25, elites=2, seed=0)
base = ZooEGRL(graphs, cfg, mode="ea", pop_shards="off")
import os
os.environ["REPRO_MODEL_SHARDS"] = "2"
wide = ZooEGRL(graphs, cfg, mode="ea", pop_shards=2)
assert wide.pop_sharding.model_shards == 2
assert wide.pop_sharding.mesh.shape == {"pop": 2, "model": 2}
assert any(wide._wide_bucket) and not all(wide._wide_bucket), \
    "big buckets go wide, small buckets keep the replicated layout"
assert wide.dispatch is None        # sharding and dispatch are either/or
for _ in range(3):
    rb, rw = base.generation(), wide.generation()
    assert rb["best_fitness"] == rw["best_fitness"]
    assert rb["best_reward_per_graph"] == rw["best_reward_per_graph"]
assert np.array_equal(base.best_reward, wide.best_reward)
print("OK")
""")


def test_mesh_fail_loud_when_oversubscribed():
    """Satellite 1: REPRO_POP_SHARDS greater than the visible device
    count dies with an actionable ValueError (device counts + the
    XLA_FLAGS remedy), through envpolicy-style validation — on the 1-D
    and the 2-D constructors alike."""
    from repro.launch.mesh import make_pop_mesh, make_pop_model_mesh

    n_dev = len(jax.devices())
    with pytest.raises(ValueError) as e:
        make_pop_mesh(n_dev + 1)
    msg = str(e.value)
    assert "device" in msg and "XLA_FLAGS" in msg
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        make_pop_model_mesh(n_dev + 1, 1)
    if n_dev == 1:
        from repro.distributed.population import resolve_pop_sharding
        with pytest.raises(ValueError, match="REPRO_POP_SHARDS"):
            resolve_pop_sharding(12, 4, 2)

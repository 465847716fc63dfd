"""Bucket-parallel dispatch: issue the BucketedZoo's K per-bucket jitted
calls on DIFFERENT devices so a generation's wall time approaches the
slowest bucket instead of the sum of all buckets.

The serial zoo path (core/egrl.py) runs one forward + sample + evaluate
pipeline per size bucket on the default device: jax dispatch is async,
but a single device executes the K pipelines back to back, so
``generation time = sum over buckets``.  On a multi-device host the
buckets are independent — each has its own padded GraphBatch and its own
PRNG keys — so the dispatcher:

1. assigns buckets to devices with a greedy LPT (longest-processing-time
   first) bin packing over a per-bucket cost model — ``G_k * N_max_k^2``
   (attention-bound forward) until ``measure()`` replaces the proxy with
   MEASURED per-bucket pipeline times;
2. stages immutable per-bucket state (the bucket GraphBatch and the
   parameter template) on the assigned devices once, at construction;
3. per generation, ships each bucket an exclusive population replica
   (``jax.device_put`` is async) and issues the per-bucket
   forward/sample/evaluate calls without blocking — the replica is
   DONATED to the forward (it is dead after the call, so XLA reclaims
   the buffer for scratch immediately instead of holding it until the
   next python GC);
4. pulls per-bucket results back to the primary device (again async)
   only where a cross-bucket op needs them on one device: the zoo-order
   reward gather and the EA step's bucket-major logits concat.

Everything is bit-identical to the serial path: the per-bucket programs
are the same jitted functions over the same values (placement never
changes math on same-typed devices), the PRNG keys come from the same
``bucket_keys_batch`` split, and the gather is the same concat + exact
permutation — ``tests/test_bucket_dispatch.py`` asserts bitwise-equal
rewards on a forced-8-device CPU mesh.

Policy (``REPRO_BUCKET_DISPATCH`` env var, or the ``dispatch=`` argument
of ``ZooEGRL``):

- ``"auto"`` (default): dispatch when the zoo has K > 1 buckets AND more
  than one device is visible; single-device hosts keep the serial path
  byte for byte.
- ``"async"``: force the dispatch path (on one device it still runs —
  same math, useful for testing the code path).
- ``"off"``: always serial.

The dispatcher composes with the ("pop",) population sharding only as
either/or: a pop-sharded array spans ALL devices, so per-bucket device
placement has no devices left to claim — ``ZooEGRL`` keeps the serial
path when the sharding is active.

``autotune_bucket_k`` closes the bucketing follow-up (ROADMAP): instead
of trusting octave geometry, it measures per-bucket pipeline times on
the octave bucketing, fits a ``t = c0 + c1 * G * N^2`` time model, and
picks the K whose predicted LPT makespan over the visible devices is
smallest.  Wired into ``build_bucketed_zoo`` via
``REPRO_ZOO_BUCKETS=autotune``.
"""
from __future__ import annotations

import time
import warnings
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import gnn
from repro.memsim.batch import SCALARS, evaluate_population_zoo
from repro.utils.envpolicy import env_policy

# The donated population replica rarely aliases an output buffer (the
# logits have a different shape), so jax warns the donation "was not
# usable" — but the donation is FOR the early dealloc, not aliasing:
# the replica is dead after the forward and donating it lets XLA
# reclaim the memory for scratch.  Silence just that warning.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

# module-level jits (see core/egrl.py's hoisting rationale): one cache
# entry per bucket geometry, shared across dispatcher instances
_FWD = jax.jit(gnn.population_logits_zoo, static_argnames=("backend",))
_FWD_DONATE = jax.jit(gnn.population_logits_zoo,
                      static_argnames=("backend",), donate_argnums=(5,))
_SAMPLE = jax.jit(jax.vmap(gnn.sample_actions))


def resolve_dispatch_policy(override: Optional[str] = None) -> str:
    """``REPRO_BUCKET_DISPATCH`` -> "auto" | "off" | "async", fail-loud
    through the shared envpolicy resolver."""
    return env_policy("REPRO_BUCKET_DISPATCH",
                      choices=("auto", "off", "async"),
                      default="auto", override=override)


def _lpt_assign(costs: Sequence[float], n_bins: int) -> List[int]:
    """Greedy longest-processing-time-first bin packing: bin id per
    item.  Deterministic (ties broken by item index, then bin index)."""
    order = sorted(range(len(costs)), key=lambda k: (-costs[k], k))
    load = [0.0] * n_bins
    out = [0] * len(costs)
    for k in order:
        d = min(range(n_bins), key=lambda i: (load[i], i))
        out[k] = d
        load[d] += costs[k]
    return out


def _lpt_makespan(costs: Sequence[float], n_bins: int) -> float:
    """Wall-time estimate of running ``costs`` over ``n_bins`` devices."""
    assign = _lpt_assign(costs, n_bins)
    load = [0.0] * n_bins
    for k, d in enumerate(assign):
        load[d] += costs[k]
    return max(load)


class BucketDispatcher:
    """Per-bucket device placement + async issue for one BucketedZoo.

    Construct once per driver; when ``active`` is False every method
    must be bypassed (the driver keeps the serial path).  The population
    matrix handed to ``forward`` must be unsharded (single-device).
    """

    def __init__(self, zoo, template, *, policy: Optional[str] = None):
        self.zoo = zoo
        self.policy = resolve_dispatch_policy(policy)
        devices = jax.devices()
        self.active = (zoo.n_buckets > 1 and self.policy != "off"
                       and (self.policy == "async" or len(devices) > 1))
        if not self.active:
            return
        self.devices = devices
        self.primary = devices[0]
        self.bucket_ms: Optional[Dict[int, float]] = None
        self._template_src = template
        self._assign_and_stage()

    # ------------------------------------------------------- placement
    def _cost(self, k: int) -> float:
        """Per-bucket cost: measured pipeline ms when available, else
        the G*N^2 proxy (the GAT forward is attention-bound)."""
        if self.bucket_ms is not None:
            return self.bucket_ms[k]
        b = self.zoo.buckets[k]
        return float(b.n_graphs) * float(b.n_max) ** 2

    def _assign_and_stage(self) -> None:
        """LPT-assign buckets to devices and stage the immutable
        per-bucket state (bucket GraphBatch + parameter template) there.
        Re-run by ``measure()`` once real timings replace the proxy."""
        zoo, devices = self.zoo, self.devices
        costs = [self._cost(k) for k in range(zoo.n_buckets)]
        bins = _lpt_assign(costs, len(devices))
        self.bucket_device = [devices[d] for d in bins]
        self._staged = tuple(
            jax.device_put(b, dev)
            for b, dev in zip(zoo.buckets, self.bucket_device))
        self._templates = {
            dev: jax.device_put(self._template_src, dev)
            for dev in set(self.bucket_device)}

    def device_map(self) -> Dict[int, int]:
        """bucket id -> device ordinal (introspection / tests)."""
        return {k: self.devices.index(dev)
                for k, dev in enumerate(self.bucket_device)}

    def time_model(self) -> Optional[Dict[int, float]]:
        """Measured per-bucket pipeline ms (None until ``measure``)."""
        return dict(self.bucket_ms) if self.bucket_ms is not None else None

    # ------------------------------------------------- per-generation
    def forward(self, pop: jnp.ndarray) -> List[jnp.ndarray]:
        """Issue the K per-bucket population forwards asynchronously.

        Each off-primary bucket gets an exclusive ``device_put`` replica
        of ``pop``, donated to the forward (dead after the call).  The
        bucket living on the population's own device reuses the caller's
        buffer and must NOT donate it — the driver still owns it.
        Returns per-bucket logits committed to their bucket devices.
        """
        pop_devs = pop.devices() if hasattr(pop, "devices") else set()
        out = []
        for k, b in enumerate(self._staged):
            dev = self.bucket_device[k]
            tpl = self._templates[dev]
            if pop_devs == {dev}:
                out.append(_FWD(tpl, b.feats, b.adj, b.node_mask,
                                b.n_nodes, pop))
            else:
                replica = jax.device_put(pop, dev)
                out.append(_FWD_DONATE(tpl, b.feats, b.adj, b.node_mask,
                                       b.n_nodes, replica))
        return out

    def sample(self, bucket_keys: Sequence[jnp.ndarray],
               logits: Sequence[jnp.ndarray]) -> Tuple[jnp.ndarray, ...]:
        """Per-bucket action sampling next to the logits.  ``bucket_keys``
        are the serial path's per-bucket row keys (``bucket_keys_batch``
        of the padded row keys, same values), each shipped to its
        bucket's device."""
        out = []
        for kc, lg, dev in zip(bucket_keys, logits, self.bucket_device):
            out.append(_SAMPLE(jax.device_put(kc, dev), lg))
        return tuple(out)

    def pull(self, arrays: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
        """Copy per-bucket results back to the primary device (async) so
        cross-bucket ops (concat/gather) see one placement."""
        return [jax.device_put(a, self.primary) for a in arrays]

    def evaluate(self, mappings: Sequence[jnp.ndarray],
                 reward_scale: float = 5.0) -> Dict:
        """``evaluate_population_bucketed`` with per-bucket placement:
        each bucket's mappings are shipped to its device (no-op when the
        sampler already put them there), evaluated against the STAGED
        bucket, and only the per-graph scalars are pulled back to the
        primary device for the zoo-order gather.  Same dict shape and
        bitwise the same values as the serial path, and the same count
        in ``memsim.scan_calls``."""
        assert len(mappings) == self.zoo.n_buckets
        per = []
        for k, m in enumerate(mappings):
            dev = self.bucket_device[k]
            per.append(evaluate_population_zoo(
                self._staged[k], jax.device_put(m, dev), reward_scale))
        obs.counter("memsim.scan_calls").inc(len(per))
        out = self.zoo.gather_zoo(
            [{key: jax.device_put(r[key], self.primary) for key in SCALARS}
             for r in per])
        out["rectified"] = tuple(r["rectified"] for r in per)
        return out

    # ------------------------------------------------------ time model
    def measure(self, pop: jnp.ndarray, *, reward_scale: float = 5.0,
                reps: int = 2, seed: int = 0) -> Dict[int, float]:
        """Blocked per-bucket pipeline times (ms): replica copy ->
        forward -> sample -> evaluate -> block, per bucket in isolation.
        The sum over buckets is what the serial path pays per generation
        (plus its K host-sync gaps); the measured model replaces the
        G*N^2 proxy and the device assignment is re-balanced (LPT).
        Recorded per bucket as ``dispatch.bucket<k>_ms`` gauges."""
        keys = jax.random.split(jax.random.PRNGKey(seed), pop.shape[0])
        ms: Dict[int, float] = {}
        for k, b in enumerate(self._staged):
            dev = self.bucket_device[k]
            tpl = self._templates[dev]

            def run_bucket():
                replica = jax.device_put(pop, dev)
                lg = _FWD(tpl, b.feats, b.adj, b.node_mask, b.n_nodes,
                          replica)
                acts = _SAMPLE(jax.device_put(keys, dev), lg)
                r = evaluate_population_zoo(b, acts, reward_scale)
                jax.block_until_ready(r["reward"])

            run_bucket()                     # compile + warmup
            t0 = time.perf_counter()
            for _ in range(reps):
                run_bucket()
            ms[k] = (time.perf_counter() - t0) / reps * 1e3
            obs.gauge(f"dispatch.bucket{k}_ms").set(ms[k])
        self.bucket_ms = ms
        self._assign_and_stage()
        return ms


# ------------------------------------------------------ bucket-K autotune
def fit_time_model(points: Sequence[Tuple[int, int, float]]
                   ) -> Tuple[float, float]:
    """Least-squares fit of ``t_ms = c0 + c1 * G * N^2`` over measured
    per-bucket ``(G, N, ms)`` points.  With a single point the per-call
    overhead c0 is pinned to a small floor so candidate bucketings that
    multiply the call count still pay for it."""
    pts = list(points)
    x = np.asarray([float(g) * float(n) ** 2 for g, n, _ in pts])
    y = np.asarray([t for _, _, t in pts])
    if len(pts) < 2:
        c0 = min(0.05, float(y[0]) / 2)
        c1 = max(float(y[0]) - c0, 1e-9) / max(float(x[0]), 1.0)
        return c0, c1
    a = np.stack([np.ones_like(x), x], axis=1)
    coef, *_ = np.linalg.lstsq(a, y, rcond=None)
    # a degenerate fit (negative overhead or slope) falls back to the
    # through-origin slope with a small overhead floor
    c0, c1 = float(coef[0]), float(coef[1])
    if c0 <= 0 or c1 <= 0:
        c0 = 0.05
        c1 = max(float((y / np.maximum(x, 1.0)).mean()), 1e-9)
    return c0, c1


def predict_bucket_ms(model: Tuple[float, float], g: int, n: int) -> float:
    c0, c1 = model
    return c0 + c1 * float(g) * float(n) ** 2


_AUTOTUNE_CACHE: Dict[tuple, int] = {}


def autotune_bucket_k(graphs, *, pop: int = 4, reps: int = 2,
                      max_k: int = 8) -> int:
    """Pick the bucket count K from a MEASURED per-bucket time model
    instead of octave geometry.

    Measures per-bucket pipeline times on the default octave bucketing
    (small probe population), fits the ``c0 + c1*G*N^2`` model, then
    scores every distinct candidate assignment for K = 1..max_k by its
    predicted LPT makespan over the visible devices (sum on one device)
    and returns the argmin K.  Cached per (size signature, device
    count) — repeated zoo builds in one process measure once.
    """
    from repro.graphs.bucketed import assign_buckets, build_bucketed_zoo

    sizes = tuple(g.n for g in graphs)
    n_dev = len(jax.devices())
    key = (sizes, n_dev)
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]

    with obs.span("bucket_autotune", graphs=len(sizes), n_dev=n_dev) as sp:
        probe = build_bucketed_zoo(graphs, "auto")
        measured = _probe_bucket_ms(probe, pop=pop, reps=reps)
        model = fit_time_model(
            [(b.n_graphs, b.n_max, measured[k])
             for k, b in enumerate(probe.buckets)])

        best_k, best_cost = 1, float("inf")
        seen = set()
        for k in range(1, min(len(set(sizes)), max_k) + 1):
            assign = tuple(assign_buckets(sizes, k))
            if assign in seen:
                continue
            seen.add(assign)
            n_buckets = max(assign) + 1
            costs = []
            for bk in range(n_buckets):
                members = [s for s, a in zip(sizes, assign) if a == bk]
                costs.append(predict_bucket_ms(
                    model, len(members), max(members)))
            cost = _lpt_makespan(costs, n_dev)
            if cost < best_cost - 1e-9:
                best_cost, best_k = cost, k
        sp.set(chosen_k=best_k, predicted_ms=round(best_cost, 3),
               c0=round(model[0], 4))
    _AUTOTUNE_CACHE[key] = best_k
    return best_k


def _probe_bucket_ms(zoo, *, pop: int = 4, reps: int = 2,
                     seed: int = 0) -> Dict[int, float]:
    """Standalone per-bucket pipeline timing on the default device (the
    autotune probe — relative costs are what the model needs)."""
    k0 = jax.random.PRNGKey(seed)
    template = gnn.init_gnn(k0, zoo.n_features)
    vec = gnn.flatten_params(template)
    pops = jnp.broadcast_to(vec, (pop, vec.shape[0]))
    keys = jax.random.split(k0, pop)
    ms: Dict[int, float] = {}
    for k, b in enumerate(zoo.buckets):
        fwd = partial(_FWD, template, b.feats, b.adj, b.node_mask,
                      b.n_nodes)

        def run_bucket():
            lg = fwd(pops)
            acts = _SAMPLE(keys, lg)
            r = evaluate_population_zoo(b, acts)
            jax.block_until_ready(r["reward"])

        run_bucket()                         # compile + warmup
        t0 = time.perf_counter()
        for _ in range(reps):
            run_bucket()
        ms[k] = (time.perf_counter() - t0) / reps * 1e3
    return ms

"""Placement-as-a-service: a persistent optimizer server answering
"(arch, shape) -> memory placement" requests.

The paper's agent optimizes ONE workload per training run; a serving
deployment instead sees a stream of placement requests over a catalog
of architectures, most of them repeats.  This module turns the EGRL
stack into that server:

- **Graph-hash cache.**  Every request is extracted to a
  ``WorkloadGraph`` (graphs/extract.py) and keyed by its CANONICAL
  content hash (graphs/hashing.py) — not the (arch, shape) pair — so
  two registry entries that lower to the same graph share one cache
  slot, and any simulator-visible change (a dim, an edge, a ring
  width) misses.  Hits are answered at submit time without touching the
  evaluator (asserted by tests/test_placement_service.py via the
  ``evaluator_calls`` counter).

- **Nearest-neighbor cache (PR 9).**  A miss probes a banded-LSH index
  over WL similarity sketches (``graphs/hashing.py:wl_sketch`` /
  ``SketchIndex``, grouped by size class) for a near-identical cached
  graph — one resized layer away, not byte-identical.  A neighbor's
  committed mapping is adapted to the new graph (tail rows filled from
  the compiler reference) and RE-SCORED on the graph's own canonical
  batch geometry; if the re-scored mapping beats the compiler reference
  it is served immediately (``source="neighbor"``, ``nn_hit=True``) —
  one jitted evaluation instead of a full refinement, so a neighbor hit
  is strictly cheaper than a cold miss at equal budget, and the
  never-worse-than-compiler guarantee holds because anything at or
  below speedup 1.0 is NOT served from the neighbor.  In that case the
  request queues like a normal miss, but its refinement warm-starts
  from the neighbor's mapping: the Boltzmann population is re-seeded
  from one-hot mapping logits blended into the GNN prior's posterior
  (``ZooEGRL.warm_start(logits=...)``) instead of the prior
  alone.  Exact-hash semantics are unchanged: the sketch is only
  consulted after an exact-match miss.

- **Miss queue -> refinement slots (PR 9, pool in PR 10).**  Misses
  queue up; a ``tick()`` first drains every finished refinement slot
  (commit + answer), then dispatches size-class batches (up to
  ``batch_max`` distinct graphs of the oldest queued class) into free
  slots as units of background work, ``serving/engine.py``-style.
  Each slot owns ONE size class; with ``thread:N`` (N slots) queued
  classes refine concurrently.  ``REPRO_SERVE_SLOTS`` picks how a slot
  advances:

  * ``off`` (default): the slot runs to completion inside the same
    ``tick`` — PR 7's fully synchronous behavior, bit-identical
    placements and hit/miss sequencing.
  * ``step``: each ``tick`` advances the slot by ONE unit (batch
    assembly, then one budgeted generation each) on the calling
    thread — deterministic cooperative scheduling; cache hits
    submitted between ticks return immediately, mid-refinement.
  * ``thread``: a daemon worker thread drains the slot around the
    already-jitted evolve program (XLA CPU execution releases the
    GIL), so the submit path keeps streaming cache/neighbor hits
    while the miss batch refines; ``tick`` only polls and drains.
  * ``thread:N``: a pool of N such slots — queued size classes
    refine concurrently, one worker thread per slot, with per-slot
    span attribution (``slot=<idx>`` on ``slot_dispatch``/
    ``slot_drain``, thread name ``refine<idx>-n<class>``).  Each
    slot snapshots the warm-start prior at dispatch and carries its
    own autoscaled budget (thread-local), so sibling slots never
    race each other's state; commits stay main-thread, in dispatch
    order, and a poisoned class fails alone while the other slots
    keep committing (the PR 9 fault-isolation/drain guarantees).

  Each class refines over a single-bucket zoo padded to a canonical
  grid: pow2 node count, ring width = the class width, pow2 producer /
  release-table widths, graph slots cyclically filled to ``batch_max``
  and renamed ``slot0..`` (GraphBatch names are STATIC pytree
  metadata).  All of that padding is bit-inert (graphs/batch.py), and
  it pins every array shape + treedef, so the module-level jitted
  programs of core/egrl.py are compiled ONCE per class and reused by
  every subsequent miss batch — compile cost is a first-request tax,
  not a per-request one.

- **Budget autoscaling (PR 9).**  When the budget is ``auto``, each
  dispatch sizes its generation budget per class from the class's
  commit history: a class whose prior is WEAK (EGRL beat the compiler
  on fewer than half its commits, with at least ``batch_max`` commits
  observed) gets ``_AUTOSCALE_FACTOR`` x the base generations — the
  leftover SLO headroom is spent exactly where the warm start is not
  carrying its weight.  The rule reads only deterministic commit
  outcomes (never wall-clock), so placements stay content-
  deterministic; the ``budget_rebalance`` span records the decision
  and the class's refine-time p50 for telemetry.

- **Zero-shot warm start.**  The service carries the best GNN genome
  out of each refinement (``best_gnn_vec``) and seeds the next miss
  batch's population with it (``ZooEGRL.warm_start``: exact prior in
  row 0, noisy copies, Boltzmann genomes re-seeded from the prior's
  logits).  GNN parameters are graph-size independent, so the prior
  transfers across size classes; the server literally gets better at
  placing the longer it runs (tested as: warm-started refinement is
  never worse than cold at equal budget).  Refinement is best-effort:
  if the evolved best does not beat the heuristic compiler (short
  budgets often leave only invalid mappings), the service serves the
  always-valid compiler reference mapping instead — a placement answer
  is NEVER invalid and never slower than the compiler's.

- **Persistence (PR 9).**  ``REPRO_SERVE_PERSIST=<dir>`` (or the
  ``persist=`` argument) checkpoints the cache (mappings + metadata),
  the sketch index, the online GNN prior and the per-class budget
  stats through ``checkpoint/manager.py`` (atomic rename, checksummed,
  keep-N); a fresh service pointed at the same directory restores all
  of it and answers previously-seen graphs from the cache without
  touching the evaluator.  ``run()`` persists at the end of each
  stream; call ``persist()`` for an explicit save point.

- **Fault isolation.**  Extraction failures (unknown arch, unsupported
  shape) fail the one request at submit.  A refinement failure re-runs
  the class one graph at a time, so a poisoned graph fails alone and
  the rest of the batch is still served; failures are never cached, the
  poisoned slot still closes its error span, and the queue always
  drains (``run_until_drained`` bounds the tick count).

Determinism: each miss batch's refinement is seeded by folding the
SORTED member hashes with the service seed, and the batch is built in
hash order — so placements depend on the request CONTENT (and the
order in which batches were formed, via the evolving prior), not on
intra-tick arrival order.  Two fresh services fed the same stream
produce bit-identical placements and the same hit/miss sequence in
``off`` and ``step`` modes; ``thread`` mode keeps placements
content-deterministic but may answer a duplicate from the cache
earlier or later depending on when the slot lands.

Env knobs (utils/envpolicy.py, fail-loud):

- ``REPRO_SERVE_CACHE``   — "on" (default) | "off" (every request
  refines; for benchmarking the miss path).
- ``REPRO_SERVE_BUDGET``  — "auto" (default, 4 + autoscaling) | int:
  refinement generations per miss batch (an explicit int disables
  autoscaling).
- ``REPRO_SERVE_BATCH``   — "auto" (default, 4) | int: max distinct
  graphs per refinement batch AND the canonical graph-slot count.
- ``REPRO_SERVE_SLOTS``   — "off" (default) | "step" | "thread" |
  "thread:N": how a dispatched refinement slot advances, and (thread:N)
  how many refine concurrently (see above).
- ``REPRO_SERVE_NN``      — "on" (default) | "off": the WL-sketch
  nearest-neighbor cache (needs the exact cache on).
- ``REPRO_SERVE_PERSIST`` — unset (default) | a directory path for
  cache + prior checkpoints.  Parsed manually (NOT through
  ``env_policy``, which lowercases values — paths are case-sensitive).

Observability (PR 8 + PR 9): the serve path is traced end-to-end with
``repro.obs`` spans — ``submit`` (children ``extract``/``hash``/
``cache_lookup``/``nn_lookup``) and ``tick`` -> ``slot_drain`` /
``slot_dispatch`` (child ``budget_rebalance``) -> ``refine_class`` ->
``batch_assembly``/``warm_start``/``evolve``/``commit`` — and ALL
service bookkeeping (served/hits/misses/nn_hits/failed/ticks/faults
counters, per-path wall-time and per-size-class refinement histograms)
lives in a per-service ``MetricsRegistry``.  ``stats()`` reads those
counters directly, so ``stats()``, ``bench_serve`` and the SLO summary
report from one source of truth in every ``REPRO_OBS`` mode (metrics
are always on; only span EMISSION is mode-gated).  In ``thread`` mode
the worker's spans root on their own thread (the tracer keeps a
per-thread stack), so a trace never nests a streaming hit under a
paused refinement.  See docs/observability.md.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import os
import threading
import time
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.checkpoint import manager as ckpt
from repro.core.egrl import EGRLConfig, ZooEGRL
from repro.graphs.batch import build_graph_batch
from repro.graphs.extract import extract_for
from repro.graphs.graph import WorkloadGraph
from repro.graphs.hashing import SketchIndex, wl_sketch
from repro.memsim.batch import evaluate_zoo
from repro.memsim.compiler import compiler_reference
from repro.obs.metrics import MetricsRegistry
from repro.utils.envpolicy import env_policy

_N_CLASS_MIN = 64        # smallest canonical node count
_IN_WIDTH_MIN = 4        # producer-list width floor
_RELEASE_MIN = 4         # release-table width floor
_AUTO_BUDGET = 4         # generations per miss batch
_AUTO_BATCH = 4          # distinct graphs per refinement batch
_NN_THRESHOLD = 0.4      # min sketch similarity for a neighbor
_NN_LOGIT_SCALE = 4.0    # one-hot logit magnitude for mapping seeds
_WEAK_WIN_RATE = 0.5     # egrl win rate below this = weak prior
_AUTOSCALE_FACTOR = 2    # weak classes get factor x base generations
_PERSIST_KEEP = 3        # checkpoints retained per service


def _pow2(x: int, lo: int = 1) -> int:
    return max(lo, 1 << max(0, x - 1).bit_length())


def size_class(n: int) -> int:
    """Canonical padded node count for an ``n``-node graph: the next
    power of two (>= ``_N_CLASS_MIN``), so the whole registry lands in
    a handful of compile classes."""
    return _pow2(n, _N_CLASS_MIN)


@dataclasses.dataclass(frozen=True)
class PlacementRequest:
    request_id: int
    arch: str               # registry id or paper-workload name
    shape: str              # configs.base.SHAPES key


@dataclasses.dataclass
class PlacementResult:
    request_id: int
    arch: str
    shape: str
    status: str                            # "ok" | "failed"
    cache_hit: bool = False
    nn_hit: bool = False                   # served from a near neighbor
    graph_hash: Optional[str] = None
    mapping: Optional[np.ndarray] = None   # (n, 2) int32 per-op tiers
    speedup: float = 0.0                   # vs the heuristic compiler
    latency_ms: float = 0.0
    source: str = ""          # "egrl" | "compiler" | "neighbor" (ok only)
    error: Optional[str] = None
    wall_ms: float = 0.0                   # time-to-placement

    @property
    def ok(self) -> bool:
        return self.status == "ok"


@dataclasses.dataclass
class _Pending:
    """One queued miss, with everything its eventual commit needs."""
    req: PlacementRequest
    graph: WorkloadGraph
    hash: str
    t0: float
    sketch: Optional[Tuple[int, ...]] = None


class _RefinementSlot:
    """One in-flight size-class refinement: the unit of background work
    a ``tick`` dispatches.  ``items`` is the hash-sorted (hash, graph)
    batch, ``budget`` the (possibly autoscaled) generation count;
    ``result`` is filled by ``_guarded_refine`` when the work is done
    ({hash: entry}, error entries included — faults fail alone).
    ``idx`` is the service-wide dispatch ordinal (per-slot span
    attribution in the multi-slot pool); ``prior_vec`` snapshots the
    service's GNN prior at DISPATCH time, so concurrently-refining
    slots each see a deterministic warm start instead of racing the
    other slot's mid-flight prior update."""

    def __init__(self, n_class: int, items: List[Tuple[str, WorkloadGraph]],
                 budget: int, idx: int = 0, prior_vec=None):
        self.n_class = n_class
        self.items = items
        self.budget = budget
        self.idx = idx
        self.prior_vec = prior_vec
        self.hashes = frozenset(h for h, _ in items)
        self.result: Optional[Dict[str, dict]] = None
        self.gen: Optional[Iterator] = None          # off / step modes
        self.thread: Optional[threading.Thread] = None   # thread mode

    @property
    def finished(self) -> bool:
        if self.thread is not None and self.thread.is_alive():
            return False
        return self.result is not None

    def wait(self, timeout: Optional[float] = None) -> None:
        if self.thread is not None:
            self.thread.join(timeout)


class PlacementService:
    """Persistent placement server; see the module docstring.

    ``submit`` answers exact hits, neighbor hits and extraction
    failures immediately and queues the remaining misses; ``tick``
    drains/dispatches/advances the single refinement slot; ``run``
    drives a whole request stream (heartbeat ticks while submitting,
    drain at the end, persist if configured)."""

    def __init__(self, seed: int = 0, cache: Optional[str] = None,
                 budget=None, batch=None, pop_size: int = 8,
                 reward_scale: float = 5.0, slots: Optional[str] = None,
                 nn: Optional[str] = None, persist: Optional[str] = None,
                 nn_threshold: float = _NN_THRESHOLD):
        self.seed = int(seed)
        self.cache_enabled = env_policy(
            "REPRO_SERVE_CACHE", choices=("on", "off"), default="on",
            override=cache) == "on"
        b = env_policy("REPRO_SERVE_BUDGET", choices=("auto",),
                       default="auto", override=budget, int_ok=True)
        self.budget = _AUTO_BUDGET if b == "auto" else int(b)
        self.autoscale = b == "auto"
        m = env_policy("REPRO_SERVE_BATCH", choices=("auto",),
                       default="auto", override=batch, int_ok=True)
        self.batch_max = _AUTO_BATCH if m == "auto" else int(m)
        s = env_policy(
            "REPRO_SERVE_SLOTS", choices=("off", "step", "thread"),
            default="off", override=slots, int_prefixes=("thread",))
        # "thread:N" -> N concurrent worker slots; bare modes get one.
        # self.slots stays one of the three base modes so every mode
        # check below is unchanged.
        if s.startswith("thread:"):
            self.slots, self.n_slots = "thread", int(s.split(":", 1)[1])
        else:
            self.slots, self.n_slots = s, 1
        self.nn_enabled = self.cache_enabled and env_policy(
            "REPRO_SERVE_NN", choices=("on", "off"), default="on",
            override=nn) == "on"
        self.nn_threshold = float(nn_threshold)
        # path-valued: case-sensitive, so read the env var directly
        # (env_policy lowercases values); empty string means unset
        raw = os.environ.get("REPRO_SERVE_PERSIST", "") \
            if persist is None else persist
        self.persist_dir = str(raw).strip() or None
        self.pop_size = int(pop_size)
        self.reward_scale = float(reward_scale)

        self._cache: Dict[str, dict] = {}      # hash -> placement entry
        self._index = SketchIndex()            # hash -> WL sketch (LSH)
        self._queue: List[_Pending] = []       # misses, arrival order
        self._slots: List[_RefinementSlot] = []   # in dispatch order
        self._slot_seq = 0                     # per-slot span attribution
        self._tls = threading.local()          # worker-local current slot
        self._nbr_seeds: Dict[str, np.ndarray] = {}   # hash -> mapping
        self._last_sketch: Optional[Tuple[int, ...]] = None
        self._class_stats: Dict[int, Tuple[int, int]] = {}  # (wins, n)
        self._prior_vec: Optional[np.ndarray] = None
        self._persist_step = 0
        # per-service metrics: THE bookkeeping (stats() reads these);
        # pre-created so stats() has stable keys before any traffic
        self.metrics = MetricsRegistry()
        for name in ("served", "hits", "misses", "failed", "ticks",
                     "faults", "evaluator_calls", "nn_hits",
                     "nn_rescored"):
            self.metrics.counter(name)
        if self.persist_dir:
            self._load_persisted()

    @property
    def evaluator_calls(self) -> int:
        """Refinement batches run (cache hits never increment it)."""
        return self.metrics.counter("evaluator_calls").value

    @property
    def _slot(self) -> Optional[_RefinementSlot]:
        """Single-slot view of the pool (PR 9 compatibility): the oldest
        still-running slot, else the oldest undrained one, else None."""
        for slot in self._slots:
            if not slot.finished:
                return slot
        return self._slots[0] if self._slots else None

    # ------------------------------------------------------------ intake
    def submit(self, req: PlacementRequest,
               graph: Optional[WorkloadGraph] = None
               ) -> Optional[PlacementResult]:
        """Exact cache hits, neighbor hits and extraction failures come
        back immediately; misses enqueue and return ``None`` (answered
        by a later ``tick``).  ``graph`` injects a pre-built
        ``WorkloadGraph`` instead of extracting ``(arch, shape)`` from
        the registry (tests and the concurrent-load bench use this to
        submit synthetic near/far variants)."""
        t0 = time.perf_counter()
        with obs.span("submit", request_id=req.request_id, arch=req.arch,
                      shape=req.shape) as sp:
            try:
                with obs.span("extract", injected=graph is not None):
                    g = graph if graph is not None \
                        else extract_for(req.arch, req.shape)
                with obs.span("hash"):
                    h = g.canonical_hash()
            except Exception as e:  # unknown arch/shape, malformed graph
                sp.set(outcome="fault")
                return self._result(
                    req, None, {"error": f"{type(e).__name__}: {e}"}, t0)
            with obs.span("cache_lookup") as cl:
                entry = self._cache.get(h) if self.cache_enabled else None
                cl.set(hit=entry is not None)
            if entry is not None:
                # the hit path never builds a batch, never runs a driver
                self.metrics.counter("hits").inc()
                sp.set(outcome="hit")
                return self._result(req, h, entry, t0, cache_hit=True)
            # exact miss: probe the WL-sketch index for a near-identical
            # cached graph (always emitted so the miss-path taxonomy is
            # complete on every trace, even with the knob off)
            sketch: Optional[Tuple[int, ...]] = None
            with obs.span("nn_lookup", enabled=self.nn_enabled) as nsp:
                if self.nn_enabled:
                    served = self._nn_lookup(req, g, h, t0, nsp)
                    if served is not None:
                        sp.set(outcome="nn_hit")
                        return served
                    sketch = self._last_sketch
            self.metrics.counter("misses").inc()
            sp.set(outcome="miss")
            self._queue.append(_Pending(req, g, h, t0, sketch))
            return None

    def _nn_lookup(self, req: PlacementRequest, g: WorkloadGraph,
                   h: str, t0: float, nsp) -> Optional[PlacementResult]:
        """Probe the sketch index; serve the re-scored neighbor mapping
        if it beats the compiler, else stash it as a warm-start seed for
        the queued refinement.  Returns a result only when serving."""
        n_class = size_class(g.n)
        sketch = wl_sketch(g)
        self._last_sketch = sketch
        nbr_hash, sim = self._index.query(sketch, group=n_class,
                                          exclude=(h,))
        nsp.set(neighbor=nbr_hash is not None, sim=round(sim, 4),
                served=False)
        if nbr_hash is None or sim < self.nn_threshold:
            return None
        nbr = self._cache.get(nbr_hash)
        if nbr is None or "mapping" not in nbr:
            return None
        adapted = self._adapt_mapping(g, nbr["mapping"])
        sp_, lat_ms, rect, ref_ms = self._rescore_neighbor(g, adapted)
        self.metrics.counter("nn_rescored").inc()
        nsp.set(rescored_speedup=round(sp_, 4))
        if sp_ <= 1.0:
            # never worse than the compiler: do NOT serve; refine
            # instead, warm-started from the neighbor's mapping
            self._nbr_seeds[h] = adapted
            return None
        entry = {"mapping": rect, "speedup": sp_, "latency_ms": lat_ms,
                 "ref_latency_ms": ref_ms, "source": "neighbor"}
        self._cache[h] = entry
        self._index.add(h, sketch, group=n_class)
        self.metrics.counter("nn_hits").inc()
        nsp.set(served=True)
        return self._result(req, h, entry, t0, nn=True)

    @staticmethod
    def _adapt_mapping(g: WorkloadGraph, nbr_map) -> np.ndarray:
        """A neighbor's (possibly padded) mapping fitted to ``g``:
        shared rows copied, tail rows (nodes the neighbor did not have)
        filled from ``g``'s own compiler reference.  Always re-scored
        before use — this is a seed, not an answer."""
        cmap, _ = compiler_reference(g)
        m = np.asarray(cmap, np.int32).copy()
        nbr_map = np.asarray(nbr_map, np.int32)
        k = min(nbr_map.shape[0], g.n)
        m[:k] = nbr_map[:k]
        return m

    def _rescore_neighbor(self, g: WorkloadGraph, mapping: np.ndarray
                          ) -> Tuple[float, float, np.ndarray, float]:
        """Score ``mapping`` on ``g``'s canonical class geometry (one
        jitted ``evaluate_zoo`` call, compiled once per geometry);
        returns (speedup, latency_ms, rectified (n, 2) mapping,
        ref_latency_ms).  Invalid mappings score speedup 0.0, so they
        can never pass the > 1.0 serve bar."""
        n_class = size_class(g.n)
        _, batch = self._canonical_batch(n_class, [g])
        maps = np.zeros((self.batch_max, n_class, 2), np.int32)
        maps[:, :g.n] = np.clip(mapping[None, :g.n], 0, 2)
        res = evaluate_zoo(batch, maps, reward_scale=self.reward_scale)
        sp = float(res["speedup"][0])
        lat_ms = float(res["latency"][0]) * 1e3
        ref_ms = float(batch.ref_latency[0]) * 1e3
        rect = np.asarray(res["rectified"][0][:g.n], np.int32)
        return sp, lat_ms, rect, ref_ms

    # ------------------------------------------------------- refinement
    def tick(self) -> List[PlacementResult]:
        """One service heartbeat: drain every finished slot (commit to
        the cache + sketch index, answer every queued request they
        cover), dispatch size-class refinements into free slots (one
        class per slot, oldest request first), and advance non-thread
        slots (to completion in ``off`` mode, by one unit in ``step``
        mode).  Never blocks on an in-flight ``thread``-mode slot —
        that is what keeps hits streaming during a miss batch."""
        if not self._queue and not self._slots:
            return []
        with obs.span("tick", queued=len(self._queue)) as sp:
            self.metrics.counter("ticks").inc()
            out = self._drain_slots()
            while len(self._slots) < self.n_slots and self._queue:
                if not self._dispatch():
                    break          # every queued class is already claimed
            for slot in list(self._slots):
                if self.slots == "off":
                    collections.deque(slot.gen, maxlen=0)
                elif self.slots == "step":
                    next(slot.gen, None)
            out += self._drain_slots()
            sp.set(answered=len(out), in_flight=bool(self._slots),
                   slots=len(self._slots))
            return out

    def _dispatch(self) -> bool:
        """Claim up to ``batch_max`` distinct graphs of the OLDEST
        queued size class not already being refined and start a slot
        for it.  Hashes and classes held by in-flight slots are skipped
        — each slot owns one class, so queued classes refine
        concurrently when the pool has room.  Returns False when
        nothing was claimable."""
        claimed_h = frozenset(h for s in self._slots for h in s.hashes)
        claimed_c = {s.n_class for s in self._slots}
        head = next((p for p in self._queue
                     if p.hash not in claimed_h
                     and size_class(p.graph.n) not in claimed_c), None)
        if head is None:
            return False
        with obs.span("slot_dispatch", mode=self.slots,
                      slot=self._slot_seq) as sp:
            n_class = size_class(head.graph.n)
            todo: Dict[str, WorkloadGraph] = {}
            for p in self._queue:
                if size_class(p.graph.n) == n_class \
                        and p.hash not in todo \
                        and p.hash not in claimed_h \
                        and len(todo) < self.batch_max:
                    todo[p.hash] = p.graph
            budget = self._budget_for(n_class)
            items = sorted(todo.items())   # hash order: arrival-order
            slot = _RefinementSlot(n_class, items, budget,  # independence
                                   idx=self._slot_seq,
                                   prior_vec=self._prior_vec)
            self._slot_seq += 1
            self._slots.append(slot)
            sp.set(n_class=n_class, graphs=len(items), budget=budget)
            gen = self._guarded_refine(slot)
            if self.slots == "thread":
                slot.thread = threading.Thread(
                    target=lambda: collections.deque(gen, maxlen=0),
                    name=f"refine{slot.idx}-n{n_class}", daemon=True)
                slot.thread.start()
            else:
                slot.gen = gen
        return True

    def _budget_for(self, n_class: int) -> int:
        """Autoscaled generation budget for one dispatch: classes whose
        prior is weak (EGRL won < ``_WEAK_WIN_RATE`` of at least
        ``batch_max`` commits) get ``_AUTOSCALE_FACTOR`` x the base.
        Reads only deterministic commit outcomes — the refine-time p50
        in the span is telemetry, never an input — so placements stay
        content-deterministic."""
        with obs.span("budget_rebalance", n_class=n_class) as sp:
            base = self.budget
            wins, total = self._class_stats.get(n_class, (0, 0))
            weak = total >= self.batch_max \
                and wins < _WEAK_WIN_RATE * total
            budget = base * _AUTOSCALE_FACTOR \
                if (self.autoscale and weak) else base
            hist = self.metrics.histogram("refine_ms", cls=f"n{n_class}")
            sp.set(base=base, budget=budget, wins=wins, commits=total,
                   weak=weak,
                   refine_p50_ms=round(hist.quantile(0.5), 3)
                   if hist.count else 0.0)
            return budget

    def _drain_slots(self) -> List[PlacementResult]:
        """Drain every finished slot, in dispatch order (deterministic
        commit order, whatever order the worker threads finished in)."""
        out: List[PlacementResult] = []
        for slot in [s for s in self._slots if s.finished]:
            out.extend(self._drain_one(slot))
        return out

    def _drain_one(self, slot: _RefinementSlot) -> List[PlacementResult]:
        """Commit a FINISHED slot's results (cache + sketch index +
        class stats — all main-thread mutations, whatever mode ran the
        work) and answer every queued request they cover, duplicates
        included."""
        with obs.span("slot_drain", n_class=slot.n_class,
                      graphs=len(slot.items), slot=slot.idx) as sp:
            self._slots.remove(slot)
            refined = slot.result or {}
            n_egrl = 0
            for h, entry in refined.items():
                if "error" in entry:
                    continue   # failures are never cached or counted
                src = entry.get("source", "")
                if src in ("egrl", "compiler"):
                    wins, total = self._class_stats.get(slot.n_class,
                                                        (0, 0))
                    self._class_stats[slot.n_class] = (
                        wins + (src == "egrl"), total + 1)
                    n_egrl += src == "egrl"
                if self.cache_enabled:
                    self._cache[h] = entry
            out, keep = [], []
            for p in self._queue:
                entry = refined.get(p.hash)
                if entry is None and self.cache_enabled:
                    entry = self._cache.get(p.hash)
                if entry is None:
                    keep.append(p)
                    continue
                if self.nn_enabled and p.sketch is not None \
                        and "error" not in entry \
                        and p.hash in self._cache:
                    self._index.add(p.hash, p.sketch, group=slot.n_class)
                self._nbr_seeds.pop(p.hash, None)
                out.append(self._result(p.req, p.hash, entry, p.t0))
            self._queue = keep
            sp.set(answered=len(out), egrl=n_egrl)
            return out

    def _refine_overridden(self) -> bool:
        """Tests monkeypatch ``_refine_class``; an overridden unit runs
        un-stepped (one shot) so the patch sees its exact signature."""
        return "_refine_class" in self.__dict__ or \
            type(self)._refine_class is not PlacementService._refine_class

    def _guarded_refine(self, slot: _RefinementSlot):
        """Generator driving one slot to completion with the PR 7 fault
        isolation: a failing class batch is retried one graph at a time
        so only the poisoned graph fails; every span (including the
        error-attributed ``refine_class``) closes before the result
        lands.  ``off`` mode drains it inline, ``step`` mode pumps one
        unit per tick, ``thread`` mode drains it on a worker thread."""
        t0 = time.perf_counter()
        out: Dict[str, dict] = {}
        # mark this slot as the executing thread's current one, so
        # _active_budget / _assemble read the slot's own budget and
        # dispatch-time prior snapshot instead of racing a sibling slot
        self._tls.slot = slot
        try:
            if self.slots == "step" and not self._refine_overridden():
                out = yield from self._refine_class_steps(
                    slot.n_class, slot.items, slot.budget)
            else:
                # the refine_class span wraps the CALL (not the body),
                # so a monkeypatched/faulting refinement still closes
                # its span with the exception as an ``error`` attribute
                with obs.span("refine_class", n_class=slot.n_class,
                              graphs=len(slot.items)):
                    out = self._refine_class(slot.n_class, slot.items)
        except Exception as e:
            self.metrics.counter("faults").inc()
            if len(slot.items) == 1:
                h = slot.items[0][0]
                out = {h: {"error": f"{type(e).__name__}: {e}"}}
            else:
                out = {}
                for h, g in slot.items:    # isolate the bad graph
                    try:
                        with obs.span("refine_class",
                                      n_class=slot.n_class,
                                      graphs=1, retry=True):
                            out.update(
                                self._refine_class(slot.n_class,
                                                   [(h, g)]))
                    except Exception as e1:
                        self.metrics.counter("faults").inc()
                        out[h] = {"error": f"{type(e1).__name__}: {e1}"}
        finally:
            self._tls.slot = None
        self.metrics.histogram(
            "refine_ms", cls=f"n{slot.n_class}").observe(
            (time.perf_counter() - t0) * 1e3)
        slot.result = out
        return out

    def _active_budget(self) -> int:
        """Budget of the slot the CALLING thread is refining (thread-
        local: concurrent slots must not read each other's autoscaled
        budgets).  Falls back to the base budget for direct
        ``_refine_class`` calls outside any slot."""
        slot = getattr(self._tls, "slot", None)
        return slot.budget if slot is not None else self.budget

    def _active_prior(self) -> Optional[np.ndarray]:
        """Warm-start prior for the calling thread's slot: the snapshot
        taken at dispatch (deterministic given arrival order), else the
        live service prior."""
        slot = getattr(self._tls, "slot", None)
        return slot.prior_vec if slot is not None else self._prior_vec

    def _canonical_batch(self, n_class: int,
                         graphs: List[WorkloadGraph]):
        """Canonical class geometry: always ``batch_max`` graph slots
        (cyclic fill; filler results are discarded), pow2 widths,
        normalized slot names -> one jit executable per (class, fan,
        release).  Shared by refinement and the neighbor re-score."""
        filled = [graphs[i % len(graphs)] for i in range(self.batch_max)]
        arrs = [g.arrays() for g in filled]
        fan = max(1, max((len(p) for a in arrs
                          for p in a["producers_of"]), default=0))
        # bincount of last_consumer bounds the release-table
        # multiplicity
        rel = max(int(np.bincount(
            a["last_consumer"].astype(np.int64), minlength=1).max())
            for a in arrs)
        batch = build_graph_batch(
            [dataclasses.replace(g, name=f"slot{i}")
             for i, g in enumerate(filled)],
            n_max=n_class, w_max=n_class,
            in_width=_pow2(fan, _IN_WIDTH_MIN),
            release_width=_pow2(rel, _RELEASE_MIN))
        return filled, batch

    def _assemble(self, n_class: int,
                  items: List[Tuple[str, WorkloadGraph]]):
        """Batch assembly + warm start for one class refinement."""
        hashes = [h for h, _ in items]
        graphs = [g for _, g in items]
        with obs.span("batch_assembly", n_class=n_class,
                      graphs=len(items)):
            filled, batch = self._canonical_batch(n_class, graphs)
            cfg = EGRLConfig(pop_size=self.pop_size,
                             seed=self._batch_seed(hashes),
                             reward_scale=self.reward_scale)
            drv = ZooEGRL(filled, cfg, mode="ea", zoo=batch)
        seeds = {h: self._nbr_seeds[h] for h in hashes
                 if h in self._nbr_seeds}
        prior = self._active_prior()
        # always emitted (warm=False on the first-ever batch) so the
        # serve span taxonomy is complete on every trace
        with obs.span("warm_start", warm=prior is not None,
                      nn_seeds=len(seeds)):
            if prior is not None or seeds:
                vec = prior if prior is not None \
                    else drv.best_gnn_vec()
                drv.warm_start(vec, logits=self._warm_logits(
                    drv, n_class, items, seeds, vec))
        return drv, batch

    def _warm_logits(self, drv, n_class: int,
                     items: List[Tuple[str, WorkloadGraph]],
                     seeds: Dict[str, np.ndarray], vec) -> np.ndarray:
        """The Boltzmann seeding grid: the GNN prior's posterior logits
        (zeros when there is no prior yet) with one-hot mapping logits
        written into the node rows of every slot whose graph has a
        nearest-neighbor seed — the population starts FROM the
        neighbor's answer instead of the prior alone."""
        if self._active_prior() is not None:
            base = np.array(drv.prior_logits(vec), np.float32, copy=True)
        else:
            base = np.zeros((self.batch_max * n_class, 2, 3), np.float32)
        base = base.reshape(self.batch_max * n_class, 2, 3)
        for slot_i in range(self.batch_max):
            h, g = items[slot_i % len(items)]
            m = seeds.get(h)
            if m is None:
                continue
            idx = np.clip(np.asarray(m[:g.n], np.int64), 0, 2)
            seg = base[slot_i * n_class: slot_i * n_class + n_class]
            rows = np.arange(g.n)
            for d in (0, 1):
                seg[:g.n, d, :] = -_NN_LOGIT_SCALE
                seg[rows, d, idx[:, d]] = _NN_LOGIT_SCALE
        return base

    def _refine_class(self, n_class: int,
                      items: List[Tuple[str, WorkloadGraph]]
                      ) -> Dict[str, dict]:
        """One short warm-started EGRL refinement over a canonical-grid
        batch; returns {hash: placement entry} for every item.  The
        synchronous unit of work (``off``/``thread`` modes and the
        per-graph fault retries); ``step`` mode runs the generation-
        granular ``_refine_class_steps`` instead."""
        budget = self._active_budget()
        drv, batch = self._assemble(n_class, items)
        self.metrics.counter("evaluator_calls").inc()
        with obs.span("evolve", n_class=n_class, generations=budget):
            for _ in range(budget):
                drv.generation()
            self._prior_vec = drv.best_gnn_vec()  # continual warm start
        return self._commit_results(drv, batch, items)

    def _refine_class_steps(self, n_class: int,
                            items: List[Tuple[str, WorkloadGraph]],
                            budget: int):
        """Generation-granular variant of ``_refine_class`` for
        ``slots=step``: one yield per unit of work, and NO span held
        across a yield — a paused span would adopt the main thread's
        streaming-hit spans and break the child-sum gate — so each
        resumable segment opens and closes its own ``refine_class``
        span (``phase=assemble|evolve|commit``)."""
        with obs.span("refine_class", n_class=n_class,
                      graphs=len(items), phase="assemble"):
            drv, batch = self._assemble(n_class, items)
        self.metrics.counter("evaluator_calls").inc()
        yield
        for k in range(budget):
            with obs.span("refine_class", n_class=n_class,
                          graphs=len(items), phase="evolve"):
                with obs.span("evolve", n_class=n_class, generations=1,
                              step=k):
                    drv.generation()
            yield
        self._prior_vec = drv.best_gnn_vec()
        with obs.span("refine_class", n_class=n_class,
                      graphs=len(items), phase="commit"):
            return self._commit_results(drv, batch, items)

    def _commit_results(self, drv, batch,
                        items: List[Tuple[str, WorkloadGraph]]
                        ) -> Dict[str, dict]:
        with obs.span("commit", graphs=len(items)) as commit_sp:
            out = {}
            n_egrl = 0
            for i, (h, g) in enumerate(items):  # slots >= len(items)
                sp = float(drv.best_reward[i]) / self.reward_scale
                ref_ms = float(batch.ref_latency[i]) * 1e3  # fillers
                if sp > 1.0:   # valid AND beats the heuristic compiler
                    n_egrl += 1
                    out[h] = {
                        "mapping": np.asarray(drv.best_mapping[i],
                                              np.int32),
                        "speedup": sp, "latency_ms": ref_ms / sp,
                        "ref_latency_ms": ref_ms, "source": "egrl",
                    }
                else:
                    # never-worse-than-compiler guarantee: a short
                    # budget (or an unlucky batch) must not serve an
                    # invalid or slower placement — fall back to the
                    # always-valid heuristic reference mapping
                    # (speedup 1.0)
                    cmap, _ = compiler_reference(g)
                    out[h] = {
                        "mapping": np.asarray(cmap, np.int32),
                        "speedup": 1.0, "latency_ms": ref_ms,
                        "ref_latency_ms": ref_ms, "source": "compiler",
                    }
            commit_sp.set(egrl=n_egrl, compiler=len(items) - n_egrl)
        return out

    def _batch_seed(self, hashes: List[str]) -> int:
        """Content-derived refinement seed: sorted member hashes folded
        with the service seed, so a batch's trajectory is a function of
        WHAT it contains, not when or in which order it arrived."""
        m = hashlib.sha256()
        for h in sorted(hashes):
            m.update(h.encode())
            m.update(b",")
        m.update(str(self.seed).encode())
        return int.from_bytes(m.digest()[:4], "little")

    # ---------------------------------------------------------- results
    def _result(self, req: PlacementRequest, h: Optional[str],
                entry: dict, t0: float, cache_hit: bool = False,
                nn: bool = False) -> PlacementResult:
        wall = (time.perf_counter() - t0) * 1e3
        self.metrics.counter("served").inc()
        if "error" in entry:
            self.metrics.counter("failed").inc()
            return PlacementResult(
                request_id=req.request_id, arch=req.arch, shape=req.shape,
                status="failed", cache_hit=cache_hit, graph_hash=h,
                error=entry["error"], wall_ms=wall)
        path = "hit" if cache_hit else ("nn" if nn else "miss")
        self.metrics.histogram("wall_ms", path=path).observe(wall)
        return PlacementResult(
            request_id=req.request_id, arch=req.arch, shape=req.shape,
            status="ok", cache_hit=cache_hit, nn_hit=nn, graph_hash=h,
            mapping=entry["mapping"].copy(), speedup=entry["speedup"],
            latency_ms=entry["latency_ms"],
            source=entry.get("source", ""), wall_ms=wall)

    # ------------------------------------------------------- persistence
    def persist(self) -> Optional[str]:
        """Checkpoint cache + sketch index + GNN prior + class stats to
        ``persist_dir`` (atomic, checksummed, keep-N); returns the
        checkpoint path, or None when persistence is off."""
        if not self.persist_dir:
            return None
        maps = {h: np.asarray(e["mapping"], np.int32)
                for h, e in self._cache.items()}
        tree: Dict[str, object] = {"maps": maps}
        if self._prior_vec is not None:
            tree["prior"] = np.asarray(self._prior_vec, np.float32)
        extra = {
            "entries": {h: {k: e[k] for k in ("speedup", "latency_ms",
                                              "ref_latency_ms", "source")
                            if k in e}
                        for h, e in self._cache.items()},
            "sketches": {k: list(sig)
                         for k, sig, _ in self._index.items()},
            "groups": {k: grp for k, _, grp in self._index.items()},
            "class_stats": {str(k): list(v)
                            for k, v in self._class_stats.items()},
            "has_prior": self._prior_vec is not None,
            "seed": self.seed,
        }
        self._persist_step += 1
        return ckpt.save(self.persist_dir, self._persist_step, tree,
                         extra=extra, keep=_PERSIST_KEEP)

    def _load_persisted(self) -> None:
        """Restore the latest checkpoint from ``persist_dir`` (no-op on
        an empty/missing directory; fail-loud on a corrupt one)."""
        step = ckpt.latest_step(self.persist_dir)
        if step is None:
            return
        path = os.path.join(self.persist_dir, f"step_{step:08d}")
        if not ckpt.verify(path):
            raise IOError(f"REPRO_SERVE_PERSIST: corrupt checkpoint "
                          f"at {path}")
        data = np.load(os.path.join(path, "arrays.npz"))
        extra = ckpt.load_manifest(self.persist_dir, step)["extra"]
        for h, meta in extra.get("entries", {}).items():
            entry = dict(meta)
            entry["mapping"] = np.asarray(data[f"maps{ckpt.SEP}{h}"],
                                          np.int32)
            self._cache[h] = entry
        groups = extra.get("groups", {})
        for k, sig in extra.get("sketches", {}).items():
            self._index.add(k, [int(x) for x in sig],
                            group=int(groups[k]))
        self._class_stats = {
            int(k): (int(v[0]), int(v[1]))
            for k, v in extra.get("class_stats", {}).items()}
        if extra.get("has_prior") and "prior" in data.files:
            self._prior_vec = np.asarray(data["prior"], np.float32)
        self._persist_step = step

    # ----------------------------------------------------------- driving
    def _distinct_queued(self) -> int:
        """Distinct UNCLAIMED graphs waiting (hashes already claimed by
        any in-flight slot are excluded — they are being worked on)."""
        claimed = {h for s in self._slots for h in s.hashes}
        return len({p.hash for p in self._queue} - claimed)

    def run(self, requests: Iterable[PlacementRequest]
            ) -> List[PlacementResult]:
        """Drive a request stream: submit each request, heartbeat-tick
        while work is pending (in ``thread`` mode the tick only polls —
        hits stream while the slot refines), drain at the end, persist
        if configured.  Results come back in completion order (sort by
        ``request_id`` for a per-request view)."""
        out = []
        for req in requests:
            r = self.submit(req)
            if r is not None:
                out.append(r)
            if self.slots == "thread":
                if self._slots \
                        or self._distinct_queued() >= self.batch_max:
                    out.extend(self.tick())
            else:
                while self._distinct_queued() >= self.batch_max:
                    out.extend(self.tick())
        out.extend(self.run_until_drained())
        if self.persist_dir:
            self.persist()
        return out

    def run_until_drained(self, max_ticks: int = 1000
                          ) -> List[PlacementResult]:
        """Tick until the queue is empty and no slot is in flight.
        This IS the blocking drain call: in ``thread`` mode a tick that
        answered nothing while the slot runs waits for the worker, so
        every iteration makes progress and ``max_ticks`` (a generous
        bound: a class costs dispatch + budget steps + drain) can
        assert the queue never wedges."""
        out = []
        ticks = 0
        while self._queue or self._slots:
            ticks += 1
            assert ticks <= max_ticks, "placement queue is not draining"
            got = self.tick()
            out.extend(got)
            if not got and self._slots and self.slots == "thread":
                # any slot finishing unblocks the next tick; waiting on
                # the oldest is enough (it always terminates — budgets
                # are finite and faults resolve to error entries)
                self._slots[0].wait()
        return out

    def stats(self) -> dict:
        """Service counters, read straight off the per-service obs
        metrics registry — the same counters the serve spans annotate
        and the SLO summary/bench consume, so there is exactly ONE
        bookkeeping source of truth (asserted by
        tests/test_placement_service.py)."""
        c = {k: self.metrics.counter(k).value
             for k in ("served", "hits", "misses", "failed", "ticks",
                       "faults", "nn_hits")}
        c.update(queued=len(self._queue), cache_size=len(self._cache),
                 evaluator_calls=self.evaluator_calls,
                 hit_rate=c["hits"] / max(c["served"], 1),
                 in_flight=bool(self._slots),
                 slots_in_flight=len(self._slots))
        return c

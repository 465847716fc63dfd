"""EA population sharding policy: pick a shard count, build the
``("pop",)`` mesh, pad the populations to divisible row counts, and
place the stacked (P, ...) genome arrays.

The EGRL inner loop stores its population as stacked device arrays
(core/egrl.py); this module decides whether those arrays live on one
chip or are row-sharded across a 1-D device mesh.  The actual sharded
EA step is ``repro.core.ea.evolve_sharded`` (bit-identical to the
single-device ``evolve`` on real rows for any valid shard count);
population evaluation partitions automatically under jit once its
inputs carry a ``NamedSharding`` (auto-SPMD), and the population GNN
forward runs shard by shard through ``PopSharding.map_rows`` — every
per-genome computation is independent, so no collectives are needed
outside the EA step.

Padded slots (PR 3): a shard count that does not divide a
sub-population no longer forces the single-device fallback.  The
resolver rounds each sub-population up to the next multiple of the
shard count and reports the padded row counts (``n_g_pad``/
``n_b_pad``); the EGRL driver allocates those extra masked rows, feeds
them ``-inf`` fitness, and sizes every PRNG draw by the REAL counts, so
the real-row trajectory stays bit-identical to the unpadded
single-device run (tests/test_ea_sharding.py).  Padding rows cost only
their share of redundant evaluation work, never correctness.

Shard-count policy (``REPRO_POP_SHARDS`` env var, or the ``pop_shards``
argument to ``EGRL``):

- ``"auto"`` (default): all visible devices, capped at the larger
  sub-population size (a shard with zero real rows in BOTH
  sub-populations would be pure waste).  On a single-device host this
  resolves to 1, i.e. the plain single-device path, so CPU tests and
  benchmarks are unaffected.  Note the deliberate trade-off: maximizing
  shards minimizes per-generation WALL time (per-shard row counts never
  grow with more shards; padding rows run on otherwise-idle devices in
  parallel with real work) but can inflate total FLOPs when a small
  sub-population is padded far up (e.g. n_b=3 over 13 shards evaluates
  10 throwaway Boltzmann rollouts per generation — concurrently, but
  they still burn energy).  Pass an explicit shard count when total
  compute matters more than latency.
- ``"1"`` / ``"0"`` / ``"off"``: force the single-device path.
- an integer > 1: shard over exactly that many devices (padding as
  needed); raises ``ValueError`` only when it exceeds the visible
  device count.

2-D (pop, model) meshes (PR 10): ``REPRO_MODEL_SHARDS`` (or the
``model_shards`` argument) adds a second mesh axis.  The EA genome
arrays keep their ``P("pop")`` sharding — shard_map specs that never
mention "model" replicate across it, so ``evolve_sharded`` runs
unchanged and stays bit-identical.  What the extra axis buys is the
*wide* layout (``wide_sharding``): big-bucket population forwards split
their rows over the flattened ``P(("pop", "model"))`` super-axis — a
pure row split over pop*model devices, so per-row results stay
bit-identical — while small buckets keep the replicated layout.
Padding rounds to pop*model so the super-axis split always divides.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.ea import POP_AXIS
from repro.launch.mesh import make_pop_mesh, make_pop_model_mesh
from repro.utils.envpolicy import env_policy

MODEL_AXIS = "model"


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class PopSharding:
    """Resolved placement for the stacked population arrays."""
    mesh: Optional[Mesh]    # None => single-device path
    n_shards: int
    # padded global row counts (None => no padding, rows == real sizes)
    n_g_pad: Optional[int] = None
    n_b_pad: Optional[int] = None
    model_shards: int = 1

    @property
    def active(self) -> bool:
        return self.mesh is not None

    @property
    def sharding(self) -> NamedSharding:
        """Rows split over the "pop" mesh axis (leading-dim sharding).
        On a 2-D mesh the arrays replicate over "model"."""
        assert self.mesh is not None
        return NamedSharding(self.mesh, PartitionSpec(POP_AXIS))

    @property
    def wide_sharding(self) -> NamedSharding:
        """Rows split over EVERY device: the flattened ("pop", "model")
        super-axis on a 2-D mesh (== ``sharding`` on a 1-D mesh).  Used
        for big-bucket population forwards, where pop*model-way row
        parallelism beats replicating the work model_shards times."""
        assert self.mesh is not None
        if self.model_shards <= 1:
            return self.sharding
        return NamedSharding(self.mesh,
                             PartitionSpec((POP_AXIS, MODEL_AXIS)))

    def put(self, x):
        """Place a stacked (P, ...) array (no-op when unsharded)."""
        return jax.device_put(x, self.sharding) if self.active else x

    def put_wide(self, x):
        """Place a stacked (P, ...) array row-split over all devices."""
        return jax.device_put(x, self.wide_sharding) if self.active else x

    def map_rows(self, fn, wide: bool = False):
        """Jitted ``fn(*replicated, rows)`` run shard by shard over the
        rows of its last argument (``sharding``, or ``wide_sharding``
        when ``wide``), every other argument replicated.  The per-row
        work is independent, and a Pallas kernel inside cannot be
        partitioned by the compiler, so the split is an explicit
        ``shard_map`` rather than auto-SPMD."""
        assert self.mesh is not None
        spec = (self.wide_sharding if wide else self.sharding).spec

        def run(*args):
            in_specs = (PartitionSpec(),) * (len(args) - 1) + (spec,)
            return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=spec, check_vma=False)(*args)

        return jax.jit(run)

    def padded(self, n_g: int, n_b: int) -> Tuple[int, int]:
        """Row counts the population arrays must be allocated with."""
        return (self.n_g_pad if self.n_g_pad is not None else n_g,
                self.n_b_pad if self.n_b_pad is not None else n_b)


def resolve_pop_sharding(n_g: int, n_b: int,
                         requested: Union[int, str, None] = None,
                         model_shards: Union[int, str, None] = None
                         ) -> PopSharding:
    """Resolve the shard count for an (n_g, n_b) population split.

    ``requested`` overrides the ``REPRO_POP_SHARDS`` env var and
    ``model_shards`` the ``REPRO_MODEL_SHARDS`` env var; see the module
    docstring for the accepted values.  Unknown values fail loud through
    the shared ``repro.utils.envpolicy`` resolver (valid options listed
    in the error), like every other REPRO_* policy.
    """
    req = env_policy("REPRO_POP_SHARDS",
                     choices=("auto", "", "off", "0", "1"),
                     default="auto", override=requested, int_ok=True)
    m_req = env_policy("REPRO_MODEL_SHARDS",
                       choices=("auto", "", "off", "0", "1"),
                       default="off", override=model_shards, int_ok=True)
    if n_g + n_b == 0:                      # pure-PG mode: nothing to shard
        return PopSharding(None, 1)
    n_dev = len(jax.devices())
    if m_req in ("auto", ""):
        # opt-in axis: auto claims leftover devices only after the pop
        # axis took its share (resolved below), so compute it lazily
        m = 0
    elif m_req in ("off", "0", "1"):
        m = 1
    else:
        m = m_req                           # an integer >= 1
    if req in ("auto", ""):
        n = min(n_dev // max(m, 1), max(n_g, n_b, 1))
        n = max(n, 1)
    elif req in ("off", "0", "1"):
        n = 1
    else:
        n = req                             # an integer >= 1
        if n > n_dev:
            raise ValueError(
                f"REPRO_POP_SHARDS={n} but only {n_dev} device(s) visible")
    if m == 0:                              # model auto: leftover devices
        m = max(n_dev // max(n, 1), 1)
        m = 1 if n <= 1 else m              # no pop mesh -> no model mesh
    if n * m > n_dev:
        raise ValueError(
            f"REPRO_POP_SHARDS={n} x REPRO_MODEL_SHARDS={m} needs "
            f"{n * m} device(s) but only {n_dev} visible")
    if n <= 1:
        return PopSharding(None, 1)
    # wide row splits divide rows by n*m, evolve splits by n — rounding
    # to n*m satisfies both (n divides n*m)
    mesh = make_pop_model_mesh(n, m) if m > 1 else make_pop_mesh(n)
    return PopSharding(mesh, n,
                       _round_up(n_g, n * m) if n_g else 0,
                       _round_up(n_b, n * m) if n_b else 0,
                       model_shards=m)

"""Production mesh builders. Functions, not module constants, so importing
this module never touches jax device state (dry-run must set XLA_FLAGS
before first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Mesh with every axis ``AxisType.Auto``: the sharding of each
    intermediate is left to the compiler, which population evaluation
    (auto-SPMD over "pop") and ``with_sharding_constraint`` rely on.
    Every mesh in the repo is built here (tests use small shapes like
    (2, 4))."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def _check_devices(needed: int, what: str) -> None:
    """Fail loud BEFORE building the mesh when a requested mesh wants more
    devices than exist — otherwise the request surfaces much later as an
    opaque XLA sharding error deep inside a jitted call."""
    n_dev = len(jax.devices())
    if needed > n_dev:
        raise ValueError(
            f"{what} requests {needed} device(s) but only {n_dev} are "
            f"visible — lower the shard count or raise the device count "
            f"(e.g. XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"for CPU testing)")


def make_pop_mesh(n_shards: int | None = None):
    """1-D mesh over the EA population axis ``("pop",)``.

    Uses the first ``n_shards`` local devices (default: all of them).
    The EGRL driver shards the stacked (P, ...) genome arrays over this
    axis; see repro.distributed.population for the shard-count policy.
    """
    n = n_shards or len(jax.devices())
    _check_devices(n, f"REPRO_POP_SHARDS={n_shards}" if n_shards
                   else "make_pop_mesh()")
    return make_mesh((n,), ("pop",))


def make_pop_model_mesh(pop_shards: int, model_shards: int):
    """2-D mesh ``("pop", "model")`` over pop_shards * model_shards
    devices.

    The EA genome arrays are sharded ``P("pop")`` (replicated over
    "model" — shard_map specs that never mention the axis replicate
    across it, so ``evolve_sharded`` runs unchanged and bit-identical).
    Wide per-bucket GNN forwards shard their population rows over the
    flattened ``P(("pop", "model"))`` super-axis — a pure row split, so
    per-row results stay bit-identical to the replicated path.
    """
    needed = pop_shards * model_shards
    _check_devices(needed, f"REPRO_POP_SHARDS={pop_shards} x "
                           f"REPRO_MODEL_SHARDS={model_shards}")
    return make_mesh((pop_shards, model_shards), ("pop", "model"))

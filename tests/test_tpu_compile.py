"""Compile-only checks against a described TPU v5e: the GAT kernel pair
and its XLA fallback must be accepted by the TPU compiler at the zoo's
graph sizes (resnet50 57, bert 388, moe_transformer 1043 nodes), both
alone and vmapped over a population as ``core/gnn.py`` calls them.

Nothing runs: the v5e compiler in the installed libtpu compiles for a
chip that is described, not attached.  The topology is described inside
a fixture, never at import, so every test worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gat_mp.ops import gat_mp, gat_mp_chunked

D, HEADS, POP = 128, 4, 16
SIZES = (57, 388, 1043)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _args(n, lead, sharding):
    def sds(*shape):
        return jax.ShapeDtypeStruct(lead + shape, jnp.float32,
                                    sharding=sharding)
    return sds(n, D), sds(n, HEADS), sds(n, HEADS), sds(n, n)


def _compiled_text(fn, args):
    grad = jax.value_and_grad(
        lambda z, es, ed, adj: (fn(z, es, ed, adj) ** 2).sum(),
        argnums=(0, 1, 2))
    if args[0].ndim == 3:
        grad = jax.vmap(grad)
    return jax.jit(grad).lower(*args).compile().as_text()


@pytest.mark.parametrize("vmapped", [False, True], ids=["single", "pop16"])
@pytest.mark.parametrize("n", SIZES)
def test_gat_pair_compiles_for_v5e(one_chip, n, vmapped):
    """Forward + backward kernels compiled (not interpreted) for TPU."""
    lead = (POP,) if vmapped else ()
    text = _compiled_text(
        lambda *a: gat_mp(*a, heads=HEADS, interpret=False),
        _args(n, lead, one_chip))
    assert "tpu_custom_call" in text


def test_chunked_fallback_compiles_for_v5e(one_chip):
    n = 1043
    text = _compiled_text(
        lambda *a: gat_mp_chunked(*a, heads=HEADS), _args(n, (), one_chip))
    assert "tpu_custom_call" not in text

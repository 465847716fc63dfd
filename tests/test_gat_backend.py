"""GAT backend dispatch: forward parity of the fused kernel + chunked
XLA backends vs the dense jnp path, gradient parity of both custom_vjp
pairs vs ``jax.grad`` through the dense path (unmasked and masked/padded
— pad rows inert in the backward too), interpret-mode backward-kernel
parity vs the XLA fallback, and a jaxpr assertion that the DEFAULT
training path contains no dense ``(N, N, H)`` attention intermediate.
Pallas runs in interpret mode on CPU (parity only)."""
import numpy as np

import jax
import jax.extend
import jax.numpy as jnp
import pytest

from repro.core import gat_tune, gnn
from repro.core.sac import critic_defs, critic_forward_masked
from repro.graphs.zoo import resnet50
from repro.kernels.gat_mp.ops import gat_mp, gat_mp_chunked
from repro.kernels.gat_mp.ref import gat_mp_ref
from repro.utils.params import init_params

TOL = 1e-4
GRAD_TOL = 1e-5           # acceptance bar: custom_vjp grads vs dense path


def _random_graph_inputs(n, key):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    feats = jax.random.normal(k1, (n, 19))
    adj = (jax.random.uniform(k2, (n, n)) < 0.08).astype(np.float32)
    adj = np.asarray(adj)
    adj = np.maximum(adj, adj.T) + np.eye(n, dtype=np.float32)
    adj = adj / adj.sum(1, keepdims=True)   # row-normalized, self loops
    return feats, jnp.asarray(adj)


def _op_inputs(n, heads, hd, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    z = jax.random.normal(ks[0], (n, heads * hd))
    es = jax.random.normal(ks[1], (n, heads))
    ed = jax.random.normal(ks[2], (n, heads))
    adj = (jax.random.uniform(ks[3], (n, n)) < 0.08)
    adj = np.asarray(adj)
    adj = np.maximum(adj, adj.T) | np.eye(n, dtype=bool)
    return z, es, ed, jnp.asarray(adj, jnp.float32)


def test_resolve_backend():
    assert gnn.resolve_backend("jnp") == "jnp"
    assert gnn.resolve_backend("pallas") == "pallas"
    assert gnn.resolve_backend("chunked") == "chunked"
    auto = gnn.resolve_backend("auto")   # shape-free platform default
    assert auto == ("pallas" if jax.default_backend() == "tpu"
                    else "chunked")
    # shape-aware auto resolves through the autotune cache and never
    # picks the dense materializing path
    assert gnn.resolve_backend("auto", n=57) in ("chunked", "pallas")
    with pytest.raises(ValueError, match="REPRO_GAT_BACKEND"):
        gnn.resolve_backend("cuda")


def test_resolve_backend_env_policy(monkeypatch):
    """REPRO_GAT_BACKEND resolves through the shared fail-loud helper:
    unknown values raise listing every valid option."""
    monkeypatch.setenv("REPRO_GAT_BACKEND", "chunked")
    assert gnn.resolve_backend() == "chunked"
    monkeypatch.setenv("REPRO_GAT_BACKEND", "jnp")
    assert gnn.resolve_backend(n=57) == "jnp"    # env wins over autotune
    monkeypatch.setenv("REPRO_GAT_BACKEND", "cuda")
    with pytest.raises(ValueError) as e:
        gnn.resolve_backend()
    for opt in gnn.GAT_BACKENDS:
        assert opt in str(e.value)


def test_autotune_caches_and_skips_dense():
    res = gat_tune.autotune(57, 128, 4, jnp.float32)
    assert res.backend in ("chunked", "pallas")
    assert res is gat_tune.autotune(57, 128, 4, jnp.float32)   # cache hit
    timed = gat_tune.autotune(200, 128, 4, jnp.float32,
                              include_dense=True, force_time=True)
    assert "jnp" in timed.timings            # dense is timed for the record
    assert timed.backend != "jnp"            # ... but never selected
    for row in timed.timings.values():
        assert row["fwd_us"] > 0 and row["fwd_bwd_us"] > 0


@pytest.mark.parametrize("backend", ["pallas", "chunked"])
def test_gnn_forward_backend_parity_real_graph(backend):
    """resnet50: N=57 — every pooling level needs padding in the kernel."""
    g = resnet50()
    feats, adj = jnp.asarray(g.features()), jnp.asarray(g.adjacency())
    p = gnn.init_gnn(jax.random.PRNGKey(0), feats.shape[1])
    ref = gnn.gnn_forward(p, feats, adj, backend="jnp")
    out = gnn.gnn_forward(p, feats, adj, backend=backend)
    assert out.shape == (g.n, 2, 3)
    assert float(jnp.abs(out - ref).max()) < TOL


@pytest.mark.parametrize("backend", ["pallas", "chunked"])
@pytest.mark.parametrize("n", [64, 128])
def test_gnn_forward_backend_parity_synthetic(n, backend):
    """n=128 hits the no-padding fast path at level 0; n=64 pads."""
    feats, adj = _random_graph_inputs(n, key=1)
    p = gnn.init_gnn(jax.random.PRNGKey(2), feats.shape[1])
    ref = gnn.gnn_forward(p, feats, adj, backend="jnp")
    out = gnn.gnn_forward(p, feats, adj, backend=backend)
    assert float(jnp.abs(out - ref).max()) < TOL


def test_gat_backend_parity_under_vmap():
    """The population forward vmaps gnn_forward over stacked flat params —
    the kernels must batch correctly."""
    g = resnet50()
    feats, adj = jnp.asarray(g.features()), jnp.asarray(g.adjacency())
    template = gnn.init_gnn(jax.random.PRNGKey(0), feats.shape[1])
    vecs = jnp.stack([
        gnn.flatten_params(gnn.init_gnn(jax.random.PRNGKey(i), 19))
        for i in range(3)])

    def fwd(vec, backend):
        return gnn.gnn_forward(gnn.unflatten_params(template, vec),
                               feats, adj, backend=backend)

    ref = jax.vmap(lambda v: fwd(v, "jnp"))(vecs)
    for backend in ("pallas", "chunked"):
        out = jax.vmap(lambda v: fwd(v, backend))(vecs)
        assert float(jnp.abs(out - ref).max()) < TOL


# --------------------------------------------------- custom_vjp gradients
@pytest.mark.parametrize("n,heads,hd", [(57, 4, 32), (200, 4, 32)])
@pytest.mark.parametrize("op", ["pallas", "chunked"])
def test_op_grad_parity_vs_dense(n, heads, hd, op):
    """Op-level gradient parity: both custom_vjp pairs match jax.grad
    through the dense jnp oracle to <= 1e-5 on z, e_src and e_dst."""
    z, es, ed, adj = _op_inputs(n, heads, hd)
    w = jax.random.normal(jax.random.PRNGKey(9), (n, heads * hd))
    fused = (gat_mp if op == "pallas"
             else lambda *a, **k: gat_mp_chunked(*a, chunk=64, **k))

    def loss(fn):
        return lambda z, es, ed: (fn(z, es, ed, adj, heads=heads) * w).sum()

    g_ref = jax.grad(loss(gat_mp_ref), argnums=(0, 1, 2))(z, es, ed)
    g_op = jax.grad(loss(fused), argnums=(0, 1, 2))(z, es, ed)
    for a, b in zip(g_ref, g_op):
        assert float(jnp.abs(a - b).max()) <= GRAD_TOL


@pytest.mark.parametrize("op", ["pallas", "chunked"])
def test_op_grad_masked_pad_rows_inert(op):
    """Masked/padded graph: with zero cotangents on pad rows, (a) grads
    match the dense path, (b) pad-row grads are exact zeros off the
    self-loop, and (c) garbage content in pad slots changes NO real-row
    gradient bitwise (the attention weights into pad columns are exact
    zeros in the backward too)."""
    n_real, n = 40, 64
    heads, hd = 4, 32
    z, es, ed, _ = _op_inputs(n, heads, hd, key=2)
    adj = np.zeros((n, n), np.float32)
    rng = np.random.default_rng(0)
    block = (rng.random((n_real, n_real)) < 0.15).astype(np.float32)
    adj[:n_real, :n_real] = np.maximum(block, block.T)
    adj[np.arange(n), np.arange(n)] = 1.0            # pad rows: self-loop
    adj = jnp.asarray(adj)
    w = np.array(jax.random.normal(jax.random.PRNGKey(3), (n, heads * hd)))
    w[n_real:] = 0.0                                 # zero pad cotangents
    w = jnp.asarray(w)
    fused = (gat_mp if op == "pallas"
             else lambda *a, **k: gat_mp_chunked(*a, chunk=32, **k))

    def grads(fn, z_, es_, ed_):
        return jax.grad(
            lambda z, es, ed: (fn(z, es, ed, adj, heads=heads) * w).sum(),
            argnums=(0, 1, 2))(z_, es_, ed_)

    g_ref = grads(lambda *a, **k: gat_mp_ref(*a, **k), z, es, ed)
    g_op = grads(fused, z, es, ed)
    for a, b in zip(g_ref, g_op):
        assert float(jnp.abs(a - b).max()) <= GRAD_TOL
    # pad rows receive no gradient (their only attention is the inert
    # self-loop whose cotangent is zero)
    for g in g_op:
        assert float(jnp.abs(g[n_real:]).max()) == 0.0
    # garbage in pad slots is invisible to real-row grads, bitwise
    garb = jnp.asarray(
        np.where(np.arange(n)[:, None] >= n_real, 1e6, 0.0), jnp.float32)
    g_garb = grads(fused, z + garb, es + garb[:, :heads],
                   ed + garb[:, :heads])
    for a, b in zip(g_op, g_garb):
        np.testing.assert_array_equal(np.asarray(a[:n_real]),
                                      np.asarray(b[:n_real]))


def test_pallas_backward_matches_chunked_fallback():
    """Interpret-mode backward-kernel parity vs the pure-XLA fallback:
    the two custom_vjp pairs are the same operator."""
    n, heads, hd = 130, 2, 64
    z, es, ed, adj = _op_inputs(n, heads, hd, key=5)
    w = jax.random.normal(jax.random.PRNGKey(6), (n, heads * hd))

    def grads(fn):
        return jax.grad(
            lambda z, es, ed: (fn(z, es, ed, adj, heads=heads) * w).sum(),
            argnums=(0, 1, 2))(z, es, ed)

    g_p = grads(gat_mp)
    g_c = grads(lambda *a, **k: gat_mp_chunked(*a, chunk=64, **k))
    for a, b in zip(g_p, g_c):
        assert float(jnp.abs(a - b).max()) <= GRAD_TOL


# ---------------------------------------------- no dense (N, N, H) tensor
def _all_shapes(jaxpr, acc):
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.add(tuple(aval.shape))
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                _all_shapes(sub, acc)
    return acc


def _sub_jaxprs(val):
    if isinstance(val, jax.extend.core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jax.extend.core.Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _sub_jaxprs(v)


def _has_dense_attention(jaxpr, n, heads):
    shapes = _all_shapes(jaxpr.jaxpr, set())
    return any(
        len(s) >= 3 and any(s[i] == n and s[i + 1] == n and s[i + 2] == heads
                            for i in range(len(s) - 2))
        for s in shapes)


def test_default_training_path_has_no_dense_attention():
    """The jaxpr of jax.grad through the DEFAULT-backend actor forward
    and critic contains no (N, N, H)-shaped intermediate; the explicit
    dense jnp path does (validating the detector).  N=200 collides with
    no parameter dimension (hidden 128, pools 100/50)."""
    n = 200
    feats, adj = _random_graph_inputs(n, key=7)
    p = gnn.init_gnn(jax.random.PRNGKey(8), feats.shape[1])
    w = jax.random.normal(jax.random.PRNGKey(9), (n, 2, 3))

    def actor_loss(p, backend=None):
        return (gnn.gnn_forward(p, feats, adj, backend) * w).sum()

    jx = jax.make_jaxpr(jax.grad(actor_loss))(p)
    assert not _has_dense_attention(jx, n, gnn.HEADS)
    jx_dense = jax.make_jaxpr(lambda p: jax.grad(actor_loss)(p, "jnp"))(p)
    assert _has_dense_attention(jx_dense, n, gnn.HEADS)

    cp = init_params(critic_defs(feats.shape[1]), jax.random.PRNGKey(10))
    oh = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(11), (n, 2), 0, 3), 3)
    live = jnp.ones((n,), feats.dtype)

    def critic_loss(cp, backend=None):
        q1, q2 = critic_forward_masked(cp, feats, adj, live, oh, backend)
        return q1 + q2

    jc = jax.make_jaxpr(jax.grad(critic_loss))(cp)
    assert not _has_dense_attention(jc, n, gnn.HEADS)
    jc_dense = jax.make_jaxpr(lambda cp: jax.grad(critic_loss)(cp, "jnp"))(cp)
    assert _has_dense_attention(jc_dense, n, gnn.HEADS)

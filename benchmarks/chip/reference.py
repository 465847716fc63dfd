"""Plain reference of one EGRL generation, written from the paper and
independent of the program under test (it imports nothing from it).

Layers, each a straightforward implementation of what the paper
(arXiv 2007.07298, sections 3-4 and appendices D-E) and the
configuration state:

- the Graph U-Net policy forward on one UNPADDED graph, dense
  attention, with the top-k pooling of Gao & Ji;
- the double-Q critic and the SAC update (Adam, critic step on noisy
  one-hot actions, actor step through the updated critic, exact
  discrete entropy), as a scan over the gradient steps of one update;
- the EA step: elitism, tournament selection, single-point crossover,
  GNN->Boltzmann seeding and Gaussian mutation, drawing its random
  numbers with ``jax.random`` in the order the configuration's EA
  defines, so its children can be compared row by row;
- the memory simulator: the rectifier's sequential allocation in
  float32 (the simulated compiler's counters are float32), the
  roofline latency in float64, the compiler-heuristic baseline and the
  reward.

Every function takes ``dtype``: float32 (run under ``highest`` matmul
precision) is the reference, bfloat16 is the control that the
comparison must reject.  Weights are made here from the seed
(``make_weights``), so the reference never reads weights the program
made.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

HIDDEN, HEADS, DEPTH = 128, 4, 4
N_SUB, N_TIER = 2, 3

# ---------------------------------------------------------- parameters
# Flat genome layout: leaves in sorted-key order, each row-major.


def gnn_layout(n_features: int, d: int = HIDDEN, h: int = HEADS
               ) -> List[Tuple[str, Tuple[int, ...]]]:
    gat = [("a_dst", (h, d // h)), ("a_src", (h, d // h)), ("b", (d,)),
           ("w", (d, d))]
    out = []
    for i in range(DEPTH):
        out += [(f"gat{i}.{k}", s) for k, s in gat]
    out += [("inp", (n_features, d)), ("out1", (d, d)),
            ("out2", (d, N_SUB * N_TIER)), ("out_b1", (d,)),
            ("pool1", (d,)), ("pool2", (d,))]
    return out


def critic_layout(n_features: int, d: int = HIDDEN, h: int = HEADS
                  ) -> List[Tuple[str, Tuple[int, ...]]]:
    gat = [("a_dst", (h, d // h)), ("a_src", (h, d // h)), ("b", (d,)),
           ("w", (d, d))]
    out = [("b1", (d,)), ("b2", (d,))]
    for i in range(2):
        out += [(f"gat{i}.{k}", s) for k, s in gat]
    out += [("h1", (d, d)), ("h2", (d, d)), ("inp", (n_features + 6, d)),
            ("q1", (d, 1)), ("q2", (d, 1))]
    return out


def unflatten(layout, vec) -> Dict[str, jnp.ndarray]:
    out, off = {}, 0
    for name, shape in layout:
        n = math.prod(shape)
        out[name] = vec[off:off + n].reshape(shape)
        off += n
    return out


def flatten(layout, p) -> jnp.ndarray:
    return jnp.concatenate([p[name].reshape(-1) for name, _ in layout])


def _init_leaf(key, name, shape):
    leaf = name.split(".")[-1]
    if leaf in ("b", "b1", "b2", "out_b1"):
        return jnp.zeros(shape, jnp.float32)
    fan_in = math.prod(shape[:-1]) if len(shape) > 1 else 1
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _init_tree(key, layout):
    keys = jax.random.split(key, len(layout))
    return {name: _init_leaf(k, name, shape)
            for k, (name, shape) in zip(keys, layout)}


def make_weights(seed: int, *, n_features: int, n_gnn: int, n_bz: int,
                 bz_nodes: int):
    """All initial weights of a run from its seed, in one jitted call:
    the GNN genomes (n_gnn, V), the Boltzmann genomes (n_bz, F) (prior
    one-hot on tier 0 plus 0.1 noise, temperature 1), and the SAC actor
    and critic as flat vectors.  Scaled-normal weights (std
    1/sqrt(fan-in)), zero biases."""
    g_lay, c_lay = gnn_layout(n_features), critic_layout(n_features)

    @jax.jit
    def make(key):
        kg, kb, ka, kc = jax.random.split(key, 4)
        gnn = jax.vmap(lambda k: flatten(g_lay, _init_tree(k, g_lay)))(
            jax.random.split(kg, n_gnn))
        prior = (jnp.zeros((n_bz, bz_nodes, N_SUB, N_TIER)).at[..., 0]
                 .set(1.0) + 0.1 * jax.random.normal(
                     kb, (n_bz, bz_nodes, N_SUB, N_TIER)))
        bz = jnp.concatenate([prior.reshape(n_bz, -1),
                              jnp.zeros((n_bz, bz_nodes * N_SUB))], axis=1)
        actor = flatten(g_lay, _init_tree(ka, g_lay))
        critic = flatten(c_lay, _init_tree(kc, c_lay))
        return gnn, bz, actor, critic

    return make(jax.random.PRNGKey(seed))


# ------------------------------------------------------------- graphs
def graph_arrays(graph) -> Dict[str, np.ndarray]:
    """Static arrays of a workload graph (its nodes in topological order
    and its edges), read from the graph's own fields."""
    nodes = graph.nodes
    n = len(nodes)
    last = np.arange(n)
    producers: List[List[int]] = [[] for _ in range(n)]
    a = np.zeros((n, n), np.float32)
    for s, d in graph.edges:
        last[s] = max(last[s], d)
        producers[d].append(s)
        a[s, d] = a[d, s] = 1.0
    a += np.eye(n, dtype=np.float32)
    act = np.array([float(np.prod(nd.ofm)) * 2 * nd.batch for nd in nodes])
    return {
        "n": n, "adj": a > 0,
        "weight_bytes": np.array([nd.weight_bytes for nd in nodes],
                                 np.float64),
        "weight_frac": np.array([nd.weight_access_frac for nd in nodes],
                                np.float64),
        "act_bytes": act, "flops": np.array([nd.flops for nd in nodes],
                                            np.float64),
        "last_consumer": last, "producers": producers,
    }


# ---------------------------------------------------------- simulator
# The simulated memory hierarchy (capacity, bytes/s) and compute model
# the configuration's reward is defined on.
CAPACITY = (16 * 2 ** 30, 128 * 2 ** 20, 48 * 2 ** 20)     # HBM, CMEM, VMEM
BANDWIDTH = (819e9, 2.8e12, 22e12)
PEAK_FLOPS, OP_UTILIZATION, OVERHEAD_S = 197e12, 0.6, 2e-6
HBM, CMEM, VMEM = 0, 1, 2


def rectify_rows(ga, maps: np.ndarray):
    """Sequential allocation of ``maps`` (P, n, 2) in topological order:
    a weight stays for the whole run, an activation until its last
    consumer; a tensor that does not fit its tier goes to HBM.  float32
    counters, releases added in ascending node order.  Returns
    (rectified (P, n, 2), eps (P,) float32)."""
    maps = np.asarray(maps)
    p, n = maps.shape[0], ga["n"]
    wb = ga["weight_bytes"].astype(np.float32)
    ab = ga["act_bytes"].astype(np.float32)
    released: List[List[int]] = [[] for _ in range(n)]
    for node, t in enumerate(ga["last_consumer"]):
        released[int(t)].append(node)
    free = np.tile(np.asarray(CAPACITY, np.float32), (p, 1))
    moved = np.zeros(p, np.float32)
    out = np.zeros((p, n, 2), np.int32)
    rows = np.arange(p)
    for t in range(n):
        for col, nbytes in ((0, wb[t]), (1, ab[t])):
            want = maps[:, t, col]
            fits = free[rows, want] >= nbytes
            tier = np.where(fits, want, HBM)
            moved = np.where(fits, moved, (moved + nbytes).astype(np.float32))
            free[rows, tier] = (free[rows, tier] - nbytes).astype(np.float32)
            out[:, t, col] = tier
        per_tier = np.zeros((p, 3), np.float32)
        for r in released[t]:
            per_tier[rows, out[:, r, 1]] = (per_tier[rows, out[:, r, 1]]
                                            + ab[r]).astype(np.float32)
        free = (free + per_tier).astype(np.float32)
    total = np.float32(0.0)
    for v in np.concatenate([wb, ab]):
        total = np.float32(total + v)
    eps = (moved / max(total, np.float32(1.0))).astype(np.float32)
    return out, eps


def latency_rows(ga, rect: np.ndarray) -> np.ndarray:
    """Roofline latency (float64 seconds) of rectified mappings (P, n, 2):
    per node max(compute, weight fetch + output write + input reads)
    plus a fixed overhead, summed over the sequential schedule."""
    bw = np.asarray(BANDWIDTH, np.float64)
    w_t = ga["weight_bytes"] * ga["weight_frac"] / bw[rect[..., 0]]
    out_t = ga["act_bytes"] / bw[rect[..., 1]]
    in_t = np.zeros_like(out_t)
    for i, prods in enumerate(ga["producers"]):
        for s in prods:
            in_t[:, i] += ga["act_bytes"][s] / bw[rect[:, s, 1]]
    comp = ga["flops"] / (PEAK_FLOPS * OP_UTILIZATION)
    return (np.maximum(w_t + out_t + in_t, comp) + OVERHEAD_S).sum(-1)


def heuristic_mapping(graph) -> np.ndarray:
    """The compiler baseline: pin tensors up to 64 KiB in VMEM and up to
    1 MiB in CMEM, within half of each tier, everything else in HBM."""
    budget = {VMEM: CAPACITY[VMEM] * 0.5, CMEM: CAPACITY[CMEM] * 0.5}
    m = np.zeros((len(graph.nodes), 2), np.int32)
    for i, nd in enumerate(graph.nodes):
        ab = float(np.prod(nd.ofm)) * 2 * nd.batch
        for col, nbytes in ((0, nd.weight_bytes), (1, ab)):
            tier = HBM
            if nbytes <= 64 * 2 ** 10 and budget[VMEM] >= nbytes:
                tier = VMEM
            elif nbytes <= 2 ** 20 and budget[CMEM] >= nbytes:
                tier = CMEM
            if tier != HBM:
                budget[tier] -= nbytes
            m[i, col] = tier
    return m


def rewards(graph, ga, maps: np.ndarray, reward_scale: float,
            dtype=np.float64) -> np.ndarray:
    """Reward of each mapping (P, n, 2): reward_scale x speedup over the
    compiler baseline when the mapping needs no rectification, else
    minus the share of bytes the rectifier moved.  ``dtype`` is the
    precision of the latency sums (the control passes a lower one)."""
    rect, eps = rectify_rows(ga, maps)
    base_rect, _ = rectify_rows(ga, heuristic_mapping(graph)[None])
    lat = latency_rows(ga, rect).astype(dtype)
    base = latency_rows(ga, base_rect).astype(dtype)[0]
    speed = (base / lat).astype(np.float64)
    return np.where(eps <= 0.0, reward_scale * speed, -eps.astype(np.float64))


# ------------------------------------------------------------- policy
def _dot(a, b):
    return jnp.matmul(a, b, preferred_element_type=a.dtype)


def gat(p, h, mask, d=HIDDEN, heads=HEADS):
    n = h.shape[0]
    z = _dot(h, p["w"])
    zh = z.reshape(n, heads, d // heads)
    e_src = jnp.einsum("nhd,hd->nh", zh, p["a_src"])
    e_dst = jnp.einsum("nhd,hd->nh", zh, p["a_dst"])
    e = jax.nn.leaky_relu(e_src[:, None, :] + e_dst[None, :, :], 0.2)
    e = jnp.where(mask[:, :, None], e, jnp.asarray(-1e30, e.dtype))
    alpha = jax.nn.softmax(e, axis=1)
    out = jnp.einsum("njh,jhd->nhd", alpha, zh).reshape(n, d)
    return jax.nn.elu(out + p["b"]) + h


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _pool(w, h, mask, k):
    score = jnp.tanh(_dot(h, w) / (jnp.linalg.norm(w) + 1e-6))
    val, idx = jax.lax.top_k(score, k)
    return h[idx] * val[:, None], mask[idx][:, idx], idx


def gnn_forward(p, feats, mask):
    """Policy logits (n, 2, 3) of one unpadded graph."""
    n = feats.shape[0]
    k1, k2 = max(2, n // 2), max(2, n // 4)
    h = jnp.tanh(_dot(feats, p["inp"]))
    h = gat(_sub(p, "gat0."), h, mask)
    h1, m1, i1 = _pool(p["pool1"], h, mask, k1)
    h1 = gat(_sub(p, "gat1."), h1, m1)
    h2, m2, i2 = _pool(p["pool2"], h1, m1, k2)
    h2 = gat(_sub(p, "gat2."), h2, m2)
    h1u = jnp.zeros_like(h1).at[i2].set(h2) + h1
    h1u = gat(_sub(p, "gat3."), h1u, m1)
    hu = jnp.zeros_like(h).at[i1].set(h1u) + h
    z = jax.nn.elu(_dot(hu, p["out1"]) + p["out_b1"])
    return _dot(z, p["out2"]).reshape(n, N_SUB, N_TIER)


def features(graph) -> np.ndarray:
    """The policy's node features (paper Table 1): op id, log sizes and
    dims of weights and feature maps, ops and weight bytes left, conv
    parameters, batch; z-normed per graph except the op id."""
    from itertools import accumulate
    op_types = ("input", "conv", "pool", "fc", "embed", "norm_proj", "qkv",
                "attn", "o_proj", "mlp", "moe_router", "expert_bank", "ssm",
                "conv1d", "cross_attn", "lm_head", "kv_cache", "add",
                "softmax")
    nodes = graph.nodes
    n = len(nodes)
    w_after = list(accumulate(nd.weight_bytes for nd in reversed(nodes)))
    w_after = [0.0] + w_after          # w_after[j]: weights of the last j
    rows = []
    for i, nd in enumerate(nodes):
        ifm_b = float(np.prod(nd.ifm)) * 2 * nd.batch
        ofm_b = float(np.prod(nd.ofm)) * 2 * nd.batch
        rows.append([op_types.index(nd.op), np.log1p(nd.weight_bytes),
                     nd.ifm[0], nd.ifm[1], np.log1p(nd.ifm[2]),
                     nd.ofm[0], nd.ofm[1], np.log1p(nd.ofm[2]),
                     np.log1p(ifm_b), np.log1p(ofm_b), (n - 1 - i) / n,
                     np.log1p(w_after[n - 1 - i]), nd.groups,
                     nd.kernel[0], nd.kernel[1], nd.stride, nd.pad,
                     nd.dilation, nd.batch])
    f = np.asarray(rows, np.float32)
    out = (f - f.mean(0, keepdims=True)) / (f.std(0, keepdims=True) + 1e-6)
    out[:, 0] = f[:, 0] / len(op_types)
    return out


def population_logits(layout, pop, feats, mask, dtype=jnp.float32):
    """(P, V) genomes -> (P, n, 2, 3) logits on one graph."""
    f = jnp.asarray(feats, dtype)
    return jax.vmap(lambda v: gnn_forward(
        unflatten(layout, v.astype(dtype)), f, mask))(pop)


# ------------------------------------------------------------- critic
def critic_forward(cp, feats, mask, act):
    """Double-Q values of one action (n, 2, 3) on one unpadded graph."""
    n = feats.shape[0]
    x = jnp.concatenate([feats, act.reshape(n, 6).astype(feats.dtype)], -1)
    h = jnp.tanh(_dot(x, cp["inp"]))
    h = gat(_sub(cp, "gat0."), h, mask)
    h = gat(_sub(cp, "gat1."), h, mask)
    g = h.mean(axis=0)
    q1 = _dot(jax.nn.elu(_dot(g, cp["h1"]) + cp["b1"]), cp["q1"])[0]
    q2 = _dot(jax.nn.elu(_dot(g, cp["h2"]) + cp["b2"]), cp["q2"])[0]
    return q1, q2


def entropy(logits):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -(jnp.exp(lp) * lp).sum(-1).mean()


def adam(lr, p, g, m, v, t):
    """Adam (b1 0.9, b2 0.999, eps 1e-8, bias-corrected), each leaf kept
    in its own dtype."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def leaf(p_, g_, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * g_ * g_
        dt = p_.dtype
        new = p_ - lr * (m_ / c1.astype(dt)) / (jnp.sqrt(v_ / c2.astype(dt))
                                                + eps)
        return new.astype(dt), m_.astype(dt), v_.astype(dt)

    out = jax.tree.map(leaf, p, g, m, v)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], p, out)  # noqa: E731
    return pick(0), pick(1), pick(2)


def sac_update(graphs_in, actor, critic, acts, rews, noise, *, n_features,
               lr_actor, lr_critic, alpha, adam_state=None,
               dtype=jnp.float32, keep=None):
    """One SAC update: a scan of gradient steps.

    graphs_in: per graph (feats (n, F), mask (n, n)); actor / critic flat
    vectors; acts, rews and noise are lists over the graphs (zoo order)
    of (U, B, n_pad, 2), (U, B) and (U, B, n_pad, 2, 3) arrays, of which
    only each graph's first n node rows are read.  ``adam_state`` is the
    optimizers' state the update starts from, (actor m, actor v, actor
    step count, critic m, critic v, critic step count) with the moments
    as flat vectors; None starts both afresh.  ``keep`` (a fault for the
    comparison's own test) trains the critic on the first ``keep``
    transitions of each batch only.  Returns (actor, critic, last critic
    loss, last actor loss, first critic gradient, first actor gradient),
    parameters and gradients as flat vectors."""
    ns = tuple(int(f.shape[0]) for f, _ in graphs_in)
    run = _sac_program(n_features, lr_actor, lr_critic, alpha,
                       jnp.dtype(dtype).name, keep, ns)
    feats = [jnp.asarray(f, dtype) for f, _ in graphs_in]
    masks = [jnp.asarray(m) for _, m in graphs_in]
    if adam_state is None:
        za, zc = np.zeros_like(actor), np.zeros_like(critic)
        adam_state = (za, za, 0, zc, zc, 0)
    ma, va, ta, mc, vc, tc = adam_state
    state = tuple(jnp.asarray(x, jnp.float32) for x in (ma, va, ta, mc, vc,
                                                        tc))
    ap, cp, cl, al, cg0, ag0 = run(jnp.asarray(actor), jnp.asarray(critic),
                                   state, feats, masks, acts, rews, noise)
    return (np.asarray(ap), np.asarray(cp), float(cl), float(al),
            np.asarray(cg0), np.asarray(ag0))


@functools.lru_cache(maxsize=None)
def _sac_program(n_features, lr_actor, lr_critic, alpha, dtype_name, keep,
                 ns):
    dtype = jnp.dtype(dtype_name)
    g_lay, c_lay = gnn_layout(n_features), critic_layout(n_features)

    def critic_loss(cp, graphs, oh, r):
        losses = []
        for gi, (f, m) in enumerate(graphs):
            one = jax.checkpoint(lambda a, f=f, m=m: critic_forward(
                cp, f, m, a))
            q1, q2 = jax.lax.map(one, oh[gi][:keep, :ns[gi]])
            r_ = r[gi][:keep]
            losses.append(jnp.mean((q1 - r_) ** 2 + (q2 - r_) ** 2))
        return jnp.mean(jnp.stack(losses))

    def actor_loss(ap, cp, graphs):
        qs, ents = [], []
        for f, m in graphs:
            lg = gnn_forward(ap, f, m)
            q1, q2 = critic_forward(cp, f, m, jax.nn.softmax(lg, axis=-1))
            qs.append(jnp.minimum(q1, q2))
            ents.append(entropy(lg))
        ent = jnp.mean(jnp.stack(ents))
        return -(jnp.mean(jnp.stack(qs)) + alpha * ent)

    def step(graphs, carry, xs):
        ap, cp, ma, va, ta, mc, vc, tc = carry
        a_, r_, nz = xs
        oh = [jax.nn.one_hot(a, 3, dtype=dtype) + n.astype(dtype)
              for a, n in zip(a_, nz)]
        closs, cg = jax.value_and_grad(critic_loss)(
            cp, graphs, oh, [r.astype(dtype) for r in r_])
        cp, mc, vc = adam(lr_critic, cp, cg, mc, vc, tc + 1)
        aloss, ag = jax.value_and_grad(actor_loss)(ap, cp, graphs)
        ap, ma, va = adam(lr_actor, ap, ag, ma, va, ta + 1)
        return (ap, cp, ma, va, ta + 1, mc, vc, tc + 1), (closs, aloss, cg,
                                                          ag)

    @jax.jit
    def run(actor, critic, state, feats, masks, acts, rews, noise):
        graphs = list(zip(feats, masks))

        def tree(lay, v):
            return jax.tree.map(lambda x: x.astype(dtype), unflatten(lay, v))

        ma, va, ta, mc, vc, tc = state
        carry = (tree(g_lay, actor), tree(c_lay, critic), tree(g_lay, ma),
                 tree(g_lay, va), ta, tree(c_lay, mc), tree(c_lay, vc), tc)
        # the first step outside the scan keeps its gradients
        first = jax.tree.map(lambda x: x[0], (acts, rews, noise))
        rest = jax.tree.map(lambda x: x[1:], (acts, rews, noise))
        carry, (cl0, al0, cg0, ag0) = step(graphs, carry, first)
        carry, (cl, al) = jax.lax.scan(
            lambda c, x: (lambda c2, o: (c2, o[:2]))(*step(graphs, c, x)),
            carry, rest)
        cl = jnp.concatenate([cl0[None], cl])
        al = jnp.concatenate([al0[None], al])

        def f32(t):
            return jax.tree.map(lambda x: x.astype(jnp.float32), t)

        return (flatten(g_lay, f32(carry[0])), flatten(c_lay, f32(carry[1])),
                cl[-1], al[-1], flatten(c_lay, f32(cg0)),
                flatten(g_lay, f32(ag0)))

    return run


# ----------------------------------------------------------------- EA
def _tournament(key, fit, n_picks, k, n_pool):
    cands = jax.random.randint(key, (n_picks, k), 0, n_pool)
    return cands[jnp.arange(n_picks), jnp.argmax(fit[cands], axis=1)]


def _crossover(key, mate, child):
    v = mate.shape[-1]
    pt = jax.random.randint(key, (), 1, v)
    return jnp.where(jnp.arange(v) < pt, mate, child)


def _mutate_gnn(key, g, frac, std, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    sd = jnp.where(jax.random.uniform(k1) < 0.05, std * 10.0, std)
    mask = jax.random.uniform(k2, g.shape) < frac
    noise = (jax.random.normal(k3, g.shape).astype(dtype) * sd.astype(dtype)
             * (jnp.abs(g.astype(dtype)) + 0.05))
    return (g.astype(dtype) + noise * mask).astype(g.dtype)


def _mutate_bz(key, flat, n_nodes, frac, dtype):
    n_prior = n_nodes * 6
    kp, kt, mp, mt = jax.random.split(key, 4)
    prior, log_t = flat[:n_prior].astype(dtype), flat[n_prior:].astype(dtype)
    prior = prior + (jax.random.normal(kp, prior.shape).astype(dtype) * 0.3
                     * (jax.random.uniform(mp, prior.shape) < frac * 3))
    log_t = log_t + (jax.random.normal(kt, log_t.shape).astype(dtype) * 0.2
                     * (jax.random.uniform(mt, log_t.shape) < frac * 3))
    return jnp.concatenate([prior, jnp.clip(log_t, -3.0, 2.0)]).astype(
        flat.dtype)


def _seed_bz(logits, key, t_init=0.5):
    """Boltzmann genome seeded from a GNN posterior (paper Alg. 2)."""
    log_t = (jnp.full(logits.shape[:2], jnp.log(t_init))
             + 0.1 * jax.random.normal(key, logits.shape[:2]))
    return jnp.concatenate([logits.reshape(-1), log_t.reshape(-1)])


def evolve(key, gpop, fit_g, bpop, fit_b, logits, *, n_nodes, e_g, e_b,
           tournament_k, crossover_prob, mut_prob, mut_frac, mut_std,
           dtype=jnp.float32):
    """One EA generation over the real rows: elites of each encoding
    kept in fitness order, the rest tournament children crossed with an
    elite and mutated; a Boltzmann child whose mate is a GNN elite is
    re-seeded from that elite's posterior."""
    n_g, n_b = gpop.shape[0], bpop.shape[0]
    keys = jax.random.split(key, 12)
    order_g = jnp.argsort(-fit_g)
    elites = gpop[order_g[:e_g]]
    n_child = n_g - e_g
    parents = gpop[_tournament(keys[0], fit_g, n_child, tournament_k, n_g)]
    mates = elites[jax.random.randint(keys[1], (n_child,), 0, e_g)]
    crossed = jax.vmap(_crossover)(jax.random.split(keys[2], n_child),
                                   mates, parents)
    gate_x = jax.random.uniform(keys[3], (n_child,)) < crossover_prob
    children = jnp.where(gate_x[:, None], crossed, parents)
    mutated = jax.vmap(lambda k, g: _mutate_gnn(k, g, mut_frac, mut_std,
                                                dtype))(
        jax.random.split(keys[4], n_child), children)
    gate_m = jax.random.uniform(keys[5], (n_child,)) < mut_prob
    new_g = jnp.concatenate([elites, jnp.where(gate_m[:, None], mutated,
                                               children)])

    order_b = jnp.argsort(-fit_b)
    elites_b = bpop[order_b[:e_b]]
    n_child = n_b - e_b
    parents = bpop[_tournament(keys[6], fit_b, n_child, tournament_k, n_b)]
    mate_idx = jax.random.randint(keys[7], (n_child,), 0, e_g + e_b)
    elite_logits = logits[order_g[:e_g]]

    def cross_one(k, mi, child):
        ks, kc = jax.random.split(k)
        seeded = _seed_bz(elite_logits[jnp.clip(mi, 0, e_g - 1)], ks)
        bz_mate = (elites_b[jnp.clip(mi - e_g, 0, max(e_b - 1, 0))]
                   if e_b else child)
        return jnp.where(mi < e_g, seeded, _crossover(kc, bz_mate, child))

    crossed = jax.vmap(cross_one)(jax.random.split(keys[8], n_child),
                                  mate_idx, parents)
    gate_x = jax.random.uniform(keys[9], (n_child,)) < crossover_prob
    children = jnp.where(gate_x[:, None], crossed, parents)
    mutated = jax.vmap(lambda k, g: _mutate_bz(k, g, n_nodes, mut_frac,
                                               dtype))(
        jax.random.split(keys[10], n_child), children)
    gate_m = jax.random.uniform(keys[11], (n_child,)) < mut_prob
    new_b = jnp.concatenate([elites_b, jnp.where(gate_m[:, None], mutated,
                                                 children)])
    return new_g, new_b

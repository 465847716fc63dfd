"""SAC policy-gradient learner, modified for the huge multi-discrete action
space per Appendix D:

- discrete entropy computed exactly and averaged over nodes;
- double-Q critic evaluated on NOISY one-hot behavioral actions
  (clipped Gaussian, smooths the value estimate);
- actor trained through the critic with the softmax probabilities as a
  differentiable soft action (the sampled-policy-gradient of App. D);
- single-step episodes (Table 2: '# steps per episode' = 1) make the
  bootstrap term vanish: the Bellman target is the (scaled) reward, so no
  target networks are required — noted deviation from the generic
  pseudocode, exact for this MDP.

``ZooSAC`` is the policy-gradient member of ``ZooEGRL``: actor and
double-Q critic run over a size-bucketed zoo (``BucketedZoo``, PR 5) —
per gradient step, each bucket contributes a ``(G_k, B)`` replay batch
evaluated at ITS OWN padded width, and all steps of a generation run in
one jitted scan (``_make_update_scan``).  Since the fused GAT op gained
its ``custom_vjp`` pair, the learner trains on the default GAT backend —
no loss function materializes a dense ``(N, N, H)`` attention tensor
(the attention transient is ``(N_max_k, C, H)`` per neighbor chunk on
the chunked backend).  The scan's per-step inputs are pytrees (one array
per bucket); losses are per-graph losses averaged over the whole zoo, so
a one-graph zoo trains one graph's policy (tests/test_zoo_egrl.py holds
its update to a per-graph reference written in the test), and a
single-bucket zoo consumes its PRNG keys unchanged (``bucket_keys``),
keeping those trajectories bit-identical to the flat ``GraphBatch``
path.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import gnn
from repro.core.replay import ReplayBank
from repro.graphs.batch import GraphBatch
from repro.graphs.bucketed import BucketedZoo, bucket_keys
from repro.utils.params import ParamDef, init_params


@dataclasses.dataclass
class SACConfig:
    lr_actor: float = 1e-3
    lr_critic: float = 1e-3
    alpha: float = 0.05
    batch: int = 24
    action_noise: float = 0.2
    noise_clip: float = 0.5


def critic_defs(n_features: int, hidden: int = gnn.HIDDEN):
    d = {
        "inp": ParamDef((n_features + 6, hidden), (None, None), "scaled"),
        "gat0": gnn._gat_defs(hidden, hidden),
        "gat1": gnn._gat_defs(hidden, hidden),
        "h1": ParamDef((hidden, hidden), (None, None), "scaled"),
        "b1": ParamDef((hidden,), (None,), "zeros"),
        "q1": ParamDef((hidden, 1), (None, None), "scaled"),
        "h2": ParamDef((hidden, hidden), (None, None), "scaled"),
        "b2": ParamDef((hidden,), (None,), "zeros"),
        "q2": ParamDef((hidden, 1), (None, None), "scaled"),
    }
    return d


def critic_forward_masked(p, feats, adj, node_mask, act_onehot,
                          backend=None):
    """Double-Q critic over ONE padded graph: feats (N_max, F), adj
    (N_max, N_max) with padding rows self-loop-only, node_mask (N_max,)
    1.0 = real, act_onehot (N_max, 2, 3) -> (q1, q2) scalars.

    Padding rows are zeroed at the input and after every GAT level, and
    the global pool divides by the REAL node count, so garbage in
    padding slots (replay contents, sampled pad actions, noise) cannot
    reach the Q values.  With no padding every mask op is an identity
    and sum/count equals the mean pool.

    Runs under ``jax.grad`` on the DEFAULT GAT backend: every backend is
    differentiable since the fused op gained its ``custom_vjp`` pair, so
    no dense ``(N, N, H)`` attention tensor is materialized in training
    (the former "jnp" pin is gone; tests/test_gat_backend.py asserts the
    training jaxpr is free of the dense intermediate).  The two Q heads
    share the GAT trunk and run as one vmapped two-wide forward.
    """
    live = node_mask.astype(feats.dtype)
    mask = adj > 0
    x = jnp.concatenate([feats, act_onehot.reshape(feats.shape[0], 6)], -1)
    h = jnp.tanh((x * live[:, None]) @ p["inp"]) * live[:, None]
    h = gnn._gat(p["gat0"], h, mask, backend) * live[:, None]
    h = gnn._gat(p["gat1"], h, mask, backend) * live[:, None]
    g = h.sum(axis=0) / jnp.maximum(live.sum(), 1.0)
    heads = {"h": jnp.stack([p["h1"], p["h2"]]),
             "b": jnp.stack([p["b1"], p["b2"]]),
             "q": jnp.stack([p["q1"], p["q2"]])}
    q = jax.vmap(lambda hp: (jax.nn.elu(g @ hp["h"] + hp["b"]) @ hp["q"])[0])(
        heads)
    return q[0], q[1]


def _adam_init(params):
    return {"m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def _adam_step(lr, params, grads, state):
    b1, b2, eps = 0.9, 0.999, 1e-8
    t = state["t"] + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"], grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        params, m, v)
    return new, {"m": m, "v": v, "t": t}


def _make_update_scan(cfg: SACConfig, critic_loss, actor_loss):
    """All gradient steps of a generation in ONE jitted scan: per step,
    one critic Adam step on the noisy one-hot behavioral actions, then
    one actor Adam step through the updated critic.  ``acts`` /
    ``rewards`` / ``noise`` carry a leading (steps,) axis and may be
    pytrees (ZooSAC passes one array per size bucket — lax.scan slices
    every leaf); the loss callables define the per-step batch shape."""

    def update_scan(actor, critic, oa, oc, acts, rewards, noise):
        def step(carry, xs):
            actor, critic, oa, oc = carry
            a_, r_, nz = xs
            oh = jax.tree.map(lambda a, n: jax.nn.one_hot(a, 3) + n, a_, nz)
            closs, cg = jax.value_and_grad(critic_loss)(critic, oh, r_)
            critic, oc = _adam_step(cfg.lr_critic, critic, cg, oc)
            (aloss, ent), ag = jax.value_and_grad(
                actor_loss, has_aux=True)(actor, critic)
            actor, oa = _adam_step(cfg.lr_actor, actor, ag, oa)
            return (actor, critic, oa, oc), (closs, aloss, ent)

        (actor, critic, oa, oc), (cl, al, en) = jax.lax.scan(
            step, (actor, critic, oa, oc), (acts, rewards, noise))
        return actor, critic, oa, oc, cl[-1], al[-1], en[-1]

    return jax.jit(update_scan)


@partial(jax.jit, static_argnames=("n",))
def _zoo_explore(actor, key, buckets, n):
    """``ZooSAC.explore_actions`` in one launch: the learner key's two
    splits, then per rollout key the actor's zoo forward and a sample
    per bucket (``bucket_keys``).  Returns the next learner key and the
    per-bucket (n, G_k, N_max_k, 2) actions.  Module-level, with the
    buckets as arguments, so every learner over a bucket geometry
    shares the executable."""
    key, k = jax.random.split(key)

    def sample_one(kk):
        ks = bucket_keys(kk, len(buckets))
        return tuple(gnn.sample_actions(kb, gnn.gnn_forward_zoo(
            actor, fe, ad, li, nr))
            for kb, (fe, ad, li, nr) in zip(ks, buckets))

    return key, jax.vmap(sample_one)(jax.random.split(k, n))


class ZooSAC:
    """Multi-workload SAC learner over a size-bucketed zoo — the PG
    member of ``ZooEGRL``.

    The actor is the masked zoo GNN forward (``gnn.gnn_forward_zoo``)
    run once per bucket; the double-Q critic is
    ``critic_forward_masked`` evaluated per graph at its bucket's
    padded width.  Each gradient step trains on one ``(G_k, B)`` batch
    per bucket — B transitions from EVERY workload's replay buffer
    (``ReplayBank``, keyed by zoo index) — and all steps of a
    generation run in one jitted ``lax.scan`` (``_make_update_scan``
    with per-bucket pytree inputs), so the per-step gradient cost that
    dominates ``generation.egrl_ms`` is amortized across the whole zoo
    in one device call AND the dense ``(N, N)`` attention work shrinks
    from zoo-wide ``N_max`` to bucket size.

    Losses are per-graph losses averaged over the whole zoo (equal
    weight per workload; per-graph terms are concatenated bucket-major
    before the mean, which for a single-bucket zoo is exactly the flat
    path's graph order): the critic's squared Bellman error on the
    noisy one-hot behavioral actions, and the actor's ``-(min(q1, q2) +
    alpha * entropy)`` on its softmax probabilities.  A one-graph zoo
    is the single-graph learner (its update matches a per-graph
    reference to ~1e-6, tests/test_zoo_egrl.py).  Critic parameters are
    graph-size independent (shared GAT weights + masked mean pool),
    exactly like the actor's.
    """

    def __init__(self, zoo, key, cfg: SACConfig = SACConfig()):
        if isinstance(zoo, GraphBatch):      # flat batch = one bucket
            zoo = BucketedZoo.from_batch(zoo)
        self.cfg = cfg
        self.zoo = zoo
        k1, k2 = jax.random.split(key)
        self.actor = gnn.init_gnn(k1, zoo.n_features)
        self.critic = init_params(critic_defs(zoo.n_features), k2)
        self.opt_a = _adam_init(self.actor)
        self.opt_c = _adam_init(self.critic)
        self.key = jax.random.PRNGKey(17)

        buckets = tuple((b.feats, b.adj, b.node_mask, b.n_nodes)
                        for b in zoo.buckets)
        n_buckets = zoo.n_buckets
        alpha = cfg.alpha
        # zoo indices per bucket, slot order (for the replay sampler)
        self._bucket_ids = tuple(
            tuple(i for i in range(zoo.n_graphs)
                  if zoo.graph_bucket[i] == k) for k in range(n_buckets))

        def critic_loss(cp, acts_oh, rewards):
            # acts_oh: per-bucket (G_k, B, N_max_k, 2, 3) noisy/soft
            # one-hots; rewards: per-bucket (G_k, B).  Zoo mean = mean
            # over the concatenated per-graph losses (equal weight per
            # workload, any bucketing).
            def one_graph(f, a, m, oh_b, r_b):
                q1, q2 = jax.vmap(
                    lambda oh: critic_forward_masked(cp, f, a, m, oh))(oh_b)
                return jnp.mean((q1 - r_b) ** 2 + (q2 - r_b) ** 2)

            losses = [jax.vmap(one_graph)(fe, ad, li, oh_k, r_k)
                      for (fe, ad, li, _), oh_k, r_k
                      in zip(buckets, acts_oh, rewards)]
            return jnp.mean(jnp.concatenate(losses))

        def actor_loss(ap, cp):
            # default backend: every GAT backend differentiates (custom_vjp)
            def one_graph(f, a, m, lg, pr):
                q1, q2 = critic_forward_masked(cp, f, a, m, pr)
                return jnp.minimum(q1, q2), gnn.entropy_masked(lg, m)

            qs, ents = [], []
            for fe, ad, li, nr in buckets:
                logits = gnn.gnn_forward_zoo(ap, fe, ad, li, nr)
                probs = jax.nn.softmax(logits, axis=-1)
                q, e = jax.vmap(one_graph)(fe, ad, li, logits, probs)
                qs.append(q)
                ents.append(e)
            ent = jnp.mean(jnp.concatenate(ents))
            return -(jnp.mean(jnp.concatenate(qs)) + alpha * ent), ent

        # acts: per-bucket (U, G_k, B, N_max_k, 2); rewards (U, G_k, B);
        # noise adds (3,) — all tuples, scanned leaf-wise
        self._update_scan = _make_update_scan(cfg, critic_loss, actor_loss)
        self._buckets = buckets

    def explore_actions(self, n: int):
        """Per-bucket (n, G_k, N_max_k, 2) rollout-action tuple as ONE
        jitted device call: each key samples every graph's sub-actions
        at once (a K==1 zoo consumes the key unchanged — bit-identical
        to the flat path; padding rows sample throwaway uniform actions
        — inert downstream)."""
        self.key, acts = _zoo_explore(self.actor, self.key, self._buckets,
                                      n=n)
        return acts

    def update(self, bank: ReplayBank, steps: int) -> Dict[str, float]:
        """``steps`` zoo-wide gradient steps in one jitted scan, each on
        a fresh per-bucket ``(G_k, B)`` replay batch from the bank."""
        cfg = self.cfg
        if len(bank) < cfg.batch or steps <= 0:
            return {}
        # the loss reads of sac.read are the update's existing host
        # syncs, so the spans add no device wait
        with obs.span("sac_update", learner="zoo_sac", steps=steps,
                      batch=cfg.batch) as sp:
            with obs.span("replay.sample"):
                batches = [bank.sample_bucket(ids, cfg.batch, steps)
                           for ids in self._bucket_ids]
            with obs.span("sac.upload"):
                acts = tuple(jnp.asarray(a) for a, _ in batches)
                rews = tuple(jnp.asarray(r) for _, r in batches)
                self.key, k = jax.random.split(self.key)
                noise = tuple(jnp.clip(
                    cfg.action_noise * jax.random.normal(
                        kk, a.shape + (3,)),
                    -cfg.noise_clip, cfg.noise_clip)
                    for kk, a in zip(bucket_keys(k, self.zoo.n_buckets),
                                     acts))
            with obs.span("sac.scan"):
                (self.actor, self.critic, self.opt_a, self.opt_c,
                 cl, al, en) = self._update_scan(
                    self.actor, self.critic, self.opt_a, self.opt_c,
                    acts, rews, noise)
            with obs.span("sac.read"):
                out = {"critic_loss": obs.device_read(float, cl),
                       "actor_loss": obs.device_read(float, al),
                       "entropy": obs.device_read(float, en)}
            sp.set(**out)
            return out

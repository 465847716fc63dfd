"""The comparison that decides ``correct``.

While the window runs, ``Tap`` keeps references to what the timed path's
own programs took and produced (no copy, no extra device sync): the
population forward per size bucket, the simulator's rewards per
population part, the EA step, and the SAC update with the state it
started from and the outputs of the update before it.  A seeded
reservoir keeps ``keep`` of the window's generations, so the sample is
drawn from the seed whatever the number of generations.

After the window the kept arrays are copied to the host, the program is
freed, and ``readings`` compares them with ``reference.py`` run on the
same inputs:

- ``logits_gap_p50``, ``logits_gap_p90`` (and the 99th percentile and
  the largest, as detail): policy forward, per (genome, graph) that
  percentile over the graph's real nodes of |program - reference|
  logit, over the largest |reference| logit of that (genome, graph);
  the worst pair.  Top-k pooling swaps nodes whose scores differ by
  less than the rounding, and a swapped node's logits differ by O(1),
  so the largest gap is no comparison; the cell's limits file says
  which percentile is compared;
- ``reward_gap``: every reward of the kept generations (all genome
  rows, all graphs), |program - reference| / |reference|; the worst;
- ``ea_gap``: the next population of the kept generations' EA steps,
  max |program - reference| / max |reference|;
- ``sac_chain_gap``: the largest |difference| between what each kept
  SAC update started from (actor, critic, both optimizers' moments and
  step counts) and what the update before it returned: 0 unless the
  learner lost or changed its state between updates;
- ``critic_loss_gap``: each kept update's last critic loss, |program -
  reference| / |reference|, the reference started from the program's
  own state (the actor's objective, -(min Q + alpha H), sits near zero,
  so its relative gap is not compared; its pair is logged); the worst;
- ``param_change_gap``: per actor and critic leaf, the gap between the
  norms of the parameter change over a kept update, over the larger of
  the reference leaf's change norm and the median leaf's; the worst
  leaf of the worst update.  Leaves whose reference first-step gradient
  norm is under a thousandth of the median leaf's are left out (they
  move by round-off under Adam).  On the chip the worst leaf is a small
  actor leaf (an attention vector, a pooling vector) whose gradient
  turns on a leaky-ReLU kink or a top-k choice that rounding decides,
  so over an update's 21 Adam steps it swings by O(1) on sound runs;
  it is read, and the limits compare the steadier numbers below;
- ``actor_change_gap``, ``critic_change_gap``: the same per-leaf gaps,
  their median over the live leaves of the actor, and of the critic;
  the worst update.

A tap that caught nothing (a kept generation without a forward of some
bucket, without rewards or an EA step, or, where the cell learns,
without a SAC update) makes its numbers infinite, so the run is not
correct: a renamed or fused program cannot leave a layer unchecked.

The control (``dtype=bfloat16``) is the reference computed one
precision lower, put in the program's place.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

import reference as ref

class Tap:
    def __init__(self, algo, egrl_module, keep: int, seed: int):
        self.keep = keep
        self.learns = algo.learner is not None
        self.rng = np.random.default_rng(seed)
        self.kept = []
        self.seen = 0
        self.cur = None
        self.updates = 0
        self._last_update = None
        self._egrl = egrl_module
        self._orig_eval = egrl_module.evaluate_population_bucketed

        def evaluate(zoo, maps, *a, **k):
            out = self._orig_eval(zoo, maps, *a, **k)
            if self.cur is not None:
                self.cur["evals"].append((maps, out["reward"]))
            return out

        egrl_module.evaluate_population_bucketed = evaluate

        def logits_tap(k, f):
            def call(pop):
                out = f(pop)
                if self.cur is not None:
                    self.cur["logits"][k] = (pop, out)
                return out
            return call

        algo._pop_logits = [logits_tap(k, f)
                            for k, f in enumerate(algo._pop_logits)]
        evolve = algo._evolve

        def evolve_tap(*args):
            out = evolve(*args)
            if self.cur is not None:
                self.cur["evolve"] = (args, out)
            return out

        algo._evolve = evolve_tap
        if self.learns:
            scan = algo.learner._update_scan

            def scan_tap(*args):
                out = scan(*args)
                if self.cur is not None:
                    self.cur["update"] = (args, out, self._last_update)
                self._last_update = out
                self.updates += 1
                return out

            algo.learner._update_scan = scan_tap

    def close(self):
        self._egrl.evaluate_population_bucketed = self._orig_eval

    def start(self):
        self.cur = {"logits": {}, "evals": [], "evolve": None,
                    "update": None}

    def finish(self, sample: bool):
        """End a generation; ``sample`` says it was in the window."""
        cur, self.cur = self.cur, None
        if not sample:
            return
        if len(self.kept) < self.keep:
            self.kept.append(cur)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.keep:
                self.kept[j] = cur
        self.seen += 1

    def to_host(self):
        """Copy the kept arrays to the host, dropping device references."""
        self.kept = jax.tree.map(np.asarray, self.kept)
        self._last_update = None


# ------------------------------------------------------------ readings
# A variant says what stands in the program's place:
#   "program"   the program's own outputs (a real run);
#   "control"   the reference computed in bfloat16;
#   "half_batch" the SAC update's reference on the first half of each
#               batch, the mean taken over it (other layers: the program);
#   "answer_altered" the program's outputs with the first genome row's
#               logits and rewards zeroed on every graph (a lost write);
#   "nodes_altered" the program's logits with the last eighth of every
#               graph's real nodes zeroed on every genome row (one tile
#               of the forward lost);
#   "state_unchanged" the EA step and the SAC update returning the state
#               they were given (zero losses).
VARIANTS = ("program", "control", "half_batch", "answer_altered",
            "nodes_altered", "state_unchanged")
INF = float("inf")


def _rel(a, b):
    return float(abs(a - b) / max(abs(b), 1e-30))


def _leaf_norms(layout, vec):
    out, off = [], 0
    for _, shape in layout:
        n = int(np.prod(shape))
        out.append(float(np.linalg.norm(np.asarray(vec[off:off + n],
                                                   np.float64))))
        off += n
    return np.asarray(out)


def _flat(tree):
    return np.concatenate([np.asarray(x, np.float32).reshape(-1)
                           for x in jax.tree.leaves(tree)])


class Inputs:
    """Everything the reference needs besides the kept arrays: the
    graphs in zoo order and how the zoo lays them out."""

    def __init__(self, graphs, algo):
        zoo = algo.zoo
        self.graphs = graphs
        self.arrays = [ref.graph_arrays(g) for g in graphs]
        self.feats = [ref.features(g) for g in graphs]
        self.bucket = list(zoo.graph_bucket)
        self.slot = list(zoo.graph_slot)
        self.cfg = algo.cfg
        self.n_features = zoo.n_features
        self.bz_nodes = zoo.n_eff
        self.consts = {"n_g": algo.n_g, "n_b": algo.n_b,
                       "e_g": algo.e_g, "e_b": algo.e_b}


@functools.lru_cache(maxsize=None)
def _forward_program(n_features, dtype_name):
    lay = ref.gnn_layout(n_features)
    return jax.jit(lambda pop, f, m: ref.population_logits(
        lay, pop, f, m, dtype_name))


@functools.lru_cache(maxsize=None)
def _evolve_program(dtype_name, **static):
    return jax.jit(lambda *x: ref.evolve(*x, dtype=dtype_name, **static))


QUANTILES = {"p50": 50, "p90": 90, "p99": 99, "max": 100}


def _logits_gap(inp, kept, variant, detail):
    """The worst (genome, graph) pair's quantiles over nodes of the
    relative logit gap, by name (``QUANTILES``); all go to ``detail``."""
    fwd = _forward_program(inp.n_features, "float32")
    low = _forward_program(inp.n_features, "bfloat16")
    worst = dict.fromkeys(QUANTILES, 0.0)
    for gen in kept:
        for gi, ga in enumerate(inp.arrays):
            if inp.bucket[gi] not in gen["logits"]:
                return dict.fromkeys(QUANTILES, INF)
            pop, out = gen["logits"][inp.bucket[gi]]
            n = ga["n"]
            args = (jnp.asarray(pop), inp.feats[gi], jnp.asarray(ga["adj"]))
            want = np.asarray(fwd(*args), np.float64)
            got = (np.asarray(low(*args), np.float64) if variant == "control"
                   else np.array(out[:, inp.slot[gi], :n], np.float64))
            if variant == "answer_altered":
                got[0] = 0.0
            if variant == "nodes_altered":
                got[:, n - max(1, n // 8):] = 0.0
            scale = np.maximum(np.abs(want).reshape(len(want), -1).max(1),
                               1e-30)
            err = np.abs(got - want).reshape(len(want), n, -1).max(-1)
            for k, q in QUANTILES.items():
                worst[k] = max(worst[k], float(
                    (np.percentile(err, q, axis=1) / scale).max()))
    if not kept:
        return dict.fromkeys(QUANTILES, INF)
    detail["logits_gap"] = worst
    return worst


def _reward_gap(inp, kept, variant):
    worst = INF if not kept else 0.0
    for gen in kept:
        if not gen["evals"]:
            return INF
        for maps, reward in gen["evals"]:
            got_all = np.array(reward, np.float64)
            rows = got_all.shape[0]
            if variant == "answer_altered":
                got_all[0] = 0.0
            for gi, (g, ga) in enumerate(zip(inp.graphs, inp.arrays)):
                m = np.asarray(maps[inp.bucket[gi]])[:rows, inp.slot[gi],
                                                     :ga["n"]]
                want = ref.rewards(g, ga, m, inp.cfg.reward_scale)
                got = (ref.rewards(g, ga, m, inp.cfg.reward_scale,
                                   dtype=jnp.bfloat16)
                       if variant == "control" else got_all[:, gi])
                gap = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
                worst = max(worst, float(gap.max()))
    return worst


def _ea_gap(inp, kept, variant):
    cfg, a = inp.cfg, inp.consts

    def evolve(dtype_name):
        return _evolve_program(
            dtype_name, n_nodes=inp.bz_nodes, e_g=a["e_g"], e_b=a["e_b"],
            tournament_k=cfg.tournament_k,
            crossover_prob=cfg.crossover_prob, mut_prob=cfg.mut_prob,
            mut_frac=cfg.mut_frac, mut_std=cfg.mut_std)

    fwd, low = evolve("float32"), evolve("bfloat16")
    worst = INF if not kept else 0.0
    for gen in kept:
        if gen["evolve"] is None:
            return INF
        (key, gpop, fit_g, bpop, fit_b, logits), out = gen["evolve"]
        args = (jnp.asarray(key), jnp.asarray(gpop[:a["n_g"]]),
                jnp.asarray(fit_g[:a["n_g"]]), jnp.asarray(bpop[:a["n_b"]]),
                jnp.asarray(fit_b[:a["n_b"]]),
                jnp.asarray(logits[:a["n_g"]]))
        want = fwd(*args)
        got = (low(*args) if variant == "control" else
               (gpop, bpop) if variant == "state_unchanged" else out)
        for g_, w_, n in zip(got, want, (a["n_g"], a["n_b"])):
            w_ = np.asarray(w_, np.float64)
            g_ = np.asarray(g_[:n], np.float64)
            worst = max(worst, float(np.abs(g_ - w_).max()
                                     / max(np.abs(w_).max(), 1e-30)))
    return worst


def _bucket_to_graphs(inp, per_bucket):
    """Per-bucket (U, G_k, ...) arrays -> per-graph (U, ...) list."""
    return [np.asarray(per_bucket[inp.bucket[gi]])[:, inp.slot[gi]]
            for gi in range(len(inp.graphs))]


def _chain_gap(args, prev):
    """Largest |difference| between the state an update started from and
    what the update before it returned (actor, critic, optimizers)."""
    if prev is None:
        return INF
    start, end = args[:4], prev[:4]
    if jax.tree.structure(start) != jax.tree.structure(end):
        return INF
    return max(float(np.abs(np.asarray(x, np.float64)
                            - np.asarray(y, np.float64)).max(initial=0.0))
               for x, y in zip(jax.tree.leaves(start), jax.tree.leaves(end)))


def _sac_update_numbers(inp, update, variant, detail):
    (actor, critic, oa, oc, acts, rews, noise), \
        (actor1, critic1, _, _, cl, al, _en), prev = update
    a0, c0 = _flat(actor), _flat(critic)
    sac = inp.cfg.sac
    graphs_in = [(f, ga["adj"]) for f, ga in zip(inp.feats, inp.arrays)]
    batch = [_bucket_to_graphs(inp, x) for x in (acts, rews, noise)]
    state = (_flat(oa["m"]), _flat(oa["v"]), int(oa["t"]),
             _flat(oc["m"]), _flat(oc["v"]), int(oc["t"]))

    def reference(dtype=jnp.float32, keep=None):
        return ref.sac_update(
            graphs_in, a0, c0, *batch, n_features=inp.n_features,
            lr_actor=sac.lr_actor, lr_critic=sac.lr_critic,
            alpha=sac.alpha, adam_state=state, dtype=dtype, keep=keep)

    ra, rc, rcl, ral, cg0, ag0 = reference()
    if variant == "control":
        ga, gc, gcl, gal, _, _ = reference(jnp.bfloat16)
    elif variant == "half_batch":
        ga, gc, gcl, gal, _, _ = reference(keep=sac.batch // 2)
    elif variant == "state_unchanged":
        ga, gc, gcl, gal = a0, c0, 0.0, 0.0
    else:
        ga, gc, gcl, gal = _flat(actor1), _flat(critic1), float(cl), float(al)
    worst, median, leaves = 0.0, {}, []
    for side, lay, p0, p1, r1, g0 in (
            ("actor", ref.gnn_layout(inp.n_features), a0, ga, ra, ag0),
            ("critic", ref.critic_layout(inp.n_features), c0, gc, rc, cg0)):
        got = _leaf_norms(lay, np.asarray(p1) - p0)
        want = _leaf_norms(lay, np.asarray(r1) - p0)
        grad = _leaf_norms(lay, np.asarray(g0))
        live = grad >= 1e-3 * np.median(grad)
        floor = max(np.median(want[live]), 1e-30)
        gap = np.abs(got - want) / np.maximum(want, floor)
        worst = max(worst, float(gap[live].max()))
        median[f"{side}_change_gap"] = float(np.median(gap[live]))
        i = int(np.argmax(np.where(live, gap, -1.0)))
        leaves.append([f"{side}.{lay[i][0]}", float(gap[i]), float(got[i]),
                       float(want[i]), float(floor),
                       float(grad[i] / np.median(grad))])
    # the worst leaf of each side: name, gap, program and reference
    # change norms, the median floor, first gradient over the median's
    detail.setdefault("param_change_worst", []).append(leaves)
    detail.setdefault("critic_loss", []).append([gcl, rcl])
    detail.setdefault("actor_loss", []).append([gal, ral])
    detail.setdefault("adam_step", []).append(state[2])
    return {"sac_chain_gap": _chain_gap(update[0], prev),
            "critic_loss_gap": _rel(gcl, rcl), "param_change_gap": worst,
            **median}


def _sac_numbers(inp, kept, variant, detail):
    out = {"sac_chain_gap": 0.0, "critic_loss_gap": 0.0,
           "param_change_gap": 0.0, "actor_change_gap": 0.0,
           "critic_change_gap": 0.0}
    if not kept or any(gen["update"] is None for gen in kept):
        return dict.fromkeys(out, INF)
    for gen in kept:
        one = _sac_update_numbers(inp, gen["update"], variant, detail)
        out = {k: max(v, one[k]) for k, v in out.items()}
    return out


def readings(inp, tap, variant: str = "program", detail=None) -> dict:
    """The numbers compared, with ``variant`` in the program's place.
    ``detail`` (a dict) receives the raw readings behind them: the logit
    gap's quantiles and the loss pairs (got, reference)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    detail = {} if detail is None else detail
    with jax.default_matmul_precision("highest"):
        gaps = _logits_gap(inp, tap.kept, variant, detail)
        out = {"logits_gap_p50": gaps["p50"], "logits_gap_p90": gaps["p90"],
               "reward_gap": _reward_gap(inp, tap.kept, variant),
               "ea_gap": _ea_gap(inp, tap.kept, variant)}
        if tap.learns:
            out.update(_sac_numbers(inp, tap.kept, variant, detail))
    return out


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number compared is within its limit (a missing number, or
    one that is not finite, fails)."""
    return all(name in numbers and np.isfinite(numbers[name])
               and numbers[name] <= lim for name, lim in limits.items())

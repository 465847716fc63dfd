"""Replay buffer + zoo replay bank: ring wraparound, seeded sample
determinism, and the per-graph bank's stacking/draw-order contracts
(the G=1 contract backs the ZooSAC parity test in test_zoo_egrl.py)."""
import numpy as np
import pytest

from repro.core.replay import ReplayBank, ReplayBuffer


def _rows(n, nodes=3, base=0):
    acts = np.arange(n * nodes * 2).reshape(n, nodes, 2) % 3
    rews = base + np.arange(n, dtype=np.float32)
    return acts, rews


def test_add_batch_wraps_around_capacity():
    buf = ReplayBuffer(n_nodes=3, capacity=8, seed=0)
    a1, r1 = _rows(5)
    buf.add_batch(a1, r1)
    assert len(buf) == 5 and buf.ptr == 5
    a2, r2 = _rows(6, base=100.0)
    buf.add_batch(a2, r2)           # 5 + 6 = 11 > 8: wraps
    assert len(buf) == 8
    assert buf.ptr == 11 % 8 == 3
    # slots 5..7 hold rows 0..2 of the second batch, slots 0..2 its tail
    np.testing.assert_array_equal(buf.rewards[5:8], r2[:3])
    np.testing.assert_array_equal(buf.rewards[0:3], r2[3:6])
    np.testing.assert_array_equal(buf.actions[5:8], a2[:3])
    # slots 3..4 still hold the surviving first-batch rows
    np.testing.assert_array_equal(buf.rewards[3:5], r1[3:5])


def test_add_batch_larger_than_capacity_keeps_tail():
    buf = ReplayBuffer(n_nodes=2, capacity=4, seed=0)
    acts = np.random.default_rng(0).integers(0, 3, (10, 2, 2))
    rews = np.arange(10, dtype=np.float32)
    buf.add_batch(acts, rews)
    assert len(buf) == 4
    # only the LAST capacity rows survive
    assert set(buf.rewards.tolist()) == {6.0, 7.0, 8.0, 9.0}


def test_sample_is_deterministic_under_seed():
    def make(seed):
        buf = ReplayBuffer(n_nodes=3, capacity=32, seed=seed)
        acts, rews = _rows(20)
        buf.add_batch(acts, rews)
        return buf

    a1, r1 = make(7).sample(12)
    a2, r2 = make(7).sample(12)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(r1, r2)
    assert a1.dtype == np.int32
    # a different seed draws a different index stream
    _, r3 = make(8).sample(12)
    assert not (r1 == r3).all()
    # successive samples from ONE buffer advance the stream
    buf = make(7)
    s1 = buf.sample(12)[1]
    s2 = buf.sample(12)[1]
    assert not (s1 == s2).all()


def test_bank_routes_rows_per_graph_and_stacks_samples():
    n_graphs, n_max = 3, 4
    bank = ReplayBank([n_max] * n_graphs, capacity=16, seed=0)
    rng = np.random.default_rng(1)
    acts = rng.integers(0, 3, (6, n_graphs, n_max, 2))
    rews = rng.standard_normal((6, n_graphs)).astype(np.float32)
    bank.add_batch(acts, rews)
    assert len(bank) == 6
    for gi in range(n_graphs):
        np.testing.assert_array_equal(bank.buffers[gi].rewards[:6],
                                      rews[:, gi])
    a, r = bank.sample_stack(batch=5, steps=2)
    assert a.shape == (2, n_graphs, 5, n_max, 2) and a.dtype == np.int32
    assert r.shape == (2, n_graphs, 5) and r.dtype == np.float32
    # every sampled (action, reward) pair is a row of the right graph
    for u in range(2):
        for gi in range(n_graphs):
            for b in range(5):
                (hit,) = np.where(rews[:, gi] == r[u, gi, b])
                assert len(hit) >= 1
                assert (acts[hit[0], gi] == a[u, gi, b]).all()


def test_bank_single_graph_matches_buffer_draw_order():
    """The G=1 bank must reproduce a plain ReplayBuffer's sample stream
    — a one-graph zoo draws its replay as one graph's buffer does."""
    acts, rews = _rows(10)
    buf = ReplayBuffer(n_nodes=3, capacity=32, seed=5)
    buf.add_batch(acts, rews)
    bank = ReplayBank([3], capacity=32, seed=5)
    bank.add_batch(acts[:, None], rews[:, None])
    want = [buf.sample(4) for _ in range(3)]
    got_a, got_r = bank.sample_stack(batch=4, steps=3)
    for u in range(3):
        np.testing.assert_array_equal(got_a[u, 0], want[u][0])
        np.testing.assert_array_equal(got_r[u, 0], want[u][1])


def test_bank_per_bucket_sampling_matches_flat_draws():
    """Buffers are keyed by ZOO index with independent seeded rngs, so
    sampling per bucket draws exactly what the flat whole-zoo sweep
    draws for the same buffers — bucket iteration order cannot change
    any graph's stream."""
    widths = [4, 7, 4]                      # graphs 0 and 2 share a bucket
    acts = [np.arange(12 * w * 2).reshape(12, w, 2) % 3 for w in widths]
    rews = [np.arange(12, dtype=np.float32) + 100 * i
            for i in range(len(widths))]

    def fresh():
        bank = ReplayBank(widths, capacity=32, seed=9)
        for i in range(len(widths)):
            bank.add_graph(i, acts[i], rews[i])
        return bank

    flat = fresh()
    want = {i: [flat.buffers[i].sample(5) for _ in range(2)]
            for i in range(3)}
    bank = fresh()
    # bucket order deliberately scrambled vs zoo order
    a1, r1 = bank.sample_bucket([1], batch=5, steps=2)
    a0, r0 = bank.sample_bucket([0, 2], batch=5, steps=2)
    assert a1.shape == (2, 1, 5, 7, 2) and a0.shape == (2, 2, 5, 4, 2)
    for u in range(2):
        np.testing.assert_array_equal(a1[u, 0], want[1][u][0])
        np.testing.assert_array_equal(a0[u, 0], want[0][u][0])
        np.testing.assert_array_equal(a0[u, 1], want[2][u][0])
        np.testing.assert_array_equal(r0[u, 1], want[2][u][1])


def test_bank_rejects_mixed_width_buckets():
    bank = ReplayBank([4, 7], capacity=8, seed=0)
    with pytest.raises(AssertionError, match="mixed widths"):
        bank.sample_bucket([0, 1], batch=2, steps=1)

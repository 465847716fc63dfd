"""Operation and byte counts against numbers worked by hand at a small
shape (n=4 nodes, F=3 features, D=8, H=2 heads), and the rule that
padding is not work."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import flops  # noqa: E402

N, F, D, H = 4, 3, 8, 2


def test_gat_layer():
    # attention: 7 n^2 h + 2 n^2 d = 224 + 256
    assert flops.gat_attention_flops(N, D, H) == 480
    # + z = hW (2 n d^2 = 512) + e_src, e_dst (4 n d = 128)
    assert flops.gat_flops(N, D, H) == 1120
    assert flops.gat_flops(2, D, H) == 256 + 64 + 56 + 64


def test_policy_and_critic():
    assert flops.pool_sizes(N) == (2, 2)
    assert flops.gnn_gat_levels(N) == [4, 2, 2, 2]
    # input 192 + GATs 1120 + 3 x 440 + pooling 64 + 32 + output 512 + 384
    assert flops.gnn_forward_flops(N, F, D, H) == 192 + 2440 + 96 + 896
    # input 2 n (F + 6) d = 576, two GATs 2240, two Q heads 2 (2 d^2 + 2 d)
    assert flops.critic_forward_flops(N, F, D, H) == 576 + 2240 + 288


def test_generation():
    sac = 3 * (2 * 3104 + (3624 + 3104))
    assert flops.sac_step_flops([N], F, 2, D, H) == sac
    got = flops.generation_flops([N], F, gnn_rows=2, pg_rows=1, sac_steps=3,
                                 batch=2, d=D, h=H)
    assert got == 3 * 3624 + 3 * sac
    ea = flops.generation_flops([N], F, gnn_rows=2, pg_rows=0, sac_steps=0,
                                batch=2, d=D, h=H)
    assert ea == 2 * 3624


def test_kernel_work():
    f, b = flops.gat_fwd_work(N, D, H)
    assert (f, b) == (480, 4 * (16 + 32 + 16 + 32 + 16))
    f, b = flops.gat_bwd_work(N, D, H)
    assert f == 7 * 16 * 2 + 4 * 16 * 8 + 4 * 16 * 2
    assert b == 4 * (16 + 3 * 32 + 4 * 8 + 32 + 16)
    least, fl, by = flops.kernel_least_time_s([(N, "fwd", 10)], 1e3, 1e3,
                                              D, H)
    assert least == pytest.approx(10 * 0.480)       # FLOP-bound here
    assert (fl, by) == (4800, 4480)


def test_padding_is_not_work():
    """A graph padded to its bucket's size counts at its real size."""
    kw = dict(gnn_rows=3, pg_rows=1, sac_steps=2, batch=4)
    padded = flops.gat_kernel_calls([(8, [N])], backend_of=lambda n: "pallas",
                                    **kw)
    alone = flops.gat_kernel_calls([(N, [N])], backend_of=lambda n: "pallas",
                                   **kw)
    assert padded == alone
    assert all(n <= N for n, _, _ in padded)
    assert flops.generation_flops([N], F, **kw) < flops.generation_flops(
        [8], F, **kw)


def test_kernel_calls_follow_the_chosen_backend():
    kw = dict(gnn_rows=3, pg_rows=1, sac_steps=2, batch=4)
    # level 0 and the critic run at the padded size 8: not the kernel
    calls = flops.gat_kernel_calls(
        [(8, [N])], backend_of=lambda n: "chunked" if n == 8 else "pallas",
        **kw)
    assert calls == [(2, "bwd", 3 * 2), (2, "fwd", 3 * (3 + 1 + 2))]
    all_calls = dict(((n, k), c) for n, k, c in flops.gat_kernel_calls(
        [(8, [N])], backend_of=lambda n: "pallas", **kw))
    # level 0: forwards of 3 + 1 rows and 2 actor steps; the critic's two
    # GAT levels over 4 actions plus the actor loss, for 2 steps
    assert all_calls[(N, "fwd")] == (3 + 1 + 2) + 2 * 2 * (4 + 1)
    assert all_calls[(N, "bwd")] == 2 + 2 * 2 * (4 + 1)

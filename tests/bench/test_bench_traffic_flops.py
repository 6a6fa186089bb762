"""The traffic generator and the operation counts, on the CPU."""

from __future__ import annotations

import numpy as np
import pytest

from benchlib import common, flops, traffic

SEEDS = (1, 2**33 + 7)


@pytest.fixture(scope="module")
def chat():
    return common.load("traffic", "chat-p512")


def test_serve_schedule_deterministic_in_the_seed(chat):
    a = traffic.serve_schedule(chat, 1000, SEEDS[1], 30.0)
    b = traffic.serve_schedule(chat, 1000, SEEDS[1], 30.0)
    c = traffic.serve_schedule(chat, 1000, SEEDS[0], 30.0)
    assert [x[0] for x in a] == [x[0] for x in b]
    assert all((x[1] == y[1]).all() for x, y in zip(a, b))
    assert [x[2] for x in a] == [x[2] for x in b]
    assert any((x[1] != y[1]).any() for x, y in zip(a, c))
    # the mix fixes the order of arrivals and lengths for every seed
    assert [x[0] for x in a] == [x[0] for x in c]
    assert [x[2] for x in a] == [x[2] for x in c]
    d = traffic.serve_schedule(dict(chat, order_seed=chat["order_seed"] + 1),
                               1000, SEEDS[1], 30.0)
    assert [x[2] for x in d] != [x[2] for x in a]
    assert sorted(x[2] for x in d) == sorted(x[2] for x in a)


def test_serve_schedule_same_work_for_every_seed(chat):
    a = traffic.serve_schedule(chat, 1000, SEEDS[0], 30.0)
    b = traffic.serve_schedule(chat, 1000, SEEDS[1], 30.0)
    assert sorted(x[2] for x in a) == sorted(x[2] for x in b)
    gaps = lambda s: sorted(np.round(np.diff([x[0] for x in s]), 9))
    assert len(a) == len(b) == traffic.expected_requests(chat, 30.0)
    # the same gaps but one, which begins the schedule at zero
    assert len(set(gaps(a)) & set(gaps(b))) >= len(a) - 3


def test_serve_schedule_rate_and_clip(chat):
    seconds = 200.0
    s = traffic.serve_schedule(chat, 1000, SEEDS[0], seconds)
    due = np.array([x[0] for x in s])
    assert due[0] == 0.0 and (np.diff(due) > 0).all()
    rate = (len(s) - 1) / due[-1]
    assert rate == pytest.approx(chat["rate_per_s"], rel=0.05)
    outs = np.array([x[2] for x in s])
    o = chat["output"]
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    assert np.median(outs) == pytest.approx(o["median"], rel=0.05)
    assert all(len(x[1]) == chat["prompt_len"] for x in s)
    assert max(int(x[1].max()) for x in s) < 1000


def test_train_tokens():
    mix = common.load("traffic", "train-seq2048")
    a = traffic.train_tokens(mix, 50280, SEEDS[1], 0)
    assert a.shape == (mix["batch"], mix["seq"] + 1) and a.dtype == np.int32
    assert (a == traffic.train_tokens(mix, 50280, SEEDS[1], 0)).all()
    assert (a != traffic.train_tokens(mix, 50280, SEEDS[1], 1)).any()
    assert (a != traffic.train_tokens(mix, 50280, SEEDS[0], 0)).any()
    assert 0 <= a.min() and a.max() < 50280


@pytest.mark.parametrize("name", ["olmo-1b", "internlm2-1.8b"])
def test_flops_agree_with_the_program_count(name):
    from repro.configs import ModelConfig

    cfg = common.load("configs", name)
    m = cfg["model"]
    norms = (2 * m["n_layers"] + 1) * m["d_model"] * (m["norm"] == "rmsnorm")
    assert flops.param_count(m) == ModelConfig(**m).param_count() + norms


def test_flops_per_token_olmo():
    m = common.load("configs", "olmo-1b")["model"]
    n = flops.matmul_params(m)
    assert n == pytest.approx(1.177e9, rel=1e-3)
    per_tok = flops.train_flops_per_token(m, 2048)
    attn = 12 * m["n_layers"] * m["n_heads"] * m["d_head"] * 2049 / 2
    assert per_tok == pytest.approx(6 * n + attn)
    assert per_tok == pytest.approx(7.47e9, rel=1e-2)


def test_decode_flops_sum_of_steps():
    m = common.load("configs", "internlm2-1.8b")["model"]
    one = sum(flops.forward_flops(m, 100 + j) for j in range(1, 11))
    assert flops.decode_flops(m, 100, 10) == pytest.approx(one)
    assert flops.prefill_flops(m, 4) == pytest.approx(
        sum(flops.forward_flops(m, 2.5) for _ in range(4)))


def test_nearest_rank():
    v = list(range(1, 101))
    assert common.nearest_rank(v, 95) == 95
    assert common.nearest_rank(v + [float("inf")] * 10, 95) == float("inf")
    assert common.nearest_rank([3.0], 95) == 3.0


@pytest.mark.parametrize("name", ["olmo-1b", "internlm2-1.8b"])
def test_reference_layout_is_the_programs(name):
    """The reference regenerates the benchmark's weights from the
    configuration alone; its shapes are the program's tree's."""
    from benchlib import reference, weights
    from repro.configs import ModelConfig

    m = common.load("configs", name)["model"]
    assert reference.leaf_shapes(m) == dict(
        weights.leaf_paths(ModelConfig(**m)))

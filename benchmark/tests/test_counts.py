"""The functions that count operations and bytes, against hand-worked
values for both configurations; the stratified length generator."""
import collections
import json
import os

import pytest

from benchmark.lib import bytes as byte_counts
from benchmark.lib import flops, lengths, manifest

CFG = os.path.join(manifest.BENCH, "configs")


def cfg(name):
    with open(os.path.join(CFG, name + ".json")) as f:
        return json.load(f)


def test_resnet50_forward_is_7_7_gflop():
    per = flops.train_flops_per_sample(cfg("resnet50_v1"), {})
    assert per / 3 == pytest.approx(7.716e9, rel=1e-3)
    # by hand: the stem, 3 x 64 x 49 multiply-adds at 112 x 112
    assert 2 * 64 * 3 * 49 * 112 * 112 == 236027904


def test_opt_train_flops_are_6n_plus_attention():
    c = cfg("opt-1.3b")
    n = 2 * 50272 * 2048 + 2048 * 2048 + 24 * (
        4 * 2048 * 2048 + 2 * 2048 * 8192 + 8192 + 5 * 2048) + 2 * 2048
    assert n == 1418514432
    assert flops.train_flops_per_sample(c, {"seq": 2048}) == \
        6 * n + 12 * 24 * 2048 * 2048


def test_opt_serve_flops_and_decode_bytes():
    c = cfg("opt-1.3b")
    mm = 24 * (4 * 2048 * 2048 + 2 * 2048 * 8192) + 50272 * 2048
    assert flops.serve_flops(c, [0, 100]) == 2 * (2 * mm) + 4 * 24 * 2048 * 100
    # bf16 weights once + K and V (2 x 24 layers x 2048 x 2 B) a position
    assert byte_counts.decode_step_min_bytes(c, 1000) == \
        2 * mm + 2 * 24 * 2048 * 2 * 1000
    assert flops.mfu_percent(197e12, 1.0, 1, 197e12) == pytest.approx(100.0)


def test_every_seed_offers_the_same_requests():
    t = manifest.traffic("serve_chat")
    shapes = lengths.request_shapes(t)
    assert len(shapes) == t["strata"]
    assert all(t["prompt_len"]["lo"] <= p <= t["prompt_len"]["hi"] and
               t["answer_len"]["lo"] <= a <= t["answer_len"]["hi"] and
               p + a <= t["cache_len"] for p, a in shapes)

    def first_cycle(traffic, seed):
        s = lengths.request_stream(traffic, seed, 50272)
        return [next(s) for _ in range(len(shapes))]

    # the file fixes the order: the same work in every window, whatever the
    # seed, which draws the token ids alone
    runs = [first_cycle(t, seed) for seed in (1, 2**31 + 7, 2**33 + 1)]
    for got in runs:
        assert [(len(p), a) for p, a in got] == [shapes[i] for i in t["order"]]
        assert all(0 <= int(p.min()) and int(p.max()) < 50272 for p, _ in got)
    assert not (runs[0][5][0] == runs[1][5][0]).all()
    assert (first_cycle(t, 1)[5][0] == runs[0][5][0]).all()
    # without an order the seed shuffles the same multiset
    free = {k: v for k, v in t.items() if k != "order"}
    orders = []
    for seed in (1, 2**31 + 7):
        got = [(len(p), a) for p, a in first_cycle(free, seed)]
        assert collections.Counter(got) == collections.Counter(shapes)
        orders.append(got)
    assert orders[0] != orders[1]
    with pytest.raises(ValueError):
        next(lengths.request_stream(dict(t, order=[0] * t["strata"]), 1, 9))


def test_quantile_midpoints_are_the_distributions():
    q = lengths.quantile_midpoints(256, 0.8, 32, 768, 64)
    assert q == sorted(q) and q[0] >= 32 and q[-1] == 768
    assert 244 <= q[31] <= 256 <= q[32] <= 268

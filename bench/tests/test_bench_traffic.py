"""The traffic generator: the same seed gives the same requests, another
seed other ones, over the same fixed set of sizes and arrivals."""
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import loadgen  # noqa: E402

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
BIG = 3_000_000_017          # past 32 signed bits, as large run seeds are


def reqs(mix, seed, n=64):
    t = loadgen.Traffic(mix, seed)
    return [t.request(i) for i in range(n)], t


def test_same_seed_same_requests():
    mix = loadgen.load_mix(TRAFFIC / "chat.json")
    a, ta = reqs(mix, BIG)
    b, tb = reqs(mix, BIG)
    assert a == b
    assert [ta.offset(i) for i in range(64)] == \
        [tb.offset(i) for i in range(64)]


def test_other_seed_other_requests_same_sizes():
    mix = loadgen.load_mix(TRAFFIC / "chat.json")
    a, ta = reqs(mix, BIG)
    b, tb = reqs(mix, BIG + 1)
    assert a != b
    assert sum(x["prompt"] == y["prompt"] for x, y in zip(a, b)) == 0
    # the work is the same set of sizes, in another order
    pool = loadgen.size_pool(mix)
    assert sorted(ta.order) == sorted(tb.order) == list(range(len(pool)))
    assert list(ta.order) != list(tb.order)
    for r, j in zip(a, ta.order):
        assert (len(r["prompt"]), r["budgets"]) == \
            (pool[j]["prompt_bytes"], pool[j]["budgets"])
    # and the arrival times are the same fixed Poisson realization
    assert np.allclose([ta.offset(i) for i in range(64)],
                       [tb.offset(i) for i in range(64)])


def test_sizes_caps_and_check_requests():
    mix = loadgen.load_mix(TRAFFIC / "chat.json")
    pool = loadgen.size_pool(mix)
    budgets = np.array([p["budgets"] for p in pool])
    assert budgets.max() <= mix["max_new"] and budgets.min() >= mix["min_new"]
    assert (np.diff(budgets, axis=1) <= 0).all()      # L0 >= L1 >= L2
    assert max(p["prompt_bytes"] for p in pool) <= mix["prompt_bytes_max"]
    rs, _ = reqs(mix, 7, 32)
    for i, r in enumerate(rs):
        assert len(r["prompt"]) == len(r["prompt"].encode())
        assert "<" not in r["prompt"]
        assert r["greedy"] == (i % mix["greedy_every"] == 0)
        assert (r["temperature"], r["top_p"]) == \
            ((0.0, 1.0) if r["greedy"] else (0.7, 0.9))
    # mean Poisson rate as written in the file
    t = loadgen.Traffic(mix, 7)
    n = len(t.offsets)
    assert abs(n / t.offsets[-1] - mix["arrival"]["rate_per_s"]) \
        < 0.05 * mix["arrival"]["rate_per_s"]


def test_chat_order_is_shuffled_within_blocks():
    mix = loadgen.load_mix(TRAFFIC / "chat.json")
    block = mix["order_block"]
    assert block > 1
    ta, tb = loadgen.Traffic(mix, BIG), loadgen.Traffic(mix, BIG + 1)
    assert all(j // block == i // block for i, j in enumerate(ta.order))
    assert list(ta.order) != list(tb.order)


def test_batch_mix_is_greedy_backlog():
    mix = loadgen.load_mix(TRAFFIC / "batch.json")
    rs, t = reqs(mix, BIG, 16)
    assert t.kind == "backlog"
    assert all(r["greedy"] and r["temperature"] == 0.0 for r in rs)
    # the pool's own order for every seed: every run holds the same work
    assert mix["order_block"] == 1
    tb = loadgen.Traffic(mix, BIG + 1)
    assert list(t.order) == list(tb.order) == list(range(len(t.pool)))

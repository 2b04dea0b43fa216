"""Traffic from mix files: deterministic by seed, only the stated
widths, the same work for every seed, and a new mix found by name."""
import json
from collections import Counter

import numpy as np
import pytest

from bench import arrivals

VOCAB = 49152


@pytest.fixture(scope="module")
def chat():
    return arrivals.load_mix("chat")


def _key(sends):
    return [(round(s.t, 9), s.domain, s.max_new, s.prompt.tolist())
            for s in sends]


def test_same_seed_same_traffic(chat):
    a = arrivals.schedule(chat, VOCAB, 2 ** 31 + 77, 10.0)
    b = arrivals.schedule(chat, VOCAB, 2 ** 31 + 77, 10.0)
    assert _key(a) == _key(b)
    c = arrivals.schedule(chat, VOCAB, 5, 10.0)
    assert _key(a) != _key(c)


def test_only_stated_widths_and_budget_range(chat):
    sends = arrivals.schedule(chat, VOCAB, 11, 30.0)
    assert {s.prompt.size for s in sends} <= set(chat["prompt"]["widths"])
    o = chat["output"]
    assert all(o["min"] <= s.max_new <= o["max"] for s in sends)
    assert all(0 <= s.prompt.min() and s.prompt.max() < VOCAB
               for s in sends)


def test_every_seed_offers_the_same_work(chat):
    a = arrivals.schedule(chat, VOCAB, 1, 30.0)
    b = arrivals.schedule(chat, VOCAB, 2, 30.0)
    assert len(a) == len(b) == round(chat["rate_rps"] * 30.0)
    assert Counter((s.prompt.size, s.max_new) for s in a) == \
        Counter((s.prompt.size, s.max_new) for s in b)
    ga = np.sort(np.diff([0.0] + [s.t for s in a]))
    gb = np.sort(np.diff([0.0] + [s.t for s in b]))
    np.testing.assert_allclose(ga, gb, rtol=1e-9, atol=1e-12)
    assert a[-1].t < 30.0 and b[-1].t < 30.0


def test_seeds_reorder_only_within_blocks(chat):
    block = chat["reorder_block"]
    gaps, widths, budgets = arrivals.shapes(chat, 50.0, "window")
    sends = arrivals.schedule(chat, VOCAB, 2 ** 31 + 3, 50.0)
    for s0 in range(0, len(sends), block):
        got = sends[s0:s0 + block]
        assert Counter((s.prompt.size, s.max_new) for s in got) == \
            Counter(zip(widths[s0:s0 + block].tolist(),
                        budgets[s0:s0 + block].tolist()))
    times = np.array([s.t for s in sends])
    np.testing.assert_allclose(times[block - 1::block],
                               np.cumsum(gaps)[block - 1::block],
                               rtol=1e-9)


def test_window_follows_warm_phase(chat):
    w = arrivals.schedule(chat, VOCAB, 3, 10.0, phase="window",
                          start=chat["warm_s"])
    assert min(s.t for s in w) > chat["warm_s"]


def test_new_mix_found_by_name(tmp_path):
    mix = {"rate_rps": 2.0, "warm_s": 1, "template_seed": 9,
           "prompt": {"median": 100, "sigma": 0.3, "widths": [64, 128]},
           "output": {"median": 20, "sigma": 0.2, "min": 8, "max": 40},
           "domains": {"names": ["x"], "branchings": [2]},
           "serving": {}}
    (tmp_path / "burst-2.json").write_text(json.dumps(mix))
    got = arrivals.load_mix("burst-2", str(tmp_path))
    assert got["name"] == "burst-2"
    sends = arrivals.schedule(got, 1000, 4, 5.0)
    assert len(sends) == 10
    assert {s.prompt.size for s in sends} <= {64, 128}


def test_warm_bursts_cover_every_row_bucket():
    bursts = arrivals.warm_bursts([256, 512, 1024], 16)
    solo = {(w, n) for b in bursts if len(b) == 1 for w, n in b.items()}
    assert solo == {(w, n) for w in (256, 512, 1024)
                    for n in (1, 2, 4, 8, 16)}
    cohort = [b for b in bursts if len(b) > 1]
    assert all(sum(b.values()) <= 16 for b in cohort)
    buckets = {(w, 1 << (n - 1).bit_length()) for b in cohort
               for w, n in b.items() if n > 1 or len(b) > 1}
    assert {(w, b) for w in (256, 512, 1024)
            for b in (1, 2, 4, 8, 16)} <= buckets

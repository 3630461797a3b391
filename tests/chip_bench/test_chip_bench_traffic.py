"""The traffic generator: a seed fixes the requests, every seed gets the
same multiset of lengths and gaps on one schedule of arrivals and
documents, and every length keeps to its mix's clips."""

from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import traffic

MIXES = Path(__file__).resolve().parents[2] / "benchmarks/chip/traffic"
CELL = {"rate": 4.0}


@pytest.mark.parametrize("name", ["chat", "backlog", "docqa"])
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(MIXES / f"{name}.json")
    a = traffic.generate(mix, CELL, 2**31 + 77, 32000, 20.0)
    b = traffic.generate(mix, CELL, 2**31 + 77, 32000, 20.0)
    c = traffic.generate(mix, CELL, 2**31 + 78, 32000, 20.0)
    assert a == b
    assert a.reqs != c.reqs


@pytest.mark.parametrize("name", ["chat", "backlog", "docqa"])
def test_lengths_keep_to_the_clips(name):
    mix = traffic.load_mix(MIXES / f"{name}.json")
    tr = traffic.generate(mix, CELL, 5, 32000, 51.0)
    out = np.array([r.max_new for r in tr.reqs])
    assert out.min() >= mix["output"]["min"]
    assert out.max() <= mix["output"]["max"]
    docs = mix.get("documents")
    for d in tr.docs:
        assert docs["min"] <= len(d) <= docs["max"]
    q = np.array([len(r.prompt) - (len(tr.docs[r.doc]) if docs else 0)
                  for r in tr.reqs])
    assert q.min() >= mix["prompt"]["min"]
    assert q.max() <= mix["prompt"]["max"]
    assert all(0 <= t < 32000 for r in tr.reqs[:20] for t in r.prompt)


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_every_seed_gets_the_same_work(name):
    mix = traffic.load_mix(MIXES / f"{name}.json")
    runs = [traffic.generate(mix, CELL, s, 32000, 30.0) for s in (1, 2)]

    def work(tr):
        def own(r):
            return len(r.prompt) - (len(tr.docs[r.doc]) if r.doc >= 0
                                    else 0)
        return (sorted(own(r) for r in tr.reqs),
                sorted(r.max_new for r in tr.reqs),
                sorted(r.doc for r in tr.reqs),
                [len(d) for d in tr.docs])     # a length per rank

    assert work(runs[0]) == work(runs[1])
    gaps = [np.sort(np.diff([0.0] + [r.due_s for r in tr.reqs]))
            for tr in runs]
    np.testing.assert_allclose(gaps[0], gaps[1])
    # one schedule: the same arrival times and document choices
    assert ([(r.due_s, r.doc) for r in runs[0].reqs]
            == [(r.due_s, r.doc) for r in runs[1].reqs])
    assert runs[0].reqs != runs[1].reqs
    assert len(runs[0].reqs) == len(runs[1].reqs) == 120


def test_open_loop_rate_and_backlog_count():
    chat = traffic.load_mix(MIXES / "chat.json")
    tr = traffic.generate(chat, CELL, 3, 32000, 50.0)
    assert len(tr.reqs) == 200
    due = [r.due_s for r in tr.reqs]
    assert due == sorted(due)
    assert 45.0 < due[-1] < 50.0          # mean gap 1 / rate
    backlog = traffic.load_mix(MIXES / "backlog.json")
    tr = traffic.generate(backlog, CELL, 3, 32000, 50.0)
    assert len(tr.reqs) == 200 + backlog["waiting"]
    assert all(r.due_s == 0.0 for r in tr.reqs)


def test_zipf_popularity():
    ranks = traffic.zipf_ranks(32, 1.0, 4000, np.random.default_rng(0))
    counts = np.bincount(ranks, minlength=32)
    w = 1.0 / np.arange(1, 33)
    np.testing.assert_allclose(counts / 4000, w / w.sum(), atol=1e-3)


def test_shape_warmup_reaches_every_bucket_and_tail():
    buckets = [128, 256, 512, 1024, 2048]
    reqs = traffic.shape_warmup(buckets, 3072, 32000, 9, rid0=0)
    lens = [len(r.prompt) for r in reqs]
    assert lens[0] == 128                  # a whole page: decode grows it
    for a in buckets:
        assert a - 2 in lens
    # each (a, b < a) pair: a prefix of a - 2 - b tokens, then the prompt
    pairs = 0
    for p, q in zip(reqs, reqs[1:]):
        if q.prompt[:len(p.prompt)] == p.prompt and len(q.prompt) > len(
                p.prompt):
            assert len(q.prompt) - len(p.prompt) in buckets
            pairs += 1
    assert pairs == 10

"""A request preempted for pages comes back with its tokens appended to
its prompt, past the largest prompt bucket the set-up warmed.  The engine
serves it (a new bucket, compiled then) and what it served agrees with
the plain reference, on a tiny cell on the CPU."""

import tempfile
from pathlib import Path

import numpy as np

import tiny
from benchmarks.chip import harness, spec, traffic
from benchmarks.chip.reference import dense


def test_readmitted_past_the_warmed_buckets_matches_reference():
    # 4 slots of 24-token requests need 12 pages of 8; the pool holds 8
    root = tiny.make_root(Path(tempfile.mkdtemp()), cell=dict(pages=9))
    cell = spec.load(root, "tiny-chat")
    seed = 2**31 + 5
    eng = harness.build(cell, seed)
    harness.setup_traffic(eng, cell, seed, [])
    warmed = next(b for b in eng.buckets
                  if b >= traffic.longest_prompt(cell.mix))
    readmitted = {}
    preempt = eng._preempt

    def watch(slot, now):
        preempt(slot, now)
        req = eng.pending[0]
        readmitted[req.rid] = len(req.prompt)

    eng._preempt = watch
    rng = np.random.default_rng(3)
    reqs = [traffic.Req(100 + i, 0.0, tuple(int(t) for t in rng.integers(
        0, 256, 14)), 10) for i in range(4)]
    harness.Client(eng, reqs).drain()
    past = {rid for rid, n in readmitted.items() if n > warmed}
    assert past, (readmitted, warmed)
    done = {c.rid: c for c in eng.completions}
    sample = [r for r in reqs if r.rid in past]
    for r in sample:
        assert done[r.rid].reason == "max_tokens"
        assert len(done[r.rid].tokens) == r.max_new
    gaps, _ = dense.served_gaps(dense.Forward(cell.config, seed),
                                [r.prompt for r in sample],
                                [done[r.rid].tokens for r in sample])
    assert max(gaps) <= tiny.CELL["max_logit_gap"], gaps

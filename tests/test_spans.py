"""Scheduler spans (``serving/spans.py``) as the continuous-batching engine
records them: nothing while off; while on, one ``sched.step`` around each
step's phases, a queue wait per admission, counts that agree with the
engine's own counters, and the same tokens as with spans off.  Also the
engine's TTFT clock, which the queue wait starts from."""

import time

import jax
import numpy as np
import pytest

from repro.models import build_model
from repro.serving import spans as spans_mod
from repro.serving.scheduler import ContinuousBatchingEngine, Request

PREFIX = tuple(range(5, 21))            # two whole pages at page size 8
PHASES = ("sched.admit", "sched.pages", "sched.decode", "sched.retire")
# roomy: the default arena; tight: so few pages that admissions wait for
# pages and decode growth preempts
SCENARIOS = {"roomy": None, "tight": 6}


@pytest.fixture(scope="module")
def model():
    m = build_model("qwen2.5-14b", reduced=True)
    return m, m.init(jax.random.PRNGKey(0))


def _reqs():
    return [Request(rid=i,
                    prompt=PREFIX + tuple(100 + 10 * i + j
                                          for j in range(3 + i % 4)),
                    max_new_tokens=4 + 3 * (i % 3))
            for i in range(8)]


def _serve(model, on: bool, pages=None):
    """Serve ``_reqs`` through ``step()``, as an external loop does."""
    m, params = model
    eng = ContinuousBatchingEngine(m, params, slots=3, max_len=64,
                                   temperature=1.0, seed=3, page_size=8,
                                   pages=pages, prefix_cache=True)
    eng.spans.on = on
    for r in _reqs():
        eng.submit(r)
    while eng.pending or eng.active_slots():
        eng.step()
    return eng


@pytest.fixture(scope="module")
def served(model):
    return {k: _serve(model, True, pages) for k, pages in SCENARIOS.items()}


def _admitted(recs):
    return [r for r in recs if r.name == "sched.admit" and r.n > 0]


def test_off_records_nothing(model):
    eng = _serve(model, False)
    assert eng.spans.span("sched.step") is spans_mod._OFF
    with eng.spans.span("sched.admit") as sp:
        sp.n = 5                          # ignored, not an error
    eng.spans.record("sched.queue", 0, 1)
    assert eng.spans.records == []
    assert eng.stats["admitted"] == 8


def _check_parents(eng):
    recs = eng.spans.records
    assert all(r.end_ns >= r.start_ns > 0 for r in recs)
    for r in recs:
        if r.name in PHASES:
            p = recs[r.parent]
            assert p.name == "sched.step"
        elif r.name.startswith("prefix.") or r.name == "sched.queue":
            p = recs[r.parent]
            assert p.name == "sched.admit" and p.rid == r.rid
        else:
            assert r.name == "sched.step" and r.parent == -1
            continue
        if r.name != "sched.queue":
            assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns


def _check_queue(eng):
    recs = eng.spans.records
    queues = [r for r in recs if r.name == "sched.queue"]
    admits = _admitted(recs)
    assert len(queues) == len(admits) == eng.stats["admitted"]
    for a in admits:
        (q,) = [q for q in queues if q.rid == a.rid
                and q.end_ns == a.start_ns]
        assert q.start_ns < q.end_ns


def _check_prefix(eng):
    reused = sum(r.n for r in eng.spans.records if r.name == "prefix.match")
    assert reused == eng.stats["prefix_tokens_reused"] > 0


def _check_prefill(eng):
    admit_ns = sum(r.end_ns - r.start_ns for r in _admitted(eng.spans.records))
    assert admit_ns * 1e-9 >= eng.stats["prefill_s"] > 0
    # n counts the tokens each admission prefilled
    assert (sum(r.n for r in _admitted(eng.spans.records))
            == eng.stats["prefill_tokens"])


def _check_decode(eng):
    dec = [r for r in eng.spans.records if r.name == "sched.decode"]
    total = sum(r.end_ns - r.start_ns for r in dec) * 1e-9
    assert total == pytest.approx(eng.stats["decode_s"], rel=0.01)
    assert sum(r.runahead for r in dec) == eng.stats["steps"]
    assert sum(r.n * r.runahead for r in dec) == eng.stats["decode_tokens"]


def _check_stalled(eng):
    admits = _admitted(eng.spans.records)
    # the first admission finds every slot free; the next ones in the same
    # step hold back the first request's decode
    assert not admits[0].stalled
    assert admits[1].stalled


CHECKS = {"parents": _check_parents, "queue": _check_queue,
          "prefix": _check_prefix, "prefill": _check_prefill,
          "decode": _check_decode, "stalled": _check_stalled}


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("check", CHECKS)
def test_spans_on(served, scenario, check):
    CHECKS[check](served[scenario])


def test_tight_pool_waits_and_preempts(served):
    eng = served["tight"]
    assert eng.stats["preempted"] > 0
    # attempts that found no pages keep n = 0
    assert any(r.name == "sched.admit" and r.n == 0
               for r in eng.spans.records)


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_tokens_same_with_spans_on_and_off(model, served, scenario):
    off = _serve(model, False, SCENARIOS[scenario])
    on = served[scenario]
    assert ({c.rid: c.tokens for c in on.completions}
            == {c.rid: c.tokens for c in off.completions})


def test_ttft_from_submit_when_driven_by_step(model):
    """An external loop: the TTFT counts the wait before the step that
    admits the request, which its sched.queue span covers."""
    m, params = model
    eng = ContinuousBatchingEngine(m, params, slots=2, max_len=64,
                                   temperature=0.0, page_size=8)
    eng.run([Request(rid=0, prompt=PREFIX, max_new_tokens=2)])  # compile
    eng.spans.on = True
    eng.submit(Request(rid=1, prompt=PREFIX, max_new_tokens=2))
    time.sleep(0.05)
    while eng.pending or eng.active_slots():
        eng.step()
    (q,) = [r for r in eng.spans.records if r.name == "sched.queue"]
    (a,) = _admitted(eng.spans.records)
    comp = eng.completions[-1]
    assert comp.rid == 1
    assert (q.end_ns - q.start_ns) * 1e-9 >= 0.05
    assert comp.ttft_s == pytest.approx((a.end_ns - q.start_ns) * 1e-9,
                                        abs=1e-3)


def test_ttft_from_arrival_under_run(model):
    """``run()`` offers a request at its arrival time, not at submit: a
    request due 1 s in is admitted within a step of it."""
    m, params = model
    eng = ContinuousBatchingEngine(m, params, slots=2, max_len=64,
                                   temperature=0.0, page_size=8)
    eng.run([Request(rid=0, prompt=PREFIX, max_new_tokens=2)])  # compile
    t0 = time.perf_counter()
    comps = eng.run([
        Request(rid=1, prompt=PREFIX, max_new_tokens=2),
        Request(rid=2, prompt=PREFIX, max_new_tokens=2, arrival_s=1.0)])
    wall = time.perf_counter() - t0
    late = {c.rid: c for c in comps}[2]
    assert 0.0 <= late.ttft_s < 1.0
    assert late.ttft_s + 1.0 <= wall


@pytest.mark.parametrize("enc_chunk", [None, 2])
def test_encdec_admissions_are_spans(enc_chunk):
    """encdec reports the same spans: whole or window by window, the
    admissions' counts add up to the tokens and frames prefilled."""
    m = build_model("whisper-base", reduced=True)
    eng = ContinuousBatchingEngine(m, m.init(jax.random.PRNGKey(0)),
                                   slots=2, max_len=32, temperature=0.0,
                                   max_cross_len=8, enc_chunk=enc_chunk)
    eng.spans.on = True
    rng = np.random.default_rng(0)
    eng.run([Request(rid=i, prompt=(1, 2, 3 + i), max_new_tokens=3,
                     frames=rng.standard_normal(
                         (6, m.cfg.d_model)).astype(np.float32))
             for i in range(3)])
    recs = eng.spans.records
    assert (sum(r.n for r in recs if r.name == "sched.admit")
            == eng.stats["prefill_tokens"])
    assert sorted(r.rid for r in recs if r.name == "sched.queue") == [0, 1, 2]
    assert all(recs[r.parent].name == "sched.step" for r in recs
               if r.name == "sched.admit")

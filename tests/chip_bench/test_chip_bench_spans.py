"""The engine's scheduler spans as the benchmark reads them: the trace
reduction names an idle gap by the span the host was in, and the four
``sched.*`` readers, on span lists worked out by hand and on the spans of
a tiny cell's engine served through the harness's client loop."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest
import tiny

from benchmarks.chip import harness, spec, trace, traffic
from repro.serving.spans import Span

DATA = Path(__file__).resolve().parents[2] / "benchmarks/chip/testdata"
NS = 1e-9
READERS = ("sched.decode_stall_share", "sched.decode_step_p50_ms",
           "sched.queue_wait_p90_ms", "sched.admit_p90_ms")


def _reduce(name):
    return trace.reduce(json.loads((DATA / name).read_text()))


def test_idle_gaps_named_by_scheduler_spans():
    """trace_danube_chat_5ms_sched.json is the recorded 5 ms with a
    sched.retire span over its first idle stretch (165,618,798-168,637,422)
    and a sched.pages span over its second (168,637,960-169,256,591), both
    inside bench.step; the 10 ns between ops stay under bench.step."""
    plain = _reduce("trace_danube_chat_5ms.json")
    red = _reduce("trace_danube_chat_5ms_sched.json")
    for key in ("window_s", "busy_s", "op_s", "module_s", "calls"):
        assert red[key] == plain[key]
    assert red["gaps_s"] == pytest.approx({
        "sched.retire": (168_637_422 - 165_618_798) * NS,
        "sched.pages": (169_256_591 - 168_637_960) * NS,
        "bench.step": 10 * NS})
    assert sum(red["gaps_s"].values()) == pytest.approx(
        sum(plain["gaps_s"].values()))


def _span(name, start_ms, dur_ms, n=0, runahead=0, stalled=False):
    return Span(name, int(start_ms * 1e6), int((start_ms + dur_ms) * 1e6),
                n=n, runahead=runahead, stalled=stalled)


# a 2 s window: three admissions (one into an idle engine, two that held
# decoding slots back), a failed attempt, three decode bursts, ten waits
HAND = [
    _span("sched.admit", 0, 80, n=512),
    _span("sched.admit", 300, 0.5, n=0, stalled=True),    # found no pages
    _span("sched.admit", 400, 100, n=1024, stalled=True),
    _span("sched.admit", 900, 20, n=64, stalled=True),
    _span("sched.decode", 100, 120, n=1, runahead=1),
    _span("sched.decode", 520, 360, n=2, runahead=3),
    _span("sched.decode", 1000, 130, n=3, runahead=1),
    _span("sched.step", 0, 230),
    *[_span("sched.queue", 10 * i, float(i)) for i in range(1, 11)],
]
EXPECTED = {
    # (100 + 20) ms of stalled admissions over 2 s
    "sched.decode_stall_share": 100.0 * 0.120 / 2.0,
    # per step: 120, 120, 130 ms
    "sched.decode_step_p50_ms": 120.0,
    # waits 1..10 ms: rank 0.9 x 9 = 8.1 -> 9 + 0.1 x (10 - 9)
    "sched.queue_wait_p90_ms": 9.1,
    # admissions 80, 100, 20 ms: rank 0.9 x 2 = 1.8 -> 80 + 0.8 x 20
    "sched.admit_p90_ms": 96.0,
}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return spec.load(tiny.make_root(tmp_path_factory.mktemp("root")),
                     "tiny-chat")


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_hand_made_spans(cell, metric):
    ctx = SimpleNamespace(spans=HAND, window_s=2.0)
    assert cell.reader(metric)(ctx) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", READERS)
@pytest.mark.parametrize("spans", ["absent", "empty", "other kinds"])
def test_reader_without_its_spans_reads_nothing(cell, metric, spans):
    ctx = SimpleNamespace(window_s=2.0)
    if spans != "absent":
        ctx.spans = [] if spans == "empty" else [
            _span("sched.step", 0, 10), _span("sched.admit", 0, 1, n=0)]
    assert cell.reader(metric)(ctx) is None


@pytest.fixture(scope="module")
def window_spans(cell):
    """The tiny cell's engine, set up as a run sets it up, serving a 2 s
    window through the harness's client loop with its spans on; the
    records come back with times relative to the window's start."""
    seed = 2**31 + 11
    eng = harness.build(cell, seed)
    tr = traffic.generate(cell.mix, cell.settings, seed,
                          cell.config["vocab_size"], 2.0)
    harness.setup_traffic(eng, cell, seed, tr.docs)
    client = harness.Client(eng, tr.reqs)
    eng.spans.on = True
    t0_ns = time.perf_counter_ns()
    window_s, _ = harness.serve_window(client, 2.0, t0_ns * 1e-9)
    spans = [Span(s.name, s.start_ns - t0_ns, s.end_ns - t0_ns, s.parent,
                  s.rid, s.n, s.runahead, s.stalled)
             for s in eng.spans.records]
    return SimpleNamespace(spans=spans, window_s=window_s,
                           admitted=eng.stats["admitted"])


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_a_tiny_window(cell, window_spans, metric):
    ctx = window_spans
    assert ctx.admitted > 0
    assert all(0 <= s.start_ns <= s.end_ns <= ctx.window_s * 1e9
               for s in ctx.spans)
    v = cell.reader(metric)(ctx)
    assert v is not None and v >= 0
    if metric == "sched.decode_stall_share":
        assert 0 < v < 100

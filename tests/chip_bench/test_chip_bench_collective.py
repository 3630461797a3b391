"""``collective.share`` on recorded device traces: on 5 ms of the
four-chip qwen cell's trace (TPU v5 lite) against numbers worked out by
hand from the events listed in
``benchmarks/chip/testdata/trace_qwen14b_tp4_5ms.json`` (nanoseconds),
and on the one-chip danube trace, which holds no collective."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import tiny
from benchmarks.chip import spec, trace

DATA = Path(__file__).resolve().parents[2] / "benchmarks/chip/testdata"


@pytest.fixture(scope="module")
def read():
    return spec.load(tiny.REPO, "qwen14b-tp4-backlog").reader(
        "collective.share")


def _ctx(name):
    return SimpleNamespace(
        trace=trace.reduce(json.loads((DATA / name).read_text())))


def test_one_chip_trace_reads_nothing(read):
    ctx = _ctx("trace_danube_chat_5ms.json")
    assert ctx.trace["module_s"]["_fused_decode"] > 0
    assert read(ctx) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_share_of_the_decode_step_on_four_chips(read):
    """The slice lies inside one ``_fused_decode`` (2,327,575,434 +
    40,746,545 ns), so the program's time in it is the window's 5 ms.
    It holds 16 all-reduces of bf16[48, 5120] (``wo`` and ``down``,
    alternating), from 2,329,077,625 to 2,333,883,028 + 13,840, with no
    op nested in any: 14,401 + 14,121 + 14,637 + 13,883 + 15,036 +
    13,856 + 14,645 + 14,163 + 14,780 + 14,167 + 14,228 + 14,245 +
    14,348 + 14,171 + 14,623 + 13,840 = 229,144 ns."""
    ctx = _ctx("trace_qwen14b_tp4_5ms.json")
    assert ctx.trace["module_s"] == pytest.approx({"_fused_decode": 5e-3})
    assert ctx.trace["collective_s"] == pytest.approx(
        {"_fused_decode": 229_144e-9})
    assert read(ctx) == pytest.approx(100 * 229_144 / 5_000_000)

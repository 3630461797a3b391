"""The trace reduction on 5 ms of a trace recorded on a TPU v5 lite
(danube-chat: the end of one decode step, a host gap, the start of the
next), against numbers worked out by hand from the events listed in
``benchmarks/chip/testdata/trace_danube_chat_5ms.json`` (nanoseconds)."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import trace

DATA = (Path(__file__).resolve().parents[2]
        / "benchmarks/chip/testdata/trace_danube_chat_5ms.json")
NS = 1e-9


@pytest.fixture(scope="module")
def red():
    return trace.reduce(json.loads(DATA.read_text()))


def test_busy_and_idle(red):
    assert red["window_s"] == pytest.approx(5_000_000 * NS)
    # busy: copy.73 runs on into the window, 165,000,000-165,618,798;
    # copy.1 538; then 169,256,591 to the window's end under while.5,
    # less six 1-2 ns gaps between the ops before the while (10 ns)
    busy = 618_798 + 538 + (170_000_000 - 169_256_591) - 10
    assert red["busy_s"]["0"] == pytest.approx(busy * NS)
    # both idle stretches, 165,618,798-168,637,422 and 168,637,960-
    # 169,256,591, and the 10 ns fall inside a bench.step span
    assert red["gaps_s"] == pytest.approx(
        {"bench.step": (5_000_000 - busy) * NS})


def test_programs_and_self_time(red):
    # the first decode step ends at 165,618,799; the second starts at
    # 169,256,271 and runs past the window's end
    assert red["module_s"] == pytest.approx({
        "_fused_decode": (618_799 + 743_729) * NS,
        "convert_element_type": 541 * NS})
    # while.5 from 169,261,987, clipped to 738,013 ns, holds 24 ops that
    # cover 737,986 ns of it (the last clipped to 102,993)
    assert red["op_s"]["while"] == pytest.approx(27 * NS)
    # copies that start in the window: copy.1, .24, .47, .48, .50
    assert red["op_s"]["copy"] == pytest.approx(
        (538 + 592 + 126_022 + 250 + 126_300) * NS)
    assert red["calls"] == {("_fused_decode", "custom-call"): [
        [4 * NS, "bf16", (24, 320, 128, 8, 120)],
        [4 * NS, "bf16", (24, 320, 128, 8, 120)]]}
    assert red["collective_s"] == {}


def test_breakdown_orders_by_time(red):
    b = trace.breakdown(red, top=2)
    assert [k for k, _ in b["device_ops"]] == [
        "copy", "constant_dynamic-slice_fusion"]
    assert b["device_ops"][1][1] == pytest.approx((11_593 + 239_285) * NS)
    assert b["idle_gaps"][0][0] == "bench.step"


def test_parse_op():
    assert trace.parse_op(
        "%twopass_softmax_2d.21 = f32[98304,3072]{1,0:T(8,128)} "
        "custom-call(%bitcast.175)") == ("twopass_softmax_2d", "f32",
                                         (98304, 3072))
    assert trace.parse_op(
        "%decode_attention_paged_pallas.8 = (f32[64,8,4,120]{3,2,1,0}, "
        "f32[64,8,4,1]") == ("decode_attention_paged_pallas", "f32",
                             (64, 8, 4, 120))
    assert trace.module_name("jit__fused_decode(1096)") == "_fused_decode"

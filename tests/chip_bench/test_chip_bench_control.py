"""The precision control at a size a test run holds: the reference in
float8 in the program's place.  Served the float32 reference's own
greedy tokens, the check reads a gap of 0; the control's tokens, read
against the float32 reference, lie farther below its best than the tiny
cell's limit, on every seed; and in a whole run of the tiny cell the
harness's own check finds the program correct and the control not."""

import time

import pytest

import tiny
from benchmarks.chip import harness, spec, traffic
from benchmarks.chip.reference import dense


def greedy(fwd, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        xs = fwd.hidden([seq], fp8=False)
        _, _, am = fwd.head_stats(xs, [[-1] * len(seq)])[0]
        seq.append(int(am[-1]))
    return seq[len(prompt):]


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_control_fails_the_limit_where_the_reference_passes(seed):
    limit = tiny.CELL["max_logit_gap"]
    tr = traffic.generate(tiny.MIX, tiny.CELL, seed, 256, 1.0)
    prompts = [r.prompt for r in tr.reqs[:4]]
    fwd = dense.Forward(tiny.CONFIG, seed)
    served = [greedy(fwd, p, 8) for p in prompts]
    prog, ctl = dense.served_gaps(fwd, prompts, served, control=True)
    assert max(prog) <= 1e-5
    assert max(ctl) > limit


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_precision_control_in_the_programs_place_is_not_correct(tmp_path,
                                                                seed):
    root = tiny.make_root(tmp_path)
    cell = spec.load(root, "tiny-chat")
    res = harness.run(cell, seed, 2.0, False, time.perf_counter(),
                      root / ".bench_trace", control=True)
    assert res["correct"] is True
    ctl = res["control"]
    assert ctl["correct"] is False
    gap = ctl["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] == res["checks"]["max_logit_gap"][
        "limit"]
    assert ctl["checks"]["tokens_checked"] == res["checks"]["tokens_checked"]

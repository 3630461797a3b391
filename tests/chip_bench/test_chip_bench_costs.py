"""Operations and bytes against hand counts, for one h2o-danube-3-4b
shape and one qwen2.5-14b shape, and the peaks table."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import costs, peaks, weights

CONFIGS = Path(__file__).resolve().parents[2] / "benchmarks/chip/configs"


def dims(name):
    return weights.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_danube_counts():
    m = dims("h2o-danube-3-4b")
    # per layer: q/k/v 3840 x 48 heads x 120, o 32 x 120 x 3840, MLP 3 x
    # 3840 x 10240; 24 layers; LM head 3840 x 32000
    assert costs.matmul_params(m) == 24 * (22_118_400 + 14_745_600
                                           + 117_964_800) + 122_880_000
    assert costs.attention_flops(m, 1000) == 4 * 24 * 32 * 120 * 1000
    f, b = costs.paged_decode_cost(m, 1000)
    assert f == 368_640_000
    # K and V of 1000 positions x 8 heads x 120 x bf16, q and o 32 x 120
    assert b == 24 * (2 * 1000 * 8 * 120 * 2 + 2 * 32 * 120 * 2)
    assert costs.decode_flops(m, [10, 20]) == (
        2 * 2 * 3_838_771_200 + 4 * 24 * 32 * 120 * 30)
    # 3 tokens after 100 cached: contexts 101, 102, 103
    assert costs.prefill_flops(m, 100, 3) == (
        2 * 3_838_771_200 * 3 + 4 * 24 * 32 * 120 * 306)


def test_qwen_counts_per_chip():
    m = dims("qwen2.5-14b-tp4")
    assert costs.matmul_params(m) == 48 * (36_700_160 + 26_214_400
                                           + 212_336_640) + 778_567_680
    f, b = costs.paged_decode_cost(m, 1000, shards=4)
    assert f == 4 * 48 * 10 * 128 * 1000
    assert b == 48 * (2 * 1000 * 2 * 128 * 2 + 2 * 10 * 128 * 2)


def test_softmax_and_least_time():
    f, b = costs.softmax_cost(32 * 2048, 2048)
    assert b == 65536 * 2048 * 8 and f == 5 * 65536 * 2048
    pk = peaks.peaks("TPU v5 lite")
    t, bound = costs.least_time(f, b, pk)
    assert bound == "memory" and t == pytest.approx(b / 819e9)
    t, bound = costs.least_time(197e12, 1.0, pk)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")

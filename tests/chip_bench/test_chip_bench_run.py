"""Whole runs of a tiny cell on the CPU, past the harness's look for a
chip: a sound run is correct and reports its metrics; a run whose served
tokens are altered where they are produced is not; a cell, configuration,
mix and metric added as files alone are found by name."""

import json
import os
import shutil
import subprocess
import sys
import time


import tiny
from benchmarks.chip import harness, spec

NEW_METRIC = '''"""Steps the engine ran in the window (a test metric)."""


def read(ctx):
    return ctx.stats["steps"]
'''


def new_cell_root(tmp_path):
    """A checkout holding a cell, configuration, mix and metric that the
    repository does not have, added as files and entries only."""
    root = tiny.make_root(tmp_path, name="fresh-cell")
    pkg = root / tiny.PKG
    (pkg / "metrics" / "engine.steps.py").write_text(NEW_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "engine.steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tpot_p90_ms", "workloads": ["fresh-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_tiny(root, name, seed, trace=False):
    cell = spec.load(root, name)
    return harness.run(cell, seed, 2.0, trace, time.perf_counter(),
                       root / ".bench_trace")


def test_new_cell_config_mix_and_metric_are_files_only(tmp_path):
    root = new_cell_root(tmp_path)
    res = run_tiny(root, "fresh-cell", 2**31 + 3, trace=True)
    assert res["correct"] is True
    assert res["metrics"]["engine.steps"]["value"] > 0
    assert res["metrics"]["launcher.window_compiles"]["value"] == 0
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    res = run_tiny(root, "fresh-cell", 5)
    assert res["correct"] is True
    for name in ("ttft_p90_ms", "setup_s"):
        assert res["metrics"][name]["value"] > 0
    # a run-ahead burst hands the client several tokens at once, so at
    # this size a request can see all its tokens in one step
    assert res["metrics"]["tpot_p90_ms"]["value"] >= 0
    assert res["attempted"] == 40 and res["failed"] == 0


def test_token_altered_where_produced_is_not_correct(tmp_path,
                                                      monkeypatch):
    from repro.serving import engine

    sample = engine.sample_token

    def altered(logits, key, temperature=1.0, **kw):
        tok = sample(logits, key, temperature, **kw)
        return (tok + 1) % kw["vocab"]

    monkeypatch.setattr(engine, "sample_token", altered)
    root = tiny.make_root(tmp_path)
    res = run_tiny(root, "tiny-chat", 7)
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def _cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "-m", "benchmarks.chip.run",
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


ARGS = ["--workload", "danube-chat", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_without_a_tpu_no_result():
    out = _cli(ARGS, tiny.REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    bench = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        shutil.copytree(tiny.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    out = _cli(ARGS, tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""

"""A cell at a size the CPU runs in seconds, laid out as a checkout: the
repository's BENCHMARK.json entries and data files, plus a tiny
configuration and its cell, under a temporary root."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
PKG = "benchmarks/chip"

CONFIG = {
    "name": "tiny-dense", "source": "test", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "hidden_act": "silu", "attention_bias": True,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "sliding_window": None,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "program_arch": "qwen2.5-14b", "reference": "dense_decoder",
}
MIX = {
    "arrivals": "poisson",
    "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2,
               "max": 16},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
               "max": 12},
}
# the limit from this size's own readings on the CPU: sound runs read at
# most 0.05 over two dozen seeds, the float8 control at least 0.087
CELL = {
    "rate": 20.0, "slots": 4, "page_size": 8, "max_len": 32, "pages": 24,
    "temperature": 0.01, "trace_seconds": 0.5, "check_tokens": 20,
    "check_requests": 4, "max_logit_gap": 0.08,
}


def make_root(tmp: Path, *, mix=None, cell=None, config=None,
              name="tiny-chat") -> Path:
    """A checkout-like root holding one tiny cell ``name``."""
    root = Path(tmp)
    shutil.copytree(REPO / PKG / "metrics", root / PKG / "metrics")
    for d in ("configs", "traffic", "cells"):
        (root / PKG / d).mkdir(parents=True, exist_ok=True)
    config = dict(CONFIG, **(config or {}))
    (root / PKG / "configs" / "tiny-dense.json").write_text(
        json.dumps(config))
    (root / PKG / "traffic" / "tiny.json").write_text(
        json.dumps(mix or MIX))
    (root / PKG / "cells" / f"{name}.json").write_text(
        json.dumps(dict(CELL, **(cell or {}))))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-dense", "source": "test",
                         "file": f"{PKG}/configs/tiny-dense.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": name, "config": "tiny-dense",
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root

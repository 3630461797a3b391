"""qwen2.5-14b's tensor-parallel serving path at a tiny size, over four
virtual CPU devices, past the harness's look for a chip: QKV bias,
rope_theta 1e6, a query group of 5 on each shard (20 query and 4 KV
heads, one KV head and five query heads a shard), backlog traffic.
Prefill then paged decode through ``harness.run`` agrees with the plain
reference; with the QKV bias dropped in the program only (the reference
keeps it), it does not.  In a subprocess: the device count is fixed at
JAX's start."""

import json
import os
import subprocess
import sys
import textwrap

import tiny

SCRIPT = textwrap.dedent('''
    import json, sys, tempfile, time
    from pathlib import Path
    sys.path[:0] = [{src!r}, {root!r}, {here!r}]
    import tiny
    from benchmarks.chip import harness, spec
    from repro.models import layers

    waiting = json.loads(Path({backlog!r}).read_text())["waiting"]
    mix = dict(tiny.MIX, arrivals="backlog", waiting=waiting)
    cfg = dict(num_attention_heads=20, num_key_value_heads=4, head_dim=8,
               hidden_size=160, rope_theta=1e6, mesh=[1, 4])
    root = tiny.make_root(Path(tempfile.mkdtemp()), mix=mix, config=cfg,
                          cell=dict(rate=10.0))
    cell = spec.load(root, "tiny-chat")
    cell.chips = 4
    eng = harness.build(cell, 1)
    attn = eng.params["blocks"]["attn"]
    shards = dict(
        q=attn["wq"]["w"].sharding.shard_shape(attn["wq"]["w"].shape)[-1],
        kv=attn["wk"]["b"].sharding.shard_shape(attn["wk"]["b"].shape)[-1])
    del eng

    def run():
        res = harness.run(cell, 2**31 + 57, 3.0, False,
                          time.perf_counter(), root / ".bench_trace")
        return (res["correct"], res["checks"]["max_logit_gap"]["value"],
                res["attempted"])

    sound = run()
    dense = layers.dense
    layers.dense = lambda p, x: dense({{"w": p["w"]}}, x)
    print(json.dumps(dict(shards=shards, sound=sound, fault=run())))
''')


def test_qkv_bias_dropped_is_not_correct():
    here = os.path.dirname(os.path.abspath(__file__))
    code = SCRIPT.format(
        src=str(tiny.REPO / "src"), root=str(tiny.REPO), here=here,
        backlog=str(tiny.REPO / tiny.PKG / "traffic" / "backlog.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # each chip holds five query heads and one KV head of 8
    assert res["shards"] == {"q": 5 * 8, "kv": 8}
    assert res["sound"][0] is True, res
    assert res["sound"][2] > 0
    assert res["fault"][0] is False, res
    assert res["fault"][1] > tiny.CELL["max_logit_gap"]

"""A tensor-parallel run of a tiny cell over four virtual CPU devices,
past the harness's look for a chip: sound, it is correct; with the
exchange between chips left out (each chip keeps only its own partial
sum of the row-parallel projections, as a missing all-reduce would), it
is not.  In a subprocess: the device count is fixed at JAX's start."""

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
    import jax.numpy as jnp
    import tiny
    from benchmarks.chip import harness, spec
    from repro.models import layers

    mix = dict(tiny.MIX, arrivals="backlog", waiting=6)
    cfg = dict(num_attention_heads=8, num_key_value_heads=4, mesh=[1, 4])
    root = tiny.make_root(Path(tempfile.mkdtemp()), mix=mix, config=cfg,
                          cell=dict(rate=10.0))
    cell = spec.load(root, "tiny-chat")
    cell.chips = 4

    def run():
        res = harness.run(cell, 2**31 + 21, 3.0, False,
                          time.perf_counter(), root / ".bench_trace")
        return res["correct"], res["checks"]["max_logit_gap"]["value"]

    sound = run()
    dense = layers.dense
    row_inputs = (8 * 16, tiny.CONFIG["intermediate_size"])

    def local_partial_only(p, x):
        if p["w"].shape[0] in row_inputs:    # wo, down: row-parallel
            keep = jnp.arange(x.shape[-1]) < x.shape[-1] // 4
            x = jnp.where(keep, x, 0)
        return dense(p, x)

    layers.dense = local_partial_only
    print(json.dumps(dict(sound=sound, fault=run())))
''')


def test_exchange_left_out_is_not_correct():
    here = os.path.dirname(os.path.abspath(__file__))
    code = SCRIPT.format(src=str(tiny.REPO / "src"), root=str(tiny.REPO),
                         here=here)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sound"][0] is True
    assert res["fault"][0] is False
    assert res["fault"][1] > tiny.CELL["max_logit_gap"]

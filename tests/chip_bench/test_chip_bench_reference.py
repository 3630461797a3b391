"""The plain float32 reference against the program's own prefill followed
by decoding through its cache, at a reduced size on the CPU, on the
benchmark's seeded weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from benchmarks.chip import harness, weights
from benchmarks.chip.reference import dense


def program_logits(config, seed, tokens, n_prompt):
    """Logits the program gives at positions n_prompt - 1 .. len - 1:
    prefill of the prompt, then one cached decode step per token."""
    from repro.models import build_model
    from repro.serving import engine

    model = build_model(config["program_arch"], tp=1, use_kernels=False,
                        **harness.program_config(config),
                        dtype="float32", param_dtype="float32")
    cfg = dataclasses.replace(model.cfg, scan_layers=True)
    params = weights.program_params(model.init_shape(), config, seed)
    toks = jnp.asarray(tokens, jnp.int32)[None]
    logits, cache = engine.prefill(params, toks[:, :n_prompt], cfg=cfg,
                                   max_len=len(tokens))
    out = [logits[0]]
    for p in range(n_prompt, len(tokens)):
        logits, cache = engine.decode_step(params, cache, toks[:, p],
                                           jnp.int32(p), cfg=cfg)
        out.append(logits[0])
    return np.stack([np.asarray(o[:config["vocab_size"]]) for o in out])


@pytest.mark.parametrize("bias,arch", [(True, "qwen2.5-14b"),
                                       (False, "h2o-danube-3-4b")])
def test_reference_matches_prefill_then_cached_decode(bias, arch):
    config = dict(tiny.CONFIG, torch_dtype="float32", attention_bias=bias,
                  program_arch=arch)
    seed = 2**31 + 11
    tokens = np.random.default_rng(0).integers(0, 256, 24)
    got = program_logits(config, seed, tokens, 16)
    fwd = dense.Forward(config, seed)
    xs = fwd.hidden([list(tokens)], fp8=False)
    head = weights.global_weight(config, seed, "lm_head", jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(xs[0][15:24] @ head)
    assert got.shape == want.shape == (9, 256)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=2e-4 * scale)


def test_weights_served_and_reference_agree():
    """The served tree (all layers at once) and the reference's one layer
    read the same numbers."""
    from repro.models import build_model

    config = tiny.CONFIG
    model = build_model(config["program_arch"], tp=1, use_kernels=False,
                        **harness.program_config(config),
                        dtype="bfloat16", param_dtype="bfloat16")
    params = weights.program_params(model.init_shape(), config, 123)
    one = weights.layer_weights(config, 123, 1)
    np.testing.assert_array_equal(
        np.asarray(params["blocks"]["mlp"]["down"]["w"][1], np.float32),
        np.asarray(one["w_down"]))
    np.testing.assert_array_equal(
        np.asarray(params["blocks"]["attn"]["wk"]["b"][1], np.float32),
        np.asarray(one["bk"]))
    head = weights.global_weight(config, 123, "lm_head")
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"]["w"], np.float32), np.asarray(head))


def test_a_program_leaf_the_benchmark_cannot_name_is_refused():
    abstract = {"embed": {"table": jax.ShapeDtypeStruct((256, 64),
                                                        jnp.float32)},
                "mystery": {"w": jax.ShapeDtypeStruct((2,), jnp.float32)}}
    with pytest.raises(ValueError, match="mystery"):
        weights.program_params(abstract, tiny.CONFIG, 0)

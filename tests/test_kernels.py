"""Per-kernel validation: shape/dtype sweeps, kernel vs pure-jnp oracle.

Every Pallas kernel is exercised in interpret mode (CPU) over a grid of
shapes (aligned, unaligned, degenerate) and dtypes, asserting allclose
against ``repro.kernels.ref``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # bare jax+pytest env
    from _hypothesis_fallback import given, settings, st

from repro.core.softmax_api import SoftmaxAlgorithm
from repro.kernels import ops, ref

ALGOS = list(SoftmaxAlgorithm)
KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=5e-6, rtol=1e-5) if dtype == jnp.float32 else dict(
        atol=1e-2, rtol=1e-2)


class TestSoftmaxKernels:
    @pytest.mark.parametrize("algo", ALGOS)
    @pytest.mark.parametrize("shape", [
        (8, 128),          # single tile
        (16, 512),         # one row-block, multiple lanes
        (5, 1000),         # unaligned both dims
        (1, 131072),       # long row, many col tiles (out-of-VMEM regime)
        (300, 130),        # many rows, tiny cols
        (2, 3, 257),       # leading dims collapse
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, algo, shape, dtype):
        x = (jax.random.normal(KEY, shape) * 10).astype(dtype)
        got = ops.softmax(x, algorithm=algo)
        want = ref.softmax_ref(x)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **_tol(dtype))

    @pytest.mark.parametrize("cols", [2304, 3072])
    def test_prefill_width_past_2048_streams_columns(self, cols):
        """Prefill scores past the 2048 bucket: the resolved block is at
        most 2048 columns wide (what fits v5e's scoped VMEM), so the row
        streams over column blocks, and the result is still the softmax.
        The causal mask's -inf tail is kept."""
        from repro.kernels import registry

        assert registry.block_shapes("softmax", 40, cols)[1] <= 2048
        x = jax.random.normal(KEY, (40, cols)) * 8
        x = jnp.where(jnp.arange(cols) < cols - 100 - jnp.arange(40)[:, None],
                      x, -jnp.inf)
        np.testing.assert_allclose(np.asarray(ops.softmax(x)),
                                   np.asarray(jax.nn.softmax(x, axis=-1)),
                                   atol=5e-6)

    @pytest.mark.parametrize("algo", ALGOS)
    def test_block_shape_sweep(self, algo):
        """Meta-parameter sweep (the paper's auto-tuning axis): results must
        be identical across tilings."""
        x = jax.random.normal(KEY, (64, 2048)) * 8
        want = ref.softmax_ref(x)
        for br in (8, 32, 64):
            for bc in (128, 512, 2048):
                got = ops.softmax(x, algorithm=algo, block_rows=br,
                                  block_cols=bc)
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           atol=5e-6)

    def test_wide_dynamic_range_two_pass_only(self):
        """Rows whose exp() range exceeds f32: two-pass handles them without
        the max pass; values straddle 600 decades."""
        x = jnp.array([[-500.0, 0.0, 500.0] + [0.0] * 125], jnp.float32)
        got = ops.softmax(x, algorithm=SoftmaxAlgorithm.TWO_PASS)
        want = ref.softmax_ref(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)

    def test_neg_inf_mask_columns(self):
        x = jax.random.normal(KEY, (8, 256)) * 5
        x = x.at[:, 100:].set(-jnp.inf)
        for algo in ALGOS:
            got = ops.softmax(x, algorithm=algo)
            np.testing.assert_allclose(np.asarray(got[:, 100:]), 0.0)
            np.testing.assert_allclose(np.asarray(got.sum(-1)), 1.0,
                                       atol=1e-5)

    @given(st.integers(1, 64), st.integers(2, 700))
    @settings(max_examples=15, deadline=None)
    def test_property_random_shapes(self, rows, cols):
        x = jax.random.normal(jax.random.PRNGKey(rows * cols),
                              (rows, cols)) * 6
        got = ops.softmax(x, algorithm=SoftmaxAlgorithm.TWO_PASS)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.softmax_ref(x)), atol=5e-6)


class TestCrossEntropyKernel:
    @pytest.mark.parametrize("t,v", [(8, 128), (64, 1000), (3, 49152),
                                     (256, 512), (7, 131)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fwd_matches_oracle(self, t, v, dtype):
        logits = (jax.random.normal(KEY, (t, v)) * 5).astype(dtype)
        labels = jax.random.randint(jax.random.PRNGKey(1), (t,), 0, v)
        got = ops.cross_entropy(logits, labels)
        want = ref.cross_entropy_ref(logits, labels)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **_tol(dtype))

    @pytest.mark.parametrize("t,v", [(16, 512), (5, 1000)])
    def test_bwd_matches_oracle(self, t, v):
        logits = jax.random.normal(KEY, (t, v)) * 5
        labels = jax.random.randint(jax.random.PRNGKey(1), (t,), 0, v)
        dloss = jax.random.normal(jax.random.PRNGKey(2), (t,))
        got = jax.grad(
            lambda l: (ops.cross_entropy(l, labels) * dloss).sum())(logits)
        want = ref.cross_entropy_grad_ref(logits, labels, dloss)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-6)

    def test_grad_rows_sum_to_zero(self):
        """Each dlogits row sums to dloss_t * (sum p - 1) = 0."""
        logits = jax.random.normal(KEY, (32, 777)) * 8
        labels = jax.random.randint(jax.random.PRNGKey(3), (32,), 0, 777)
        g = jax.grad(lambda l: ops.cross_entropy(l, labels).sum())(logits)
        np.testing.assert_allclose(np.asarray(g.sum(-1)), 0.0, atol=1e-5)

    def test_extreme_logits(self):
        logits = jnp.array([[300.0, -300.0, 299.0, 0.0] * 32], jnp.float32)
        labels = jnp.array([0])
        got = float(ops.cross_entropy(logits, labels)[0])
        want = float(ref.cross_entropy_ref(logits, labels)[0])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_vs_jax_nn_logsoftmax(self):
        logits = jax.random.normal(KEY, (64, 4096)) * 4
        labels = jax.random.randint(jax.random.PRNGKey(5), (64,), 0, 4096)
        got = ops.cross_entropy(logits, labels)
        want = -jax.nn.log_softmax(logits)[jnp.arange(64), labels]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("b,h,sq,skv,d", [
        (1, 1, 128, 128, 64),
        (2, 4, 256, 256, 64),
        (1, 2, 200, 200, 128),     # unaligned seq
        (1, 1, 128, 384, 64),      # cross/decode: skv > sq
    ])
    def test_matches_oracle(self, causal, b, h, sq, skv, d):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, h, sq, d))
        k = jax.random.normal(ks[1], (b, h, skv, d))
        v = jax.random.normal(ks[2], (b, h, skv, d))
        got = ops.flash_attention(q, k, v, causal)
        want = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_sliding_window(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 2, 256, 64))
        k = jax.random.normal(ks[1], (1, 2, 256, 64))
        v = jax.random.normal(ks[2], (1, 2, 256, 64))
        got = ops.flash_attention(q, k, v, True, None, 64)
        want = ref.attention_ref(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_bf16(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64)).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(jnp.bfloat16)
        got = ops.flash_attention(q, k, v, True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=3e-2)

    def test_large_score_magnitudes_no_overflow(self):
        """Scores ~ +-1000: exp() overflows f32, the (m,n) path must not."""
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 1, 128, 64)) * 40
        k = jax.random.normal(ks[1], (1, 1, 128, 64)) * 40
        v = jax.random.normal(ks[2], (1, 1, 128, 64))
        got = ops.flash_attention(q, k, v, False, 1.0)  # scale=1: huge scores
        assert not bool(jnp.isnan(got).any() | jnp.isinf(got).any())
        want = ref.attention_ref(q, k, v, causal=False, scale=1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_grad_flows(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64))
        k = jax.random.normal(ks[1], (1, 2, 128, 64))
        v = jax.random.normal(ks[2], (1, 2, 128, 64))
        loss = lambda q_, k_, v_: ops.flash_attention(q_, k_, v_, True).sum()
        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        ref_loss = lambda q_, k_, v_: ref.attention_ref(
            q_, k_, v_, causal=True).sum()
        rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, r in ((gq, rq), (gk, rk), (gv, rv)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       atol=2e-5)

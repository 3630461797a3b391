"""Unit + property tests for the ExtExp / (m, n) monoid core (paper SS4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # bare jax+pytest env
    from _hypothesis_fallback import given, settings, st

from repro.core import numerics, twopass
from repro.core.numerics import ExtFloat, ext_add, ext_exp, ext_sum, ext_zero
from repro.core.softmax_api import SoftmaxAlgorithm, logsumexp, softmax

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# ExtExp: e^x == m * 2^n, m in [sqrt(2)/2, sqrt(2)], <2 ULP-ish accuracy.
# ---------------------------------------------------------------------------
class TestExtExp:
    def test_reconstruction_matches_exp(self):
        # Stay in the normal range: exp(-87) is subnormal and the paper
        # explicitly allows flush-to-zero there.
        x = jnp.linspace(-85.0, 87.0, 8192, dtype=jnp.float32)
        m, n = ext_exp(x)
        rec = m * jnp.exp2(n)
        np.testing.assert_allclose(rec, np.exp(np.asarray(x, np.float64)),
                                   rtol=1e-6)

    def test_mantissa_range(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (65536,)) * 200
        m, _ = ext_exp(x)
        # m = e^t, t in [-ln2/2, ln2/2] => m in [1/sqrt2, sqrt2] (small slack
        # for round-to-nearest on n and polynomial minimax error)
        assert float(m.min()) >= 0.7070
        assert float(m.max()) <= 1.4145

    def test_exponent_is_integral(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4096,)) * 50
        _, n = ext_exp(x)
        np.testing.assert_array_equal(np.asarray(n), np.round(np.asarray(n)))

    def test_no_overflow_anywhere(self):
        x = jnp.array([-3.4e38, -1e30, -1e5, -104.0, 0.0, 89.0, 1e5, 1e30,
                       3.4e38, jnp.inf, -jnp.inf], jnp.float32)
        m, n = ext_exp(x)
        assert not bool(jnp.isnan(m).any() | jnp.isinf(m).any())
        assert not bool(jnp.isnan(n).any() | jnp.isinf(n).any())

    def test_plain_exp_saturates_where_extexp_does_not(self):
        """The motivating failure (paper SS3): plain f32 exp over/underflows."""
        x = jnp.array([95.0, -110.0], jnp.float32)
        y = jnp.exp(x)
        assert bool(jnp.isinf(y[0])) and float(y[1]) == 0.0
        m, n = ext_exp(x)
        rec64 = np.asarray(m, np.float64) * 2.0 ** np.asarray(n, np.float64)
        np.testing.assert_allclose(rec64, np.exp(np.array([95.0, -110.0])),
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# (m, n) monoid algebra.
# ---------------------------------------------------------------------------
class TestMonoid:
    def test_identity(self):
        e = ext_exp(jnp.float32(3.7))
        z = ext_zero()
        for combined in (ext_add(e, z), ext_add(z, e)):
            v = combined.mantissa * jnp.exp2(combined.exponent)
            np.testing.assert_allclose(float(v), np.exp(3.7), rtol=1e-6)

    def test_commutative(self):
        a, b = ext_exp(jnp.float32(2.0)), ext_exp(jnp.float32(-40.0))
        ab, ba = ext_add(a, b), ext_add(b, a)
        assert float(ab.mantissa) == float(ba.mantissa)
        assert float(ab.exponent) == float(ba.exponent)

    @given(st.lists(st.floats(-80, 80, width=32), min_size=1, max_size=32))
    @settings(max_examples=50, deadline=None)
    def test_fold_matches_vectorized_sum(self, vals):
        """Sequential Alg-3 fold == max+rescale+sum vectorized reduction."""
        x = jnp.array(vals, jnp.float32)
        e = ext_exp(x)
        acc = ext_zero()
        for i in range(len(vals)):
            acc = ext_add(acc, ExtFloat(e.mantissa[i], e.exponent[i]))
        vec = ext_sum(e, axis=0)
        seq = float(acc.mantissa) * 2.0 ** (
            float(acc.exponent) - float(vec.exponent))
        np.testing.assert_allclose(seq, float(vec.mantissa), rtol=1e-5)

    @given(st.lists(st.floats(-200, 200, width=32), min_size=3, max_size=24),
           st.integers(1, 22))
    @settings(max_examples=50, deadline=None)
    def test_associativity_split(self, vals, split):
        """sum(A++B) == sum(A) + sum(B) up to FP rounding — the property that
        legalizes distributing pass 1 over tiles/lanes/mesh shards."""
        split = min(split, len(vals) - 1)
        x = jnp.array(vals, jnp.float32)
        whole = ext_sum(ext_exp(x), axis=0)
        left = ext_sum(ext_exp(x[:split]), axis=0)
        right = ext_sum(ext_exp(x[split:]), axis=0)
        merged = ext_add(left, right)
        v_whole = float(whole.mantissa) * 2.0 ** float(whole.exponent)
        v_merged = float(merged.mantissa) * 2.0 ** float(merged.exponent)
        np.testing.assert_allclose(v_merged, v_whole, rtol=1e-5)

    def test_power_of_two_scaling_is_exact(self):
        """2^k multiplication is error-free — the property DESIGN SS1 leans on.

        Note ``jnp.exp2`` is NOT exact on all backends (CPU lowers it through
        exp); :func:`numerics.exp2_int` reproduces the paper's exponent-field
        bit trick and is exact by construction.
        """
        m = jnp.float32(1.2345678)
        ks = jnp.arange(-126.0, 128.0, dtype=jnp.float32)
        scaled = m * numerics.exp2_int(ks)
        for k, s in zip(np.asarray(ks), np.asarray(scaled)):
            assert float(s) == float(m) * 2.0 ** float(k)


# ---------------------------------------------------------------------------
# Two-pass softmax vs references (paper Alg 3 vs Alg 1/2).
# ---------------------------------------------------------------------------
class TestTwoPassSoftmax:
    @pytest.mark.parametrize("algo", list(SoftmaxAlgorithm))
    @pytest.mark.parametrize("shape", [(8, 128), (3, 1000), (1, 49152),
                                       (2, 7, 333)])
    def test_matches_jax_nn(self, algo, shape):
        x = jax.random.normal(jax.random.PRNGKey(42), shape) * 12
        y = softmax(x, algorithm=algo)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(jax.nn.softmax(x, -1)),
                                   atol=2e-6)

    @pytest.mark.parametrize("algo", list(SoftmaxAlgorithm))
    def test_rows_sum_to_one(self, algo):
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 4096)) * 30
        y = softmax(x, algorithm=algo)
        np.testing.assert_allclose(np.asarray(y.sum(-1)), 1.0, atol=1e-5)

    def test_extreme_inputs_no_nan(self):
        x = jnp.array([[1e4, 1e4 - 1, -1e4], [-1e30, 0.0, 1e30],
                       [-jnp.inf, 0.0, 1.0], [3.4e38, -3.4e38, 0.0]],
                      jnp.float32)
        y = twopass.twopass_softmax(x)
        assert not bool(jnp.isnan(y).any())
        np.testing.assert_allclose(np.asarray(y.sum(-1)), 1.0, atol=1e-6)

    @given(st.floats(-1e4, 1e4))
    @settings(max_examples=30, deadline=None)
    def test_shift_invariance_parity(self, c):
        """softmax(x + c) stays in agreement with the max-subtracting
        reference on the *same shifted inputs* — the numerical stability the
        third pass exists to provide, without the third pass.  (Testing
        softmax(x) == softmax(x+c) directly would measure f32 input
        quantization at |c|~1e4, not the algorithm.)"""
        x = jax.random.normal(jax.random.PRNGKey(7), (4, 257)) * 3
        xs = x + jnp.float32(c)
        y = twopass.twopass_softmax(xs)
        ref = jax.nn.softmax(xs, axis=-1)
        # Cody-Waite reduced-argument error grows ~linearly in |n| ~ 1.44|x|:
        # exact to ~1e-6 for logits in the practical |x| <~ 300 domain, and
        # degrades gracefully (never catastrophically) beyond.
        atol = max(2e-5, abs(c) * 3e-8)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=atol)

    def test_bf16_inputs(self):
        x = (jax.random.normal(jax.random.PRNGKey(3), (4, 512)) * 8
             ).astype(jnp.bfloat16)
        y = twopass.twopass_softmax(x)
        assert y.dtype == jnp.bfloat16
        ref = jax.nn.softmax(x.astype(jnp.float32), -1).astype(jnp.bfloat16)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(ref, np.float32), atol=1e-2)

    def test_non_last_axis(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (6, 33, 4)) * 5
        y = softmax(x, axis=1, algorithm=SoftmaxAlgorithm.TWO_PASS)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(jax.nn.softmax(x, 1)), atol=2e-6)


class TestLogsumexp:
    @pytest.mark.parametrize("algo", list(SoftmaxAlgorithm))
    def test_matches_scipy(self, algo):
        x = jax.random.normal(jax.random.PRNGKey(11), (9, 777)) * 20
        got = logsumexp(x, algorithm=algo)
        want = jax.scipy.special.logsumexp(x, axis=-1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-5)

    def test_wide_dynamic_range(self):
        """lse of values whose exp() overflows f32 — only (m,n) survives."""
        x = jnp.array([[500.0, 499.0, -500.0]], jnp.float32)
        got = float(twopass.twopass_logsumexp(x)[0])
        want = 500.0 + np.log(1 + np.exp(-1.0))
        np.testing.assert_allclose(got, want, rtol=1e-6)

    @given(st.lists(st.floats(-300, 300, width=32), min_size=2, max_size=64))
    @settings(max_examples=40, deadline=None)
    def test_property_vs_float64(self, vals):
        x = jnp.array(vals, jnp.float32)[None, :]
        got = float(twopass.twopass_logsumexp(x)[0])
        v64 = np.asarray(x[0], np.float64)
        want = float(np.log(np.sum(np.exp(v64 - v64.max()))) + v64.max())
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-5)


class TestShardedCombine:
    """Distributed (m,n) combine == unsharded result (single-collective path)."""

    def test_sharded_softmax_matches_full(self):
        devs = jax.devices()
        if len(devs) < 1:
            pytest.skip("no devices")
        # Emulate the shard decomposition manually (associativity already
        # hypothesis-tested); here check the exact shard_map code path on a
        # 1-device mesh.
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1,), ("model",), devices=jax.devices()[:1])
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 256)) * 10
        fn = jax.shard_map(
            lambda xl: twopass.twopass_softmax_sharded(xl, "model"),
            mesh=mesh, in_specs=P(None, "model"), out_specs=P(None, "model"))
        np.testing.assert_allclose(np.asarray(fn(x)),
                                   np.asarray(jax.nn.softmax(x, -1)),
                                   atol=2e-6)

    def test_combine_partials_matches_monolithic(self):
        """Flash-decoding (o, m, n) partial combine (DESIGN SS2.4)."""
        key = jax.random.PRNGKey(9)
        k1, k2 = jax.random.split(key)
        s = jax.random.normal(k1, (2, 8, 64)) * 9     # scores [b,h,kv]
        v = jax.random.normal(k2, (2, 8, 64, 16))     # values [b,h,kv,d]
        ref = jnp.einsum("bhk,bhkd->bhd", jax.nn.softmax(s, -1), v)

        chunks = jnp.split(s, 4, axis=-1)
        vchunks = jnp.split(v, 4, axis=2)
        ms, ns, os_ = [], [], []
        for sc, vc in zip(chunks, vchunks):
            e = ext_exp(sc)
            st_ = ext_sum(e, axis=-1, keepdims=True)
            w = e.mantissa * jnp.exp2(e.exponent - st_.exponent)
            o = jnp.einsum("bhk,bhkd->bhd", w, vc)    # unnormalized / 2^n_loc
            ms.append(st_.mantissa[..., 0])
            ns.append(st_.exponent[..., 0])
            os_.append(o)
        m_star, n_star, o_star = twopass.ext_combine_partials(
            jnp.stack(ms), jnp.stack(ns), jnp.stack(os_))
        got = o_star / m_star[..., None]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=3e-5)

"""SoftmaxPolicy + kernel registry + autotune cache tests (ISSUE 1).

Covers: policy resolution (all three algorithms x kernel on/off x ragged
shapes that exercise the -inf padding path), config -> policy construction,
the collapsed block-shape model (overrides, alignment clamps, parity ops),
and the autotune cache round-trip (write, reload, hit).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import SoftmaxPolicy
from repro.core.softmax_api import SoftmaxAlgorithm
from repro.kernels import autotune, ref, registry

KEY = jax.random.PRNGKey(0)
ALGOS = list(SoftmaxAlgorithm)
# ragged shapes force col/row padding in the kernel path (-inf monoid zero)
RAGGED_SHAPES = [(5, 130), (3, 257), (7, 1000), (2, 3, 129)]


class TestPolicyResolution:
    @pytest.mark.parametrize("algo", ALGOS)
    @pytest.mark.parametrize("use_kernels", [False, True])
    @pytest.mark.parametrize("shape", RAGGED_SHAPES)
    def test_softmax_matches_oracle(self, algo, use_kernels, shape):
        pol = SoftmaxPolicy(algorithm=algo, use_kernels=use_kernels)
        x = jax.random.normal(KEY, shape) * 8
        np.testing.assert_allclose(np.asarray(pol.softmax(x)),
                                   np.asarray(ref.softmax_ref(x)),
                                   atol=5e-6)

    @pytest.mark.parametrize("use_kernels", [False, True])
    def test_masked_columns_neg_inf(self, use_kernels):
        """-inf mask columns (the attention padding path) stay exact."""
        pol = SoftmaxPolicy(use_kernels=use_kernels)
        x = jax.random.normal(KEY, (6, 200)) * 5
        x = x.at[:, 150:].set(-jnp.inf)
        y = pol.softmax(x)
        np.testing.assert_allclose(np.asarray(y[:, 150:]), 0.0)
        np.testing.assert_allclose(np.asarray(y.sum(-1)), 1.0, atol=1e-5)

    def test_non_last_axis_falls_back_to_jnp(self):
        pol = SoftmaxPolicy(use_kernels=True)
        x = jax.random.normal(KEY, (4, 8, 16))
        y = pol.softmax(x, axis=1)
        np.testing.assert_allclose(np.asarray(y.sum(1)), 1.0, atol=1e-5)

    @pytest.mark.parametrize("use_kernels", [False, True])
    def test_cross_entropy_matches_oracle(self, use_kernels):
        pol = SoftmaxPolicy(use_kernels=use_kernels)
        logits = jax.random.normal(KEY, (16, 777)) * 5
        labels = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 777)
        np.testing.assert_allclose(
            np.asarray(pol.cross_entropy(logits, labels)),
            np.asarray(ref.cross_entropy_ref(logits, labels)), atol=1e-5)

    def test_kernel_softmax_is_differentiable(self):
        """Kernel sites must train: analytic VJP over the Pallas forward."""
        pol = SoftmaxPolicy(use_kernels=True)
        x = jax.random.normal(KEY, (4, 260)) * 4
        w = jnp.arange(260.0)
        g = jax.grad(lambda t: (pol.softmax(t) * w).sum())(x)
        gr = jax.grad(lambda t: (ref.softmax_ref(t) * w).sum())(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=5e-5)

    def test_string_algorithm_coerced(self):
        assert SoftmaxPolicy(algorithm="three_pass_reload").algorithm \
            is SoftmaxAlgorithm.THREE_PASS_RELOAD

    def test_policy_is_hashable_and_frozen(self):
        p = SoftmaxPolicy()
        assert hash(p) == hash(SoftmaxPolicy())
        with pytest.raises(Exception):
            p.use_kernels = True


class TestConfigIntegration:
    def test_from_config_fields(self):
        cfg = get_config("granite-20b").reduced()
        import dataclasses

        cfg = dataclasses.replace(
            cfg, softmax_algorithm="three_pass_recompute", use_kernels=True,
            softmax_block_rows=16, softmax_autotune=True)
        pol = cfg.softmax_policy()
        assert pol.algorithm is SoftmaxAlgorithm.THREE_PASS_RECOMPUTE
        assert pol.use_kernels and pol.autotune
        assert pol.block_rows == 16 and pol.block_cols is None

    def test_sampler_resolves_through_policy(self):
        from repro.serving import engine

        cfg = get_config("granite-20b").reduced()
        logits = jax.random.normal(KEY, (3, cfg.vocab))
        t1 = engine.sample_token(logits, jax.random.PRNGKey(1), 1.0,
                                 cfg=cfg, vocab=cfg.vocab)
        t2 = engine.sample_token(
            logits, jax.random.PRNGKey(1), 1.0, vocab=cfg.vocab,
            policy=SoftmaxPolicy(algorithm="three_pass_reload",
                                 use_kernels=True))
        # same distribution, same key -> same samples across policies
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))

    def test_router_honors_kernel_switch(self):
        """MoE router previously dropped use_kernels (ISSUE satellite)."""
        from repro.models import moe as moe_mod

        cfg = get_config("granite-moe-3b-a800m").reduced()
        import dataclasses

        key = jax.random.PRNGKey(3)
        p = moe_mod.init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.d_model))
        outs = []
        for uk in (False, True):
            c = dataclasses.replace(cfg, use_kernels=uk)
            w, idx, probs = moe_mod._router(p, x, c)
            outs.append(np.asarray(probs))
        np.testing.assert_allclose(outs[0], outs[1], atol=5e-6)


class TestRegistryBlocks:
    def test_overrides_win(self):
        assert registry.block_shapes("softmax", 64, 2048, block_rows=16,
                                     block_cols=256,
                                     use_cache=False) == (16, 256)

    def test_alignment_clamped(self):
        br, bc = registry.block_shapes("softmax", 64, 2048, block_rows=5,
                                       block_cols=100, use_cache=False)
        assert br % 8 == 0 and bc % 128 == 0

    def test_former_heuristics_collapsed(self):
        """Parity with the three deleted per-site heuristics."""
        # ops._pick_blocks
        assert registry.block_shapes("softmax", 1, 131072,
                                     use_cache=False) == (8, 2048)
        assert registry.block_shapes("softmax", 300, 130,
                                     use_cache=False) == (256, 256)
        # ops._xent_blocks (cap 2048 regardless of width)
        assert registry.block_shapes("xent", 64, 49152,
                                     use_cache=False) == (64, 2048)
        assert registry.block_shapes("xent", 8, 131,
                                     use_cache=False) == (8, 256)
        # flash attention inline bq/bk
        assert registry.block_shapes("flash_attention", 200, 384,
                                     use_cache=False) == (128, 128)

    def test_unknown_op_raises(self):
        with pytest.raises(KeyError):
            registry.block_shapes("nope", 8, 128)

    def test_candidates_are_aligned_and_bounded(self):
        for br, bc in registry.candidate_blocks("softmax", 64, 8192):
            assert br % 8 == 0 and bc % 128 == 0
            assert 2 * 4 * br * bc <= 4 << 20


class TestAutotuneCache:
    def test_round_trip_write_reload_hit(self, tmp_path):
        cache = str(tmp_path / "tune.json")
        res = autotune.autotune_op(
            "softmax", 8, 256, candidates=[(8, 128), (8, 256)], reps=1,
            min_time_s=0.01, cache_file=cache)
        assert os.path.exists(cache)
        with open(cache) as f:
            data = json.load(f)
        assert res.cache_key in data
        assert data[res.cache_key]["block_rows"] == res.best[0]

        # reload from disk (fresh load, not the in-memory copy) and hit
        registry.load_cache(cache, force=True)
        hit = registry.block_shapes("softmax", 8, 256, use_cache=True,
                                    cache_file=cache)
        assert hit == res.best
        # nearby shape in the same pow-2 bucket hits the same entry
        near = registry.block_shapes("softmax", 7, 200, use_cache=True,
                                     cache_file=cache)
        assert near == res.best
        # miss path: different op keeps the heuristic
        spec = registry.get_spec("xent")
        assert registry.block_shapes("xent", 8, 256, use_cache=True,
                                     cache_file=cache) == \
            spec.heuristic_blocks(8, 256)

    def test_policy_autotune_flag_consults_cache(self, tmp_path):
        cache = str(tmp_path / "tune.json")
        registry.load_cache(cache, force=True)
        registry.record_tuned("softmax", 16, 256, jnp.float32, (16, 128),
                              path=cache)
        registry.load_cache(cache, force=True)
        on = SoftmaxPolicy(autotune=True, autotune_cache=cache)
        off = SoftmaxPolicy(autotune=False, autotune_cache=cache)
        assert on.resolve_blocks("softmax", 16, 256) == (16, 128)
        assert off.resolve_blocks("softmax", 16, 256) == \
            registry.get_spec("softmax").heuristic_blocks(16, 256)
        # bucket neighbor with fewer cols (1100 -> c2048 bucket): the tuned
        # tile clamps to the neighbor's own padded width instead of
        # inheriting the full-bucket-width tile
        registry.record_tuned("softmax", 64, 2048, jnp.float32, (64, 2048),
                              path=cache)
        assert on.resolve_blocks("softmax", 64, 2048) == (64, 2048)
        assert on.resolve_blocks("softmax", 64, 1100) == (64, 1152)
        # a tuned tile wider than 2048 columns clamps to 2048: a wider
        # softmax block outgrows v5e's scoped VMEM
        registry.record_tuned("softmax", 64, 4096, jnp.float32, (64, 4096),
                              path=cache)
        assert on.resolve_blocks("softmax", 64, 4096) == (64, 2048)
        assert on.resolve_blocks("softmax", 64, 2100) == (64, 2048)

    def test_tuned_blocks_still_exact(self, tmp_path):
        """Whatever the tuner picks, results must match the oracle."""
        cache = str(tmp_path / "tune.json")
        autotune.autotune_op("softmax", 16, 300,
                             candidates=[(8, 128), (16, 384)], reps=1,
                             min_time_s=0.01, cache_file=cache)
        registry.load_cache(cache, force=True)
        pol = SoftmaxPolicy(use_kernels=True, autotune=True,
                            autotune_cache=cache)
        x = jax.random.normal(KEY, (16, 300)) * 6
        np.testing.assert_allclose(np.asarray(pol.softmax(x)),
                                   np.asarray(ref.softmax_ref(x)),
                                   atol=5e-6)

    def teardown_method(self):
        # restore the default cache binding for other tests
        registry.load_cache(force=True)

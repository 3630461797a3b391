"""Quantized int8 KV pages + host-RAM swap tier tests: symmetric-absmax
round-trip bounds per scale granularity, fused-dequant paged-decode parity
(jnp and Pallas paths, shuffled and aliased page tables, poisoned pages
past the lengths left unread), equal-byte-budget capacity math (int8
admits >= 1.8x the page tokens), the bf16 default path staying
byte-for-byte untouched, bit-exact demote/promote through the swap tier,
the shared-page (refcount > 1) demote refusal, and the swap-vs-preempt
choice under page pressure."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as da
from repro.kernels import ops
from repro.models import build_model
from repro.serving import kv_cache
from repro.serving.scheduler import ContinuousBatchingEngine, Request

KEY = jax.random.PRNGKey(0)


def _quant_arena(key, pages, ps, h, d, granularity):
    """A random int8 page arena + fp32 scale sidecar at op-level shapes
    (no layer axis): arena [P, ps, H, D], scales [P, ps] or [P, ps, H]."""
    raw = jax.random.normal(key, (pages, ps, h, d))
    axes = (2, 3) if granularity == "page" else (3,)
    q, scale = kv_cache.quantize_symmetric(raw, axes)
    scale = scale.reshape((pages, ps) if granularity == "page"
                          else (pages, ps, h))
    deq = q.astype(jnp.float32) * (scale[..., None, None]
                                   if granularity == "page"
                                   else scale[..., None])
    return q, scale, deq


# ---------------------------------------------------------------------------
# quantize/dequantize round trip.
# ---------------------------------------------------------------------------
class TestRoundTrip:
    @pytest.mark.parametrize("granularity", ["page", "page_head"])
    def test_error_bounded_by_half_step(self, granularity):
        # symmetric absmax: |x - deq| <= scale/2 = absmax/254 per group
        x = jax.random.normal(KEY, (3, 8, 2, 16)) * 4.0
        axes = (2, 3) if granularity == "page" else (3,)
        q, scale = kv_cache.quantize_symmetric(x, axes)
        err = np.abs(np.asarray(x, np.float32)
                     - np.asarray(q, np.float32) * np.asarray(scale))
        assert (err <= np.asarray(scale) / 2 + 1e-6).all()

    def test_page_head_tighter_than_page(self):
        # per-head groups can only shrink the absmax, never grow it
        x = jax.random.normal(KEY, (4, 8, 4, 16))
        x = x * jnp.asarray([0.1, 1.0, 10.0, 100.0])[None, None, :, None]
        errs = {}
        for gran, axes in (("page", (2, 3)), ("page_head", (3,))):
            q, s = kv_cache.quantize_symmetric(x, axes)
            errs[gran] = float(np.abs(
                np.asarray(x, np.float32)
                - np.asarray(q, np.float32) * np.asarray(s)).mean())
        assert errs["page_head"] < errs["page"]

    def test_zero_rows_round_trip_exactly(self):
        q, scale = kv_cache.quantize_symmetric(jnp.zeros((2, 4, 2, 8)),
                                               (2, 3))
        assert (np.asarray(q) == 0).all()
        assert (np.asarray(scale) == 1.0).all()   # guard, not 0/0

    @pytest.mark.parametrize("granularity", ["page", "page_head"])
    def test_dequantize_pages_matches_manual(self, granularity):
        ls, pages, ps, h, d = 2, 3, 4, 2, 8
        raw = jax.random.normal(KEY, (ls, pages, ps, h, d))
        axes = (3, 4) if granularity == "page" else (4,)
        q, scale = kv_cache.quantize_symmetric(raw, axes)
        sshape = ((ls, pages, ps) if granularity == "page"
                  else (ls, pages, ps, h))
        kv = {"k": q, "v": q, "k_scale": scale.reshape(sshape),
              "v_scale": scale.reshape(sshape)}
        deq = kv_cache.dequantize_pages(kv, jnp.float32)
        assert set(deq) == {"k", "v"}              # scale leaves dropped
        want = q.astype(jnp.float32) * scale
        np.testing.assert_allclose(np.asarray(deq["k"]), np.asarray(want),
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# fused-dequant paged decode parity.
# ---------------------------------------------------------------------------
class TestFusedDequantOp:
    def setup_method(self, _):
        ks = jax.random.split(KEY, 2)
        self.s, self.h, self.g, self.d = 4, 2, 3, 16
        self.ps, self.pmax = 8, 4
        pages = 1 + self.s * self.pmax
        self.q = jax.random.normal(ks[0], (self.s, self.h, self.g, self.d))
        self.lengths = jnp.array([1, 9, 32, 0], jnp.int32)
        rng = np.random.default_rng(3)
        self.pt = jnp.asarray(rng.permutation(np.arange(1, pages))
                              [:self.s * self.pmax]
                              .reshape(self.s, self.pmax).astype(np.int32))
        self.key = ks[1]
        self.pages = pages

    @pytest.mark.parametrize("granularity", ["page", "page_head"])
    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_fused_matches_dequant_then_reference(self, granularity,
                                                  use_kernel):
        kq, ksc, kdeq = _quant_arena(self.key, self.pages, self.ps, self.h,
                                     self.d, granularity)
        vq, vsc, vdeq = _quant_arena(jax.random.fold_in(self.key, 1),
                                     self.pages, self.ps, self.h, self.d,
                                     granularity)
        want = ops.decode_attention_paged(self.q, kdeq, vdeq, self.pt,
                                          self.lengths, use_kernel=False)
        got = ops.decode_attention_paged(self.q, kq, vq, self.pt,
                                         self.lengths, k_scale=ksc,
                                         v_scale=vsc, use_kernel=use_kernel)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_aliased_table_rows(self):
        # two slots sharing pages (prefix sharing): the gather must read
        # the same scales for both readers
        kq, ksc, kdeq = _quant_arena(self.key, self.pages, self.ps, self.h,
                                     self.d, "page")
        vq, vsc, vdeq = _quant_arena(jax.random.fold_in(self.key, 1),
                                     self.pages, self.ps, self.h, self.d,
                                     "page")
        pt = np.asarray(self.pt).copy()
        pt[1] = pt[0]                              # slot 1 aliases slot 0
        pt = jnp.asarray(pt)
        lengths = jnp.array([17, 17, 5, 3], jnp.int32)
        want = ops.decode_attention_paged(self.q, kdeq, vdeq, pt, lengths,
                                          use_kernel=False)
        got = ops.decode_attention_paged(self.q, kq, vq, pt, lengths,
                                         k_scale=ksc, v_scale=vsc,
                                         use_kernel=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


    @pytest.mark.parametrize("granularity", ["page", "page_head"])
    @pytest.mark.parametrize("ppt", [1, 4])
    def test_pages_past_length_never_read(self, granularity, ppt):
        # every page backing no valid position gets saturated codes and
        # NaN scales: a page read past a length would reach the output
        # through 0 * NaN in the fused dequant
        kq, ksc, kdeq = _quant_arena(self.key, self.pages, self.ps, self.h,
                                     self.d, granularity)
        vq, vsc, vdeq = _quant_arena(jax.random.fold_in(self.key, 1),
                                     self.pages, self.ps, self.h, self.d,
                                     granularity)
        tab = np.asarray(self.pt)
        live = {int(tab[i, p]) for i, n in enumerate(np.asarray(self.lengths))
                for p in range(-(-int(n) // self.ps))}
        dead = np.array(sorted(set(range(1, self.pages)) - live))
        kq, vq = kq.at[dead].set(127), vq.at[dead].set(127)
        ksc, vsc = ksc.at[dead].set(jnp.nan), vsc.at[dead].set(jnp.nan)
        want = ops.decode_attention_paged(self.q, kdeq, vdeq, self.pt,
                                          self.lengths, use_kernel=False)
        got = np.asarray(da.decode_attention_paged_pallas(
            self.q, kq, vq, self.pt, self.lengths, ksc, vsc,
            scale=self.d ** -0.5, pages_per_tile=ppt))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_array_equal(got[3], 0.0)       # free slot


# ---------------------------------------------------------------------------
# pool construction + budget math.
# ---------------------------------------------------------------------------
class TestQuantPool:
    def setup_method(self, _):
        self.model = build_model("qwen2.5-14b", reduced=True, head_dim=32,
                                 dtype="bfloat16")
        self.cfg = self.model.cfg

    def test_resolve_page_quant(self):
        ps, gran = kv_cache.resolve_page_quant(self.cfg, 1024)
        assert ps > 0 and gran == "page"           # heuristic default
        assert kv_cache.resolve_page_quant(self.cfg, 1024, 32,
                                           "page_head") == (32, "page_head")
        with pytest.raises(ValueError, match="granularity"):
            kv_cache.resolve_page_quant(self.cfg, 1024, 32, "tensor")

    @pytest.mark.parametrize("granularity,sdims", [("page", 3),
                                                   ("page_head", 4)])
    def test_int8_pool_leaves(self, granularity, sdims):
        pool = kv_cache.init_paged_pool(self.cfg, 2, 64, page_size=16,
                                        page_dtype="int8",
                                        scale_granularity=granularity)
        kv = pool["kv"]
        assert kv["k"].dtype == jnp.int8 and kv["v"].dtype == jnp.int8
        assert kv["k_scale"].dtype == jnp.float32
        assert kv["k_scale"].ndim == sdims

    def test_default_pool_untouched(self):
        # page_dtype=None: the exact pre-quantization pool — no scale
        # leaves, arenas in the model's cache dtype
        pool = kv_cache.init_paged_pool(self.cfg, 2, 64, page_size=16)
        assert set(pool["kv"]) == {"k", "v"}
        assert pool["kv"]["k"].dtype == kv_cache.cache_dtype(self.cfg)

    def test_rejects_unquantizable(self):
        with pytest.raises(ValueError, match="page_dtype"):
            kv_cache.init_paged_pool(self.cfg, 2, 64, page_dtype="fp4")
        mla = build_model("deepseek-v2-lite-16b", reduced=True).cfg
        assert not kv_cache.supports_page_quant(mla)
        with pytest.raises(ValueError, match="int8"):
            kv_cache.init_paged_pool(mla, 2, 64, page_dtype="int8")
        hyb = build_model("hymba-1.5b", reduced=True).cfg
        assert not kv_cache.supports_page_quant(hyb)

    def test_equal_budget_admits_1p8x_tokens(self):
        # the tentpole capacity claim, as pure byte accounting: at one
        # fp32 scale per position the per-token arena bytes fall from
        # 2*2*Hkv*hd (bf16 k+v) to 2*(Hkv*hd + 4), and the same byte
        # budget must buy >= 1.8x the page tokens
        budget = kv_cache.slot_pool_bytes(self.cfg, 4, 64, 1)
        kw = dict(page_size=16, avg_tokens=16)
        _, pages_bf = kv_cache.paged_dims_in_budget(self.cfg, 64, budget, 1,
                                                    **kw)
        _, pages_q = kv_cache.paged_dims_in_budget(
            self.cfg, 64, budget, 1, page_dtype="int8",
            scale_granularity="page", **kw)
        assert (pages_q - 1) >= 1.8 * (pages_bf - 1)

    def test_pool_bytes_ordering(self):
        kw = dict(page_size=16, pages=9)
        b16 = kv_cache.paged_pool_bytes(self.cfg, 2, 64, 1, **kw)
        q_page = kv_cache.paged_pool_bytes(self.cfg, 2, 64, 1,
                                           page_dtype="int8",
                                           scale_granularity="page", **kw)
        q_head = kv_cache.paged_pool_bytes(self.cfg, 2, 64, 1,
                                           page_dtype="int8",
                                           scale_granularity="page_head",
                                           **kw)
        assert q_page < q_head < b16


# ---------------------------------------------------------------------------
# end-to-end serving: quantized engine + the bf16 default contract.
# ---------------------------------------------------------------------------
def _greedy_reqs(n, vocab, plen=8, new=8, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=tuple(rng.integers(1, vocab, plen)),
                    max_new_tokens=new) for i in range(n)]


class TestQuantServing:
    def setup_method(self, _):
        self.model = build_model("qwen2.5-14b", reduced=True, head_dim=32,
                                 dtype="bfloat16")
        self.params = self.model.init(jax.random.PRNGKey(0))
        self.vocab = self.model.cfg.vocab

    def _serve(self, **kw):
        eng = ContinuousBatchingEngine(self.model, self.params, slots=4,
                                       max_len=64, temperature=0.0, seed=1,
                                       **kw)
        comps = eng.run(_greedy_reqs(6, self.vocab))
        return eng, [tuple(c.tokens) for c in comps]

    def test_int8_engine_top1_agreement(self):
        _, bt = self._serve()
        eng, qt = self._serve(page_dtype="int8",
                              scale_granularity="page_head")
        assert eng.pool["kv"]["k"].dtype == jnp.int8
        matched = sum(a == b for x, y in zip(bt, qt) for a, b in zip(x, y))
        total = sum(len(x) for x in bt)
        assert matched / total >= 0.8, (matched, total)

    def test_strip_pool_rejects_int8(self):
        with pytest.raises(ValueError, match="paged"):
            ContinuousBatchingEngine(self.model, self.params, slots=2,
                                     max_len=64, paged=False,
                                     page_dtype="int8")

    def test_bf16_default_exact_strip_parity(self):
        # the bf16 paged path must stay EXACT (the int8 top-1 tolerance
        # never applies when page_dtype defaults): paged vs strip serve
        # identical greedy tokens
        _, paged_toks = self._serve()
        _, strip_toks = self._serve(paged=False)
        assert paged_toks == strip_toks


# ---------------------------------------------------------------------------
# host-RAM swap tier.
# ---------------------------------------------------------------------------
class TestSwapTier:
    def setup_method(self, _):
        self.model = build_model("qwen2.5-14b", reduced=True)
        self.params = self.model.init(jax.random.PRNGKey(0))
        self.vocab = self.model.cfg.vocab

    def _engine(self, **kw):
        kw.setdefault("prefix_cache", False)
        return ContinuousBatchingEngine(
            self.model, self.params, slots=3, max_len=128, page_size=16,
            pages=1 + 9, temperature=0.0, seed=1, **kw)

    def _overload(self, plen=48, new=16, n=5):
        rng = np.random.default_rng(7)
        return [Request(rid=i, prompt=tuple(rng.integers(1, self.vocab,
                                                         plen)),
                        max_new_tokens=new) for i in range(n)]

    def test_restore_slot_is_bit_exact(self):
        # kv_cache-level: gather a slot's pages into a host blob (what
        # _demote captures), scatter them into FRESH pages via
        # restore_slot_paged — the restored bytes must be identical, int8
        # pages and fp32 scale sidecars included
        cfg = self.model.cfg
        pool = kv_cache.init_paged_pool(cfg, 2, 64, page_size=16,
                                        page_dtype="int8",
                                        scale_granularity="page")
        rng = np.random.default_rng(5)
        pool["kv"] = {
            n_: jnp.asarray(
                rng.integers(-127, 128, leaf.shape).astype(np.int8)
                if leaf.dtype == jnp.int8
                else rng.random(leaf.shape).astype(np.float32))
            for n_, leaf in pool["kv"].items()}
        trash = kv_cache.TRASH_PAGE
        src = np.array([1, 2, 3, trash], np.int32)   # 40 tok + table pad
        dst = np.array([4, 5, 6, trash], np.int32)
        blob = {n_: np.asarray(jax.device_get(leaf[:, src]))
                for n_, leaf in pool["kv"].items()}
        copy_row = np.where(dst == trash, trash, dst).astype(np.int32)
        out = kv_cache.restore_slot_paged(pool, blob, 1, 40, dst,
                                          copy_row=copy_row)
        for n_, leaf in out["kv"].items():
            assert leaf.dtype == pool["kv"][n_].dtype
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(leaf[:, dst[:3]])),
                blob[n_][:, :3])
        assert int(np.asarray(out["lengths"])[1]) == 40
        np.testing.assert_array_equal(np.asarray(out["page_table"])[1], dst)

    def test_swap_token_parity_and_stats(self):
        ep = self._engine()
        pt = [tuple(c.tokens) for c in ep.run(self._overload())]
        es = self._engine(host_swap_bytes=1 << 30)
        st = [tuple(c.tokens) for c in es.run(self._overload())]
        assert st == pt                            # byte-exact round trip
        assert es.stats["demoted"] > 0
        assert es.stats["prefetched"] == es.stats["demoted"]
        assert es.stats["preempted"] == 0          # swap chosen first
        assert ep.stats["preempted"] > 0
        assert es.host_swap.bytes_used == 0        # fully drained

    def test_tiny_swap_budget_falls_back_to_preempt(self):
        eng = self._engine(host_swap_bytes=8)      # nothing fits
        eng.run(self._overload())
        assert eng.stats["demoted"] == 0
        assert eng.stats["preempted"] > 0

    def test_shared_pages_refuse_demotion(self):
        eng = self._engine(host_swap_bytes=1 << 30)
        eng.submit(Request(rid=0, prompt=tuple(range(1, 33)),
                           max_new_tokens=8))
        eng._admit_arrived(0.0)       # prefill only — no burst, no retire
        slot = eng.active_slots()[0]
        # a second reader appears (prefix index / another slot's table row)
        eng.allocator.share(eng.slot_pages[slot][:1])
        assert not eng._demote(slot, 0.0)          # rc > 1: must refuse
        assert eng.stats["demoted"] == 0
        eng.allocator.free(eng.slot_pages[slot][:1])

    def test_prefix_cache_pins_pages_preempt_fallback(self):
        # with the prefix index holding references, whole-slot demotion is
        # refused and pressure falls back to preemption — shared prefix
        # bytes never leave the arena while referenced
        eng = self._engine(prefix_cache=True, host_swap_bytes=1 << 30)
        eng.run(self._overload())
        assert eng.stats["demoted"] == 0
        assert eng.stats["preempted"] > 0

    def test_swap_rejects_strip_and_hybrid(self):
        with pytest.raises(ValueError, match="paged"):
            ContinuousBatchingEngine(self.model, self.params, slots=2,
                                     max_len=64, paged=False,
                                     host_swap_bytes=1 << 20)
        hyb = build_model("hymba-1.5b", reduced=True)
        hp = hyb.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="hybrid"):
            ContinuousBatchingEngine(hyb, hp, slots=2, max_len=64,
                                     prefix_cache=False,
                                     host_swap_bytes=1 << 20)

    def test_host_swap_store_budget(self):
        store = kv_cache.HostSwapStore(100)
        blob = {"k": np.zeros((2, 3, 4), np.int8)}          # 24 bytes
        assert store.put(1, blob) and store.bytes_used == 24
        assert not store.put(1, blob)                        # dup rid
        assert store.put(2, blob) and store.put(3, blob)
        assert not store.put(4, {"k": np.zeros(40, np.int8)})  # over budget
        store.pop(2)
        assert store.bytes_used == 48 and 2 not in store

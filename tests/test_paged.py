"""Paged KV cache tests: the decode_attention_paged registry op, the page
arena / page-table pool (adopt, free, allocator, budgeting), and the
scheduler's paged edge cases (page-capacity rejection, EOS-frees-pages,
preemption, bucketed prefill).  Paged-vs-lockstep token parity is the
per-family matrix in test_family_parity.py; allocator/refcount invariants
under random action sequences are test_serving_invariants.py."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, registry
from repro.models import build_model
from repro.serving import engine, kv_cache
from repro.serving.scheduler import ContinuousBatchingEngine, Request

KEY = jax.random.PRNGKey(0)


def _paged_copy(k, v, n_slots, pmax, ps, seed=0):
    """Scatter contiguous [S, H, T, D] K/V into a shuffled page arena;
    returns (k_pages, v_pages, page_table)."""
    s, h, t, d = k.shape
    pages = 1 + n_slots * pmax
    rng = np.random.default_rng(seed)
    pt = rng.permutation(np.arange(1, pages))[:s * pmax].reshape(s, pmax)
    kp = np.zeros((pages, ps, h, d), np.float32)
    vp = np.zeros((pages, ps, h, d), np.float32)
    for i in range(s):
        for p in range(pmax):
            kp[pt[i, p]] = np.asarray(
                k[i, :, p * ps:(p + 1) * ps]).transpose(1, 0, 2)
            vp[pt[i, p]] = np.asarray(
                v[i, :, p * ps:(p + 1) * ps]).transpose(1, 0, 2)
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt, jnp.int32)


# ---------------------------------------------------------------------------
# decode_attention_paged op.
# ---------------------------------------------------------------------------
class TestPagedDecodeOp:
    def setup_method(self, _):
        ks = jax.random.split(KEY, 3)
        self.s, self.h, self.g, self.d = 5, 2, 3, 16
        self.ps, self.pmax = 8, 6
        t = self.ps * self.pmax
        self.q = jax.random.normal(ks[0], (self.s, self.h, self.g, self.d))
        self.k = jax.random.normal(ks[1], (self.s, self.h, t, self.d))
        self.v = jax.random.normal(ks[2], (self.s, self.h, t, self.d))
        self.lengths = jnp.array([1, 7, 48, 0, 23], jnp.int32)
        self.kp, self.vp, self.pt = _paged_copy(self.k, self.v, self.s,
                                                self.pmax, self.ps)

    def test_matches_contiguous_op(self):
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths)
        got = ops.decode_attention_paged(self.q, self.kp, self.vp, self.pt,
                                         self.lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        assert not np.isnan(np.asarray(got)).any()   # incl. length-0 slot

    def test_window_and_chunking(self):
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                    window=6)
        for bs, bt in ((None, None), (8, 8), (8, 16), (16, 128)):
            got = ops.decode_attention_paged(
                self.q, self.kp, self.vp, self.pt, self.lengths, window=6,
                block_s=bs, block_t=bt)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=f"{bs},{bt}")

    def test_trash_entries_invisible(self):
        """Pages past a slot's length may point anywhere (here: another
        slot's live page) without leaking into the output."""
        pt = np.asarray(self.pt).copy()
        pt[0, 1:] = pt[2, :self.pmax - 1]            # slot 0 len=1: covered
        got = ops.decode_attention_paged(self.q, self.kp, self.vp,
                                         jnp.asarray(pt), self.lengths)
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_registry_resolution_and_autotune(self):
        assert "decode_attention_paged" in registry.registered_ops()
        with tempfile.TemporaryDirectory() as td:
            cf = td + "/cache.json"
            res = autotune.autotune_op("decode_attention_paged", 8, 256,
                                       reps=1, min_time_s=0.005,
                                       cache_file=cf)
            registry.load_cache(cf, force=True)
            hit = registry.block_shapes("decode_attention_paged", 8, 256,
                                        use_cache=True, cache_file=cf)
            assert hit == res.best


# ---------------------------------------------------------------------------
# Page-size resolution + pool mechanics.
# ---------------------------------------------------------------------------
class TestPagedPool:
    def test_page_size_resolution_chain(self):
        cfg = build_model("qwen2.5-14b", reduced=True).cfg
        assert kv_cache.resolve_page_size(cfg, 4096) == 128   # heuristic
        assert kv_cache.resolve_page_size(cfg, 24) == 32      # tiny pool
        assert kv_cache.resolve_page_size(cfg, 4096, 64) == 64  # explicit
        with tempfile.TemporaryDirectory() as td:
            cf = td + "/cache.json"
            registry.record_tuned("kv_page", 1, 4096, jnp.bfloat16, (1, 64),
                                  path=cf)
            _, ps = registry.block_shapes("kv_page", 1, 4096, jnp.bfloat16,
                                          use_cache=True, cache_file=cf)
            assert ps == 64                                   # cache hit

    def test_adopt_free_allocator_roundtrip(self):
        m = build_model("qwen2.5-14b", reduced=True)
        cfg = m.cfg
        params = m.init(KEY)
        ps, max_len = 8, 32
        npp = kv_cache.pages_per_slot(max_len, ps)
        pool = kv_cache.init_paged_pool(cfg, 2, max_len, page_size=ps)
        alloc = kv_cache.PageAllocator(1 + 2 * npp)
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 11), 0,
                                  cfg.vocab)
        _, cache = engine.prefill(params, toks, cfg=cfg, max_len=16)
        need = 2                                     # ceil(11 / 8)
        ids = alloc.alloc(need)
        row = jnp.zeros((npp,), jnp.int32).at[:need].set(jnp.asarray(ids))
        pool = kv_cache.adopt_slot_paged(pool, cache, 1, 11, row)
        assert pool["lengths"].tolist() == [0, 11]
        # gather back through the table == the prefilled strip
        got = pool["kv"]["k"][:, pool["page_table"][1]]
        got = got.reshape(cfg.n_layers, npp * ps, cfg.n_kv_heads, -1)
        np.testing.assert_allclose(
            np.asarray(got[:, :11], np.float32),
            np.asarray(cache["k"][:, 0, :11], np.float32), atol=1e-6)
        pool = kv_cache.free_slot_paged(pool, 1)
        assert pool["lengths"].tolist() == [0, 0]
        assert pool["page_table"][1].tolist() == [kv_cache.TRASH_PAGE] * npp
        alloc.free(ids)
        assert alloc.free_pages == alloc.usable_pages
        assert alloc.alloc(100) is None              # too big: nothing taken
        assert alloc.free_pages == alloc.usable_pages

    def test_ssm_not_pageable(self):
        cfg = build_model("rwkv6-1.6b", reduced=True).cfg
        assert not kv_cache.supports_paging(cfg)
        with pytest.raises(ValueError, match="no pageable cache"):
            kv_cache.init_paged_pool(cfg, 2, 32)

    def test_paged_dims_fit_budget_and_oversubscribe(self):
        cfg = build_model("qwen2.5-14b", reduced=True).cfg
        max_len = 256
        budget = kv_cache.slot_pool_bytes(cfg, 4, max_len)
        slots, pages = kv_cache.paged_dims_in_budget(
            cfg, max_len, budget, page_size=16, avg_tokens=max_len // 4)
        assert (kv_cache.paged_pool_bytes(cfg, slots, max_len, page_size=16,
                                          pages=pages) <= budget)
        # the acceptance claim: >= 2x the strip concurrency, page-backed
        per_req = -(-(max_len // 4) // 16)
        assert min(slots, (pages - 1) // per_req) >= 2 * 4


# ---------------------------------------------------------------------------
# Scheduler edge cases (the satellite checklist).
# ---------------------------------------------------------------------------
class TestPagedScheduler:
    def setup_method(self, _):
        self.m = build_model("qwen2.5-14b", reduced=True)
        self.params = self.m.init(KEY)

    def test_prompt_beyond_pool_capacity_rejected_not_wedged(self):
        eng = ContinuousBatchingEngine(self.m, self.params, slots=1,
                                       max_len=64, page_size=8, pages=3)
        with pytest.raises(ValueError, match="needs 5 pages"):
            eng.run([Request(rid=0, prompt=tuple(range(1, 41)),
                             max_new_tokens=2)])
        # the engine is not wedged: a pool-sized request still serves
        comps = eng.run([Request(rid=1, prompt=(1, 2, 3),
                                 max_new_tokens=2)])
        assert [c.rid for c in comps] == [1]
        # all pages back except those the prefix index retains (evictable)
        assert (eng.allocator.free_pages + eng.prefix_cache.n_pages
                == eng.allocator.usable_pages)

    def test_eos_on_first_decoded_token_frees_pages_immediately(self):
        probe = ContinuousBatchingEngine(self.m, self.params, slots=1,
                                         max_len=32, temperature=0.0,
                                         page_size=8, seed=5)
        first = probe.run([Request(rid=0, prompt=(1, 2, 3),
                                   max_new_tokens=4)])[0].tokens[0]
        eng = ContinuousBatchingEngine(self.m, self.params, slots=2,
                                       max_len=32, temperature=0.0,
                                       page_size=8, seed=5, eos_token=first)
        comp = eng.run([Request(rid=0, prompt=(1, 2, 3),
                                max_new_tokens=4)])[0]
        assert comp.reason == "eos" and len(comp.tokens) == 1
        assert eng.stats["steps"] == 0           # retired from prefill
        # the slot's references dropped; only the prefix index still holds
        # the prompt's page (refcount 1 = evictable, not leaked)
        assert (eng.allocator.free_pages + eng.prefix_cache.n_pages
                == eng.allocator.usable_pages)
        assert int(eng.pool["lengths"][comp.slot]) == 0
        assert (eng.pool["page_table"][comp.slot].tolist()
                == [kv_cache.TRASH_PAGE] * eng.pages_per_slot)

    def test_paged_and_strip_identical_tokens_at_equal_budget(self):
        budget = kv_cache.slot_pool_bytes(self.m.cfg, 3, 48)

        def serve(paged):
            eng = ContinuousBatchingEngine(
                self.m, self.params, max_len=48, temperature=0.0, seed=7,
                memory_budget_bytes=budget, paged=paged, page_size=8,
                avg_tokens_hint=16)
            rng = np.random.default_rng(3)
            reqs = [Request(rid=i,
                            prompt=tuple(rng.integers(0, self.m.cfg.vocab,
                                                      int(rng.integers(
                                                          3, 12)))),
                            max_new_tokens=6) for i in range(6)]
            return eng, [tuple(c.tokens) for c in eng.run(reqs)]

        peng, ptoks = serve(True)
        seng, stoks = serve(False)
        assert peng.n_slots > seng.n_slots       # same bytes, more requests
        assert ptoks == stoks                    # identical tokens

    def test_preemption_requeues_and_completes(self):
        # 6 usable pages of 8: two 28-token requests (4 pages each) cannot
        # coexist — the younger one is preempted, requeued, and still
        # produces its full token budget.
        eng = ContinuousBatchingEngine(self.m, self.params, slots=2,
                                       max_len=32, seed=2, page_size=8,
                                       pages=7, temperature=0.0)
        comps = eng.run([Request(rid=i, prompt=tuple(range(1, 9)),
                                 max_new_tokens=20) for i in range(2)])
        assert eng.stats["preempted"] >= 1
        for c in comps:
            assert c.reason == "max_tokens" and len(c.tokens) == 20
            assert c.prompt_len == 8             # carried tokens folded back
        assert (eng.allocator.free_pages + eng.prefix_cache.n_pages
                == eng.allocator.usable_pages)
        # preemption must not change WHAT is generated (recompute path)
        ref = ContinuousBatchingEngine(self.m, self.params, slots=2,
                                       max_len=32, seed=2, page_size=8,
                                       temperature=0.0)
        rcomps = ref.run([Request(rid=i, prompt=tuple(range(1, 9)),
                                  max_new_tokens=20) for i in range(2)])
        assert [c.tokens for c in comps] == [c.tokens for c in rcomps]

    def test_decode_page_counters(self):
        """``decode_pages_read`` sums ceil(length / page_size) over the
        active slots at every decode step, against the whole table in
        ``decode_pages_table``; both reset with the other stats."""
        eng = ContinuousBatchingEngine(self.m, self.params, slots=3,
                                       max_len=48, page_size=8,
                                       temperature=0.0, seed=6)
        ps = eng.page_size
        device_pages = []
        step = eng._step

        def spy(params, pool, toks, key, mask):
            out = step(params, pool, toks, key, mask)
            lens = np.asarray(out[1]["lengths"])[np.asarray(mask)]
            device_pages.append(sum(-(-int(n) // ps) for n in lens))
            return out

        eng._step = spy
        rng = np.random.default_rng(5)
        reqs = [Request(rid=i,
                        prompt=tuple(rng.integers(1, self.m.cfg.vocab,
                                                  int(rng.integers(3, 20)))),
                        max_new_tokens=4 + 3 * i) for i in range(5)]
        comps = eng.run(reqs)
        # the step that samples tokens[k] (k >= 1) attends over
        # prompt_len + k positions
        want = sum(-(-(c.prompt_len + k) // ps)
                   for c in comps for k in range(1, len(c.tokens)))
        st = eng.stats
        assert st["decode_pages_read"] == want == sum(device_pages) > 0
        assert st["decode_pages_table"] == (eng.n_slots * eng.pages_per_slot
                                            * st["steps"])
        assert st["decode_pages_read"] <= st["decode_pages_table"]
        assert eng.throughput()["decode_pages_read"] == want
        eng.reset_stats()
        assert eng.stats["decode_pages_read"] == 0
        assert eng.stats["decode_pages_table"] == 0

    def test_bucketed_prefill_bounds_compiles(self):
        eng = ContinuousBatchingEngine(self.m, self.params, slots=2,
                                       max_len=64, page_size=16,
                                       temperature=0.0, seed=9)
        assert eng.buckets == (16, 32, 64)
        rng = np.random.default_rng(1)
        reqs = [Request(rid=i,
                        prompt=tuple(rng.integers(0, self.m.cfg.vocab,
                                                  3 + i * 4)),
                        max_new_tokens=3) for i in range(8)]  # plens 3..31
        comps = eng.run(reqs)
        assert len(comps) == 8
        # 8 distinct prompt lengths, but only their buckets compiled
        assert eng.throughput()["prefill_compiles"] <= 2
        # bucketed logits must match an exact-length (unbucketed) prefill
        exact = ContinuousBatchingEngine(self.m, self.params, slots=2,
                                         max_len=64, page_size=16,
                                         temperature=0.0, seed=9,
                                         prefill_buckets=None)
        ecomps = exact.run([Request(rid=r.rid, prompt=r.prompt,
                                    max_new_tokens=3) for r in reqs])
        assert [c.tokens for c in comps] == [c.tokens for c in ecomps]

    def test_hybrid_pages_attention_half(self):
        m = build_model("hymba-1.5b", reduced=True)
        params = m.init(KEY)
        eng = ContinuousBatchingEngine(m, params, slots=2, max_len=32,
                                       page_size=8, temperature=0.0)
        assert eng.paged and eng.buckets is None  # ssm half: no bucketing
        comps = eng.run([Request(rid=i, prompt=(1, 2, 3, 4),
                                 max_new_tokens=4) for i in range(3)])
        assert len(comps) == 3
        strip = ContinuousBatchingEngine(m, params, slots=2, max_len=32,
                                         paged=False, temperature=0.0)
        scomps = strip.run([Request(rid=i, prompt=(1, 2, 3, 4),
                                    max_new_tokens=4) for i in range(3)])
        assert [c.tokens for c in comps] == [c.tokens for c in scomps]

    def test_ssm_falls_back_to_strip(self):
        m = build_model("rwkv6-1.6b", reduced=True)
        params = m.init(KEY)
        eng = ContinuousBatchingEngine(m, params, slots=2, max_len=24)
        assert not eng.paged
        with pytest.raises(ValueError, match="no pageable cache"):
            ContinuousBatchingEngine(m, params, slots=2, max_len=24,
                                     paged=True)
        comps = eng.run([Request(rid=0, prompt=(1, 2, 3),
                                 max_new_tokens=3)])
        assert len(comps[0].tokens) == 3

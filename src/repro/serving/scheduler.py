"""Continuous-batching request scheduler over a fixed pool of cache slots.

The serving shape that matters for the paper's bandwidth argument is decode:
one query token per sequence against its whole KV cache, softmax included —
memory-bound at any realistic batch size (Intel's Xeon study, arXiv:1904.12380),
so throughput comes from keeping the batch axis FULL, not from more FLOPs.
A fixed-batch ``generate`` loop can't do that: the whole batch decodes in
lockstep until its slowest member finishes, and no new request can join
until everyone is done.

This module schedules instead:

  * a fixed pool of ``slots`` cache slots — PAGED by default
    (``kv_cache.init_paged_pool``): a shared arena of fixed-size pages plus
    a per-slot page table, so capacity is bounded by total tokens in
    flight, not ``slots × max_len``.  Families without a position-addressed
    cache (ssm) fall back to the slot-major strip pool
    (``kv_cache.init_slot_pool``),
  * requests join by *prefilling into a free slot* (admission) — paged
    admission also requires ``ceil(prompt / page_size)`` free arena pages,
  * prompt lengths are BUCKETED to a small set of padded sizes (multiples
    of the page size, doubling up to ``max_len``) so admission compiles
    once per bucket instead of once per distinct prompt length; logits are
    read at the true last token, and the pad tail is invisible behind the
    pool's length mask.  Families whose prefill carries recurrent state
    (ssm, hybrid) prefill unpadded — padding would pollute the state,
  * one jitted ragged decode step (``engine.decode_step_ragged``) advances
    every occupied slot per iteration, whatever its age — no per-sequence
    recompilation, mixed positions in one call,
  * prompt prefixes already resident in the page arena are SHARED
    (``serving/prefix_cache.py``): admission matches the prompt against a
    radix index of token-block chains, adopts matched pages by reference
    (refcounted — ``PageAllocator.share``), and prefills only the
    unmatched tail (``engine.prefill_extend``); the first divergent or
    partially-filled page is copy-on-write.  Retired prompts stay indexed
    (evictable, LRU) until page pressure reclaims them,
  * decode-time page growth is allocated just before each burst; on
    OOM-pages the latest-admitted request is PREEMPTED — its pages are
    recycled and it is requeued with prompt = original prompt + tokens so
    far (recompute on readmission, the classic paged-serving eviction) —
    and a lone request that cannot grow retires with reason
    ``"oom_pages"``,
  * slots are freed on EOS / max-tokens / cache-full and immediately
    backfilled from the queue between decode steps.

Host state (which request owns which slot/pages, emitted tokens) stays in
Python; device state (cache arenas + page tables + lengths) stays a
jit-threaded pytree.
"""

from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.distributed import autoshard
from repro.distributed import sharding as dist_sharding
from repro.models import transformer
from repro.serving import engine, kv_cache
from repro.serving.prefix_cache import PrefixCache
from repro.serving.spans import Spans

# families whose prefill is position-local: a pad tail past the true
# prompt cannot influence earlier positions, so it stays invisible behind
# the length mask and prompts can be bucketed.  hybrid carries ssm state
# through prefill (padding would pollute the state); moe's capacity
# dispatch sizes expert capacity from the PADDED length and drops tokens
# against it, so pad tokens can displace real ones — both families must
# see exact-length prompts.  encdec's decoder prefill is position-local
# too (causal self-attention; cross-attention is per-position over the
# encoder states), so its decoder prompts bucket like dense.
_BUCKETABLE_FAMILIES = ("dense", "vlm", "encdec")


def _round_up(x: int, mult: int) -> int:
    return -(-int(x) // int(mult)) * int(mult)


def _pin_cache(cache, cfg, mesh):
    """Constrain a fresh batch=1 prefill cache to the arena's head-sharded
    layout (``sharding.prefill_cache_specs``) so admission's page copy
    into the (head-sharded) pool is shard-local, not an all-gather."""
    if mesh is None:
        return cache
    sh = dist_sharding.named(
        dist_sharding.prefill_cache_specs(cache, cfg, mesh), mesh)
    return jax.tree.map(jax.lax.with_sharding_constraint, cache, sh)


@dataclass
class Request:
    """One generation request.

    ``arrival_s`` is the offer time as an offset from ``run()`` start —
    honored against the wall clock when any pending request has a
    positive one (open-loop traffic), else everything is offered at t=0.
    ``resumed`` marks a requeue after a page preemption: its prompt is
    the ORIGINAL prompt plus the tokens generated before eviction
    (recompute on readmission), and admission failures retire it with
    what it produced instead of raising.  ``frames`` (encdec only) are
    the request's encoder frame embeddings ``[T_enc, d_model]``; they
    travel with the request through preemption so readmission can
    re-encode.  ``queued_ns`` is stamped by ``submit()`` (or the requeue)
    on ``time.perf_counter_ns``: the start of its queue wait and TTFT.
    """
    rid: int
    prompt: tuple[int, ...]            # prompt token ids
    max_new_tokens: int = 32
    arrival_s: float = 0.0             # offset from ``run()`` start
    resumed: bool = False              # requeued after a page preemption
    frames: np.ndarray | None = None   # encdec: [T_enc, d_model] embeddings
    queued_ns: int | None = field(default=None, repr=False)

    def __post_init__(self):
        self.prompt = tuple(int(t) for t in self.prompt)
        if not self.prompt:
            raise ValueError(f"request {self.rid}: empty prompt")


@dataclass
class Completion:
    """A finished request: its sampled tokens + scheduling timeline.

    ``reason``: ``"max_tokens"`` (budget reached), ``"eos"`` (the
    configured eos token was sampled), ``"cache_full"`` (the sequence hit
    ``max_len``), or ``"oom_pages"`` (a lone request the page arena could
    not grow — it keeps whatever it generated).  ``seq`` is the admission
    order; preemption evicts the HIGHEST seq (LIFO — the youngest request
    has the least sunk prefill+decode work to recompute).  Tokens
    generated before a preemption are folded back in (`_merge_carried`),
    so a completion is always one uninterrupted stream.
    """
    rid: int
    slot: int
    prompt_len: int
    max_new_tokens: int
    tokens: list[int] = field(default_factory=list)
    admitted_s: float = 0.0
    finished_s: float = 0.0
    reason: str = ""         # "max_tokens" | "eos" | "cache_full" | "oom_pages"
    seq: int = 0             # admission order (preemption picks the latest)
    ttft_s: float | None = None   # wall seconds offer -> first token (the
    #                               headline metric prefix sharing moves);
    #                               survives preemption (first admission's).
    #                               Offer = the submit() stamp, or under
    #                               run()/stream() the arrival if later


class ContinuousBatchingEngine:
    """Slot-based continuous batching for one model + parameter set.

    ``paged`` defaults to "auto": the paged pool wherever the family's
    cache is position-addressed, the strip pool otherwise (ssm).  ``slots``
    may be given directly, or derived from ``memory_budget_bytes`` — for a
    strip pool via ``kv_cache.max_slots_in_budget``; for a paged pool the
    budget buys *pages*, and the slot count is sized so concurrency matches
    ``avg_tokens_hint`` tokens per request (default ``max_len // 2``) —
    the oversubscription that lets a paged pool serve more concurrent
    requests than strips at the same byte budget.

    ``mesh`` (a ('data', 'model') mesh, see ``launch.make_serving_mesh``)
    runs the whole device path SHARDED: params tensor-parallel
    (``param_specs(fsdp=False)``), the pool per ``sharding.pool_specs``
    (arena KV heads over ``model``), every jitted fn pinned with
    ``out_shardings`` so the layout survives each step.  Admission and
    scheduling stay host-side and unchanged — page tables and lengths are
    replicated.  ``memory_budget_bytes`` is interpreted PER SHARD: with
    the KV heads split ``tp`` ways the same per-device budget buys
    ``kv_shard_factor``x the pages.  A 1-device mesh degenerates to the
    unsharded path (same layouts, trivial placements).
    """

    def __init__(self, model, params, *, slots: int | None = None,
                 max_len: int = 256, temperature: float = 1.0,
                 eos_token: int | None = None, seed: int = 0,
                 memory_budget_bytes: int | None = None,
                 moe_impl: str = "dispatch", paged: bool | str = "auto",
                 page_size: int | None = None, pages: int | None = None,
                 prefill_buckets="auto", avg_tokens_hint: int | None = None,
                 prefix_cache: bool | str = "auto", mesh=None,
                 page_dtype: str | None = None,
                 scale_granularity: str | None = None,
                 host_swap_bytes: int | None = None,
                 max_cross_len: int | None = None,
                 enc_chunk: int | None = None):
        cfg = model.cfg
        self.mesh = mesh
        if paged == "auto":
            paged = kv_cache.supports_paging(cfg)
        elif paged and not kv_cache.supports_paging(cfg):
            raise ValueError(f"family {cfg.family!r} has no pageable cache")
        if cfg.family == "encdec" and not paged:
            raise ValueError(
                "encdec serving needs the paged pool: the encoder's "
                "cross-KV lives as read-only arena pages (cross_table); "
                "the strip pool has nowhere to put it")
        self.paged = bool(paged)
        self.max_len = int(max_len)
        # encdec: bound on a request's encoder frames (its cross pages are
        # sized/validated against this); chunked admission encodes
        # ``enc_chunk`` frames per scheduler step so one long request
        # cannot head-of-line-block admission (each window is encoded
        # independently — streaming-window semantics; None = whole-sequence
        # encode, bit-identical to the lockstep oracle).
        self.max_cross_len = int(max_cross_len or max_len)
        self.enc_chunk = int(enc_chunk) if enc_chunk else None
        if enc_chunk is not None and cfg.family != "encdec":
            raise ValueError("enc_chunk only applies to the encdec family")
        self.page_dtype = page_dtype
        self.scale_granularity: str | None = None
        if page_dtype is not None:
            if not self.paged:
                raise ValueError(
                    "page_dtype needs a paged pool (the slot-strip pool "
                    "stays full-precision)")
            if not kv_cache.supports_page_quant(cfg):
                raise ValueError(
                    f"family {cfg.family!r} has no quantizable page arena "
                    "(mla latents and hybrid ssm state keep full precision)")
            self.page_size, self.scale_granularity = kv_cache.\
                resolve_page_quant(cfg, max_len, page_size, scale_granularity)
        else:
            self.page_size = (kv_cache.resolve_page_size(cfg, max_len,
                                                         page_size)
                              if self.paged else None)

        if slots is None:
            if memory_budget_bytes is None:
                raise ValueError("pass slots= or memory_budget_bytes=")
            if mesh is not None:
                # the budget is per-shard bytes: head-sharded arenas store
                # 1/tp of every page per device, so the global pool the
                # same per-device bytes can back is tp x larger
                memory_budget_bytes *= dist_sharding.kv_shard_factor(cfg,
                                                                     mesh)
            if self.paged:
                slots, pages = kv_cache.paged_dims_in_budget(
                    cfg, max_len, memory_budget_bytes, model.tp,
                    page_size=self.page_size,
                    avg_tokens=avg_tokens_hint or max(1, max_len // 2),
                    page_dtype=page_dtype,
                    scale_granularity=self.scale_granularity)
                if slots < 1 or pages < 2:
                    raise ValueError(
                        f"memory budget {memory_budget_bytes} fits no usable "
                        f"paged pool at max_len {max_len}")
            else:
                slots = kv_cache.max_slots_in_budget(
                    cfg, max_len, memory_budget_bytes, model.tp)
                if slots < 1:
                    raise ValueError(
                        f"memory budget {memory_budget_bytes} fits 0 slots "
                        f"of max_len {max_len}")
        self.model = model
        self.cfg = cfg
        if mesh is not None:
            # serving params: TP over ``model``, replicated over data (no
            # FSDP — read-only weights would all-gather every step)
            params = jax.device_put(params, dist_sharding.named(
                dist_sharding.param_specs(params, cfg, mesh, fsdp=False),
                mesh))
        self.params = params
        self.n_slots = int(slots)
        self.temperature = temperature
        self.eos_token = eos_token
        self.key = jax.random.PRNGKey(seed)

        if self.paged:
            self.pages_per_slot = kv_cache.pages_per_slot(self.max_len,
                                                          self.page_size)
            self.cross_pages_per_slot = (
                kv_cache.pages_per_slot(self.max_cross_len, self.page_size)
                if cfg.family == "encdec" else 0)
            if pages is None:
                pages = 1 + self.n_slots * (self.pages_per_slot
                                            + self.cross_pages_per_slot)
            self.pool = kv_cache.init_paged_pool(
                cfg, self.n_slots, self.max_len, model.tp,
                page_size=self.page_size, pages=int(pages), mesh=mesh,
                page_dtype=page_dtype,
                scale_granularity=self.scale_granularity,
                cross_len=(self.max_cross_len if cfg.family == "encdec"
                           else None))
            self.allocator = kv_cache.PageAllocator(int(pages))
            self.slot_pages: list[list[int]] = [[] for _ in
                                                range(self.n_slots)]
            self.slot_cross_pages: list[list[int]] = [[] for _ in
                                                      range(self.n_slots)]
        else:
            make = functools.partial(kv_cache.init_slot_pool, cfg,
                                     self.n_slots, self.max_len, model.tp)
            self.pool = (make() if mesh is None
                         else kv_cache.shard_pool(make, cfg, mesh))

        # host-RAM swap tier: under page pressure a cold slot's pages move
        # to host RAM (bit-exact, scale sidecars included) instead of being
        # preempted-and-recomputed; promotion scatters them back.  See
        # _demote / _promote_swapped.
        self.host_swap: kv_cache.HostSwapStore | None = None
        self._swapped: dict[int, dict] = {}
        if host_swap_bytes is not None:
            if not self.paged:
                raise ValueError("host_swap_bytes needs a paged pool")
            if cfg.family == "hybrid":
                raise ValueError(
                    "host swap does not cover the hybrid family: its "
                    "recurrent ssm state is slot-major, not paged, and "
                    "would be lost at demotion")
            if cfg.family == "encdec":
                raise ValueError(
                    "host swap does not cover the encdec family yet: the "
                    "demotion blob gathers only the slot's self-KV page "
                    "row, so its cross pages would be stranded")
            self.host_swap = kv_cache.HostSwapStore(int(host_swap_bytes))

        self.buckets = self._resolve_buckets(prefill_buckets)
        self._moe_impl = moe_impl

        # prefix sharing: radix index over the page arena ("auto" = on
        # wherever exact tail prefill is possible — see _prefix_shareable)
        self.prefix_cache: PrefixCache | None = None
        shareable = self._prefix_shareable()
        if prefix_cache == "auto":
            prefix_cache = shareable
        if prefix_cache:
            if not shareable:
                raise ValueError(
                    f"prefix_cache=True: family {cfg.family!r} "
                    f"(moe_impl {moe_impl!r}, paged {self.paged}) cannot "
                    "share prefixes — ssm/hybrid carry recurrent prefill "
                    "state and moe capacity dispatch couples tokens "
                    "across the sequence; use prefix_cache='auto'")
            self.prefix_cache = PrefixCache(self.allocator, self.page_size)

        # Sampling is fused INTO the jitted step/prefill: the sampler is a
        # softmax site (resolves through the config's SoftmaxPolicy) and
        # dispatching it eagerly costs more than the whole decode step at
        # serving batch sizes.
        def _fused_decode(params, pool, tokens, key, active):
            key, sub = jax.random.split(key)      # key evolves device-side
            logits, new_pool = engine.decode_step_ragged(
                params, pool, tokens, cfg=cfg, tp=model.tp,
                moe_impl=moe_impl, active=active)
            tok = engine.sample_token(logits, sub, temperature, cfg=cfg,
                                      vocab=cfg.vocab)
            return tok.astype(jnp.int32), new_pool, key

        # Pool-returning jits are pinned with ``out_shardings`` under a
        # mesh: the arena layout must survive every step or XLA would be
        # free to re-lay the pool out (resharding the whole arena) per
        # call.  Tokens/keys are tiny and stay replicated.  Every
        # pool-returning jit donates the pool: the arena is updated in place
        # instead of copied per call, so neither a run-ahead burst nor a
        # round of admissions holds one arena version per in-flight call.
        if mesh is not None:
            pool_sh = dist_sharding.named(
                dist_sharding.pool_specs(self.pool, cfg, mesh), mesh)
            rep = NamedSharding(mesh, PartitionSpec())
            self._step = self._with_mesh(jax.jit(
                _fused_decode, out_shardings=(rep, pool_sh, rep),
                donate_argnums=(1,)))
        else:
            pool_sh = None
            self._step = jax.jit(_fused_decode, donate_argnums=(1,))
        # prefill jits are cached per cache-allocation length (one compile
        # per prompt bucket); see _prefill_fn.  Tail prefills (prefix hits)
        # cache per (allocation, tail-bucket) pair — see _extend_fn.
        self._prefill_fns: dict[int, object] = {}
        self._extend_fns: dict[tuple, object] = {}
        self._prefill_shapes: set[tuple] = set()
        pool_kw = dict(donate_argnums=(0,))
        if pool_sh is not None:
            pool_kw["out_shardings"] = pool_sh
        if self.paged:
            self._adopt = self._with_mesh(
                jax.jit(kv_cache.adopt_slot_paged, **pool_kw))
            self._free = self._with_mesh(
                jax.jit(kv_cache.free_slot_paged, **pool_kw))
            self._set_row = self._with_mesh(
                jax.jit(kv_cache.set_page_row, **pool_kw))
            self._restore = self._with_mesh(
                jax.jit(kv_cache.restore_slot_paged, **pool_kw))
            if cfg.family == "encdec":
                self._adopt_encdec = self._with_mesh(
                    jax.jit(kv_cache.adopt_slot_encdec, **pool_kw))
                # one jit; recompiles per frame-count shape (chunked
                # admission keeps chunk shapes fixed at enc_chunk + one
                # tail length per distinct T_enc % enc_chunk)
                self._encode = self._with_mesh(jax.jit(functools.partial(
                    transformer.encode, cfg=cfg, tp=model.tp)))
        else:
            self._adopt = self._with_mesh(
                jax.jit(kv_cache.adopt_slot, **pool_kw))
            self._free = self._with_mesh(
                jax.jit(kv_cache.free_slot, **pool_kw))

        # host-side authoritative state
        self.slot_owner: list[Completion | None] = [None] * self.n_slots
        self.slot_req: list[Request | None] = [None] * self.n_slots
        self.next_tok = np.zeros((self.n_slots,), np.int64)
        self.pending: list[Request] = []
        self.completions: list[Completion] = []
        # encdec chunked admission: slot -> in-flight encode state (pages
        # already reserved, encoder windows still running).  The slot is
        # neither free nor active until the encode completes.
        self._encoding: dict[int, dict] = {}
        self._carried: dict[int, tuple[int, list[int], float | None]] = {}
        self._admit_seq = 0
        self._run_start: float | None = None
        # host-side phase spans (serving/spans.py); off unless a caller
        # sets ``spans.on``
        self.spans = Spans()
        # phase-separated throughput accounting (the satellite ask: a single
        # aggregate hides which phase the bandwidth argument is about)
        self.stats = dict(prefill_tokens=0, prefill_s=0.0, decode_tokens=0,
                          decode_s=0.0, steps=0, admitted=0, preempted=0,
                          peak_pages=0, prefix_hits=0, prefix_tokens_reused=0,
                          cow_copies=0, prefix_evictions=0, demoted=0,
                          prefetched=0, decode_pages_read=0,
                          decode_pages_table=0)

    # -- mesh plumbing -------------------------------------------------------
    def _with_mesh(self, fn):
        """Run ``fn`` inside the serving mesh's ``autoshard.hints`` context
        (identity without a mesh).  The hints in the model's ragged decode
        path — and the shard_map kernel dispatch in ``kernels.ops`` — bake
        in at TRACE time, so every jitted serving fn must be CALLED under
        the context, not merely created under it."""
        if self.mesh is None:
            return fn
        mesh = self.mesh

        def wrapped(*args):
            with autoshard.hints(mesh):
                return fn(*args)

        return wrapped

    # -- prefill buckets -----------------------------------------------------
    def _resolve_buckets(self, prefill_buckets):
        """Padded prompt lengths admission compiles for.  None = exact
        lengths (recurrent-state families, or an explicit opt-out)."""
        if prefill_buckets is None or prefill_buckets is False:
            return None
        if prefill_buckets == "auto":
            if self.cfg.family not in _BUCKETABLE_FAMILIES:
                return None
            base = self.page_size or kv_cache.resolve_page_size(
                self.cfg, self.max_len)
            bs, b = [], base
            while b < self.max_len:
                bs.append(b)
                b *= 2
            bs.append(self.max_len)
            return tuple(sorted(set(bs)))
        bs = tuple(sorted(int(b) for b in prefill_buckets))
        if not bs or bs[-1] < self.max_len:
            raise ValueError("prefill_buckets must cover max_len "
                             f"(got {bs}, max_len {self.max_len})")
        return bs

    def _bucket_for(self, plen: int) -> int:
        if self.buckets is None:
            return plen
        return next(b for b in self.buckets if b >= plen)

    def _prefill_fn(self, alloc_len: int):
        """Jitted fused prefill+sample for one cache-allocation length
        (strip pools always use ``max_len``; paged pools allocate the
        bucket rounded up to whole pages)."""
        fn = self._prefill_fns.get(alloc_len)
        if fn is None:
            cfg, tp, moe_impl = self.cfg, self.model.tp, self._moe_impl
            temperature, mesh = self.temperature, self.mesh

            if cfg.family == "encdec":
                # decoder-side prefill over already-encoded frames: the
                # encoder ran separately (possibly chunk-by-chunk across
                # scheduler steps) so ``enc`` arrives as an argument.
                def _fused_prefill(params, enc, prompt, key, last_pos):
                    logits, cache = engine.prefill_with_encoder(
                        params, enc, prompt, cfg=cfg, tp=tp,
                        max_len=alloc_len, last_pos=last_pos)
                    tok = engine.sample_token(logits, key, temperature,
                                              cfg=cfg, vocab=cfg.vocab)
                    return tok.astype(jnp.int32), _pin_cache(cache, cfg,
                                                             mesh)
            else:
                def _fused_prefill(params, prompt, key, last_pos):
                    logits, cache = engine.prefill(
                        params, prompt, cfg=cfg, tp=tp, max_len=alloc_len,
                        moe_impl=moe_impl, last_pos=last_pos)
                    tok = engine.sample_token(logits, key, temperature,
                                              cfg=cfg, vocab=cfg.vocab)
                    return tok.astype(jnp.int32), _pin_cache(cache, cfg,
                                                             mesh)

            fn = self._with_mesh(jax.jit(_fused_prefill))
            self._prefill_fns[alloc_len] = fn
        return fn

    # -- prefix sharing ------------------------------------------------------
    def _prefix_shareable(self) -> bool:
        """Whether a prompt tail can prefill EXACTLY after cached prefix
        pages.  Needs (a) a paged pool and (b) position-local prefill:
        ssm/hybrid carry recurrent state through the prompt (a tail cannot
        be replayed from K/V pages alone) and moe's capacity dispatch
        sizes expert queues from the whole sequence (prefix tokens compete
        with tail tokens for capacity, so splitting the prompt changes
        which tokens drop).  dense/vlm always qualify; moe qualifies under
        the per-token ``moe_impl="dense"`` path."""
        if not self.paged:
            return False
        if self.cfg.family in ("dense", "vlm"):
            return True
        return self.cfg.family == "moe" and self._moe_impl == "dense"

    def _extend_fn(self, alloc_len: int, tail_len: int):
        """Jitted fused tail-prefill+sample for one (cache allocation,
        padded tail) shape pair: gathers the matched prefix pages out of
        the arena, prefills only the unmatched tail after them (traced
        start position), samples at the true last token."""
        key = (alloc_len, tail_len)
        fn = self._extend_fns.get(key)
        if fn is None:
            cfg, tp, moe_impl = self.cfg, self.model.tp, self._moe_impl
            temperature, mesh = self.temperature, self.mesh

            def _fused_extend(params, kv, gather_row, tokens, start, key,
                              last_idx):
                logits, cache = engine.prefill_extend(
                    params, tokens, kv, gather_row, start, cfg=cfg, tp=tp,
                    moe_impl=moe_impl, last_pos=last_idx)
                tok = engine.sample_token(logits, key, temperature, cfg=cfg,
                                          vocab=cfg.vocab)
                return tok.astype(jnp.int32), _pin_cache(cache, cfg, mesh)

            fn = self._with_mesh(jax.jit(_fused_extend))
            self._extend_fns[key] = fn
        return fn

    def _plan_prefix(self, prompt, alloc_len: int):
        """Match ``prompt`` against the radix index and fit a padded tail
        after it inside ``alloc_len``: the smallest tail bucket ``B`` such
        that ``min(matched, alloc_len - B)`` matched tokens plus ``B``
        tail positions cover the prompt (tail writes may never spill past
        the allocation — they would wrap into matched pages).  A match is
        trimmed when the winning bucket leaves room for only part of it.
        Returns ``(match, matched_tokens, tail_bucket)`` or
        ``(None, 0, 0)`` when nothing (usable) is cached."""
        ps = self.page_size
        match = self.prefix_cache.match(prompt)
        m = match.matched_tokens(ps)
        plen = len(prompt)
        if m <= 0:
            return None, 0, 0
        if self.buckets is None:
            return match, m, plen - m
        for b in self.buckets:
            use = min(m, alloc_len - b)
            if use > 0 and plen - use <= b:
                if use < m:
                    match = match.trim(ps, use)
                return match, use, b
        return None, 0, 0

    def _alloc_pages(self, n: int):
        """``allocator.alloc`` with prefix-cache backpressure: on a miss,
        evict least-recently-matched UNREFERENCED cached prefix pages
        (refcount 1 — the index is their only reader) and retry.  Cached
        pages a live slot shares stay pinned."""
        ids = self.allocator.alloc(n)
        if ids is None and self.prefix_cache is not None:
            freed = self.prefix_cache.evict(n - self.allocator.free_pages)
            if freed:
                self.stats["prefix_evictions"] += freed
                ids = self.allocator.alloc(n)
        return ids

    # -- request intake ------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue ``req``; requests that can NEVER be served are rejected
        here, before they can wedge the queue (head-of-line admission would
        otherwise retry them forever)."""
        plen = len(req.prompt)
        if plen + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {plen} + "
                f"{req.max_new_tokens} new tokens exceeds max_len "
                f"{self.max_len}")
        need = self._pages_for(plen) if self.paged else 0
        if self.cfg.family == "encdec":
            if req.frames is None:
                raise ValueError(
                    f"request {req.rid}: encdec requests need frames")
            t_enc = int(req.frames.shape[0])
            if t_enc > self.max_cross_len:
                raise ValueError(
                    f"request {req.rid}: {t_enc} encoder frames exceed "
                    f"max_cross_len {self.max_cross_len}")
            need += self._pages_for(t_enc)
        if self.paged and need > self.allocator.usable_pages:
            raise ValueError(
                f"request {req.rid}: prompt {plen} needs "
                f"{need} pages; the pool has "
                f"{self.allocator.usable_pages} (page_size {self.page_size})")
        req.queued_ns = time.perf_counter_ns()
        self.pending.append(req)
        self.pending.sort(key=lambda r: r.arrival_s)

    def free_slots(self) -> list[int]:
        """Slots with no owner — admission targets, backfilled between
        decode bursts (host-side view; the device-side marker is
        ``lengths[slot] == 0``).  Slots mid-way through a chunked encode
        are reserved (pages held, not yet decoding) and excluded."""
        return [i for i, o in enumerate(self.slot_owner)
                if o is None and i not in self._encoding]

    def active_slots(self) -> list[int]:
        """Slots currently owned by an in-flight request (the rows the
        next ragged burst advances)."""
        return [i for i, o in enumerate(self.slot_owner) if o is not None]

    # -- paged bookkeeping ---------------------------------------------------
    def _pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size)

    def _dev_len(self, slot: int) -> int:
        """Positions the slot holds on the device before its next decode
        step writes one more (write-then-attend: that step attends over
        ``_dev_len + 1``)."""
        comp = self.slot_owner[slot]
        return comp.prompt_len + len(comp.tokens) - 1

    def _page_row(self, slot: int) -> np.ndarray:
        # np, not jnp: jitted callees take host arrays through the C++
        # dispatch fast path; an eager device_put per row costs more than
        # the call it feeds
        row = np.full((self.pages_per_slot,), kv_cache.TRASH_PAGE, np.int32)
        ids = self.slot_pages[slot]
        row[:len(ids)] = ids
        return row

    def _cross_row(self, slot: int) -> np.ndarray:
        """The slot's cross-table row (encdec): its cross pages,
        trash-padded to the fixed table width like :meth:`_page_row`."""
        row = np.full((self.cross_pages_per_slot,), kv_cache.TRASH_PAGE,
                      np.int32)
        ids = self.slot_cross_pages[slot]
        row[:len(ids)] = ids
        return row

    def _note_peak(self) -> None:
        used = self.allocator.usable_pages - self.allocator.free_pages
        self.stats["peak_pages"] = max(self.stats["peak_pages"], used)

    def _release_slot(self, slot: int) -> None:
        """Free device slot + (paged) its arena pages."""
        self.slot_owner[slot] = None
        self.slot_req[slot] = None
        if self.paged:
            self.allocator.free(self.slot_pages[slot])
            self.slot_pages[slot] = []
            if self.slot_cross_pages[slot]:
                self.allocator.free(self.slot_cross_pages[slot])
                self.slot_cross_pages[slot] = []
        self.pool = self._free(self.pool, np.int32(slot))

    # -- admission: prefill into a free slot ---------------------------------
    def _offered_ns(self, req: Request) -> int:
        """When ``req`` was offered, on ``time.perf_counter_ns``: its submit
        or requeue stamp, or under run()/stream() its arrival if that is
        later.  Its queue wait and its TTFT both start here."""
        t = req.queued_ns
        if self._run_start is not None:
            t = max(t, int((self._run_start + req.arrival_s) * 1e9))
        return t

    def _admit_span(self, rid: int, n: int = 0):
        """A ``sched.admit`` span, ``stalled`` when some slot was decoding
        as it began: that slot's next token waits for the admission."""
        return self.spans.span(
            "sched.admit", rid=rid, n=n,
            stalled=self.spans.on and bool(self.active_slots()))

    def _dequeued(self, req: Request, admit) -> None:
        """Record the queue wait that ``admit`` (the open ``sched.admit``
        span that takes ``req``) ends."""
        if self.spans.on:
            self.spans.record("sched.queue", self._offered_ns(req),
                              admit.start_ns, rid=req.rid)

    def _admit(self, req: Request, slot: int, now: float) -> bool:
        """Prefill ``req`` into ``slot``.  Returns False (nothing consumed)
        when the page pool cannot back the prompt right now."""
        with self._admit_span(req.rid) as admit:
            if self.cfg.family == "encdec":
                return self._admit_encdec(req, slot, now, admit)
            return self._admit_prefill(req, slot, now, admit)

    def _admit_prefill(self, req: Request, slot: int, now: float,
                       admit) -> bool:
        plen = len(req.prompt)
        if plen + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {plen} + "
                f"{req.max_new_tokens} new tokens exceeds max_len "
                f"{self.max_len}")
        bucket = self._bucket_for(plen)
        alloc_len = (_round_up(bucket, self.page_size) if self.paged
                     else self.max_len)
        page_ids = None
        match, m_tok, tail_bucket = None, 0, 0
        if self.paged:
            need = self._pages_for(plen)
            if need > self.allocator.usable_pages:
                if req.resumed:
                    # a preempted request regrew past pool capacity: retire
                    # it with what it generated rather than crashing the run
                    self._finalize_oom(req, now)
                    return True
                raise ValueError(
                    f"request {req.rid}: prompt {plen} needs {need} pages; "
                    f"the pool has {self.allocator.usable_pages} "
                    f"(page_size {self.page_size})")
            if self.prefix_cache is not None:
                with self.spans.span("prefix.match", rid=req.rid) as matched:
                    match, m_tok, tail_bucket = self._plan_prefix(
                        req.prompt, alloc_len)
            n_shared = len(match.pages) if match is not None else 0
            if n_shared:
                # take the slot's references FIRST: pins the matched pages
                # against the eviction _alloc_pages may trigger below
                self.allocator.share(match.pages)
            page_ids = self._alloc_pages(need - n_shared)
            if page_ids is None:
                if n_shared:
                    self.allocator.free(match.pages)
                return False
        t0 = time.perf_counter()
        self.key, sub = jax.random.split(self.key)
        if m_tok > 0:
            # prefix hit: adopt matched pages by reference, prefill only
            # the unmatched tail after the gathered prefix K/V
            n_shared = len(match.pages)
            width = alloc_len // self.page_size
            gather = np.full((width,), kv_cache.TRASH_PAGE, np.int32)
            gather[:n_shared] = match.pages
            if match.partial is not None:
                gather[n_shared] = match.partial[0]
            tail = np.zeros((1, tail_bucket), np.int32)
            tail[0, :plen - m_tok] = req.prompt[m_tok:]
            tok, cache = self._extend_fn(alloc_len, tail_bucket)(
                self.params, self.pool["kv"], gather, tail,
                np.int32(m_tok), sub, np.int32(plen - m_tok - 1))
            self._prefill_shapes.add(("extend", tail_bucket, alloc_len))
            self.slot_pages[slot] = list(match.pages) + page_ids
            # CoW: the table row references shared + fresh pages, but the
            # cache only ever COPIES into the fresh ones (shared entries of
            # the copy row are the trash page)
            copy = np.full((self.pages_per_slot,), kv_cache.TRASH_PAGE,
                           np.int32)
            copy[n_shared:self._pages_for(plen)] = page_ids
            self.pool = self._adopt(self.pool, cache, np.int32(slot),
                                    np.int32(plen), self._page_row(slot),
                                    copy)
            self._note_peak()
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_reused"] += m_tok
            matched.n = m_tok
            if match.partial is not None:
                self.stats["cow_copies"] += 1
        else:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = req.prompt
            tok, cache = self._prefill_fn(alloc_len)(
                self.params, padded, sub, np.int32(plen - 1))
            self._prefill_shapes.add((bucket, alloc_len))
            if self.paged:
                self.slot_pages[slot] = page_ids
                self.pool = self._adopt(self.pool, cache, np.int32(slot),
                                        np.int32(plen),
                                        self._page_row(slot))
                self._note_peak()
            else:
                self.pool = self._adopt(self.pool, cache, np.int32(slot),
                                        np.int32(plen))
        if self.prefix_cache is not None:
            with self.spans.span("prefix.insert", rid=req.rid):
                self.prefix_cache.insert(
                    req.prompt, self.slot_pages[slot][:self._pages_for(plen)])
        tok = int(jax.block_until_ready(tok)[0])
        t1 = time.perf_counter()
        self.stats["prefill_s"] += t1 - t0
        self.stats["prefill_tokens"] += plen
        self.stats["admitted"] += 1
        self._admit_seq += 1
        admit.n = plen
        self._dequeued(req, admit)

        comp = Completion(rid=req.rid, slot=slot, prompt_len=plen,
                          max_new_tokens=req.max_new_tokens, admitted_s=now,
                          seq=self._admit_seq)
        comp.ttft_s = max(0.0, t1 - self._offered_ns(req) * 1e-9)
        self.slot_owner[slot] = comp
        self.slot_req[slot] = req
        comp.tokens.append(tok)
        self.next_tok[slot] = tok
        self._maybe_retire(slot, now)        # max_new_tokens == 1 edge
        return True

    def _admit_encdec(self, req: Request, slot: int, now: float,
                      admit) -> bool:
        """encdec admission: reserve self + cross pages up-front (one
        all-or-nothing allocation), then encode the frames — wholesale, or
        one ``enc_chunk`` window per scheduler step so a long request
        cannot head-of-line-block admission (the slot PARKS in
        ``self._encoding`` and other requests keep admitting into the
        remaining slots).  The decoder-prompt prefill + adoption happen in
        :meth:`_finish_encdec` once the last window lands."""
        plen = len(req.prompt)
        if req.frames is None:
            raise ValueError(f"request {req.rid}: encdec requests need "
                             "frames")
        t_enc = int(req.frames.shape[0])
        if plen + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {plen} + {req.max_new_tokens} "
                f"new tokens exceeds max_len {self.max_len}")
        if t_enc > self.max_cross_len:
            raise ValueError(
                f"request {req.rid}: {t_enc} encoder frames exceed "
                f"max_cross_len {self.max_cross_len}")
        need = self._pages_for(plen) + self._pages_for(t_enc)
        if need > self.allocator.usable_pages:
            if req.resumed:
                self._finalize_oom(req, now)
                return True
            raise ValueError(
                f"request {req.rid}: prompt {plen} + {t_enc} frames need "
                f"{need} pages; the pool has {self.allocator.usable_pages} "
                f"(page_size {self.page_size})")
        page_ids = self._alloc_pages(need)
        if page_ids is None:
            return False
        self._dequeued(req, admit)
        n_self = self._pages_for(plen)
        self.slot_pages[slot] = page_ids[:n_self]
        self.slot_cross_pages[slot] = page_ids[n_self:]
        ent = dict(req=req, parts=[], off=0, admit_s=now)
        if self.enc_chunk is None:
            t0 = time.perf_counter()
            enc = self._encode(self.params, jnp.asarray(req.frames)[None])
            self.stats["prefill_s"] += time.perf_counter() - t0
            self._finish_encdec(slot, ent, enc, now)
            admit.n = plen + t_enc
        else:
            self._encoding[slot] = ent
        return True

    def _advance_encoding(self, now: float) -> None:
        """Encode ONE ``enc_chunk`` window for every parked slot (called
        once per scheduler step, between admission and the decode burst).
        Each window is encoded independently — bidirectional attention
        within the window only, real-time streaming-encoder semantics —
        and the windows are concatenated on the position axis when the
        last one lands.  Each window is a ``sched.admit`` span whose
        ``n`` counts its frames, and the prompt too in the last one."""
        for slot in list(self._encoding):
            ent = self._encoding[slot]
            req = ent["req"]
            t_enc = int(req.frames.shape[0])
            end = min(t_enc, ent["off"] + self.enc_chunk)
            last = end >= t_enc
            n = end - ent["off"] + (len(req.prompt) if last else 0)
            with self._admit_span(req.rid, n=n):
                t0 = time.perf_counter()
                part = self._encode(
                    self.params, jnp.asarray(req.frames[ent["off"]:end])[None])
                ent["parts"].append(part)
                ent["off"] = end
                self.stats["prefill_s"] += time.perf_counter() - t0
                if last:
                    del self._encoding[slot]
                    enc = jnp.concatenate(ent["parts"], axis=1)
                    self._finish_encdec(slot, ent, enc, now)

    def _finish_encdec(self, slot: int, ent: dict, enc, now: float) -> None:
        """Complete an encdec admission: decoder-prompt prefill against the
        encoded frames (self-KV written, cross-KV projected once), adopt
        both halves into the arena through their tables, sample the first
        token."""
        req = ent["req"]
        plen = len(req.prompt)
        t_enc = int(req.frames.shape[0])
        bucket = self._bucket_for(plen)
        alloc_len = _round_up(bucket, self.page_size)
        t0 = time.perf_counter()
        self.key, sub = jax.random.split(self.key)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = req.prompt
        tok, cache = self._prefill_fn(alloc_len)(
            self.params, enc, padded, sub, np.int32(plen - 1))
        self._prefill_shapes.add((bucket, alloc_len))
        self.pool = self._adopt_encdec(
            self.pool, cache, np.int32(slot), np.int32(plen),
            self._page_row(slot), np.int32(t_enc), self._cross_row(slot))
        self._note_peak()
        tok = int(jax.block_until_ready(tok)[0])
        t1 = time.perf_counter()
        self.stats["prefill_s"] += t1 - t0
        self.stats["prefill_tokens"] += plen + t_enc
        self.stats["admitted"] += 1
        self._admit_seq += 1
        comp = Completion(rid=req.rid, slot=slot, prompt_len=plen,
                          max_new_tokens=req.max_new_tokens,
                          admitted_s=ent["admit_s"], seq=self._admit_seq)
        comp.ttft_s = max(0.0, t1 - self._offered_ns(req) * 1e-9)
        self.slot_owner[slot] = comp
        self.slot_req[slot] = req
        comp.tokens.append(tok)
        self.next_tok[slot] = tok
        self._maybe_retire(slot, now)        # max_new_tokens == 1 edge

    def _admit_arrived(self, now: float) -> None:
        free = self.free_slots()
        # promote swapped-out work before admitting anything new: a demotee
        # resumes with a byte scatter, a fresh request costs a prefill
        while free and self._swapped:
            if not self._promote_swapped(free[0], now):
                break                        # no pages yet: keep waiting
            free = self.free_slots()
        while free and self.pending and self.pending[0].arrival_s <= now:
            if not self._admit(self.pending[0], free[0], now):
                break                        # no pages: wait for retirements
            self.pending.pop(0)
            free = self.free_slots()

    # -- retirement ----------------------------------------------------------
    def _merge_carried(self, comp: Completion) -> None:
        """Fold tokens generated before a preemption back into the final
        completion (its prompt absorbed them while requeued)."""
        if comp.rid in self._carried:
            orig_plen, prior, ttft = self._carried.pop(comp.rid)
            comp.tokens = prior + comp.tokens
            comp.max_new_tokens += len(prior)
            comp.prompt_len = orig_plen
            if ttft is not None:
                comp.ttft_s = ttft       # first admission's first token

    def _maybe_retire(self, slot: int, now: float) -> None:
        comp = self.slot_owner[slot]
        reason = None
        if self.eos_token is not None and comp.tokens[-1] == self.eos_token:
            reason = "eos"
        elif len(comp.tokens) >= comp.max_new_tokens:
            reason = "max_tokens"
        elif comp.prompt_len + len(comp.tokens) >= self.max_len:
            reason = "cache_full"
        if reason is not None:
            comp.finished_s = now
            comp.reason = reason
            self._merge_carried(comp)
            self.completions.append(comp)
            self._release_slot(slot)

    # -- paged preemption ----------------------------------------------------
    def _finalize_oom(self, req: Request, now: float) -> None:
        orig_plen, prior, ttft = self._carried.pop(
            req.rid, (len(req.prompt), [], None))
        self.completions.append(Completion(
            rid=req.rid, slot=-1, prompt_len=orig_plen,
            max_new_tokens=len(prior) + req.max_new_tokens, tokens=prior,
            finished_s=now, reason="oom_pages", ttft_s=ttft))

    def _preempt(self, slot: int, now: float) -> None:
        """Evict ``slot`` to reclaim its pages: requeue the request with
        prompt = original prompt + tokens so far (recompute on
        readmission).  Pages AND the slot free immediately."""
        comp = self.slot_owner[slot]
        req = self.slot_req[slot]
        orig_plen, prior, ttft = self._carried.get(
            comp.rid, (comp.prompt_len, [], comp.ttft_s))
        self._carried[comp.rid] = (orig_plen, prior + comp.tokens, ttft)
        remaining = comp.max_new_tokens - len(comp.tokens)
        self.pending.insert(0, Request(
            rid=comp.rid, prompt=tuple(req.prompt) + tuple(comp.tokens),
            max_new_tokens=max(1, remaining), arrival_s=0.0, resumed=True,
            frames=req.frames, queued_ns=time.perf_counter_ns()))
        self._release_slot(slot)
        self.stats["preempted"] += 1

    def _pick_victim(self) -> int:
        """Latest-admitted active slot (LIFO preemption): the youngest
        request has the least sunk prefill+decode work to recompute.
        Callers guarantee at least one active slot."""
        return max((self.slot_owner[s].seq, s)
                   for s in self.active_slots())[1]

    # -- host-RAM swap tier --------------------------------------------------
    def _demote(self, slot: int, now: float) -> bool:
        """Swap ``slot``'s pages to host RAM instead of preempting: the
        exact arena bytes (int8 pages + fp32 scale sidecars included) move
        to the :class:`kv_cache.HostSwapStore`; promotion scatters the same
        bytes back (``restore_slot_paged``), so the round trip is
        bit-lossless — no prefill recompute and, on a quantized pool, no
        second quantization error.  Refuses (caller falls back to
        ``_preempt``) when the tier is off, any of the slot's pages is
        SHARED (refcount > 1: another slot or the prefix index still reads
        it — the bytes must stay resident), or the blob is over the host
        budget."""
        if self.host_swap is None:
            return False
        ids = self.slot_pages[slot]
        if not ids or any(self.allocator.refcount(p) > 1 for p in ids):
            return False
        comp = self.slot_owner[slot]
        # constant-shape gather: pads go through the trash page, whose
        # garbage bytes are routed straight back to it at promotion
        row = self._page_row(slot)
        blob = {n: jax.device_get(leaf[:, row])
                for n, leaf in self.pool["kv"].items()}
        if not self.host_swap.put(comp.rid, blob):
            return False
        self._swapped[comp.rid] = dict(
            comp=comp, req=self.slot_req[slot],
            length=self._dev_len(slot),
            next_tok=int(self.next_tok[slot]))
        self._release_slot(slot)
        self.stats["demoted"] += 1
        return True

    def _promote_swapped(self, slot: int, now: float) -> bool:
        """Promote the oldest swapped-out request back into ``slot`` (FIFO
        — the longest-waiting demotee resumes first): re-allocate its
        pages, scatter the host blob back bit-for-bit, resume decode at the
        token it was about to write.  False (nothing consumed) while the
        arena cannot back it."""
        rid, ent = next(iter(self._swapped.items()))
        need = self._pages_for(ent["length"])
        page_ids = self._alloc_pages(need)
        if page_ids is None:
            return False
        del self._swapped[rid]
        blob = self.host_swap.pop(rid)
        self.slot_pages[slot] = page_ids
        self.pool = self._restore(self.pool, blob, np.int32(slot),
                                  np.int32(ent["length"]),
                                  self._page_row(slot))
        self._note_peak()
        comp = ent["comp"]
        comp.slot = slot
        self.slot_owner[slot] = comp
        self.slot_req[slot] = ent["req"]
        self.next_tok[slot] = ent["next_tok"]
        self.stats["prefetched"] += 1
        return True

    def _ensure_pages(self, runahead: int, now: float) -> int:
        """Make every active slot's next ``h <= runahead`` write positions
        page-backed before the decode burst.  Shrinks the horizon before
        touching anyone; preempts the latest-admitted slot when even one
        step cannot be backed; a lone slot that cannot grow retires as
        ``"oom_pages"``.  Returns the achieved horizon (0 = nothing left
        active)."""
        while True:
            active = self.active_slots()
            if not active:
                return 0

            def extra(slot: int, h: int) -> int:
                target = min(self._dev_len(slot) + h, self.max_len)
                return max(0,
                           self._pages_for(target) -
                           len(self.slot_pages[slot]))

            h = max(1, runahead)
            # page pressure reclaims cold cached prefixes BEFORE the
            # horizon shrinks or anyone is preempted: an unreferenced
            # index page is strictly cheaper to give up than live work
            short = (sum(extra(s, h) for s in active)
                     - self.allocator.free_pages)
            if short > 0 and self.prefix_cache is not None:
                freed = self.prefix_cache.evict(short)
                if freed:
                    self.stats["prefix_evictions"] += freed
            while h > 1 and (sum(extra(s, h) for s in active)
                             > self.allocator.free_pages):
                h -= 1
            if (sum(extra(s, h) for s in active)
                    <= self.allocator.free_pages):
                for s in active:
                    n = extra(s, h)
                    if n:
                        self.slot_pages[s].extend(self.allocator.alloc(n))
                        self.pool = self._set_row(self.pool, np.int32(s),
                                                  self._page_row(s))
                self._note_peak()
                return h
            if len(active) == 1:
                # nothing else to evict: retire with what it produced
                comp = self.slot_owner[active[0]]
                comp.finished_s = now
                comp.reason = "oom_pages"
                self._merge_carried(comp)
                self.completions.append(comp)
                self._release_slot(active[0])
                return 0
            # demotion first: host swap keeps the victim's computed pages
            # (promote = byte scatter); preemption throws them away
            # (readmission = full prefill recompute)
            victim = self._pick_victim()
            if not self._demote(victim, now):
                self._preempt(victim, now)

    # -- one scheduler iteration --------------------------------------------
    def _runahead(self, comps: list[Completion]) -> int:
        """How many decode steps can run back-to-back without a host
        decision.  Retirement is count-driven when there is no EOS token, so
        the loop may run device-side until the first budget/cache expiry and
        sync ONCE — otherwise every step pays a device->host round-trip the
        lockstep ``generate`` loop never pays (it checks nothing)."""
        if self.eos_token is not None:
            return 1                     # token values gate retirement
        if self.pending and self.free_slots():
            return 1                     # open-loop traffic: admit promptly
        if self._encoding:
            return 1                     # chunked encodes advance per step
        rem = min(c.max_new_tokens - len(c.tokens) for c in comps)
        head = min(self.max_len - (c.prompt_len + len(c.tokens))
                   for c in comps)
        return max(1, min(rem, head))

    def step(self, now: float | None = None) -> bool:
        """Admit arrived requests, then run one ragged decode *burst* over
        the occupied slots (one step, or a run-ahead of several when no
        retirement can occur in between).  Returns False when idle."""
        if now is None:
            now = 0.0
        spans = self.spans
        with spans.span("sched.step"):
            self._admit_arrived(now)
            if self._encoding:
                self._advance_encoding(now)
            active = self.active_slots()
            if not active:
                return bool(self._encoding)
            runahead = self._runahead([self.slot_owner[s] for s in active])
            if self.paged:
                with spans.span("sched.pages"):
                    runahead = self._ensure_pages(runahead, now)
                active = self.active_slots()  # preemption may have shrunk it
                if not active:
                    return bool(self.pending or self._swapped)
            mask = np.zeros((self.n_slots,), bool)
            mask[active] = True

            mask_dev = jnp.asarray(mask)
            toks_dev = jnp.asarray(self.next_tok, jnp.int32)
            sampled = []
            with spans.span("sched.decode", n=len(active), runahead=runahead):
                t0 = time.perf_counter()
                for _ in range(runahead):
                    toks_dev, self.pool, self.key = self._step(
                        self.params, self.pool, toks_dev, self.key, mask_dev)
                    sampled.append(toks_dev)
                # harvest host-side (np.stack, not jnp: a device stack would
                # compile a fresh concatenate for every distinct run-ahead
                # length)
                jax.block_until_ready(sampled[-1])
                harvested = np.stack([np.asarray(t) for t in sampled])
                self.stats["decode_s"] += time.perf_counter() - t0
            self.stats["decode_tokens"] += len(active) * runahead
            self.stats["steps"] += runahead
            if self.paged:
                # pages the paged decode sweep reads (each active slot's
                # ceil(length / page_size) at every step of the burst)
                # against the whole page table it could read
                self.stats["decode_pages_read"] += sum(
                    self._pages_for(self._dev_len(s) + r)
                    for s in active for r in range(1, runahead + 1))
                self.stats["decode_pages_table"] += (
                    self.n_slots * self.pages_per_slot * runahead)

            with spans.span("sched.retire"):
                for row in harvested:                # [runahead, n_slots]
                    for slot in active:
                        self.slot_owner[slot].tokens.append(int(row[slot]))
                for slot in active:
                    self.next_tok[slot] = self.slot_owner[slot].tokens[-1]
                    self._maybe_retire(slot, now)
            return True

    # -- drive to completion -------------------------------------------------
    def run(self, requests=None, *, use_wall_clock: bool | None = None
            ) -> list[Completion]:
        """Serve ``requests`` (plus anything already submitted) to completion.

        Arrival times are honored against the wall clock when any request
        has ``arrival_s > 0`` (Poisson-style open-loop traffic), otherwise
        everything is offered at t=0 (closed-loop / batch mode).  Passing
        ``use_wall_clock=False`` explicitly collapses all arrivals to t=0 —
        future arrival times would otherwise never be reached.
        """
        for req in requests or ():
            self.submit(req)
        if use_wall_clock is None:
            use_wall_clock = any(r.arrival_s > 0 for r in self.pending)
        if not use_wall_clock:
            for req in self.pending:
                req.arrival_s = 0.0
        start = time.perf_counter()
        self._run_start = start
        while (self.pending or self.active_slots() or self._swapped
               or self._encoding):
            now = (time.perf_counter() - start) if use_wall_clock else 0.0
            progressed = self.step(now=now)
            if not progressed and self.pending:
                # idle pool, traffic still to come: sleep to next arrival
                wait = self.pending[0].arrival_s - now
                if use_wall_clock and wait > 0:
                    time.sleep(min(wait, 0.05))
        self.completions.sort(key=lambda c: c.rid)
        return self.completions

    def stream(self, requests=None, *, use_wall_clock: bool | None = None):
        """Serve like :meth:`run`, but YIELD tokens as they are produced:
        a generator of ``(rid, [token, ...])`` deltas, emitted after every
        scheduler step for each request that gained tokens in that step —
        a request streams while slower batch members are still decoding,
        instead of everything surfacing at the end.

        Every family benefits (the decode burst already advances slots
        independently; this just drains the host-side token lists
        incrementally).  Preemption-safe: a preempted request's
        already-yielded tokens are not re-yielded after readmission — the
        carried-token accounting below treats the stream for one ``rid``
        as a single monotone sequence.  After the generator is exhausted,
        ``self.completions`` holds the same Completion list ``run`` would
        have returned.
        """
        for req in requests or ():
            self.submit(req)
        if use_wall_clock is None:
            use_wall_clock = any(r.arrival_s > 0 for r in self.pending)
        if not use_wall_clock:
            for req in self.pending:
                req.arrival_s = 0.0
        start = time.perf_counter()
        self._run_start = start
        emitted: dict[int, int] = {}

        def _deltas():
            # one monotone token view per rid: tokens carried across
            # preemptions, then the live/finished completion's own tokens
            views = []
            for slot in self.active_slots():
                comp = self.slot_owner[slot]
                prior = self._carried.get(comp.rid, (0, [], None))[1]
                views.append((comp.rid, prior + comp.tokens))
            for ent in self._swapped.values():
                comp = ent["comp"]
                prior = self._carried.get(comp.rid, (0, [], None))[1]
                views.append((comp.rid, prior + comp.tokens))
            for comp in self.completions:
                views.append((comp.rid, comp.tokens))
            out = []
            for rid, toks in views:
                n = emitted.get(rid, 0)
                if len(toks) > n:
                    out.append((rid, [int(t) for t in toks[n:]]))
                    emitted[rid] = len(toks)
            return out

        while (self.pending or self.active_slots() or self._swapped
               or self._encoding):
            now = (time.perf_counter() - start) if use_wall_clock else 0.0
            progressed = self.step(now=now)
            yield from _deltas()
            if not progressed and self.pending:
                wait = self.pending[0].arrival_s - now
                if use_wall_clock and wait > 0:
                    time.sleep(min(wait, 0.05))
        self.completions.sort(key=lambda c: c.rid)

    def reset_stats(self) -> None:
        """Zero the throughput counters + completions (keeps compiled fns):
        benchmarks warm up the jitted step/prefill, then measure cleanly."""
        for k in self.stats:
            self.stats[k] = 0.0 if isinstance(self.stats[k], float) else 0
        self.completions = []

    # -- reporting ----------------------------------------------------------
    def throughput(self) -> dict:
        """Phase-separated throughput: prefill vs decode tok/s (+ totals,
        + page-pool occupancy for paged pools)."""
        st = self.stats
        wall = st["prefill_s"] + st["decode_s"]
        out = dict(
            prefill_tok_s=(st["prefill_tokens"] / st["prefill_s"]
                           if st["prefill_s"] else 0.0),
            decode_tok_s=(st["decode_tokens"] / st["decode_s"]
                          if st["decode_s"] else 0.0),
            requests_s=(len(self.completions) / wall if wall else 0.0),
            slots=self.n_slots, steps=st["steps"], admitted=st["admitted"],
            prefill_tokens=st["prefill_tokens"],
            decode_tokens=st["decode_tokens"], wall_s=wall,
            paged=self.paged,
            prefill_compiles=len(self._prefill_shapes))
        if self.mesh is not None:
            out.update(mesh_axes=dict(zip(self.mesh.axis_names,
                                          self.mesh.devices.shape)),
                       kv_shards=dist_sharding.kv_shard_factor(self.cfg,
                                                               self.mesh))
        if self.paged:
            out.update(page_size=self.page_size,
                       pages=self.allocator.usable_pages,
                       peak_pages=st["peak_pages"],
                       preempted=st["preempted"],
                       decode_pages_read=st["decode_pages_read"],
                       decode_pages_table=st["decode_pages_table"],
                       prefix_cache=self.prefix_cache is not None)
            if self.page_dtype is not None:
                out.update(page_dtype=self.page_dtype,
                           scale_granularity=self.scale_granularity)
            if self.host_swap is not None:
                out.update(demoted=st["demoted"],
                           prefetched=st["prefetched"],
                           swap_bytes_used=self.host_swap.bytes_used)
            if self.prefix_cache is not None:
                out.update(prefix_hits=st["prefix_hits"],
                           prefix_tokens_reused=st["prefix_tokens_reused"],
                           cow_copies=st["cow_copies"],
                           prefix_evictions=st["prefix_evictions"],
                           cached_pages=self.prefix_cache.n_pages)
        return out

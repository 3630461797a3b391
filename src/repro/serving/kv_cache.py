"""Per-family KV/state cache construction and shape logic.

Cache pytrees are stacked on a leading layer axis so the decode layer loop is
one ``lax.scan`` (cache consumed as xs, new cache emitted as ys).

Two addressing schemes coexist:

  * **ring** (``ring=True``, single-sequence decode of SWA archs): the cache
    allocates only ``window`` positions and slots are addressed ``pos %
    window``.  Every written slot holds an in-window position (RoPE baked at
    write time), so reads need only a validity bound, not masks.
  * **full** (``ring=False``): position-addressed, ``max_len`` allocation.
    Prefill paths and the continuous-batching slot pools use this — a slot
    pool must admit sequences at arbitrary positions, so SWA becomes a mask
    over the full-length cache rather than addressing.

The slot pool (:func:`init_slot_pool`) is the continuous-batching extension:
the batch axis becomes a fixed pool of request slots, plus a per-slot
``lengths`` array — the number of valid cache positions (0 marks a free
slot; it is also the next write position, and the length-mask makes stale
entries from an evicted request invisible to the next occupant until they
are overwritten).

The PAGED pool (:func:`init_paged_pool`) replaces the slot-major ``max_len``
strips with a fixed arena of fixed-size pages plus a per-slot page table:
capacity is bounded by *total tokens in flight*, not ``slots × max_len``.
This is the paper's online (m, n) accumulation put to work — because the
running max/sum rescales are exact and order-free, decode attention can
sweep a slot's KV through the page table in whatever arena order the pages
landed, so pages are recycled individually (``PageAllocator``) instead of
whole strips.  Arena page 0 is reserved as the TRASH page: free slots' table
entries (and table entries past a slot's allocated pages) point at it, so
the writes that inactive slots still issue inside the jitted step land in a
row nothing ever reads validly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def cache_dtype(cfg: ModelConfig):
    """KV-cache storage dtype: the model's compute dtype (recurrent ssm
    state is the exception — it accumulates in f32 regardless)."""
    return jnp.dtype(cfg.dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, tp: int = 1,
               ring: bool = True):
    """Returns the stacked-layer cache pytree for decode.  ``ring=True``
    sizes SWA caches at the window (slot addressing mod window); prefill
    paths pass ring=False for position addressing."""
    dt = cache_dtype(cfg)
    hd = cfg.resolved_head_dim()
    ls = cfg.n_layers

    if cfg.family == "ssm":
        h = cfg.n_heads
        shd = cfg.ssm.head_dim
        return {
            "wkv": jnp.zeros((ls, batch, h, shd, shd), jnp.float32),
            "last_t": jnp.zeros((ls, batch, cfg.d_model), dt),
            "last_c": jnp.zeros((ls, batch, cfg.d_model), dt),
        }
    if cfg.family == "hybrid":
        h = cfg.d_model // cfg.ssm.head_dim
        alloc = max_len
        if cfg.swa_window is not None and ring:
            alloc = min(max_len, cfg.swa_window)
        return {
            "attn": {
                "k": jnp.zeros((ls, batch, alloc, cfg.n_kv_heads, hd), dt),
                "v": jnp.zeros((ls, batch, alloc, cfg.n_kv_heads, hd), dt),
            },
            "ssm": jnp.zeros((ls, batch, h, cfg.ssm.state_size,
                              cfg.ssm.head_dim), jnp.float32),
        }
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c": jnp.zeros((ls, batch, max_len, m.kv_lora_rank), dt),
            "kr": jnp.zeros((ls, batch, max_len, m.qk_rope_head_dim), dt),
        }
    if cfg.family == "encdec":
        # self-KV is position-addressed over the DECODER sequence exactly
        # like dense (it used to allocate cfg.dec_len, which silently capped
        # decode at the training decoder length); cross-KV leaves are
        # placeholders the prefill's encoder fill replaces wholesale with
        # the true frame count.
        return {
            "self": {
                "k": jnp.zeros((ls, batch, max_len, cfg.n_kv_heads, hd), dt),
                "v": jnp.zeros((ls, batch, max_len, cfg.n_kv_heads, hd), dt),
            },
            # cross-kv filled from encoder output at prefill
            "cross": {
                "k": jnp.zeros((ls, batch, max_len, cfg.n_kv_heads, hd), dt),
                "v": jnp.zeros((ls, batch, max_len, cfg.n_kv_heads, hd), dt),
            },
        }
    # dense / moe / vlm
    alloc = max_len
    if cfg.swa_window is not None and ring:
        alloc = min(max_len, cfg.swa_window)
    return {
        "k": jnp.zeros((ls, batch, alloc, cfg.n_kv_heads, hd), dt),
        "v": jnp.zeros((ls, batch, alloc, cfg.n_kv_heads, hd), dt),
    }


# ---------------------------------------------------------------------------
# Continuous-batching slot pool.
# ---------------------------------------------------------------------------
def init_slot_pool(cfg: ModelConfig, slots: int, max_len: int,
                   tp: int = 1) -> dict:
    """A fixed pool of ``slots`` cache slots for continuous batching.

    Returns ``{"kv": <stacked-layer cache, batch axis = slots, full-length
    position addressing>, "lengths": int32[slots]}``.  ``lengths[s]`` is the
    valid cache prefix of slot ``s`` (0 = free) and doubles as its next
    write position; ``engine.decode_step_ragged`` consumes/advances it.
    """
    return {"kv": init_cache(cfg, slots, max_len, tp, ring=False),
            "lengths": jnp.zeros((slots,), jnp.int32)}


def adopt_slot(pool: dict, cache, slot, length) -> dict:
    """Admit a freshly prefilled batch=1 cache into ``slot``.

    ``cache`` must come from ``engine.prefill(..., max_len=<pool max_len>)``
    so the position axis matches the pool.  jit-safe: ``slot``/``length``
    may be traced.
    """
    kv = jax.tree.map(
        lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=1), pool["kv"], cache)
    return {"kv": kv,
            "lengths": pool["lengths"].at[slot].set(
                jnp.asarray(length, jnp.int32))}


def free_slot(pool: dict, slot) -> dict:
    """Mark ``slot`` free (length 0).  Its cache contents become dead: the
    length mask hides them and the next :func:`adopt_slot` overwrites them."""
    return {"kv": pool["kv"], "lengths": pool["lengths"].at[slot].set(0)}


# ---------------------------------------------------------------------------
# Paged pool: page arena + per-slot page tables.
# ---------------------------------------------------------------------------
TRASH_PAGE = 0          # arena page 0: write target for dead/inactive rows


def supports_paging(cfg: ModelConfig) -> bool:
    """Families whose decode cache is position-addressed (pageable).  ssm
    state has no position axis and stays on the strip pool.  encdec pages
    BOTH halves: self-attention KV exactly like dense, and the encoder's
    cross-KV as read-only pages in the SAME arena (written once at
    admission, addressed by a separate per-slot ``cross_table``).  hybrid
    pages its attention half and keeps ssm state slot-major."""
    return cfg.family != "ssm"


def resolve_page_size(cfg: ModelConfig, max_len: int,
                      page_size: int | None = None) -> int:
    """Tokens per page, resolved through the kernel registry's ``kv_page``
    spec like any other block shape: explicit ``page_size`` > autotune
    cache (when the config's policy opts in) > the 128-token heuristic,
    shrunk to the pool's own padded length for tiny pools."""
    if page_size is not None:
        return int(page_size)
    from repro.kernels import registry  # lazy: kernels are optional

    pol = cfg.softmax_policy()
    _, ps = registry.block_shapes("kv_page", 1, max_len, cache_dtype(cfg),
                                  use_cache=pol.autotune,
                                  cache_file=pol.autotune_cache)
    return int(ps)


def resolve_page_quant(cfg: ModelConfig, max_len: int,
                       page_size: int | None = None,
                       scale_granularity: str | None = None
                       ) -> tuple[int, str]:
    """(page_size, scale_granularity) for an int8 paged pool, resolved
    through the ``kv_page_quant`` registry spec: block cols model the
    tokens per page (exactly like ``kv_page``) and block rows model the
    scale granularity — 1 row = one scale per page position ("page"),
    more rows = one scale per (position, kv head) ("page_head").
    Explicit arguments win per-axis; otherwise the policy's autotune
    cache, otherwise the heuristic (128-token pages, "page" scales)."""
    if page_size is not None and scale_granularity is not None:
        _check_granularity(scale_granularity)
        return int(page_size), scale_granularity
    from repro.kernels import registry  # lazy: kernels are optional

    pol = cfg.softmax_policy()
    gr, ps = registry.block_shapes(
        "kv_page_quant", cfg.n_kv_heads, max_len, jnp.int8,
        use_cache=pol.autotune, cache_file=pol.autotune_cache)
    if page_size is not None:
        ps = page_size
    if scale_granularity is None:
        scale_granularity = "page_head" if gr > 1 else "page"
    _check_granularity(scale_granularity)
    return int(ps), scale_granularity


def _check_granularity(granularity: str) -> None:
    if granularity not in ("page", "page_head"):
        raise ValueError(f"unknown scale granularity {granularity!r}; "
                         "expected 'page' or 'page_head'")


def pages_per_slot(max_len: int, page_size: int) -> int:
    """Page-table width: pages covering a slot's ``max_len`` positions."""
    return -(-int(max_len) // int(page_size))


def supports_page_quant(cfg: ModelConfig) -> bool:
    """Families whose paged pool can store int8 pages: the flat ``k``/``v``
    arenas (dense / moe / vlm).  MLA stores latents (a different numeric
    regime — quantizing ``c`` compounds through two projections) and hybrid
    carries slot-major ssm state next to its pages; both keep full-precision
    pages.  encdec keeps full precision too for now: its cross pages are
    written once and read every step, so quantizing them needs its own
    error budget (a follow-on, see ROADMAP)."""
    return (supports_paging(cfg) and cfg.mla is None
            and cfg.family not in ("hybrid", "encdec"))


def init_paged_pool(cfg: ModelConfig, slots: int, max_len: int, tp: int = 1,
                    *, page_size: int | None = None,
                    pages: int | None = None, mesh=None,
                    page_dtype: str | None = None,
                    scale_granularity: str | None = None,
                    cross_len: int | None = None) -> dict:
    """A paged KV pool: shared page arena + per-slot page table.

    Returns ``{"kv": <stacked-layer page arenas>, "page_table":
    int32[slots, pages_per_slot], "lengths": int32[slots]}``.  Positional
    cache leaves become arenas ``[L, pages, page_size, ...]``; hybrid's ssm
    state (no position axis) stays slot-major ``[L, slots, ...]``.
    ``pages`` defaults to full provisioning (``1 + slots * pages_per_slot``,
    page 0 reserved as trash) — pass fewer to oversubscribe: capacity is
    then bounded by total tokens in flight, the point of paging.  Table
    entries init to the trash page; ``lengths`` semantics match the strip
    pool (:func:`init_slot_pool`).

    encdec pools carry TWO tables over ONE arena: the encoder's cross-KV
    has the same per-position leaf shape as self-KV, so cross pages live in
    the same ``k``/``v`` arenas (one allocator, one refcount space) and the
    extra ``cross_table`` int32[slots, ceil(cross_len / ps)] +
    ``cross_lengths`` int32[slots] address them.  Cross pages are written
    once at admission and only read afterwards.  ``cross_len`` (default
    ``max_len``) bounds a request's encoder frames; the default ``pages``
    provisioning covers both tables.

    ``page_dtype="int8"`` (flat k/v families only, see
    :func:`supports_page_quant`) stores the arenas as symmetric-absmax int8
    with an fp32 scale sidecar per leaf: ``k_scale``/``v_scale`` shaped
    ``[L, pages, page_size]`` ("page" granularity — one scale per stored
    position) or ``[L, pages, page_size, n_kv_heads]`` ("page_head").
    Scales are stored PER POSITION even at "page" granularity so a decode
    write quantizes only its own row — adopting a prefilled page broadcasts
    the page-level absmax across its positions, and existing rows are never
    requantized.  Default ``page_dtype=None`` keeps the arenas in the
    model's compute dtype, byte-for-byte identical to the unquantized pool.

    ``mesh`` (a ('data', 'model') serving mesh) lays the pool out sharded
    per :func:`repro.distributed.sharding.pool_specs`: arena KV-head axis
    over ``model``, page table / lengths replicated (see
    :func:`shard_pool`).  Each device makes only its own shard.
    """
    if not supports_paging(cfg):
        raise ValueError(f"family {cfg.family!r} has no pageable cache")
    if page_dtype not in (None, "int8"):
        raise ValueError(f"unknown page_dtype {page_dtype!r}; "
                         "expected None or 'int8'")
    quant = page_dtype == "int8"
    if quant and not supports_page_quant(cfg):
        raise ValueError(f"family {cfg.family!r} (mla={cfg.mla is not None})"
                         " has no int8 page path: quantized pages need the"
                         " flat k/v arenas (dense / moe / vlm)")
    if quant:
        ps, gran = resolve_page_quant(cfg, max_len, page_size,
                                      scale_granularity)
    else:
        ps = resolve_page_size(cfg, max_len, page_size)
    n_tab = pages_per_slot(max_len, ps)
    n_xtab = 0
    if cfg.family == "encdec":
        n_xtab = pages_per_slot(cross_len or max_len, ps)
    if pages is None:
        pages = 1 + slots * (n_tab + n_xtab)
    if mesh is not None:
        return shard_pool(functools.partial(
            init_paged_pool, cfg, slots, max_len, tp, page_size=page_size,
            pages=pages, page_dtype=page_dtype,
            scale_granularity=scale_granularity, cross_len=cross_len),
            cfg, mesh)
    dt = cache_dtype(cfg)
    hd = cfg.resolved_head_dim()
    ls = cfg.n_layers

    if cfg.mla is not None:
        m = cfg.mla
        kv = {"c": jnp.zeros((ls, pages, ps, m.kv_lora_rank), dt),
              "kr": jnp.zeros((ls, pages, ps, m.qk_rope_head_dim), dt)}
    elif cfg.family == "hybrid":
        h = cfg.d_model // cfg.ssm.head_dim
        kv = {"attn": {
                  "k": jnp.zeros((ls, pages, ps, cfg.n_kv_heads, hd), dt),
                  "v": jnp.zeros((ls, pages, ps, cfg.n_kv_heads, hd), dt)},
              "ssm": jnp.zeros((ls, slots, h, cfg.ssm.state_size,
                                cfg.ssm.head_dim), jnp.float32)}
    elif quant:                                    # dense / moe / vlm, int8
        sshape = ((ls, pages, ps) if gran == "page"
                  else (ls, pages, ps, cfg.n_kv_heads))
        kv = {"k": jnp.zeros((ls, pages, ps, cfg.n_kv_heads, hd), jnp.int8),
              "v": jnp.zeros((ls, pages, ps, cfg.n_kv_heads, hd), jnp.int8),
              "k_scale": jnp.zeros(sshape, jnp.float32),
              "v_scale": jnp.zeros(sshape, jnp.float32)}
    else:                                          # dense / moe / vlm / encdec
        kv = {"k": jnp.zeros((ls, pages, ps, cfg.n_kv_heads, hd), dt),
              "v": jnp.zeros((ls, pages, ps, cfg.n_kv_heads, hd), dt)}
    pool = {"kv": kv,
            "page_table": jnp.zeros((slots, n_tab), jnp.int32),
            "lengths": jnp.zeros((slots,), jnp.int32)}
    if cfg.family == "encdec":
        pool["cross_table"] = jnp.zeros((slots, n_xtab), jnp.int32)
        pool["cross_lengths"] = jnp.zeros((slots,), jnp.int32)
    return pool


def shard_pool(make, cfg: ModelConfig, mesh) -> dict:
    """Build a serving pool (paged or strip) laid out across ``mesh`` per
    :func:`repro.distributed.sharding.pool_specs` — KV-head axis of the
    arenas over ``model``, slot/ssm axes over the data axes, page table
    and lengths replicated.  ``make()`` returns the unsharded pool; it
    runs under one jit with those shardings as its outputs, so each
    device makes only its own shard (a whole arena made on one device
    first need not fit beside that device's share of the weights)."""
    from repro.distributed import sharding as _sh  # lazy: serving↛distributed

    specs = _sh.pool_specs(jax.eval_shape(make), cfg, mesh)
    return jax.jit(make, out_shardings=_sh.named(specs, mesh))()


def quantize_symmetric(x, axes):
    """Symmetric absmax int8 quantization of ``x`` with one scale per
    element of the non-``axes`` dims: ``q = round(x / scale)`` clipped to
    [-127, 127], ``scale = absmax / 127`` (1.0 where absmax is 0, so the
    all-zero trash page round-trips to exact zeros).  Returns ``(q int8,
    scale f32 with ``axes`` kept as size-1 dims)``."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.round(jnp.clip(xf / scale, -127.0, 127.0)).astype(jnp.int8)
    return q, scale


def dequantize_pages(kv, dtype):
    """``{"k", "v", "k_scale", "v_scale"}`` int8 leaves (arena- or
    gathered-shape: scales trail the value leaves by 2 dims at "page"
    granularity, by 1 at "page_head") back to ``{"k", "v"}`` in
    ``dtype``."""
    out = {}
    for n in ("k", "v"):
        s = kv[n + "_scale"]
        s = s[..., None, None] if s.ndim == kv[n].ndim - 2 else s[..., None]
        out[n] = (kv[n].astype(jnp.float32) * s).astype(dtype)
    return out


def _copy_pages(dst, src, page_row):
    """Scatter a batch=1 position-major prefill cache ``[L, 1, T, ...]``
    into arena pages ``[L, P, ps, ...]`` at the table row's ids.  T must be
    a whole number of pages (bucketed prefill guarantees it); source pages
    past the table width — a bucket wider than the slot — are dropped, and
    table entries past the allocated count are trash (their copies land in
    the trash page, garbage over garbage).  Prefix sharing routes the row
    entries it adopts BY REFERENCE to the trash page too (``copy_row`` in
    :func:`adopt_slot_paged`): a shared page is someone else's bytes and
    must never be written."""
    ls, _, ps = dst.shape[:3]
    n_src = src.shape[2] // ps
    n_copy = min(n_src, page_row.shape[0])
    srcp = src[:, 0].reshape(ls, n_src, ps, *src.shape[3:])[:, :n_copy]
    return dst.at[:, page_row[:n_copy]].set(srcp.astype(dst.dtype))


def _copy_pages_quant(dst, scale_dst, src, page_row):
    """Quantizing :func:`_copy_pages`: scatter a full-precision prefill
    cache into an int8 arena + its fp32 scale sidecar.  The absmax is
    taken per page ("page" granularity, 3-D sidecar) or per (page, head)
    ("page_head", 4-D) and broadcast across the page's positions — see
    :func:`init_paged_pool` for why scales are stored per position."""
    ls, _, ps = dst.shape[:3]
    n_src = src.shape[2] // ps
    n_copy = min(n_src, page_row.shape[0])
    srcp = src[:, 0].reshape(ls, n_src, ps, *src.shape[3:])[:, :n_copy]
    per_head = scale_dst.ndim == 4
    q, scale = quantize_symmetric(srcp, (2, 4) if per_head else (2, 3, 4))
    if per_head:                                  # [ls, n, 1, H] -> ps rows
        srows = jnp.broadcast_to(scale[:, :, :, :, 0],
                                 (ls, n_copy, ps, srcp.shape[3]))
    else:                                         # [ls, n, 1] -> ps rows
        srows = jnp.broadcast_to(scale[:, :, :, 0, 0], (ls, n_copy, ps))
    return (dst.at[:, page_row[:n_copy]].set(q),
            scale_dst.at[:, page_row[:n_copy]].set(srows))


def adopt_slot_paged(pool: dict, cache, slot, length, page_row,
                     copy_row=None) -> dict:
    """Admit a freshly prefilled batch=1 cache into ``slot`` of a paged
    pool.  ``page_row`` is the slot's FULL page-table row (int32
    ``[pages_per_slot]``): the first ``ceil(length / ps)`` entries are the
    allocated arena pages, the rest the trash page.  ``cache`` must come
    from ``engine.prefill`` with a position allocation that is a multiple
    of the page size.  jit-safe: ``slot``/``length``/``page_row`` may be
    traced (shapes are static).

    ``copy_row`` (default: ``page_row``) decouples where cache pages are
    WRITTEN from what the table ROW references — the copy-on-write seam for
    prefix sharing: matched prefix pages appear in ``page_row`` (adopted by
    reference) but their ``copy_row`` entries are the trash page (never
    written), while the divergent/partial tail copies into fresh pages."""
    kv = pool["kv"]
    if copy_row is None:
        copy_row = page_row
    if "attn" in kv:                               # hybrid: ssm slot-major
        new_kv = {
            "attn": {n: _copy_pages(kv["attn"][n], cache["attn"][n],
                                    copy_row) for n in ("k", "v")},
            "ssm": jax.lax.dynamic_update_slice_in_dim(
                kv["ssm"], cache["ssm"].astype(kv["ssm"].dtype), slot,
                axis=1)}
    elif "k_scale" in kv:                          # int8 arena: quantize
        new_kv = {}
        for n in ("k", "v"):
            new_kv[n], new_kv[n + "_scale"] = _copy_pages_quant(
                kv[n], kv[n + "_scale"], cache[n], copy_row)
    else:
        new_kv = {n: _copy_pages(kv[n], cache[n], copy_row) for n in kv}
    return {"kv": new_kv,
            "page_table": pool["page_table"].at[slot].set(
                page_row.astype(jnp.int32)),
            "lengths": pool["lengths"].at[slot].set(
                jnp.asarray(length, jnp.int32))}


def _pad_to_pages(src, ps: int):
    """Zero-pad a batch=1 position-major cache leaf ``[L, 1, T, ...]`` on
    the position axis up to a whole number of ``ps``-sized pages (static
    shapes, jit-safe).  The pad rows land in the tail page beyond the
    slot's length and are masked by the length-prefix read."""
    t = src.shape[2]
    rem = (-t) % ps
    if rem == 0:
        return src
    pad = jnp.zeros((src.shape[0], src.shape[1], rem, *src.shape[3:]),
                    src.dtype)
    return jnp.concatenate([src, pad], axis=2)


def adopt_slot_encdec(pool: dict, cache, slot, length, page_row,
                      cross_len, cross_row) -> dict:
    """Admit a freshly prefilled encdec cache (``{"self": {k, v}, "cross":
    {k, v}}``, batch=1) into ``slot``: the decoder's self-KV scatters
    through ``page_row`` exactly like :func:`adopt_slot_paged`, and the
    encoder's cross-KV scatters through ``cross_row`` into the SAME
    arenas.  The cross half is never written again — decode only reads it
    through ``cross_table`` — so these pages behave like refcounted prefix
    pages until retirement frees them.  The cross cache's frame count need
    not be page-aligned; the tail page is zero-padded in here and hidden
    behind ``cross_lengths``."""
    kv = pool["kv"]
    ps = kv["k"].shape[2]
    new_kv = {n: _copy_pages(kv[n], cache["self"][n], page_row)
              for n in ("k", "v")}
    new_kv = {n: _copy_pages(new_kv[n],
                             _pad_to_pages(cache["cross"][n], ps), cross_row)
              for n in ("k", "v")}
    return {**pool, "kv": new_kv,
            "page_table": pool["page_table"].at[slot].set(
                page_row.astype(jnp.int32)),
            "lengths": pool["lengths"].at[slot].set(
                jnp.asarray(length, jnp.int32)),
            "cross_table": pool["cross_table"].at[slot].set(
                cross_row.astype(jnp.int32)),
            "cross_lengths": pool["cross_lengths"].at[slot].set(
                jnp.asarray(cross_len, jnp.int32))}


def free_slot_paged(pool: dict, slot) -> dict:
    """Mark ``slot`` free: length 0, table row reset to the trash page (so
    the dead writes the jitted step still issues for it can't corrupt pages
    the allocator hands to someone else).  encdec pools also reset the
    slot's cross table/length (cross pages are read-only, but a stale row
    must not alias pages the allocator re-hands out)."""
    out = {**pool,
           "page_table": pool["page_table"].at[slot].set(TRASH_PAGE),
           "lengths": pool["lengths"].at[slot].set(0)}
    if "cross_table" in pool:
        out["cross_table"] = pool["cross_table"].at[slot].set(TRASH_PAGE)
        out["cross_lengths"] = pool["cross_lengths"].at[slot].set(0)
    return out


def set_page_row(pool: dict, slot, page_row) -> dict:
    """Update one slot's page-table row (page growth during decode: the
    scheduler's ``_ensure_pages`` allocates pages for upcoming write
    positions and mirrors them here before each burst).  Invariant: every
    entry past the slot's allocated pages must be the trash page, so the
    jitted step's write at position ``lengths`` can never land in a page
    the allocator still considers free."""
    return {**pool, "page_table": pool["page_table"].at[slot].set(
        page_row.astype(jnp.int32))}


def restore_slot_paged(pool: dict, blob, slot, length, page_row,
                       copy_row=None) -> dict:
    """Re-admit a demoted slot from its host-RAM page blob (the swap tier's
    promote path).  ``blob`` is a dict matching the arena leaf names, each
    leaf page-major ``[L, pages_per_slot, ps, ...]`` — exactly what
    :meth:`HostSwapStore` captured at demotion, padded to the table width;
    ``copy_row`` (default ``page_row``) routes the pad pages to the trash
    page so the one compiled scatter covers every restored length.  The
    scatter is a dtype-preserving copy of the demoted bytes (int8 pages and
    fp32 scales included), so demote → restore is bit-lossless — unlike
    preemption, which recomputes the prefix and, on a quantized pool,
    requantizes it."""
    if copy_row is None:
        copy_row = page_row
    new_kv = {n: pool["kv"][n].at[:, copy_row].set(
        blob[n].astype(pool["kv"][n].dtype)) for n in pool["kv"]}
    return {"kv": new_kv,
            "page_table": pool["page_table"].at[slot].set(
                page_row.astype(jnp.int32)),
            "lengths": pool["lengths"].at[slot].set(
                jnp.asarray(length, jnp.int32))}


class HostSwapStore:
    """Host-RAM store for demoted slots' pages (the swap tier's cold side).

    The scheduler demotes a cold slot under page pressure by copying its
    pages here (``np.asarray`` device pull — host-pinned buffers, exact
    bytes, scale sidecars included) instead of preempting: promotion is a
    scatter of the same bytes (:func:`restore_slot_paged`), not a prefill
    recompute.  ``budget_bytes`` caps the store (None = unbounded); a
    demote that would not fit is refused and the scheduler falls back to
    preemption.  Blobs are keyed by request id."""

    def __init__(self, budget_bytes: int | None = None):
        self.budget_bytes = budget_bytes
        self.bytes_used = 0
        self._blobs: dict[int, dict] = {}

    def __len__(self) -> int:
        return len(self._blobs)

    def __contains__(self, rid: int) -> bool:
        return rid in self._blobs

    @staticmethod
    def blob_bytes(blob: dict) -> int:
        return sum(x.size * x.dtype.itemsize for x in blob.values())

    def fits(self, nbytes: int) -> bool:
        return (self.budget_bytes is None
                or self.bytes_used + nbytes <= self.budget_bytes)

    def put(self, rid: int, blob: dict) -> bool:
        """Store ``rid``'s pages; False (nothing stored) if over budget."""
        import numpy as np  # host copies only; jnp stays off this path

        nbytes = self.blob_bytes(blob)
        if rid in self._blobs or not self.fits(nbytes):
            return False
        self._blobs[rid] = {n: np.asarray(x) for n, x in blob.items()}
        self.bytes_used += nbytes
        return True

    def pop(self, rid: int) -> dict:
        blob = self._blobs.pop(rid)
        self.bytes_used -= self.blob_bytes(blob)
        return blob


class PageAllocator:
    """Host-side refcounted free list over arena pages ``1 .. pages - 1``
    (page 0 is the trash page and is never handed out).  Device state never
    sees this — the scheduler allocs/frees here and mirrors decisions into
    the pool's page table.

    Refcounts are what make prefix sharing safe: a page handed out by
    :meth:`alloc` starts at refcount 1; every additional reader (another
    slot's page table, the prefix index) takes a reference with
    :meth:`share`; :meth:`free` drops one reference and the page returns to
    the free list only when its LAST reader leaves.  A ``free`` past zero
    is the double-free bug class paging is famous for, and asserts."""

    def __init__(self, pages: int):
        self.n_pages = int(pages)
        self._free = list(range(self.n_pages - 1, 0, -1))
        self._refs = [0] * self.n_pages

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def usable_pages(self) -> int:
        return self.n_pages - 1

    def refcount(self, page_id: int) -> int:
        """Current reader count of one page (0 = on the free list)."""
        return self._refs[page_id]

    def alloc(self, n: int) -> list[int] | None:
        """``n`` distinct pages (each at refcount 1), or None (nothing
        allocated) if short — all-or-nothing, so a failed admission/growth
        never leaks a partial allocation the caller would have to unwind."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        return out

    def share(self, page_ids) -> None:
        """Take one additional reference on each page (prefix sharing: a
        second slot's table row, or the prefix index itself, now reads the
        page).  Sharing a free page is a use-after-free and asserts."""
        for p in page_ids:
            assert 0 < p < self.n_pages, f"bad page id {p}"
            assert self._refs[p] > 0, f"share of free page {p}"
            self._refs[p] += 1

    def free(self, page_ids) -> None:
        """Drop one reference per page (retirement, preemption, or prefix
        eviction); a page returns to the free list only at refcount 0.
        Callers must reset the owning table row to the trash page FIRST
        (``free_slot_paged``): a freed page may be handed to another slot
        in the same scheduler iteration, and the old owner's dead writes
        would otherwise corrupt it."""
        for p in page_ids:
            assert 0 < p < self.n_pages, f"bad page id {p}"
            assert self._refs[p] > 0, f"double free of page {p}"
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)


# ---------------------------------------------------------------------------
# Memory accounting (scheduler slot budgeting).
# ---------------------------------------------------------------------------
def cache_bytes(cfg: ModelConfig, batch: int, max_len: int,
                tp: int = 1) -> int:
    """Total bytes of a plain (non-pool) decode cache, computed via
    ``eval_shape`` — no device allocation, safe at any size.  The budget
    helpers below all follow this pattern: evaluate shapes at two sizes
    and solve the affine byte model instead of materializing pools."""
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, max_len, tp))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def slot_pool_bytes(cfg: ModelConfig, slots: int, max_len: int,
                    tp: int = 1) -> int:
    """Total bytes of a ``slots``-wide pool (cache + lengths array)."""
    pool = jax.eval_shape(lambda: init_slot_pool(cfg, slots, max_len, tp))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))


def max_slots_in_budget(cfg: ModelConfig, max_len: int, budget_bytes: int,
                        tp: int = 1) -> int:
    """Largest slot count whose pool fits ``budget_bytes`` (0 if even one
    slot does not fit).  Pool bytes are affine in the slot count, so two
    shape evaluations determine the answer."""
    one = slot_pool_bytes(cfg, 1, max_len, tp)
    two = slot_pool_bytes(cfg, 2, max_len, tp)
    per_slot = max(1, two - one)
    fixed = one - per_slot
    n = (budget_bytes - fixed) // per_slot
    return max(0, int(n))


def paged_pool_bytes(cfg: ModelConfig, slots: int, max_len: int,
                     tp: int = 1, *, page_size: int | None = None,
                     pages: int | None = None,
                     page_dtype: str | None = None,
                     scale_granularity: str | None = None) -> int:
    """Total bytes of a paged pool (arenas + page table + lengths; on an
    int8 pool the scale sidecars are counted too)."""
    pool = jax.eval_shape(lambda: init_paged_pool(
        cfg, slots, max_len, tp, page_size=page_size, pages=pages,
        page_dtype=page_dtype, scale_granularity=scale_granularity))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))


def max_pages_in_budget(cfg: ModelConfig, slots: int, max_len: int,
                        budget_bytes: int, tp: int = 1, *,
                        page_size: int | None = None,
                        page_dtype: str | None = None,
                        scale_granularity: str | None = None) -> int:
    """Largest arena page count (trash page included) whose pool fits
    ``budget_bytes`` at the given slot count.  Pool bytes are affine in
    the page count, so two shape evaluations determine the answer.  int8
    pages (plus their scale rows) cost ~half the bytes of bf16 pages, so
    the same budget buys ~2x the pages — the capacity half of the
    quantization win."""
    kw = dict(page_size=page_size, page_dtype=page_dtype,
              scale_granularity=scale_granularity)
    one = paged_pool_bytes(cfg, slots, max_len, tp, pages=1, **kw)
    two = paged_pool_bytes(cfg, slots, max_len, tp, pages=2, **kw)
    per_page = max(1, two - one)
    fixed = one - per_page
    n = (budget_bytes - fixed) // per_page
    return max(0, int(n))


def paged_dims_in_budget(cfg: ModelConfig, max_len: int, budget_bytes: int,
                         tp: int = 1, *, page_size: int,
                         avg_tokens: int,
                         page_dtype: str | None = None,
                         scale_granularity: str | None = None
                         ) -> tuple[int, int]:
    """(slots, pages) for a paged pool under ``budget_bytes``: the budget
    buys PAGES; the slot count is sized for ``avg_tokens``-token requests
    (concurrency = usable page tokens / avg request tokens) — the
    oversubscription that lets a paged pool serve more concurrent requests
    than ``max_len`` strips at the same byte budget.  Slot metadata
    (page-table rows, hybrid ssm state) also costs bytes, so the pair is
    solved by a short fixed-point iteration."""
    kw = dict(page_size=page_size, page_dtype=page_dtype,
              scale_granularity=scale_granularity)
    slots = 1
    pages = 0
    for _ in range(4):
        pages = max_pages_in_budget(cfg, slots, max_len, budget_bytes, tp,
                                    **kw)
        if pages < 2:
            break
        new_slots = max(1, ((pages - 1) * page_size) // max(1, avg_tokens))
        if new_slots == slots:
            break
        slots = new_slots
    else:
        # iteration cap hit with slots just grown: re-fit pages to the
        # final slot count so the pool stays within budget
        pages = max_pages_in_budget(cfg, slots, max_len, budget_bytes, tp,
                                    **kw)
    return slots, pages

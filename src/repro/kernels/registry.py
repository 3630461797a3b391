"""Kernel registry: the one canonical block-shape model for every Pallas op.

The paper's central meta-parameters — tile shape / number of accumulators —
were previously duplicated as three divergent heuristics (softmax, fused
xent, flash attention).  This module collapses them into one model:

  * every kernel registers a :class:`KernelSpec` describing its alignment
    grid (sublane/lane multiples) and caps,
  * :func:`block_shapes` resolves ``(rows, cols)`` for a key
    ``(op, rows, cols, dtype, backend)`` through a three-level chain:
    explicit overrides > persisted autotune cache > the spec's heuristic,
  * the autotune cache is a JSON file written by ``repro.kernels.autotune``
    and shared across processes/runs (keys are shape-bucketed so one sweep
    covers a band of nearby shapes).

``ops.py`` and ``core.softmax_api`` are thin shims over this registry.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from dataclasses import dataclass
from typing import Callable, Optional

import jax

DEFAULT_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE_FILE = os.path.join(
    os.path.expanduser("~"), ".cache", "repro_twopass", "autotune.json")


def round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


# ---------------------------------------------------------------------------
# Kernel specs.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class KernelSpec:
    """One registered kernel + its block-shape model parameters.

    The heuristic (shared by every op) is:
      cols: full row width while ``cols <= full_col_threshold`` (one grid
            step along the reduction => no fold overhead), else ``col_cap``;
            always a ``col_align`` (lane) multiple.
      rows: smallest ``row_align`` (sublane) multiple covering ``rows``,
            clamped to ``[row_align, row_cap]``.

    The tuner may explore past the heuristic caps: ``tune_row_cap`` /
    ``tune_col_cap`` bound the autotune candidate sweep AND the clamp
    applied to cache-sourced entries (None falls back to ``row_cap`` /
    ``2 * col_cap``, the pre-existing envelope).  ``sweep_budget_bytes``
    is the double-buffered f32 working-set bound for candidates — ops
    that stream through XLA rather than VMEM (chunk_attention) set it
    higher than the Pallas default.
    """
    name: str
    fn: Optional[Callable] = None        # 2-D kernel entry point (or None)
    row_align: int = 8
    row_cap: int = 256
    col_align: int = 128
    col_cap: int = 2048
    full_col_threshold: int = 4096
    tune_row_cap: Optional[int] = None
    tune_col_cap: Optional[int] = None
    sweep_budget_bytes: int = 4 << 20

    def heuristic_blocks(self, rows: int, cols: int) -> tuple[int, int]:
        bc = cols if cols <= self.full_col_threshold else self.col_cap
        bc = round_up(min(bc, round_up(cols, self.col_align)),
                      self.col_align)
        br = max(self.row_align,
                 min(self.row_cap, round_up(rows, self.row_align)))
        return br, bc

    def envelope(self) -> tuple[int, int]:
        """(max rows, max cols) a tuned/candidate block may take."""
        return (self.tune_row_cap or self.row_cap,
                self.tune_col_cap or 2 * self.col_cap)


_REGISTRY: dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get_spec(op: str) -> KernelSpec:
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; "
                       f"registered: {sorted(_REGISTRY)}")
    return _REGISTRY[op]


def registered_ops() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Autotune cache (JSON, persisted across runs).  Memoized per cache file so
# multiple policies with different cache paths coexist in one process.
# ---------------------------------------------------------------------------
_cache_lock = threading.Lock()
_caches: dict[str, dict] = {}              # cache file path -> entries


def cache_path(path: str | None = None) -> str:
    return path or os.environ.get(DEFAULT_CACHE_ENV) or DEFAULT_CACHE_FILE


def _bucket(x: int) -> int:
    """Pow-2 shape bucket: one tuned entry covers nearby shapes."""
    return 1 << max(0, (x - 1).bit_length())


def cache_key(op: str, rows: int, cols: int, dtype, backend: str,
              shards: int = 1) -> str:
    """Bucketed tuning key.  ``shards`` is the tensor-parallel head-shard
    count the op runs under (shard_map over the serving mesh): a per-shard
    grid sees ``Hkv/shards`` heads, so its best tile differs from the
    unsharded one.  ``shards=1`` keeps the historical key format — existing
    cache files stay valid."""
    key = "|".join((op, f"r{_bucket(rows)}", f"c{_bucket(cols)}",
                    str(jax.numpy.dtype(dtype)), backend))
    return key if shards <= 1 else f"{key}|s{shards}"


def load_cache(path: str | None = None, *, force: bool = False) -> dict:
    """Loads (and memoizes per path) the JSON cache; missing file => {}."""
    p = cache_path(path)
    with _cache_lock:
        if not force and p in _caches:
            return _caches[p]
        try:
            with open(p) as f:
                _caches[p] = json.load(f)
        except (OSError, ValueError):
            _caches[p] = {}
        return _caches[p]


def save_cache(path: str | None = None) -> str:
    p = cache_path(path)
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    with _cache_lock:
        tmp = p + ".tmp"
        with open(tmp, "w") as f:
            json.dump(_caches.get(p, {}), f, indent=2, sort_keys=True)
        os.replace(tmp, p)
    return p


def record_tuned(op: str, rows: int, cols: int, dtype,
                 blocks: tuple[int, int], *, backend: str | None = None,
                 meta: dict | None = None, path: str | None = None,
                 persist: bool = True, shards: int = 1) -> str:
    """Stores a tuned block shape; returns the cache key."""
    backend = backend or jax.default_backend()
    key = cache_key(op, rows, cols, dtype, backend, shards)
    p = cache_path(path)
    load_cache(p)
    with _cache_lock:
        _caches[p][key] = dict(block_rows=int(blocks[0]),
                               block_cols=int(blocks[1]), **(meta or {}))
    if persist:
        save_cache(p)
    return key


def lookup_tuned(op: str, rows: int, cols: int, dtype,
                 *, backend: str | None = None, path: str | None = None,
                 shards: int = 1) -> Optional[tuple[int, int]]:
    backend = backend or jax.default_backend()
    entry = load_cache(path).get(
        cache_key(op, rows, cols, dtype, backend, shards))
    if entry is None:
        return None
    return int(entry["block_rows"]), int(entry["block_cols"])


# ---------------------------------------------------------------------------
# Resolution: overrides > autotune cache > heuristic.
# ---------------------------------------------------------------------------
def block_shapes(op: str, rows: int, cols: int, dtype=jax.numpy.float32, *,
                 block_rows: int | None = None, block_cols: int | None = None,
                 use_cache: bool = False, backend: str | None = None,
                 cache_file: str | None = None,
                 shards: int = 1) -> tuple[int, int]:
    """The canonical block-shape model (every former heuristic collapsed).

    Explicit ``block_rows``/``block_cols`` win (per-axis); otherwise, with
    ``use_cache=True`` (opt-in: ``SoftmaxPolicy(autotune=True)``), a
    persisted autotune entry for the bucketed key; otherwise the registered
    spec's heuristic.  Cache entries are clamped to the tuner's candidate
    envelope (rows <= row_cap, cols <= 2 * col_cap) so a stale or
    hand-edited cache can't produce a pathological grid; explicit overrides
    pass through (alignment-rounded only), matching the former per-site
    heuristics.
    """
    spec = get_spec(op)
    tuned = None
    if use_cache and (block_rows is None or block_cols is None):
        tuned = lookup_tuned(op, rows, cols, dtype, backend=backend,
                             path=cache_file, shards=shards)
        if tuned is not None:
            # Clamp to the candidate envelope AND this shape's own padded
            # width — a pow-2 bucket neighbor must not inherit a tile wider
            # than its data (that would inflate padding work).
            er, ec = spec.envelope()
            tuned = (min(tuned[0], er, round_up(rows, spec.row_align)),
                     min(tuned[1], ec, round_up(cols, spec.col_align)))
    hr, hc = spec.heuristic_blocks(rows, cols)
    br = block_rows if block_rows is not None else (
        tuned[0] if tuned else hr)
    bc = block_cols if block_cols is not None else (
        tuned[1] if tuned else hc)
    br = max(spec.row_align, round_up(br, spec.row_align))
    bc = max(spec.col_align, round_up(bc, spec.col_align))
    return br, bc


def candidate_blocks(
        op: str, rows: int, cols: int, *,
        vmem_budget_bytes: int | None = None) -> list[tuple[int, int]]:
    """Autotune sweep candidates: aligned tiles around the heuristic point,
    bounded by the spec's double-buffered f32 working-set budget."""
    spec = get_spec(op)
    budget = vmem_budget_bytes or spec.sweep_budget_bytes
    er, ec = spec.envelope()
    row_opts = sorted({max(spec.row_align, min(er, r))
                       for r in (8, 16, 32, 64, 128, 256,
                                 round_up(rows, spec.row_align))})
    col_opts = sorted({max(spec.col_align, min(ec, c))
                       for c in (128, 256, 512, 1024, 2048, 4096,
                                 round_up(cols, spec.col_align))})
    cands = []
    for br in row_opts:
        if br > round_up(rows, spec.row_align):
            continue
        for bc in col_opts:
            if bc > round_up(cols, spec.col_align):
                continue
            if 2 * 4 * br * bc > budget:              # 2x double-buffer
                continue
            cands.append((br, bc))
    hr, hc = spec.heuristic_blocks(rows, cols)
    if (hr, hc) not in cands:
        cands.append((hr, hc))
    return cands


# ---------------------------------------------------------------------------
# Registered ops.  ``fn`` is filled in lazily by ops.py (kernels import this
# module, not vice versa, so specs are declared here dependency-free).
# ---------------------------------------------------------------------------
# softmax: blocks at most 2048 columns wide, tuned or not.  A wider row
# streams its columns (pass 1 folds them into (m, n)); a 2304- or
# 3072-column block at 256 rows asks more than v5e's 16 MiB of scoped
# VMEM (prefill scores past the 2048 bucket).
register(KernelSpec(name="softmax", full_col_threshold=2048,
                    tune_col_cap=2048))
register(KernelSpec(name="logsumexp"))
# fused CE: the former _xent_blocks capped block_v at 2048 unconditionally
register(KernelSpec(name="xent", full_col_threshold=2048))
# flash attention: MXU tiles, 128-aligned both axes (rows=Sq, cols=Skv).
# The heuristic stays at the safe (128, 128) MXU tile; the tuner may find
# larger tiles profitable (fewer accumulator folds per KV sweep), so its
# envelope extends to 512 on both axes.
register(KernelSpec(name="flash_attention", row_align=128, row_cap=128,
                    col_align=128, col_cap=128, full_col_threshold=0,
                    tune_row_cap=512, tune_col_cap=512))
# chunked-jnp attention (models.attention.mn_chunk_attention): blocks are
# CHUNK LENGTHS along (Sq, Skv); chunk counts are the ceil-div of the
# sequence by the resolved block.  XLA streams the chunks (no VMEM tile),
# so the sweep budget is wide; 256-alignment keeps the candidate set (and
# the number of unrolled-loop variants compiled during a sweep) small.
register(KernelSpec(name="chunk_attention", row_align=256, row_cap=2048,
                    col_align=256, col_cap=2048, full_col_threshold=2048,
                    tune_row_cap=2048, tune_col_cap=4096,
                    sweep_budget_bytes=64 << 20))
# decode attention (ops.decode_attention): single-query attention against a
# length-masked slot-major KV cache (continuous-batching decode).  rows =
# SLOTS (each slot carries exactly one query), cols = cache positions (Skv
# allocation).  Two implementations share the spec (dispatch on
# SoftmaxPolicy.use_kernels): the Pallas kernel
# (kernels/decode_attention.py) streams KV in block_cols VMEM tiles — the
# slot axis never tiles, one grid row per slot — while the jnp fallback
# uses the blocks as chunk LENGTHS for its unrolled (m, n) loop (counts =
# ceil-div capped by ops.MAX_SLOT_CHUNKS/MAX_T_CHUNKS).  The heuristic
# keeps typical serving shapes (pools <= 256 slots, caches <= 4096
# positions) single-chunk; the sweep may find streaming tiles profitable
# for long caches.  The jnp path streams through XLA (no VMEM tile), so
# the sweep budget is wide.
register(KernelSpec(name="decode_attention", row_align=8, row_cap=256,
                    col_align=128, col_cap=2048, full_col_threshold=4096,
                    tune_row_cap=256, tune_col_cap=4096,
                    sweep_budget_bytes=64 << 20))
# paged decode attention (ops.decode_attention_paged): the same single-query
# (m, n) sweep, but K/V are gathered through a per-slot page table from a
# shared page arena (serving/kv_cache.init_paged_pool) instead of read from
# a contiguous slot strip.  rows = slots, cols = LOGICAL cache positions
# (page_table width * page size); the resolved col block is rounded to a
# whole number of pages so every gather touches full pages — on the Pallas
# path that page count per tile is the scalar-prefetch gather width
# (capped by decode_attention.MAX_PAGES_PER_TILE); the jnp fallback feeds
# it to per-chunk jnp.take gathers.
register(KernelSpec(name="decode_attention_paged", row_align=8, row_cap=256,
                    col_align=128, col_cap=2048, full_col_threshold=4096,
                    tune_row_cap=256, tune_col_cap=4096,
                    sweep_budget_bytes=64 << 20))
# KV-cache page size (serving/kv_cache.resolve_page_size): cols model the
# TOKENS PER PAGE of the paged pool — the granularity requests allocate
# cache in.  Resolution runs the standard chain (explicit page_size= >
# autotune cache > heuristic); the heuristic is the classic 128-token page,
# shrunk to the pool's own padded length for tiny pools so smoke-sized
# configs don't round a 24-token cache up to a 128-token page.
register(KernelSpec(name="kv_page", row_align=1, row_cap=1,
                    col_align=16, col_cap=128, full_col_threshold=0,
                    tune_col_cap=512))
# Quantized KV pages (serving/kv_cache.resolve_page_quant): cols model the
# tokens per page of an int8 pool exactly like ``kv_page``; rows model the
# SCALE GRANULARITY — 1 = one fp32 scale per stored position ("page"),
# >1 = one per (position, kv head) ("page_head").  The heuristic keeps the
# kv_page geometry with "page" scales (smallest sidecar: 4 bytes/token per
# leaf); the tuner may find per-head scales worth their extra bytes
# (tune_row_cap=8 bounds a cache entry's row count, clamped to the pool's
# own n_kv_heads at resolution), and sweeps page sizes like kv_page.  The
# runner times the fused-dequant paged decode op under each geometry, so
# the tradeoff it measures is the real one: sidecar gather width vs
# per-tile dequant work.
register(KernelSpec(name="kv_page_quant", row_align=1, row_cap=1,
                    col_align=16, col_cap=128, full_col_threshold=0,
                    tune_row_cap=8, tune_col_cap=512))
# flash-attention backward (ops.flash_attention_bwd): recompute-style
# dq/dk/dv from the forward's saved (m, n) statistics.  Same MXU geometry
# as the forward — rows = Sq tiles, cols = Skv tiles — but the bwd streams
# BOTH directions (dq sweeps KV innermost, dk/dv sweep Q innermost), so
# the profitable tile can differ from the forward's; it gets its own cache
# entry, keyed with the ``|s{tp}`` shard suffix when the q-head axis is
# mesh-sharded.  The jnp "twopass" implementation reads the same blocks as
# chunk lengths for its unrolled (m, n) loops.
register(KernelSpec(name="flash_attention_bwd", row_align=128, row_cap=128,
                    col_align=128, col_cap=128, full_col_threshold=0,
                    tune_row_cap=512, tune_col_cap=512))
# fused LM-head CE (ops.lmhead_cross_entropy): rows = tokens, cols = VOCAB
# — the streamed axis (logits recomputed from h @ w per vocab tile in both
# passes; nothing [T, V]-shaped ever materializes).  xent's geometry, but
# its own entry: the bwd re-streams the vocab three times (fwd stats, dh,
# dw), so the profitable tile trades recompute against VMEM differently
# than the logits-in-memory xent op.  Cache keys carry ``|s{tp}`` when the
# vocab axis is mesh-sharded (each shard streams V/tp columns).
register(KernelSpec(name="lmhead_xent", full_col_threshold=2048))


def bind(op: str, fn: Callable) -> None:
    """Attach the kernel entry point to a registered spec (called by ops)."""
    _REGISTRY[op] = dataclasses.replace(get_spec(op), fn=fn)

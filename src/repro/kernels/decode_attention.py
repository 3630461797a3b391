"""Pallas TPU kernels: single-query decode attention, contiguous and PAGED.

The serving hot path is one query per slot against that slot's whole KV
cache — the most bandwidth-bound softmax consumer in the repo.  These
kernels fuse what the jnp (m, n) reference forms in ``ops.py`` do in
separate XLA stages:

  * the **length/window mask** is applied in-register per KV tile (no
    masked score matrix ever reaches HBM),
  * the online softmax runs in the paper's ``(m_sum, n_sum)`` extended
    representation — accumulator rescales are *exact* powers of two
    (``exp2_int``), so KV tiles (and therefore pages) may be folded in any
    order, which is exactly what a non-contiguous paged cache needs,
  * the paged variant gathers arena pages **tile-by-tile in VMEM** through
    a scalar-prefetched page table (``pltpu.PrefetchScalarGridSpec``): the
    table is available before the kernel body runs, so each grid step's
    page DMAs are issued from table entries instead of materializing a
    host-visible ``jnp.take`` gather of the whole slot in HBM.

Grid layout: ``(slots, Hkv, KV tiles)`` for the contiguous kernel and
``(slots, KV tiles)`` for the paged one, the KV sweep innermost either
way, so the per-(slot, head) accumulators ``(o, m_sum, n_sum)`` live in
VMEM across the whole sweep (same revisited-output pattern as
``flash_attention``).  A paged grid step fetches every KV head of its
pages: the arena is ``[P, ps, Hkv, D]``, and the TPU lowering only takes
blocks whose last two dims are (8, 128)-aligned or whole, so a one-head
``(ps, 1, D)`` slice of a page cannot be a block.  The head loop runs
inside the kernel instead.  One grid row per slot: the slot axis never
tiles — the tunable dims are the KV tile length (``block_t``, contiguous)
and the page count per tile (``pages_per_tile``, paged), swept by
``repro.kernels.autotune`` through the ``decode_attention`` /
``decode_attention_paged`` registry ops.

Dispatch: ``ops.decode_attention`` / ``ops.decode_attention_paged`` route
here when the :class:`SoftmaxPolicy` says ``use_kernels`` (interpret mode
on CPU) and fall back to the jnp (m, n) chunked forms otherwise — the jnp
forms remain the reference these kernels are tested against
(``tests/test_decode_kernels.py``).

Tensor-parallel serving: heads are independent (the grid's Hkv axis never
communicates), so under a serving mesh ``ops`` wraps these kernels in
``shard_map`` with the head axis over ``model`` — each shard's grid sees
its LOCAL ``Hkv / tp`` head count (taken from ``q.shape``, so nothing
here changes), and the per-shard variant autotunes under its own
``shards=tp`` registry key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.numerics import exp2_int, ext_exp
from repro.kernels.twopass_softmax import _interpret, _tpu_params

NEG_INF = -jnp.inf

# Pages gathered per paged-kernel grid step.  Each page is its own
# scalar-prefetch block fetch, so the cap bounds the number of BlockSpecs
# (and DMAs in flight) per step the way MAX_T_CHUNKS bounds the unrolled
# jnp loops.
MAX_PAGES_PER_TILE = 8


def _grid_spec(num_scalar_prefetch, grid, in_specs, out_specs):
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs)


def _mn_fold_tile(o_ref, m_ref, n_ref, q, k, v, kpos, length, *,
                  scale: float, window: int | None, j, last_j: int,
                  k_scale=None, v_scale=None, h: int = 0):
    """Score one KV tile, mask it, fold it into the running (o, m, n)
    accumulator refs (head ``h`` of their block), and normalize on the
    sweep's last step.

    ``q``: (G, D) f32; ``k``/``v``: (BT, D)/(BT, Dv) f32; ``kpos``: int32
    (1, BT) logical cache positions of the tile's columns (2-D for Mosaic's
    iota rules); ``length``: the slot's
    valid prefix (its own query sits at ``length - 1``, write-then-attend,
    so the validity prefix IS the causal mask and SWA is a lower bound off
    that query position).  A fully-masked tile contributes the exact
    monoid zero (m=0, n=-inf); a fully-masked SLOT (length 0, a free pool
    slot) ends with m_sum == 0 and the normalize guard returns exact
    zeros, never NaN — matching the jnp reference forms bit-for-bit in
    structure (the accumulation order within a tile differs, so parity is
    allclose, not bitwise).

    ``k_scale``/``v_scale`` ((1, BT) f32) fuse int8 dequantization into
    the fold: ``k``/``v`` then hold raw int8 codes cast to f32 in-register
    and the symmetric per-column scales commute through the dots —
    ``(q · k) * k_scale`` scores and ``(w * v_scale) · v`` output equal
    attention over dequantized tiles with zero extra passes, the paper's
    bandwidth argument applied to the arena bytes themselves.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        s = s * k_scale                              # (G, BT) * (1, BT)
    mask = kpos < length                             # (1, BT), broadcasts
    if window is not None:
        mask &= kpos > length - 1 - window
    s = jnp.where(mask, s, NEG_INF)

    m, n = ext_exp(s)                                # (G, BT) pairs
    n_loc = jnp.max(n, axis=-1, keepdims=True)       # (G, 1)
    w = m * exp2_int(n - n_loc)                      # numerators / 2^n_loc
    m_loc = jnp.sum(w, axis=-1, keepdims=True)
    if v_scale is not None:
        w = w * v_scale                              # fold AFTER m_loc
    o_loc = jax.lax.dot_general(w, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        o_ref[0, h] = o_loc
        m_ref[0, h] = m_loc
        n_ref[0, h] = n_loc

    @pl.when(j > 0)
    def _fold():
        n_old = n_ref[0, h]
        n_new = jnp.maximum(n_old, n_loc)
        a_old = exp2_int(n_old - n_new)              # exact 2^k rescales
        a_loc = exp2_int(n_loc - n_new)
        o_ref[0, h] = o_ref[0, h] * a_old + o_loc * a_loc
        m_ref[0, h] = m_ref[0, h] * a_old + m_loc * a_loc
        n_ref[0, h] = n_new

    @pl.when(j == last_j)
    def _normalize():
        # max() guard: a free slot (length 0) has m_sum == 0 -> exact zeros
        o_ref[0, h] = o_ref[0, h] / jnp.maximum(m_ref[0, h], 1e-37)


def _contig_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, n_ref, *,
                   scale: float, window: int | None, block_t: int, nt: int):
    s_idx = pl.program_id(0)
    j = pl.program_id(2)
    kpos = (j * block_t
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1))
    _mn_fold_tile(o_ref, m_ref, n_ref,
                  q_ref[0, 0].astype(jnp.float32),
                  k_ref[0, 0].astype(jnp.float32),
                  v_ref[0, 0].astype(jnp.float32),
                  kpos, len_ref[s_idx], scale=scale, window=window,
                  j=j, last_j=nt - 1)


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_t"))
def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            lengths: jax.Array, *, scale: float,
                            window: int | None = None,
                            block_t: int = 128) -> jax.Array:
    """Single-query length-masked attention, Pallas path.

    q: [S, Hkv, G, D]; k: [S, Hkv, T, D]; v: [S, Hkv, T, Dv]; lengths: [S]
    int32 (scalar-prefetched; 0 marks a free slot, output exact zeros).
    Returns [S, Hkv, G, Dv] in q.dtype — allclose to the jnp reference
    ``ops`` falls back to.  The KV axis is padded here to a ``block_t``
    multiple with zeros: padded positions sit at ``kpos >= T >= lengths``,
    so the length mask kills them (no -inf padding needed).
    """
    s, hkv, g, d = q.shape
    t = k.shape[2]
    dv = v.shape[3]
    bt = min(block_t, pl.cdiv(t, 128) * 128)
    pt = pl.cdiv(t, bt) * bt
    if pt != t:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pt - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pt - t), (0, 0)))
    nt = pt // bt

    kernel = functools.partial(_contig_kernel, scale=scale, window=window,
                               block_t=bt, nt=nt)
    grid_spec = _grid_spec(
        1, (s, hkv, nt),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda si, h, j, ln: (si, h, 0, 0)),
            pl.BlockSpec((1, 1, bt, d), lambda si, h, j, ln: (si, h, j, 0)),
            pl.BlockSpec((1, 1, bt, dv), lambda si, h, j, ln: (si, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, dv), lambda si, h, j, ln: (si, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda si, h, j, ln: (si, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda si, h, j, ln: (si, h, 0, 0)),
        ])
    o, _, _ = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, hkv, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((s, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, hkv, g, 1), jnp.float32),
        ],
        interpret=_interpret(),
        **_tpu_params(("parallel", "parallel", "arbitrary")),
    )(lengths.astype(jnp.int32), q, k, v)
    return o.astype(q.dtype)


def _paged_kernel(pt_ref, len_ref, q_ref, *refs, scale: float,
                  window: int | None, ps: int, ppt: int, nt: int,
                  hkv: int, quant: bool = False):
    krefs, vrefs = refs[:ppt], refs[ppt:2 * ppt]
    if quant:
        # int8 arenas: the pages' fp32 scale rows ride the same
        # scalar-prefetch gather, one block per page: (1, 1, ps) for
        # "page" scales, (1, ps, Hkv) for "page_head".
        ksrefs, vsrefs = refs[2 * ppt:3 * ppt], refs[3 * ppt:4 * ppt]
        o_ref, m_ref, n_ref = refs[4 * ppt:]
    else:
        o_ref, m_ref, n_ref = refs[2 * ppt:]

    def srow(r, h):                          # -> (1, ps) per-column scales
        return r[0] if r.shape[1] == 1 else r[0, :, h][None, :]

    s_idx = pl.program_id(0)
    j = pl.program_id(1)
    kpos = (j * (ppt * ps)
            + jax.lax.broadcasted_iota(jnp.int32, (1, ppt * ps), 1))
    for h in range(hkv):
        # Each of the tile's ppt pages arrived via its own scalar-prefetch
        # block fetch (non-contiguous in the arena); concatenated they
        # form the contiguous logical window [j*ppt*ps, (j+1)*ppt*ps).  On
        # the quantized path the astype is the whole dequant story: int8
        # codes widen to f32 IN REGISTER, per tile — the arena itself is
        # never copied to a full-precision buffer.
        k = jnp.concatenate([r[0, :, h, :].astype(jnp.float32)
                             for r in krefs], 0)
        v = jnp.concatenate([r[0, :, h, :].astype(jnp.float32)
                             for r in vrefs], 0)
        ks = vs = None
        if quant:
            ks = jnp.concatenate([srow(r, h) for r in ksrefs], 1)
            vs = jnp.concatenate([srow(r, h) for r in vsrefs], 1)
        _mn_fold_tile(o_ref, m_ref, n_ref, q_ref[0, h].astype(jnp.float32),
                      k, v, kpos, len_ref[s_idx], scale=scale,
                      window=window, j=j, last_j=nt - 1, k_scale=ks,
                      v_scale=vs, h=h)


@functools.partial(jax.jit,
                   static_argnames=("scale", "window", "pages_per_tile"))
def decode_attention_paged_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array, page_table: jax.Array,
                                  lengths: jax.Array,
                                  k_scale: jax.Array | None = None,
                                  v_scale: jax.Array | None = None,
                                  *, scale: float,
                                  window: int | None = None,
                                  pages_per_tile: int = 1) -> jax.Array:
    """Single-query attention against a PAGED cache, Pallas path.

    q: [S, Hkv, G, D]; k_pages/v_pages: [P, ps, Hkv, D|Dv] page arenas
    (``kv_cache.init_paged_pool`` layout); page_table: [S, Pmax] int32;
    lengths: [S] int32.  Both int32 operands are scalar-prefetched: the
    per-page BlockSpec index maps read ``page_table`` directly, so each
    grid step DMAs ``pages_per_tile`` non-contiguous arena pages (all
    their KV heads) into VMEM and attends them, head by head, as one
    contiguous logical window.  Table entries backing no valid position
    (free slots, pages past ``lengths``, the pad below) may point
    anywhere in the arena — the length mask makes their content
    invisible.  Returns [S, Hkv, G, Dv] in q.dtype.

    int8 arenas pass ``k_scale``/``v_scale`` fp32 sidecars (``[P, ps]``
    "page" granularity or ``[P, ps, Hkv]`` "page_head"): each page's scale
    row is gathered as one more scalar-prefetch block alongside its page,
    and dequantization happens inside the (m, n) fold — int8 codes widen
    to f32 in-register per tile, scales apply as per-column multipliers
    (:func:`_mn_fold_tile`); a full-precision copy of the arena is never
    materialized in HBM or VMEM.  "page" scales are viewed as
    ``[P, 1, ps]`` so that a page's row is a whole-dims block.
    """
    s, hkv, g, d = q.shape
    ps = k_pages.shape[1]
    dv = v_pages.shape[3]
    pmax = page_table.shape[1]
    quant = k_scale is not None
    ppt = max(1, min(pages_per_tile, pmax, MAX_PAGES_PER_TILE))
    ppad = pl.cdiv(pmax, ppt) * ppt
    if ppad != pmax:
        # pad the table with arena page 0 (the pool's trash page; any
        # in-bounds id works — padded logical positions are masked)
        page_table = jnp.pad(page_table, ((0, 0), (0, ppad - pmax)))
    nt = ppad // ppt

    def page_spec(i, block):
        return pl.BlockSpec(
            block,
            lambda si, j, tab, ln, i=i: (tab[si, j * ppt + i],)
            + (0,) * (len(block) - 1))

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               ps=ps, ppt=ppt, nt=nt, hkv=hkv, quant=quant)
    scale_specs, scale_args = [], ()
    if quant:
        if k_scale.ndim == 2:                        # [P, ps] "page"
            k_scale = k_scale[:, None, :]
            v_scale = v_scale[:, None, :]
        blk = (1,) + k_scale.shape[1:]
        scale_specs = [page_spec(i, blk) for i in range(ppt)] * 2
        scale_args = (*([k_scale] * ppt), *([v_scale] * ppt))
    head_spec = pl.BlockSpec((1, hkv, g, d),
                             lambda si, j, tab, ln: (si, 0, 0, 0))
    grid_spec = _grid_spec(
        2, (s, nt),
        in_specs=(
            [head_spec]
            + [page_spec(i, (1, ps, hkv, d)) for i in range(ppt)]
            + [page_spec(i, (1, ps, hkv, dv)) for i in range(ppt)]
            + scale_specs),
        out_specs=[
            pl.BlockSpec((1, hkv, g, dv),
                         lambda si, j, tab, ln: (si, 0, 0, 0)),
            pl.BlockSpec((1, hkv, g, 1),
                         lambda si, j, tab, ln: (si, 0, 0, 0)),
            pl.BlockSpec((1, hkv, g, 1),
                         lambda si, j, tab, ln: (si, 0, 0, 0)),
        ])
    o, _, _ = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, hkv, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((s, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, hkv, g, 1), jnp.float32),
        ],
        interpret=_interpret(),
        **_tpu_params(("parallel", "arbitrary")),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q, *([k_pages] * ppt), *([v_pages] * ppt), *scale_args)
    return o.astype(q.dtype)

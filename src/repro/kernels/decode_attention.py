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
    host-visible ``jnp.take`` gather of the whole slot in HBM,
  * the paged sweep is **bounded by each slot's length**: its grid is one
    step per KV tile that holds a valid position — a work list of
    (slot, tile) pairs, built on the device from the lengths and
    scalar-prefetched, whose length is the grid's (dynamic) size — and
    the page index maps clamp every fetch to the slot's last valid page.
    A slot reads ``ceil(length / ps)`` distinct pages, plus at most
    ``pages_per_tile - 1`` repeats of its last page inside its last tile;
    a free slot (length 0) has no grid step and its output is zeros.

Grid layout: ``(slots, Hkv, KV tiles)`` for the contiguous kernel, the
KV sweep innermost, and the ``(slot, tile)`` work list for the paged one,
slot-major, so the per-(slot, head) accumulators ``(o, m_sum, n_sum)``
live in VMEM across a slot's sweep (same revisited-output pattern as
``flash_attention``) and the pipeline prefetches the next tile's pages,
the next slot's included, while the current one folds.  A paged grid
step fetches every KV head of its pages: the arena is ``[P, ps, Hkv, D]``,
and the TPU lowering only takes blocks whose last two dims are
(8, 128)-aligned or whole, so a one-head ``(ps, 1, D)`` slice of a page
cannot be a block.  The head loop runs inside the kernel instead.  The
slot axis never tiles — the tunable dims are the KV tile length
(``block_t``, contiguous) and the page count per tile
(``pages_per_tile``, paged), swept by ``repro.kernels.autotune`` through
the ``decode_attention`` / ``decode_attention_paged`` registry ops.

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

from repro.core.numerics import MINUS_INF_N, exp2_int, ext_exp
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


def _init_acc(o_ref, m_ref, n_ref):
    """Set every head's (o, m_sum, n_sum) accumulator to the (m, n) monoid
    zero (0, 0, ``MINUS_INF_N``): folding a tile into it is exact (its
    rescale is 2^0 on the tile's side and a flush to 0 on the zero's), and
    a slot that folds nothing normalizes to exact zeros."""
    for h in range(o_ref.shape[1]):
        o_ref[0, h] = jnp.zeros(o_ref.shape[2:], jnp.float32)
        m_ref[0, h] = jnp.zeros(m_ref.shape[2:], jnp.float32)
        n_ref[0, h] = jnp.full(n_ref.shape[2:], MINUS_INF_N, jnp.float32)


def _normalize(o_ref, m_ref):
    # max() guard: a free slot (length 0) has m_sum == 0 -> exact zeros
    for h in range(o_ref.shape[1]):
        o_ref[0, h] = o_ref[0, h] / jnp.maximum(m_ref[0, h], 1e-37)


def _mn_fold_tile(o_ref, m_ref, n_ref, q, k, v, kpos, length, *,
                  scale: float, window: int | None,
                  k_scale=None, v_scale=None, h: int = 0):
    """Score one KV tile, mask it, and fold it into the running (o, m, n)
    accumulator refs (head ``h`` of their block; :func:`_init_acc` sets
    them before the sweep, :func:`_normalize` ends it).

    ``q``: (G, D) f32; ``k``/``v``: (BT, D)/(BT, Dv) f32; ``kpos``: int32
    (1, BT) logical cache positions of the tile's columns (2-D for Mosaic's
    iota rules); ``length``: the slot's
    valid prefix (its own query sits at ``length - 1``, write-then-attend,
    so the validity prefix IS the causal mask and SWA is a lower bound off
    that query position).  A fully-masked tile contributes the exact
    monoid zero (m=0, n=MINUS_INF_N); a fully-masked SLOT (length 0, a
    free pool slot) ends with m_sum == 0 and the normalize guard returns
    exact zeros, never NaN — matching the jnp reference forms bit-for-bit
    in structure (the accumulation order within a tile differs, so parity
    is allclose, not bitwise).

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

    n_old = n_ref[0, h]
    n_new = jnp.maximum(n_old, n_loc)
    a_old = exp2_int(n_old - n_new)                  # exact 2^k rescales
    a_loc = exp2_int(n_loc - n_new)
    o_ref[0, h] = o_ref[0, h] * a_old + o_loc * a_loc
    m_ref[0, h] = m_ref[0, h] * a_old + m_loc * a_loc
    n_ref[0, h] = n_new


def _contig_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, n_ref, *,
                   scale: float, window: int | None, block_t: int, nt: int):
    s_idx = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_acc(o_ref, m_ref, n_ref)

    kpos = (j * block_t
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1))
    _mn_fold_tile(o_ref, m_ref, n_ref,
                  q_ref[0, 0].astype(jnp.float32),
                  k_ref[0, 0].astype(jnp.float32),
                  v_ref[0, 0].astype(jnp.float32),
                  kpos, len_ref[s_idx], scale=scale, window=window)

    @pl.when(j == nt - 1)
    def _finish():
        _normalize(o_ref, m_ref)


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


def _paged_kernel(pt_ref, len_ref, ws_ref, wt_ref, q_ref, *refs,
                  scale: float, window: int | None, ps: int, ppt: int,
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

    w = pl.program_id(0)
    j = wt_ref[w]                            # tile of slot ws_ref[w]
    length = len_ref[ws_ref[w]]

    @pl.when(j == 0)
    def _init():
        _init_acc(o_ref, m_ref, n_ref)

    # Only an all-free pool's one grid step holds no valid position.
    @pl.when(j * (ppt * ps) < length)
    def _fold():
        kpos = (j * (ppt * ps)
                + jax.lax.broadcasted_iota(jnp.int32, (1, ppt * ps), 1))
        for h in range(hkv):
            # Each of the tile's ppt pages arrived via its own
            # scalar-prefetch block fetch (non-contiguous in the arena);
            # concatenated they form the contiguous logical window
            # [j*ppt*ps, (j+1)*ppt*ps).  On the quantized path the astype
            # is the whole dequant story: int8 codes widen to f32 IN
            # REGISTER, per tile — the arena itself is never copied to a
            # full-precision buffer.
            k = jnp.concatenate([r[0, :, h, :].astype(jnp.float32)
                                 for r in krefs], 0)
            v = jnp.concatenate([r[0, :, h, :].astype(jnp.float32)
                                 for r in vrefs], 0)
            ks = vs = None
            if quant:
                ks = jnp.concatenate([srow(r, h) for r in ksrefs], 1)
                vs = jnp.concatenate([srow(r, h) for r in vsrefs], 1)
            _mn_fold_tile(o_ref, m_ref, n_ref,
                          q_ref[0, h].astype(jnp.float32), k, v, kpos,
                          length, scale=scale, window=window, k_scale=ks,
                          v_scale=vs, h=h)

    @pl.when((j + 1) * (ppt * ps) >= length)
    def _finish():
        _normalize(o_ref, m_ref)


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
    lengths: [S] int32.  Both int32 operands are scalar-prefetched, with
    the work list built from ``lengths``: the per-page BlockSpec index
    maps read ``page_table`` directly, so each
    grid step DMAs ``pages_per_tile`` non-contiguous arena pages (all
    their KV heads) into VMEM and attends them, head by head, as one
    contiguous logical window.  The grid holds only the tiles below each
    slot's length (module docstring): table entries past a slot's last
    valid page are never fetched, and those inside that page and a free
    slot's row may point anywhere in the arena — the length mask makes
    their content invisible.  Returns [S, Hkv, G, Dv] in q.dtype.

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
    lengths = lengths.astype(jnp.int32)
    # The work list: slot-major (slot, tile) pairs of the tiles that hold
    # a valid position; entries past the count are never visited.  An
    # all-free pool still runs one (folding nothing) step.
    nt = pl.cdiv(pmax, ppt)
    tiles = jnp.minimum(jax.lax.div(lengths + ppt * ps - 1, ppt * ps), nt)
    ends = jnp.cumsum(tiles)
    work = jnp.arange(s * nt, dtype=jnp.int32)
    wslot = jnp.minimum(jnp.sum(ends[None, :] <= work[:, None], axis=1,
                                dtype=jnp.int32), s - 1)
    wtile = work - (ends - tiles)[wslot]
    n_work = jnp.maximum(ends[-1], 1)

    def page_index(w, tab, ln, ws, wt, i):
        # Page slots past the slot's last valid page clamp to it, so the
        # table is never read past a length (or past pmax - 1).
        si = ws[w]
        last = jnp.clip(jax.lax.div(ln[si] + ps - 1, ps) - 1, 0, pmax - 1)
        return tab[si, jnp.minimum(wt[w] * ppt + i, last)]

    def page_spec(i, block):
        return pl.BlockSpec(
            block,
            lambda w, tab, ln, ws, wt, i=i:
            (page_index(w, tab, ln, ws, wt, i),) + (0,) * (len(block) - 1))

    def slot_spec(block):
        return pl.BlockSpec(block, lambda w, tab, ln, ws, wt:
                            (ws[w],) + (0,) * (len(block) - 1))

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               ps=ps, ppt=ppt, hkv=hkv, quant=quant)
    scale_specs, scale_args = [], ()
    if quant:
        if k_scale.ndim == 2:                        # [P, ps] "page"
            k_scale = k_scale[:, None, :]
            v_scale = v_scale[:, None, :]
        blk = (1,) + k_scale.shape[1:]
        scale_specs = [page_spec(i, blk) for i in range(ppt)] * 2
        scale_args = (*([k_scale] * ppt), *([v_scale] * ppt))
    grid_spec = _grid_spec(
        4, (n_work,),
        in_specs=(
            [slot_spec((1, hkv, g, d))]
            + [page_spec(i, (1, ps, hkv, d)) for i in range(ppt)]
            + [page_spec(i, (1, ps, hkv, dv)) for i in range(ppt)]
            + scale_specs),
        out_specs=[slot_spec((1, hkv, g, dv)), slot_spec((1, hkv, g, 1)),
                   slot_spec((1, hkv, g, 1))])
    o, _, _ = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, hkv, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((s, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, hkv, g, 1), jnp.float32),
        ],
        interpret=_interpret(),
        **_tpu_params(("arbitrary",)),
    )(page_table.astype(jnp.int32), lengths, wslot, wtile,
      q, *([k_pages] * ppt), *([v_pages] * ppt), *scale_args)
    # a free slot has no grid step: its output block was never written
    o = jnp.where((lengths > 0)[:, None, None, None], o, 0.0)
    return o.astype(q.dtype)

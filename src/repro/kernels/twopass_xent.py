"""Pallas TPU kernel: fused vocabulary cross-entropy via Two-Pass softmax.

The paper motivates softmax with huge class counts (Table 1: up to 364 M
classes).  In an LM the softmax consumer is cross-entropy, and the two-pass
structure maps onto it exactly:

  * forward  == pass 1: one read of the ``[tokens, vocab]`` logits produces
    ``(m_sum, n_sum)`` per row (=> logsumexp) plus the label logit, gathered
    on the fly.  The probability tensor is NEVER written to HBM.
  * backward == pass 2: one read of the logits (exp recomputed, the Alg 1/3
    recompute discipline) writes ``dlogits = (p - onehot) * dloss``.

Total traffic: 2 reads + 1 write of the logits = the paper's 3N, versus >=5N
for an unfused softmax+gather+scatter implementation — and peak memory drops
by the size of the probability tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.numerics import LN2_HI, LN2_LO, exp2_int, ext_exp
from repro.kernels.twopass_softmax import _interpret, _tpu_params

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_V = 512

# The fused LM-head kernels hold a whole (block_t, d) hidden slab and a
# (d, block_v) weight slab per grid step, so their VMEM grows with the
# hidden width: at d=3840 even 256x512 tiles outgrow the compiler's default
# scoped limit.  They run under this raised limit (a TPU v5e core has 128
# MiB of VMEM), with tiles shrunk by :func:`fit_lmhead_blocks` until the
# estimate below fits LMHEAD_VMEM_BUDGET.
LMHEAD_VMEM_LIMIT = 100 << 20
LMHEAD_VMEM_BUDGET = 72 << 20


def lmhead_vmem_bytes(block_t: int, block_v: int, d: int,
                      itemsize: int) -> int:
    """Upper estimate of one lmhead grid step's VMEM: h/w input blocks
    (double-buffered), the widest f32 output block (dh's (block_t, d) or
    dw's (d, block_v), double-buffered), the in-kernel f32 casts of h and
    w, and three (block_t, block_v) f32 tiles (logits, p, dlogits)."""
    slabs = block_t * d + d * block_v
    return (2 * slabs * itemsize + 2 * d * max(block_t, block_v) * 4
            + slabs * 4 + 3 * block_t * block_v * 4)


def fit_lmhead_blocks(block_t: int, block_v: int, d: int,
                      itemsize: int) -> tuple[int, int]:
    """Halve block_v, then block_t (floors 128 and 8), until
    :func:`lmhead_vmem_bytes` fits LMHEAD_VMEM_BUDGET."""
    while lmhead_vmem_bytes(block_t, block_v, d, itemsize) > \
            LMHEAD_VMEM_BUDGET:
        if block_v > 128:
            block_v //= 2
        elif block_t > 8:
            block_t //= 2
        else:
            break
    return block_t, block_v


def _fwd_kernel(x_ref, lab_ref, m_ref, n_ref, ll_ref, *, block_v: int):
    """Pass 1: fold tile into (m_sum, n_sum) and gather the label logit."""
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)               # (BT, BV)
    m, n = ext_exp(x)
    n_loc = jnp.max(n, axis=-1, keepdims=True)
    m_loc = jnp.sum(m * exp2_int(n - n_loc), axis=-1, keepdims=True)

    # Label-logit gather: columns of this tile are [j*BV, (j+1)*BV).
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    hit = cols == lab_ref[...]                       # (BT, BV) vs (BT, 1)
    ll_loc = jnp.sum(jnp.where(hit, x, 0.0), axis=-1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = m_loc
        n_ref[...] = n_loc
        ll_ref[...] = ll_loc

    @pl.when(j > 0)
    def _fold():
        n_old = n_ref[...]
        n_new = jnp.maximum(n_old, n_loc)
        m_ref[...] = (m_ref[...] * exp2_int(n_old - n_new)
                      + m_loc * exp2_int(n_loc - n_new))
        n_ref[...] = n_new
        ll_ref[...] += ll_loc


def _bwd_kernel(x_ref, lab_ref, m_ref, n_ref, dl_ref, dx_ref, *,
                block_v: int):
    """Pass 2: dlogits = (softmax - onehot) * dloss, exp recomputed."""
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    m, n = ext_exp(x)
    p = m * (1.0 / m_ref[...]) * exp2_int(n - n_ref[...])
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == lab_ref[...]).astype(jnp.float32)
    dx_ref[...] = ((p - onehot) * dl_ref[...]).astype(dx_ref.dtype)


def _stat_spec(bt):
    return pl.BlockSpec((bt, 1), lambda i, j: (i, 0))


@functools.partial(jax.jit, static_argnames=("block_t", "block_v"))
def xent_fwd_2d(logits: jax.Array, labels: jax.Array,
                block_t: int = DEFAULT_BLOCK_T,
                block_v: int = DEFAULT_BLOCK_V):
    """Forward: per-token loss + (m_sum, n_sum) residuals.

    logits: (T, V); labels: (T,) int32.  T % block_t == V % block_v == 0.
    Returns (loss (T,), m_sum (T,1), n_sum (T,1)).
    """
    t, v = logits.shape
    assert t % block_t == 0 and v % block_v == 0, (t, v)
    grid = (t // block_t, v // block_v)
    lab2d = labels.astype(jnp.int32)[:, None]

    m_sum, n_sum, ll = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
                  _stat_spec(block_t)],
        out_specs=[_stat_spec(block_t), _stat_spec(block_t),
                   _stat_spec(block_t)],
        out_shape=[jax.ShapeDtypeStruct((t, 1), jnp.float32)] * 3,
        interpret=_interpret(),
        **_tpu_params(("parallel", "arbitrary")),
    )(logits, lab2d)

    ln2 = jnp.float32(LN2_HI + LN2_LO)
    lse = jnp.log(m_sum[:, 0]) + n_sum[:, 0] * ln2
    return lse - ll[:, 0], m_sum, n_sum


@functools.partial(jax.jit, static_argnames=("block_t", "block_v"))
def xent_bwd_2d(logits: jax.Array, labels: jax.Array, m_sum: jax.Array,
                n_sum: jax.Array, dloss: jax.Array,
                block_t: int = DEFAULT_BLOCK_T,
                block_v: int = DEFAULT_BLOCK_V) -> jax.Array:
    """Backward: one read of logits, one write of dlogits."""
    t, v = logits.shape
    grid = (t // block_t, v // block_v)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block_v=block_v),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
                  _stat_spec(block_t), _stat_spec(block_t),
                  _stat_spec(block_t), _stat_spec(block_t)],
        out_specs=pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, v), logits.dtype),
        interpret=_interpret(),
        **_tpu_params(("parallel", "parallel")),
    )(logits, labels.astype(jnp.int32)[:, None], m_sum, n_sum,
      dloss.astype(jnp.float32)[:, None])


# ---------------------------------------------------------------------------
# Fused LM-head + cross-entropy: the same two-pass structure, but the logits
# tile is RECOMPUTED from hidden x W_head inside every kernel — the [T, V]
# logit matrix (and its gradient) never exists in HBM at all.  Three kernels:
#
#   forward: per vocab tile, x = h @ w_j on the MXU, fold into (m, n) + the
#            on-the-fly label gather — pass 1 over a matmul that is never
#            stored.
#   dh:      per vocab tile, recompute x, p = m * 2^(n - n_sum) / m_sum,
#            dlogits = (p - onehot) * dloss, accumulate dlogits @ w_j^T.
#   dw:      the transposed sweep (token tiles innermost) accumulating
#            h_i^T @ dlogits into each vocab tile of dw.
#
# ``v_len`` masks padded vocab columns (w is zero-padded to a block_v
# multiple): a zero logit would otherwise contribute exp(0) = 1 to every
# denominator.  The d_model axis stays untiled — LM heads are [T, V]-bound.
# ---------------------------------------------------------------------------
def _lmhead_tile(h_ref, w_ref, j, *, block_v: int, v_len: int):
    """One recomputed logits tile (BT, BV) f32 + its global column ids,
    padded columns masked to -inf (exact m = 0 through ExtExp)."""
    h = h_ref[...].astype(jnp.float32)               # (BT, D)
    w = w_ref[...].astype(jnp.float32)               # (D, BV)
    x = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(cols < v_len, x, -jnp.inf)
    return x, cols


def _lmhead_fwd_kernel(h_ref, w_ref, lab_ref, m_ref, n_ref, ll_ref, *,
                       block_v: int, v_len: int):
    j = pl.program_id(1)
    x, cols = _lmhead_tile(h_ref, w_ref, j, block_v=block_v, v_len=v_len)
    m, n = ext_exp(x)
    n_loc = jnp.max(n, axis=-1, keepdims=True)
    m_loc = jnp.sum(m * exp2_int(n - n_loc), axis=-1, keepdims=True)
    hit = cols == lab_ref[...]                       # labels < v_len always
    ll_loc = jnp.sum(jnp.where(hit, x, 0.0), axis=-1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = m_loc
        n_ref[...] = n_loc
        ll_ref[...] = ll_loc

    @pl.when(j > 0)
    def _fold():
        n_old = n_ref[...]
        n_new = jnp.maximum(n_old, n_loc)
        m_ref[...] = (m_ref[...] * exp2_int(n_old - n_new)
                      + m_loc * exp2_int(n_loc - n_new))
        n_ref[...] = n_new
        ll_ref[...] += ll_loc


def _lmhead_dlogits(h_ref, w_ref, lab_ref, m_ref, n_ref, dl_ref, j, *,
                    block_v: int, v_len: int):
    """Recomputed dlogits tile = (p - onehot) * dloss.  Masked/padded
    columns give p = 0 and never match a label, so their dlogits vanish."""
    x, cols = _lmhead_tile(h_ref, w_ref, j, block_v=block_v, v_len=v_len)
    m, n = ext_exp(x)
    p = (m * (1.0 / jnp.maximum(m_ref[...], 1e-37))
         * exp2_int(n - n_ref[...]))
    onehot = (cols == lab_ref[...]).astype(jnp.float32)
    return (p - onehot) * dl_ref[...]


def _lmhead_dh_kernel(h_ref, w_ref, lab_ref, m_ref, n_ref, dl_ref, dh_ref,
                      *, block_v: int, v_len: int):
    j = pl.program_id(1)                             # vocab innermost
    dlog = _lmhead_dlogits(h_ref, w_ref, lab_ref, m_ref, n_ref, dl_ref, j,
                           block_v=block_v, v_len=v_len)
    w = w_ref[...].astype(jnp.float32)               # (D, BV)
    dh_loc = jax.lax.dot_general(dlog, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        dh_ref[...] = dh_loc

    @pl.when(j > 0)
    def _fold():
        dh_ref[...] += dh_loc


def _lmhead_dw_kernel(h_ref, w_ref, lab_ref, m_ref, n_ref, dl_ref, dw_ref,
                      *, block_v: int, v_len: int):
    j = pl.program_id(0)                             # vocab tile
    i = pl.program_id(1)                             # tokens innermost
    dlog = _lmhead_dlogits(h_ref, w_ref, lab_ref, m_ref, n_ref, dl_ref, j,
                           block_v=block_v, v_len=v_len)
    h = h_ref[...].astype(jnp.float32)               # (BT, D)
    dw_loc = jax.lax.dot_general(h, dlog, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = dw_loc

    @pl.when(i > 0)
    def _fold():
        dw_ref[...] += dw_loc


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_v", "v_len"))
def lmhead_xent_fwd_2d(h: jax.Array, w: jax.Array, labels: jax.Array,
                       block_t: int = DEFAULT_BLOCK_T,
                       block_v: int = DEFAULT_BLOCK_V,
                       v_len: int | None = None):
    """Fused LM-head CE forward.  h: (T, D); w: (D, V); labels: (T,) int.

    T % block_t == V % block_v == 0 required (``ops.lmhead_cross_entropy``
    pads h rows/w columns with zeros; ``v_len`` is the true vocab width —
    padded columns are masked to -inf inside the kernel).
    Returns (loss (T,), m_sum (T, 1), n_sum (T, 1)).
    """
    t, d = h.shape
    v = w.shape[1]
    if v_len is None:
        v_len = v
    assert t % block_t == 0 and v % block_v == 0, (t, v)
    grid = (t // block_t, v // block_v)

    m_sum, n_sum, ll = pl.pallas_call(
        functools.partial(_lmhead_fwd_kernel, block_v=block_v, v_len=v_len),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
                  _stat_spec(block_t)],
        out_specs=[_stat_spec(block_t), _stat_spec(block_t),
                   _stat_spec(block_t)],
        out_shape=[jax.ShapeDtypeStruct((t, 1), jnp.float32)] * 3,
        interpret=_interpret(),
        **_tpu_params(("parallel", "arbitrary"), LMHEAD_VMEM_LIMIT),
    )(h, w, labels.astype(jnp.int32)[:, None])

    ln2 = jnp.float32(LN2_HI + LN2_LO)
    lse = jnp.log(jnp.maximum(m_sum[:, 0], 1e-37)) + n_sum[:, 0] * ln2
    return lse - ll[:, 0], m_sum, n_sum


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_v", "v_len"))
def lmhead_xent_dh_2d(h: jax.Array, w: jax.Array, labels: jax.Array,
                      m_sum: jax.Array, n_sum: jax.Array,
                      dloss: jax.Array,
                      block_t: int = DEFAULT_BLOCK_T,
                      block_v: int = DEFAULT_BLOCK_V,
                      v_len: int | None = None) -> jax.Array:
    """dh (T, D) f32: vocab-streamed ``dlogits @ w^T``, logits recomputed."""
    t, d = h.shape
    v = w.shape[1]
    if v_len is None:
        v_len = v
    grid = (t // block_t, v // block_v)
    return pl.pallas_call(
        functools.partial(_lmhead_dh_kernel, block_v=block_v, v_len=v_len),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
                  _stat_spec(block_t), _stat_spec(block_t),
                  _stat_spec(block_t), _stat_spec(block_t)],
        out_specs=pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        interpret=_interpret(),
        **_tpu_params(("parallel", "arbitrary"), LMHEAD_VMEM_LIMIT),
    )(h, w, labels.astype(jnp.int32)[:, None], m_sum, n_sum,
      dloss.astype(jnp.float32)[:, None])


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_v", "v_len"))
def lmhead_xent_dw_2d(h: jax.Array, w: jax.Array, labels: jax.Array,
                      m_sum: jax.Array, n_sum: jax.Array,
                      dloss: jax.Array,
                      block_t: int = DEFAULT_BLOCK_T,
                      block_v: int = DEFAULT_BLOCK_V,
                      v_len: int | None = None) -> jax.Array:
    """dw (D, V) f32: token-streamed ``h^T @ dlogits``, logits recomputed.
    Grid is (vocab, tokens) — tokens innermost so each dw tile accumulates
    across consecutive grid steps."""
    t, d = h.shape
    v = w.shape[1]
    if v_len is None:
        v_len = v
    grid = (v // block_v, t // block_t)
    stat = pl.BlockSpec((block_t, 1), lambda j, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_lmhead_dw_kernel, block_v=block_v, v_len=v_len),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, d), lambda j, i: (i, 0)),
                  pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
                  stat, stat, stat, stat],
        out_specs=pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((d, v), jnp.float32),
        interpret=_interpret(),
        **_tpu_params(("parallel", "arbitrary"), LMHEAD_VMEM_LIMIT),
    )(h, w, labels.astype(jnp.int32)[:, None], m_sum, n_sum,
      dloss.astype(jnp.float32)[:, None])

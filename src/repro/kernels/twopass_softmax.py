"""Pallas TPU kernel: the Two-Pass softmax (paper Alg 3).

TPU adaptation of the paper's AVX512 streaming loops: the "passes" become
grid sweeps over HBM->VMEM tiles.  Pass 1 reads each ``(block_rows x
block_cols)`` tile once, applies ExtExp in-register (VPU), folds the tile into
per-row ``(m_sum, n_sum)`` accumulators that live in VMEM for the whole row
sweep (the revisited-output accumulation pattern), and never materializes
exponentials to HBM.  Pass 2 re-reads x and writes y.  HBM traffic is the
paper's 3N (2 reads + 1 write) versus 4N/5N for the three-pass baselines.

Block shapes are meta-parameters (the paper's "unroll factor / number of
accumulators" analogue) — sublane-multiple rows (8) and lane-multiple cols
(128) keep VPU tiles dense; defaults target a ~1 MiB double-buffered working
set, far under VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.numerics import exp2_int, ext_exp

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_COLS = 512


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _tpu_params(dims: tuple[str, ...],
                vmem_limit_bytes: int | None = None) -> dict:
    """dimension_semantics (and, where a kernel's blocks outgrow the
    compiler's default scoped VMEM, its limit) for the real-TPU lowering;
    no-op in interpret mode."""
    if _interpret():
        return {}
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=dims, vmem_limit_bytes=vmem_limit_bytes)}


def _pass1_kernel(x_ref, m_ref, n_ref):
    """Pass 1: ExtExp + (m, n) monoid fold of one tile into the row stats."""
    j = pl.program_id(1)
    m, n = ext_exp(x_ref[...])                       # (BR, BC), f32
    n_loc = jnp.max(n, axis=-1, keepdims=True)       # (BR, 1)
    m_loc = jnp.sum(m * exp2_int(n - n_loc), axis=-1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = m_loc
        n_ref[...] = n_loc

    @pl.when(j > 0)
    def _fold():
        n_old = n_ref[...]
        n_new = jnp.maximum(n_old, n_loc)
        m_ref[...] = (m_ref[...] * exp2_int(n_old - n_new)
                      + m_loc * exp2_int(n_loc - n_new))
        n_ref[...] = n_new


def _pass2_kernel(x_ref, m_ref, n_ref, y_ref):
    """Pass 2: recompute ExtExp, scale by 1/m_sum and exact 2^(n - n_sum)."""
    m, n = ext_exp(x_ref[...])
    lam = 1.0 / m_ref[...]
    y_ref[...] = (m * lam * exp2_int(n - n_ref[...])).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols"))
def twopass_softmax_2d(x: jax.Array,
                       block_rows: int = DEFAULT_BLOCK_ROWS,
                       block_cols: int = DEFAULT_BLOCK_COLS) -> jax.Array:
    """Rowwise softmax of a 2-D array via the Two-Pass Pallas kernels.

    Requires ``rows % block_rows == 0 and cols % block_cols == 0``
    (``ops.softmax`` handles padding).
    """
    rows, cols = x.shape
    assert rows % block_rows == 0 and cols % block_cols == 0, (rows, cols)
    grid = (rows // block_rows, cols // block_cols)

    m_sum, n_sum = pl.pallas_call(
        _pass1_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=_interpret(),
        **_tpu_params(("parallel", "arbitrary")),
    )(x)

    return pl.pallas_call(
        _pass2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        interpret=_interpret(),
        **_tpu_params(("parallel", "parallel")),
    )(x, m_sum, n_sum)


def twopass_stats_2d(x: jax.Array,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     block_cols: int = DEFAULT_BLOCK_COLS
                     ) -> tuple[jax.Array, jax.Array]:
    """Pass 1 only: per-row (m_sum, n_sum) — the fused-xent forward core."""
    rows, cols = x.shape
    assert rows % block_rows == 0 and cols % block_cols == 0, (rows, cols)
    grid = (rows // block_rows, cols // block_cols)
    return pl.pallas_call(
        _pass1_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=_interpret(),
        **_tpu_params(("parallel", "arbitrary")),
    )(x)

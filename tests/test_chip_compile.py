"""Compile the main path's Pallas kernels for a TPU v5e at real widths.

The chip is described (``v5e:2x2`` topology), not attached: the TPU
compiler refuses here what the chip would refuse — blocks not aligned to
the (8, 128) tiling, more VMEM than a kernel may use — which interpret-mode
parity tests cannot see.  Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Compiles happen in the test's own process.
"""

from __future__ import annotations

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Modules whose kernels pick interpret mode through a ``_interpret``
# binding of their own (imported by name from twopass_softmax).
KERNEL_MODULES = ("twopass_softmax", "decode_attention", "flash_attention",
                  "twopass_xent")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture
def tpu(one_chip, monkeypatch):
    """The described chip with every kernel module lowering for real (not
    interpret mode); trace caches cleared so no interpret-mode trace of
    the same shapes is reused."""
    for name in KERNEL_MODULES:
        mod = importlib.import_module(f"repro.kernels.{name}")
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    jax.clear_caches()
    yield one_chip
    jax.clear_caches()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("head_dim", [120, 128])
@pytest.mark.parametrize("page_dtype", ["bfloat16", "int8"])
def test_paged_decode_compiles(tpu, head_dim, page_dtype):
    """The serving decode kernel through ``ops`` dispatch, 128-token pages,
    GQA 32/8 heads.  bf16: 9 pages per slot (8 per grid step, what a
    1152-token slot resolves to); int8 with "page" scales: 4 pages."""
    from repro.kernels import ops

    slots, hkv, g, ps, pages = 8, 8, 4, 128, 73
    pmax = 9 if page_dtype == "bfloat16" else 4
    arena = _sds(tpu, (pages, ps, hkv, head_dim), jnp.dtype(page_dtype))
    args = [_sds(tpu, (slots, hkv, g, head_dim), jnp.bfloat16), arena,
            arena, _sds(tpu, (slots, pmax), jnp.int32),
            _sds(tpu, (slots,), jnp.int32)]
    if page_dtype == "int8":
        args += [_sds(tpu, (pages, ps), jnp.float32)] * 2

    def decode(q, k, v, table, lengths, k_scale=None, v_scale=None):
        return ops.decode_attention_paged(q, k, v, table, lengths,
                                          k_scale=k_scale, v_scale=v_scale,
                                          use_kernel=True)

    _compile(decode, *args)


def test_paged_decode_int8_compiles_at_eight_pages_per_tile(tpu):
    """The int8 arena with "page" scales at the benchmark cells' table:
    24 pages of 128 per slot, hd 120, swept at 8 pages per tile, with the
    length-clamped page and scale index maps."""
    from repro.kernels import decode_attention as da

    slots, hkv, g, ps, pages, pmax, hd = 64, 8, 4, 128, 320, 24, 120
    arena = _sds(tpu, (pages, ps, hkv, hd), jnp.int8)
    scales = _sds(tpu, (pages, ps), jnp.float32)
    _compile(lambda q, k, v, table, lengths, ks, vs:
             da.decode_attention_paged_pallas(
                 q, k, v, table, lengths, ks, vs, scale=hd ** -0.5,
                 pages_per_tile=8),
             _sds(tpu, (slots, hkv, g, hd), jnp.bfloat16), arena, arena,
             _sds(tpu, (slots, pmax), jnp.int32), _sds(tpu, (slots,),
                                                      jnp.int32),
             scales, scales)


@pytest.mark.parametrize("head_dim", [120, 128])
def test_flash_attention_fwd_bwd_compile(tpu, head_dim):
    """Training attention at a 4k causal sequence: the stats-saving
    forward and the recompute-from-stats backward."""
    fa = importlib.import_module("repro.kernels.flash_attention")

    x = _sds(tpu, (1, 8, 4096, head_dim), jnp.bfloat16)
    stat = _sds(tpu, (1, 8, 4096, 1), jnp.float32)
    _compile(lambda q, k, v: fa.flash_attention_fwd_gqa(q, k, v,
                                                        causal=True),
             x, x, x)
    _compile(lambda q, k, v, o, m, n, do: fa.flash_attention_bwd_gqa(
        q, k, v, o, m, n, do, causal=True), x, x, x, x, stat, stat, x)


@pytest.mark.parametrize("kernel", ["fwd", "dh", "dw"])
def test_lmhead_xent_compiles_at_d3840(tpu, kernel):
    """Fused LM-head CE at h2o-danube-3-4b's hidden width over a
    one-eighth slice of its 32000 vocab, at the tiles ``ops`` resolves."""
    from repro.kernels import ops, registry
    from repro.kernels import twopass_xent as xent

    t, d, v = 2048, 3840, 32000 // 8
    bt, bv = ops._lmhead_blocks(_sds(tpu, (t, d), jnp.bfloat16),
                                _sds(tpu, (d, v), jnp.bfloat16),
                                None, None, None)
    pt, pv = registry.round_up(t, bt), registry.round_up(v, bv)
    args = [_sds(tpu, (pt, d), jnp.bfloat16), _sds(tpu, (d, pv),
                                                   jnp.bfloat16),
            _sds(tpu, (pt,), jnp.int32)]
    if kernel != "fwd":
        args += [_sds(tpu, (pt, 1), jnp.float32)] * 2
        args += [_sds(tpu, (pt,), jnp.float32)]
    fn = getattr(xent, f"lmhead_xent_{kernel}_2d")
    _compile(lambda *a: fn(*a, block_t=bt, block_v=bv, v_len=v), *args)


@pytest.mark.parametrize("cols", [2304, 3072])
def test_softmax_compiles_past_the_2048_bucket(tpu, cols):
    """Prefill attention scores of a prompt past the 2048 bucket (a
    request preempted and readmitted with its tokens), f32[8192, cols] as
    the chip saw them: at the blocks ``ops`` resolves (at most 2048
    columns, streamed) the kernel fits the 16 MiB of scoped VMEM."""
    from repro.kernels import ops

    _compile(ops.softmax, _sds(tpu, (8192, cols), jnp.float32))

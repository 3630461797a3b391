"""Pallas decode-attention kernel tests: interpret-mode parity with the jnp
(m, n) reference forms (contiguous + paged, lengths incl. zero/full, SWA
window, shuffled/aliased page tables), the paged sweep bounded by each
slot's length (NaN pages past it stay unread), SoftmaxPolicy.use_kernels
dispatch, and a ragged end-to-end serving run asserting identical tokens
with the kernels on and off."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import SoftmaxPolicy
from repro.kernels import decode_attention as da
from repro.kernels import ops, registry
from repro.models import build_model
from repro.serving.scheduler import ContinuousBatchingEngine, Request

KEY = jax.random.PRNGKey(0)


def _paged_copy(k, v, pmax, ps, seed=0):
    """Scatter contiguous [S, H, T, D] K/V into a shuffled page arena."""
    s, h, t, d = k.shape
    pages = 1 + s * pmax
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
# Contiguous kernel vs the jnp (m, n) reference.
# ---------------------------------------------------------------------------
class TestPallasDecodeParity:
    def setup_method(self, _):
        ks = jax.random.split(KEY, 3)
        self.s, self.h, self.g, self.d, self.t = 6, 2, 3, 16, 320
        self.q = jax.random.normal(ks[0], (self.s, self.h, self.g, self.d))
        self.k = jax.random.normal(ks[1], (self.s, self.h, self.t, self.d))
        self.v = jax.random.normal(ks[2], (self.s, self.h, self.t, self.d))
        # zero (free slot), one, tile-interior, tile-boundary, full, odd
        self.lengths = jnp.array([0, 1, 100, 128, 320, 257], jnp.int32)

    def test_parity_across_tile_sizes(self):
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                    use_kernel=False)
        for bt in (128, 256, 384):       # multi-tile, uneven pad, one-tile
            got = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                       block_t=bt, use_kernel=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=f"block_t={bt}")
        assert not np.isnan(np.asarray(got)).any()   # incl. length-0 slot
        np.testing.assert_array_equal(np.asarray(got[0]), 0.0)  # free slot

    def test_window_parity(self):
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                    window=48, use_kernel=False)
        got = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                   window=48, block_t=128, use_kernel=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_low_precision_inputs(self):
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (self.q, self.k,
                                                       self.v))
        want = ops.decode_attention(qb, kb, vb, self.lengths,
                                    use_kernel=False)
        got = ops.decode_attention(qb, kb, vb, self.lengths,
                                   block_t=128, use_kernel=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=3e-2)

    def test_ragged_kv_width_is_padded(self):
        # T=40 is not a lane multiple: the kernel wrapper zero-pads the KV
        # axis and the length mask keeps the pad invisible.
        k, v = self.k[:, :, :40], self.v[:, :, :40]
        lengths = jnp.array([0, 1, 7, 40, 23, 39], jnp.int32)
        want = ops.decode_attention(self.q, k, v, lengths, use_kernel=False)
        got = ops.decode_attention(self.q, k, v, lengths, use_kernel=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# Paged kernel: scalar-prefetch page gathers vs the jnp gather reference.
# ---------------------------------------------------------------------------
class TestPallasPagedParity:
    def setup_method(self, _):
        ks = jax.random.split(KEY, 3)
        self.s, self.h, self.g, self.d = 5, 2, 3, 16
        self.ps, self.pmax = 8, 6
        t = self.ps * self.pmax
        self.q = jax.random.normal(ks[0], (self.s, self.h, self.g, self.d))
        self.k = jax.random.normal(ks[1], (self.s, self.h, t, self.d))
        self.v = jax.random.normal(ks[2], (self.s, self.h, t, self.d))
        self.lengths = jnp.array([1, 7, 48, 0, 23], jnp.int32)
        self.kp, self.vp, self.pt = _paged_copy(self.k, self.v, self.pmax,
                                                self.ps)

    def test_parity_across_pages_per_tile(self):
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                    use_kernel=False)
        for ppt in (1, 2, 3, 6):
            got = da.decode_attention_paged_pallas(
                self.q, self.kp, self.vp, self.pt, self.lengths,
                scale=self.d ** -0.5, pages_per_tile=ppt)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=f"ppt={ppt}")

    def test_dispatch_and_window(self):
        for window in (None, 6):
            want = ops.decode_attention_paged(
                self.q, self.kp, self.vp, self.pt, self.lengths,
                window=window, use_kernel=False)
            got = ops.decode_attention_paged(
                self.q, self.kp, self.vp, self.pt, self.lengths,
                window=window, use_kernel=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=f"w={window}")

    def test_aliased_trash_entries_invisible(self):
        # Entries past a slot's length may alias another slot's LIVE pages
        # (and free slots' rows are all trash): the kernel's length mask
        # must keep every such gathered byte invisible.
        pt = np.asarray(self.pt).copy()
        pt[0, 1:] = pt[2, :self.pmax - 1]        # slot 0 len=1: covered
        pt[3, :] = pt[2, :]                      # free slot aliases slot 2
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                    use_kernel=False)
        got = ops.decode_attention_paged(
            self.q, self.kp, self.vp, jnp.asarray(pt), self.lengths,
            use_kernel=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[3]), 0.0)

    def test_table_width_padded_to_tile(self):
        # pmax=6 with pages_per_tile=4: the second tile's last two page
        # slots lie past the table and clamp to the slot's last page.
        got = da.decode_attention_paged_pallas(
            self.q, self.kp, self.vp, self.pt, self.lengths,
            scale=self.d ** -0.5, pages_per_tile=4)
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                    use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_pages_per_tile_cap(self):
        # block_t big enough to ask for > MAX_PAGES_PER_TILE pages per
        # tile: the wrapper caps it rather than exploding the spec count.
        got = ops.decode_attention_paged(
            self.q, self.kp, self.vp, self.pt, self.lengths,
            block_t=4096, use_kernel=True)
        want = ops.decode_attention(self.q, self.k, self.v, self.lengths,
                                    use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# The paged sweep stops at each slot's last valid page: tiles past it are
# neither fetched nor folded, so what lies there cannot reach the output.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("poisoned", [False, True])
@pytest.mark.parametrize("ppt", [1, 2, 4, 8])
def test_paged_sweep_bounded_by_length(ppt, poisoned):
    # one slot per boundary: free, one position, a page's edges, a tile's
    # edges, the whole table (12 pages: ppt 8 leaves a part tile)
    ps, pmax = 8, 12
    lengths = [0, 1, ps - 1, ps, ps + 1, ppt * ps, ppt * ps + 1, pmax * ps]
    s, h, g, d = len(lengths), 2, 3, 16
    ks = jax.random.split(jax.random.fold_in(KEY, ppt), 3)
    q = jax.random.normal(ks[0], (s, h, g, d))
    k = jax.random.normal(ks[1], (s, h, pmax * ps, d))
    v = jax.random.normal(ks[2], (s, h, pmax * ps, d))
    lengths = jnp.asarray(lengths, jnp.int32)
    kp, vp, pt = _paged_copy(k, v, pmax, ps)
    if poisoned:
        # NaN in every page that backs no valid position (page 0, the
        # trash page, stays finite): a page read past a length would
        # reach the output as 0 * NaN
        tab = np.asarray(pt)
        live = {int(tab[i, p]) for i, n in enumerate(np.asarray(lengths))
                for p in range(-(-int(n) // ps))}
        dead = np.array(sorted(set(range(1, kp.shape[0])) - live))
        kp, vp = kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan)
    want = ops.decode_attention(q, k, v, lengths, use_kernel=False)
    got = np.asarray(da.decode_attention_paged_pallas(
        q, kp, vp, pt, lengths, scale=d ** -0.5, pages_per_tile=ppt))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)
    np.testing.assert_array_equal(got[0], 0.0)           # free slot
    idle = da.decode_attention_paged_pallas(             # all slots free
        q, kp, vp, pt, jnp.zeros_like(lengths), scale=d ** -0.5,
        pages_per_tile=ppt)
    np.testing.assert_array_equal(np.asarray(idle), 0.0)


# ---------------------------------------------------------------------------
# Dispatch plumbing: policy.use_kernels routes to the Pallas entry points.
# ---------------------------------------------------------------------------
class TestDispatch:
    def test_policy_routes_to_pallas(self, monkeypatch):
        calls = []
        real = da.decode_attention_pallas
        monkeypatch.setattr(
            ops._da, "decode_attention_pallas",
            lambda *a, **kw: calls.append(1) or real(*a, **kw))
        q = jax.random.normal(KEY, (2, 1, 1, 8))
        k = jax.random.normal(KEY, (2, 1, 16, 8))
        lengths = jnp.array([3, 16], jnp.int32)
        ops.decode_attention(q, k, k, lengths,
                             policy=SoftmaxPolicy(use_kernels=False))
        assert not calls                       # jnp reference path
        ops.decode_attention(q, k, k, lengths,
                             policy=SoftmaxPolicy(use_kernels=True))
        assert calls                           # Pallas path

    def test_registry_binds_pallas_entry_points(self):
        assert (registry.get_spec("decode_attention").fn
                is da.decode_attention_pallas)
        assert (registry.get_spec("decode_attention_paged").fn
                is da.decode_attention_paged_pallas)


# ---------------------------------------------------------------------------
# Ragged end-to-end: the serving scheduler produces identical tokens with
# the Pallas kernels on and off (greedy sampling, mixed prompt lengths so
# slots age unevenly and the paged pool grows mid-run).  Archs cover the
# three decode layouts: GQA k/v paging, MLA latent paging (contiguous op
# after the up-projection), and hybrid's SWA-windowed attention half.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen2.5-14b", "deepseek-v2-lite-16b",
                                  "hymba-1.5b"])
def test_serving_tokens_identical_kernels_on_off(arch):
    def serve(use_kernels):
        m = build_model(arch, reduced=True, use_kernels=use_kernels)
        params = m.init(KEY)
        eng = ContinuousBatchingEngine(m, params, slots=3, max_len=48,
                                       page_size=8, temperature=0.0, seed=4)
        rng = np.random.default_rng(11)
        reqs = [Request(rid=i,
                        prompt=tuple(rng.integers(0, m.cfg.vocab,
                                                  int(rng.integers(2, 11)))),
                        max_new_tokens=5 + i % 3) for i in range(5)]
        return [tuple(c.tokens) for c in eng.run(reqs)]

    assert serve(True) == serve(False)

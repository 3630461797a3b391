"""Public jit'd wrappers around the Pallas kernels.

Handles: arbitrary leading dims (collapsed to rows), padding to block
multiples (cols padded with -inf, which is an exact monoid zero through the
whole (m, n) algebra), algorithm dispatch, and ``custom_vjp`` definitions so
the fused kernels are differentiable.

Block shapes resolve through ``repro.kernels.registry`` — the one canonical
model (overrides > autotune cache > heuristic) shared by every op; this
module holds no block heuristics of its own.  A :class:`SoftmaxPolicy` may
be passed to carry overrides/autotune settings from config.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.softmax_api import SoftmaxAlgorithm
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import registry
from repro.kernels import threepass_softmax as _tp3
from repro.kernels import twopass_softmax as _tp2
from repro.kernels import twopass_xent as _xent

_round_up = registry.round_up


def _blocks(op: str, rows: int, cols: int, dtype, block_rows, block_cols,
            policy=None, shards: int = 1) -> tuple[int, int]:
    """Resolve block shapes: explicit args win, then the policy's overrides
    and cache setting, then the registry model.  ``shards`` keys the
    tensor-parallel variant of the op (per-shard grids tune separately)."""
    if policy is not None:
        return policy.resolve_blocks(op, rows, cols, dtype,
                                     block_rows=block_rows,
                                     block_cols=block_cols, shards=shards)
    return registry.block_shapes(op, rows, cols, dtype,
                                 block_rows=block_rows,
                                 block_cols=block_cols, shards=shards)


def _tp_shards(dim: int):
    """(n_shards, mesh) when an active :func:`autoshard.hints` mesh
    tensor-parallel-shards this op's ``dim``-sized axis; (1, None)
    otherwise.  The shard count keys the autotune cache (``|s{tp}``
    suffix) — a per-shard grid sees ``dim / tp`` of the axis, so its best
    tile differs from the unsharded one.

    Decode ops pass their KV-head count (inside the serving scheduler's
    mesh context the pool arenas are laid out with the KV-head axis over
    ``model`` — ``sharding.pool_specs`` — and the Pallas decode kernels
    run under ``shard_map``, each shard's grid seeing its LOCAL ``Hkv /
    tp`` heads).  The training-side backward ops pass the axis the mesh
    splits for them: q-heads for ``flash_attention_bwd``, vocab columns
    for ``lmhead_xent``."""
    from repro.distributed import autoshard  # lazy: kernels ↛ distributed

    mesh = autoshard.active_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return 1, None
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if tp <= 1 or dim % tp:
        return 1, None
    return tp, mesh


def _as_rows(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


_SOFTMAX_2D = {
    SoftmaxAlgorithm.TWO_PASS: _tp2.twopass_softmax_2d,
    SoftmaxAlgorithm.THREE_PASS_RECOMPUTE: _tp3.threepass_recompute_2d,
    SoftmaxAlgorithm.THREE_PASS_RELOAD: _tp3.threepass_reload_2d,
}


def softmax(x: jax.Array,
            algorithm: SoftmaxAlgorithm | str = SoftmaxAlgorithm.TWO_PASS,
            block_rows: int | None = None,
            block_cols: int | None = None,
            policy=None) -> jax.Array:
    """Last-axis softmax through the Pallas kernels (any leading dims).
    Differentiable: the backward is the analytic softmax VJP (needs only
    ``y``), so kernel softmax sites train (attention scores, MoE router)."""
    return _softmax_vjp(x, SoftmaxAlgorithm(algorithm), block_rows,
                        block_cols, policy)


def _softmax_padded(x, algorithm, block_rows, block_cols, policy):
    from repro.distributed import autoshard  # lazy: kernels ↛ distributed

    x2, lead = _as_rows(x)
    rows, cols = x2.shape
    br, bc = _blocks("softmax", rows, cols, x.dtype, block_rows, block_cols,
                     policy)
    mesh = autoshard.active_mesh()
    n_dev = 1 if mesh is None else mesh.size
    pr, pc = _round_up(rows, br * n_dev), _round_up(cols, bc)
    padded = jnp.full((pr, pc), -jnp.inf, x2.dtype)
    # Padded rows are all -inf: harmless garbage, sliced away below.  Padded
    # cols are -inf: exact (m=0) zero of the monoid / exp(-inf)=0 for Alg 1/2.
    padded = jax.lax.dynamic_update_slice(padded, x2, (0, 0))
    fn = functools.partial(_SOFTMAX_2D[algorithm], block_rows=br,
                           block_cols=bc)
    if n_dev > 1:
        # XLA cannot partition a Pallas kernel: under a mesh the rows (each
        # an independent softmax) split over every device.
        spec = P(mesh.axis_names, None)
        fn = jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                           check_vma=False)
    return fn(padded)[:rows, :cols].reshape(*lead, cols)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _softmax_vjp(x, algorithm, block_rows, block_cols, policy):
    return _softmax_padded(x, algorithm, block_rows, block_cols, policy)


def _softmax_fwd(x, algorithm, block_rows, block_cols, policy):
    y = _softmax_padded(x, algorithm, block_rows, block_cols, policy)
    return y, y


def _softmax_bwd(algorithm, block_rows, block_cols, policy, y, dy):
    yf, dyf = y.astype(jnp.float32), dy.astype(jnp.float32)
    dx = yf * (dyf - jnp.sum(dyf * yf, axis=-1, keepdims=True))
    return (dx.astype(y.dtype),)


_softmax_vjp.defvjp(_softmax_fwd, _softmax_bwd)


# ---------------------------------------------------------------------------
# Fused cross-entropy (differentiable): fwd = pass 1, bwd = pass 2.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def cross_entropy(logits: jax.Array, labels: jax.Array,
                  block_t: int | None = None,
                  block_v: int | None = None) -> jax.Array:
    """Per-token CE loss, probabilities never materialized.  [T,V],[T]->[T]."""
    loss, _, _ = _xent_fwd_padded(logits, labels, block_t, block_v)
    return loss


def _xent_pad(logits, labels, bt, bv):
    t, v = logits.shape
    pt, pv = _round_up(t, bt), _round_up(v, bv)
    lp = jnp.full((pt, pv), -jnp.inf, logits.dtype)
    lp = jax.lax.dynamic_update_slice(lp, logits, (0, 0))
    lab = jnp.zeros((pt,), jnp.int32).at[:t].set(labels.astype(jnp.int32))
    return lp, lab, pt, pv


def _xent_fwd_padded(logits, labels, block_t, block_v):
    t, v = logits.shape
    bt, bv = _blocks("xent", t, v, logits.dtype, block_t, block_v)
    lp, lab, _, _ = _xent_pad(logits, labels, bt, bv)
    # Padded rows: logits all -inf with label 0 -> label_logit = -inf,
    # lse = log(0) = -inf -> loss = nan, sliced off before use.
    loss, m_sum, n_sum = _xent.xent_fwd_2d(lp, lab, block_t=bt, block_v=bv)
    return loss[:t], m_sum, n_sum


def _ce_fwd(logits, labels, block_t, block_v):
    loss, m_sum, n_sum = _xent_fwd_padded(logits, labels, block_t, block_v)
    return loss, (logits, labels, m_sum, n_sum)


def _ce_bwd(block_t, block_v, res, dloss):
    logits, labels, m_sum, n_sum = res
    t, v = logits.shape
    bt, bv = _blocks("xent", t, v, logits.dtype, block_t, block_v)
    lp, lab, pt, _ = _xent_pad(logits, labels, bt, bv)
    dl = jnp.zeros((pt,), jnp.float32).at[:t].set(dloss.astype(jnp.float32))
    dlogits = _xent.xent_bwd_2d(lp, lab, m_sum, n_sum, dl,
                                block_t=bt, block_v=bv)
    return dlogits[:t, :v].astype(logits.dtype), None


cross_entropy.defvjp(_ce_fwd, _ce_bwd)


# ---------------------------------------------------------------------------
# Fused LM-head + cross-entropy: loss(h @ w, labels) with the logits
# recomputed per vocab tile in both passes — neither the [T, V] logits nor
# their gradient is ever materialized whole.  Same three implementations as
# flash attention ("pallas" kernels in twopass_xent.py / "twopass" jnp
# chunked forms / "ref" jax.vjp over the materialized-logits reference),
# dispatched by ``train_bwd_impl``.  The ``lmhead_xent`` registry op.
# ---------------------------------------------------------------------------
def _lmhead_ref_loss(h, w, labels):
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    return _ref.cross_entropy_ref(logits, labels)


def _lmhead_blocks(h, w, block_t, block_v, policy):
    t, v = h.shape[0], w.shape[1]
    shards, _ = _tp_shards(v)
    bt, bv = _blocks("lmhead_xent", t, v, h.dtype, block_t, block_v, policy,
                     shards=shards)
    return _xent.fit_lmhead_blocks(bt, bv, h.shape[1],
                                   max(h.dtype.itemsize, w.dtype.itemsize))


def _lmhead_chunks(v, bv):
    return min(MAX_T_CHUNKS, -(-v // bv))


@functools.partial(jax.jit, static_argnames=("n_v_chunks",))
def _lmhead_mn_fwd(h, w, labels, *, n_v_chunks: int):
    """jnp chunked (m, n) fused LM-head CE: (loss, m_sum, n_sum)."""
    from repro.core import numerics

    t, d = h.shape
    v = w.shape[1]
    hf, wf = h.astype(jnp.float32), w.astype(jnp.float32)
    vc = -(-v // n_v_chunks)
    m_acc = jnp.zeros((t, 1), jnp.float32)
    n_acc = jnp.full((t, 1), numerics.MINUS_INF_N)
    lab_logit = jnp.zeros((t,), jnp.float32)
    for j in range(n_v_chunks):
        lo, hi = j * vc, min(v, (j + 1) * vc)
        if lo >= hi:
            continue
        x = hf @ wf[:, lo:hi]
        m, n = numerics.ext_exp(x)
        n_loc = jnp.max(n, axis=-1, keepdims=True)
        m_loc = jnp.sum(m * numerics.exp2_int(n - n_loc), axis=-1,
                        keepdims=True)
        n_new = jnp.maximum(n_acc, n_loc)
        m_acc = (m_acc * numerics.exp2_int(n_acc - n_new)
                 + m_loc * numerics.exp2_int(n_loc - n_new))
        n_acc = n_new
        hit = jnp.arange(lo, hi)[None, :] == labels[:, None]
        lab_logit = lab_logit + jnp.sum(jnp.where(hit, x, 0.0), axis=-1)
    lse = (jnp.log(jnp.maximum(m_acc, 1e-37))
           + n_acc * jnp.float32(numerics.LN2_HI + numerics.LN2_LO))
    return lse[:, 0] - lab_logit, m_acc, n_acc


@functools.partial(jax.jit, static_argnames=("n_v_chunks",))
def _lmhead_mn_bwd(h, w, labels, m_sum, n_sum, dloss, *, n_v_chunks: int):
    """jnp chunked LM-head CE backward from saved stats: (dh, dw)."""
    from repro.core import numerics

    t, d = h.shape
    v = w.shape[1]
    hf, wf = h.astype(jnp.float32), w.astype(jnp.float32)
    inv = 1.0 / jnp.maximum(m_sum, 1e-37)
    vc = -(-v // n_v_chunks)
    dh = jnp.zeros((t, d), jnp.float32)
    dw_parts = []
    for j in range(n_v_chunks):
        lo, hi = j * vc, min(v, (j + 1) * vc)
        if lo >= hi:
            continue
        x = hf @ wf[:, lo:hi]
        m, n = numerics.ext_exp(x)
        p = m * numerics.exp2_int(n - n_sum) * inv
        hit = jnp.arange(lo, hi)[None, :] == labels[:, None]
        dlog = (p - jnp.where(hit, 1.0, 0.0)) * dloss[:, None]
        dh = dh + dlog @ wf[:, lo:hi].T
        dw_parts.append(hf.T @ dlog)
    return dh, jnp.concatenate(dw_parts, axis=1)


def _lmhead_pad(h, w, labels, bt, bv):
    """Pad tokens/vocab to tiles.  h rows pad with ZEROS (finite logits —
    an -inf-style row pad would make the recomputed probabilities NaN and
    poison dw); w columns pad with zeros and the kernel's ``v_len`` mask
    sends them to -inf score-side."""
    t, d = h.shape
    v = w.shape[1]
    pt, pv = _round_up(t, bt), _round_up(v, bv)
    if pt != t:
        h = jnp.pad(h, ((0, pt - t), (0, 0)))
        labels = jnp.pad(labels.astype(jnp.int32), (0, pt - t))
    if pv != v:
        w = jnp.pad(w, ((0, 0), (0, pv - v)))
    return h, w, labels.astype(jnp.int32), pt, pv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def lmhead_cross_entropy(h: jax.Array, w: jax.Array, labels: jax.Array,
                         block_t: int | None = None,
                         block_v: int | None = None,
                         policy=None, impl: str | None = None) -> jax.Array:
    """Per-token CE of ``h @ w`` vs ``labels`` without materializing the
    logits.  h: [T, D]; w: [D, V]; labels: [T] int -> loss [T] f32.
    Differentiable in h and w; ``impl`` pins "pallas" | "twopass" | "ref"
    (None = policy-dispatched like :func:`flash_attention`)."""
    loss, _ = _lmhead_fwd(h, w, labels, block_t, block_v, policy, impl)
    return loss


def _lmhead_fwd_stats(h, w, labels, block_t, block_v, policy, impl):
    bt, bv = _lmhead_blocks(h, w, block_t, block_v, policy)
    if impl == "twopass":
        return _lmhead_mn_fwd(h, w, labels,
                              n_v_chunks=_lmhead_chunks(w.shape[1], bv))
    t, v = h.shape[0], w.shape[1]
    hp, wp, lab, pt, pv = _lmhead_pad(h, w, labels, bt, bv)
    loss, m_sum, n_sum = _xent.lmhead_xent_fwd_2d(
        hp, wp, lab, block_t=bt, block_v=bv, v_len=v)
    return loss[:t], m_sum[:t], n_sum[:t]


def _lmhead_fwd(h, w, labels, block_t, block_v, policy, impl):
    impl = train_bwd_impl(policy, impl)
    if impl == "ref":
        loss = _lmhead_ref_loss(h, w, labels)
        return loss, (h, w, labels, None, None)
    loss, m_sum, n_sum = _lmhead_fwd_stats(h, w, labels, block_t, block_v,
                                           policy, impl)
    return loss, (h, w, labels, m_sum, n_sum)


def _lmhead_bwd(block_t, block_v, policy, impl, res, dloss):
    h, w, labels, m_sum, n_sum = res
    impl = train_bwd_impl(policy, impl)
    if impl == "ref":
        _, vjp = jax.vjp(lambda h_, w_: _lmhead_ref_loss(h_, w_, labels),
                         h, w)
        dh, dw = vjp(dloss)
        return dh, dw, None
    bt, bv = _lmhead_blocks(h, w, block_t, block_v, policy)
    if impl == "twopass":
        dh, dw = _lmhead_mn_bwd(h, w, labels, m_sum, n_sum,
                                dloss.astype(jnp.float32),
                                n_v_chunks=_lmhead_chunks(w.shape[1], bv))
    else:
        t, v = h.shape[0], w.shape[1]
        hp, wp, lab, pt, pv = _lmhead_pad(h, w, labels, bt, bv)
        dl = jnp.zeros((pt,), jnp.float32).at[:t].set(
            dloss.astype(jnp.float32))
        if pt != t:
            # Padded token rows: stats (m=1, n=0) keep the recomputed p
            # finite; dloss=0 zeroes their dlogits, so dw stays clean.
            m_sum = jnp.pad(m_sum, ((0, pt - t), (0, 0)),
                            constant_values=1.0)
            n_sum = jnp.pad(n_sum, ((0, pt - t), (0, 0)))
        dh = _xent.lmhead_xent_dh_2d(hp, wp, lab, m_sum, n_sum, dl,
                                     block_t=bt, block_v=bv, v_len=v)[:t]
        dw = _xent.lmhead_xent_dw_2d(hp, wp, lab, m_sum, n_sum, dl,
                                     block_t=bt, block_v=bv,
                                     v_len=v)[:, :v]
    return dh.astype(h.dtype), dw.astype(w.dtype), None


lmhead_cross_entropy.defvjp(_lmhead_fwd, _lmhead_bwd)


# ---------------------------------------------------------------------------
# Flash attention.  Three implementations per phase, dispatched by
# ``train_bwd_impl`` on SoftmaxPolicy.use_kernels / an explicit ``impl=``:
#
#   "pallas"  — the kernels in kernels/flash_attention.py (fwd saves the
#               (m, n) statistics; bwd re-streams K/V tiles against them).
#               Production on TPU; interpret mode on CPU (parity tests).
#   "twopass" — the jnp chunked (m, n) forms below: the same
#               recompute-from-stats backward, XLA-compiled.  Production on
#               CPU/GPU, and the reference the Pallas backward is tested
#               against at matched tiles.
#   "ref"     — jax.vjp over kernels/ref.attention_ref (materialized
#               scores): the oracle, and the bench's reference lane.
#
# Without a policy the legacy split applies — Pallas forward, reference
# VJP backward — so callers that never opted into kernels keep their exact
# previous numerics.
# ---------------------------------------------------------------------------
def _train_backend_impl() -> str:
    """The production implementation for the training-side backward ops on
    this backend: Pallas on TPU, the jnp (m, n) forms elsewhere — CPU
    Pallas is interpret mode (a correctness artifact, not a fast path; cf.
    ``autotune.decode_kernel_path``) and GPU lowering is untested."""
    return "pallas" if jax.default_backend() == "tpu" else "twopass"


def train_bwd_impl(policy=None, impl: str | None = None) -> str:
    """Backward-implementation dispatch for ``flash_attention`` /
    ``lmhead_cross_entropy``.  Explicit ``impl`` wins (tests/tuner callers
    pick knowingly); ``policy.use_kernels`` routes to the backend's
    production implementation; otherwise the reference VJP."""
    if impl is not None:
        if impl not in ("pallas", "twopass", "ref"):
            raise ValueError(f"unknown impl {impl!r}")
        return impl
    if policy is not None and policy.use_kernels:
        return _train_backend_impl()
    return "ref"


def _flash_impls(policy, impl) -> tuple[str, str]:
    """(forward, backward) implementation pair for ``flash_attention``.
    The stats-saving implementations pair with themselves; the "ref"
    backward keeps the legacy Pallas forward unless "ref" was explicit."""
    bwd = train_bwd_impl(policy, impl)
    if bwd != "ref":
        return bwd, bwd
    return ("ref" if impl == "ref" else "pallas"), "ref"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: float | None = None,
                    window: int | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    policy=None, impl: str | None = None) -> jax.Array:
    """Flash attention with registry-resolved tiles.  q/k: [B, H, S, D]
    (H pre-expanded to q-heads); v: [B, H, Skv, Dv].  ``block_q``/
    ``block_k`` are explicit overrides (the autotuner sweeps through
    them); ``policy`` (hashable, safe as a nondiff arg) carries attn
    overrides + the autotune cache setting and routes the backward through
    the saved-statistics kernels (see the dispatch table above); ``impl``
    pins "pallas" | "twopass" | "ref" explicitly."""
    o, _ = _flash_fwd(q, k, v, causal, scale, window, block_q, block_k,
                      policy, impl)
    return o


def _flash_pallas_fwd(q, k, v, causal, scale, window, block_q=None,
                      block_k=None, policy=None):
    """Pad to tiles, run the Pallas forward, slice -> (o, m_sum, n_sum)."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    bq, bk = _blocks("flash_attention", sq, skv, q.dtype, block_q, block_k,
                     policy)
    bq, bk = min(bq, _round_up(sq, 128)), min(bk, _round_up(skv, 128))
    psq, pskv = _round_up(sq, bq), _round_up(skv, bk)
    if psq != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, psq - sq), (0, 0)))
    if pskv != skv:
        # Padded KV must not receive weight: finite pads can't force -inf
        # scores, so padding sits at the END and the kernel's kv_len mask
        # (kpos < skv) kills it.
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pskv - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pskv - skv), (0, 0)))
    o, m_sum, n_sum = _fa.flash_attention_fwd_gqa(
        q, k, v, causal=causal, scale=scale, window=window,
        block_q=bq, block_k=bk, kv_len=skv, q_len=sq)
    return o[:, :, :sq, :], m_sum[:, :, :sq], n_sum[:, :, :sq]


def _flash_fwd_padded(q, k, v, causal, scale, window, block_q=None,
                      block_k=None, policy=None):
    """Output-only Pallas forward (registry bind / non-vjp callers)."""
    o, _, _ = _flash_pallas_fwd(q, k, v, causal, scale, window, block_q,
                                block_k, policy)
    return o


@functools.partial(jax.jit, static_argnames=("causal", "scale", "window",
                                             "n_q_chunks", "n_kv_chunks"))
def _flash_mn_fwd(q, k, v, *, causal: bool, scale: float,
                  window: int | None, n_q_chunks: int, n_kv_chunks: int):
    """jnp chunked (m, n) flash forward: [B, H, S, D] -> (o, m_sum, n_sum).
    The same end-aligned masking as the Pallas kernel (qpos = i + Skv - Sq,
    matching ref.attention_ref); chunk loops are Python-unrolled."""
    from repro.core import numerics

    b, h, sq, d = q.shape
    skv = k.shape[2]
    dv = v.shape[3]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    qc = -(-sq // n_q_chunks)
    kc = -(-skv // n_kv_chunks)
    os_, ms, ns = [], [], []
    for i in range(n_q_chunks):
        qlo, qhi = i * qc, min(sq, (i + 1) * qc)
        if qlo >= qhi:
            continue
        qpos = (jnp.arange(qlo, qhi) + (skv - sq))[:, None]
        o_acc = jnp.zeros((b, h, qhi - qlo, dv), jnp.float32)
        m_acc = jnp.zeros((b, h, qhi - qlo, 1), jnp.float32)
        n_acc = jnp.full((b, h, qhi - qlo, 1), numerics.MINUS_INF_N)
        for j in range(n_kv_chunks):
            klo, khi = j * kc, min(skv, (j + 1) * kc)
            if klo >= khi:
                continue
            s = jnp.einsum("bhqd,bhkd->bhqk", qf[:, :, qlo:qhi],
                           kf[:, :, klo:khi]) * scale
            if causal or window is not None:
                kpos = jnp.arange(klo, khi)[None, :]
                mask = jnp.ones((qhi - qlo, khi - klo), bool)
                if causal:
                    mask &= kpos <= qpos
                if window is not None:
                    mask &= kpos > qpos - window
                s = jnp.where(mask, s, _NEG_INF)
            m, n = numerics.ext_exp(s)
            n_loc = jnp.max(n, axis=-1, keepdims=True)
            w = m * numerics.exp2_int(n - n_loc)
            m_loc = jnp.sum(w, axis=-1, keepdims=True)
            o_loc = jnp.einsum("bhqk,bhkd->bhqd", w, vf[:, :, klo:khi])
            n_new = jnp.maximum(n_acc, n_loc)
            a_acc = numerics.exp2_int(n_acc - n_new)
            a_loc = numerics.exp2_int(n_loc - n_new)
            o_acc = o_acc * a_acc + o_loc * a_loc
            m_acc = m_acc * a_acc + m_loc * a_loc
            n_acc = n_new
        os_.append(o_acc / jnp.maximum(m_acc, 1e-37))
        ms.append(m_acc)
        ns.append(n_acc)
    return (jnp.concatenate(os_, axis=2).astype(q.dtype),
            jnp.concatenate(ms, axis=2), jnp.concatenate(ns, axis=2))


@functools.partial(jax.jit, static_argnames=("causal", "scale", "window",
                                             "n_q_chunks", "n_kv_chunks"))
def _flash_mn_bwd(q, k, v, o, m_sum, n_sum, do, *, causal: bool,
                  scale: float, window: int | None, n_q_chunks: int,
                  n_kv_chunks: int):
    """jnp recompute-style flash backward: probabilities reconstructed per
    chunk from the forward's (m_sum, n_sum) — ``p = m * 2^(n - n_sum) /
    m_sum`` with exact power-of-two rescales — then the standard dq/dk/dv
    contractions, no score matrix ever materialized whole."""
    from repro.core import numerics

    b, h, sq, d = q.shape
    skv = k.shape[2]
    dv = v.shape[3]
    qf, kf, vf, dof = (x.astype(jnp.float32) for x in (q, k, v, do))
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1, keepdims=True)
    inv = 1.0 / jnp.maximum(m_sum, 1e-37)
    qc = -(-sq // n_q_chunks)
    kc = -(-skv // n_kv_chunks)
    dqs = []
    dk_parts: dict = {}
    dv_parts: dict = {}
    for i in range(n_q_chunks):
        qlo, qhi = i * qc, min(sq, (i + 1) * qc)
        if qlo >= qhi:
            continue
        qpos = (jnp.arange(qlo, qhi) + (skv - sq))[:, None]
        do_i = dof[:, :, qlo:qhi]
        dq_i = jnp.zeros((b, h, qhi - qlo, d), jnp.float32)
        for j in range(n_kv_chunks):
            klo, khi = j * kc, min(skv, (j + 1) * kc)
            if klo >= khi:
                continue
            s = jnp.einsum("bhqd,bhkd->bhqk", qf[:, :, qlo:qhi],
                           kf[:, :, klo:khi]) * scale
            if causal or window is not None:
                kpos = jnp.arange(klo, khi)[None, :]
                mask = jnp.ones((qhi - qlo, khi - klo), bool)
                if causal:
                    mask &= kpos <= qpos
                if window is not None:
                    mask &= kpos > qpos - window
                s = jnp.where(mask, s, _NEG_INF)
            m, n = numerics.ext_exp(s)
            p = (m * numerics.exp2_int(n - n_sum[:, :, qlo:qhi])
                 * inv[:, :, qlo:qhi])
            dp = jnp.einsum("bhqe,bhke->bhqk", do_i, vf[:, :, klo:khi])
            ds = p * (dp - delta[:, :, qlo:qhi]) * scale
            dq_i += jnp.einsum("bhqk,bhkd->bhqd", ds, kf[:, :, klo:khi])
            dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf[:, :, qlo:qhi])
            dv_j = jnp.einsum("bhqk,bhqe->bhke", p, do_i)
            dk_parts[j] = dk_parts.get(j, 0.0) + dk_j
            dv_parts[j] = dv_parts.get(j, 0.0) + dv_j
        dqs.append(dq_i)
    dk = jnp.concatenate([dk_parts[j] for j in sorted(dk_parts)], axis=2)
    dv_ = jnp.concatenate([dv_parts[j] for j in sorted(dv_parts)], axis=2)
    return (jnp.concatenate(dqs, axis=2).astype(q.dtype),
            dk.astype(k.dtype), dv_.astype(v.dtype))


def _flash_chunk_counts(sq, skv, bq, bk):
    return (min(MAX_SLOT_CHUNKS, -(-sq // bq)),
            min(MAX_T_CHUNKS, -(-skv // bk)))


def flash_attention_fwd_stats(q, k, v, *, causal: bool = False,
                              scale: float | None = None,
                              window: int | None = None,
                              block_q: int | None = None,
                              block_k: int | None = None,
                              policy=None, impl: str | None = None):
    """(o, m_sum, n_sum) via a stats-saving forward — the residuals
    :func:`flash_attention_bwd` consumes.  ``impl=None`` picks the
    backend's production implementation (tuner/tests entry)."""
    if impl is None:
        impl = _train_backend_impl()
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if impl == "pallas":
        return _flash_pallas_fwd(q, k, v, causal, scale, window, block_q,
                                 block_k, policy)
    sq, skv = q.shape[2], k.shape[2]
    bq, bk = _blocks("flash_attention", sq, skv, q.dtype, block_q, block_k,
                     policy)
    nq, nkv = _flash_chunk_counts(sq, skv, bq, bk)
    return _flash_mn_fwd(q, k, v, causal=causal, scale=scale, window=window,
                         n_q_chunks=nq, n_kv_chunks=nkv)


def flash_attention_bwd(q, k, v, o, m_sum, n_sum, do, *,
                        causal: bool = False, scale: float | None = None,
                        window: int | None = None,
                        block_q: int | None = None,
                        block_k: int | None = None,
                        policy=None, impl: str | None = None):
    """dq/dk/dv from the forward's saved (m, n) statistics — the
    ``flash_attention_bwd`` registry op (what the autotuner sweeps).

    q/k: [B, H, S, D]; v/o/do: [B, H, S, Dv]; m_sum/n_sum: [B, H, Sq, 1]
    f32 from :func:`flash_attention_fwd_stats` at the same settings.
    ``impl`` is "pallas" or "twopass" (None = the backend's production
    implementation); tiles resolve through the registry with the
    tensor-parallel ``|s{tp}`` cache suffix when an active mesh shards the
    head axis."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if impl is None:
        impl = _train_backend_impl()
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    shards, _ = _tp_shards(h)
    bq, bk = _blocks("flash_attention_bwd", sq, skv, q.dtype, block_q,
                     block_k, policy, shards=shards)
    if impl == "twopass":
        nq, nkv = _flash_chunk_counts(sq, skv, bq, bk)
        return _flash_mn_bwd(q, k, v, o, m_sum, n_sum, do, causal=causal,
                             scale=scale, window=window, n_q_chunks=nq,
                             n_kv_chunks=nkv)
    bq, bk = min(bq, _round_up(sq, 128)), min(bk, _round_up(skv, 128))
    psq, pskv = _round_up(sq, bq), _round_up(skv, bk)
    if psq != sq:
        # Padded q rows: zero q/o/do with stats (m=1, n=0) makes the
        # recomputed p finite and ds exactly zero — no NaN can leak into
        # the dk/dv accumulation from the padding.
        pad4 = ((0, 0), (0, 0), (0, psq - sq), (0, 0))
        q, o, do = (jnp.pad(x, pad4) for x in (q, o, do))
        m_sum = jnp.pad(m_sum, pad4, constant_values=1.0)
        n_sum = jnp.pad(n_sum, pad4)
    if pskv != skv:
        pad4 = ((0, 0), (0, 0), (0, pskv - skv), (0, 0))
        k, v = jnp.pad(k, pad4), jnp.pad(v, pad4)
    dq, dk, dv = _fa.flash_attention_bwd_gqa(
        q, k, v, o, m_sum, n_sum, do, causal=causal, scale=scale,
        window=window, block_q=bq, block_k=bk, q_len=sq, kv_len=skv)
    return dq[:, :, :sq], dk[:, :, :skv], dv[:, :, :skv]


def _flash_fwd(q, k, v, causal, scale, window, block_q, block_k, policy,
               impl):
    fwd_impl, bwd_impl = _flash_impls(policy, impl)
    if fwd_impl == "ref":
        o = _ref.attention_ref(q, k, v, causal=causal, scale=scale,
                               window=window)
        return o, (q, k, v, None, None, None)
    if fwd_impl == "twopass":
        if scale is None:
            scale = 1.0 / (q.shape[-1] ** 0.5)
        sq, skv = q.shape[2], k.shape[2]
        bq, bk = _blocks("flash_attention", sq, skv, q.dtype, block_q,
                         block_k, policy)
        nq, nkv = _flash_chunk_counts(sq, skv, bq, bk)
        o, m_sum, n_sum = _flash_mn_fwd(q, k, v, causal=causal, scale=scale,
                                        window=window, n_q_chunks=nq,
                                        n_kv_chunks=nkv)
    else:
        o, m_sum, n_sum = _flash_pallas_fwd(q, k, v, causal, scale, window,
                                            block_q, block_k, policy)
    if bwd_impl == "ref":
        return o, (q, k, v, None, None, None)
    return o, (q, k, v, o, m_sum, n_sum)


def _flash_bwd(causal, scale, window, block_q, block_k, policy, impl, res,
               do):
    q, k, v, o, m_sum, n_sum = res
    _, bwd_impl = _flash_impls(policy, impl)
    if bwd_impl == "ref":
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _ref.attention_ref(q_, k_, v_, causal=causal,
                                                  scale=scale,
                                                  window=window),
            q, k, v)
        return vjp(do)
    return flash_attention_bwd(q, k, v, o, m_sum, n_sum, do, causal=causal,
                               scale=scale, window=window, block_q=block_q,
                               block_k=block_k, policy=policy,
                               impl=bwd_impl)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Decode attention: single query per slot against a length-masked KV cache
# (the continuous-batching serving hot path).  Online-softmax accumulation in
# the paper's (m, n) representation — rescales are exact powers of two — so
# KV can be consumed in chunks without ever materializing a full softmax row.
#
# Two implementations per op, dispatched on SoftmaxPolicy.use_kernels (or an
# explicit ``use_kernel=``): the Pallas kernels in kernels/decode_attention.py
# (length mask + page-table gather fused into the VMEM KV sweep; interpret
# mode on CPU) and the jnp chunked forms below, which remain the reference /
# fallback the kernels are tested against.
# ---------------------------------------------------------------------------
MAX_SLOT_CHUNKS = 8          # unrolled-loop guards (chunk loops are Python-
MAX_T_CHUNKS = 16            # unrolled; counts bound the traced HLO size)

_NEG_INF = -jnp.inf


def _mn_mask_update(acc, q_blk, k_chunk, v_chunk, kpos, l_blk, *,
                    scale: float, window: int | None,
                    k_scale=None, v_scale=None):
    """One (m, n) online-softmax accumulation step of the single-query
    decode sweep: score the chunk, apply the length/window mask, fold into
    the running ``(o, m, n)`` accumulator (rescales are exact powers of two,
    so chunks — and therefore pages — may be visited in any order).

    The slot's query sits at position ``l_blk - 1`` (write-then-attend), so
    the validity prefix IS the causal mask; SWA adds a lower bound relative
    to that query position.

    ``k_scale``/``v_scale`` (broadcastable to the ``[s, h, g, t]`` score
    shape) fuse int8 dequantization into the sweep: a symmetric per-column
    scale commutes through the dot products, so ``(q · k_int8) * k_scale``
    and ``(w * v_scale) · v_int8`` equal attention over the dequantized
    chunk exactly — no full-precision copy of the chunk is ever formed.
    """
    from repro.core import numerics

    o_acc, m_acc, n_acc = acc
    sco = jnp.einsum("shgd,shtd->shgt", q_blk, k_chunk) * scale
    if k_scale is not None:
        sco = sco * k_scale
    mask = kpos[None, :] < l_blk[:, None]
    if window is not None:
        mask &= kpos[None, :] > l_blk[:, None] - 1 - window
    sco = jnp.where(mask[:, None, None, :], sco, _NEG_INF)

    m, n = numerics.ext_exp(sco)
    n_loc = jnp.max(n, axis=-1, keepdims=True)
    w = m * numerics.exp2_int(n - n_loc)
    m_loc = jnp.sum(w, axis=-1, keepdims=True)
    if v_scale is not None:
        w = w * v_scale
    o_loc = jnp.einsum("shgt,shtd->shgd", w, v_chunk)

    n_new = jnp.maximum(n_acc, n_loc)
    a_acc = numerics.exp2_int(n_acc - n_new)
    a_loc = numerics.exp2_int(n_loc - n_new)
    return (o_acc * a_acc + o_loc * a_loc,
            m_acc * a_acc + m_loc * a_loc, n_new)


def _mn_init(bs: int, hkv: int, g: int, dv: int):
    from repro.core import numerics

    return (jnp.zeros((bs, hkv, g, dv), jnp.float32),
            jnp.zeros((bs, hkv, g, 1), jnp.float32),
            jnp.full((bs, hkv, g, 1), numerics.MINUS_INF_N))


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "n_s_chunks", "n_t_chunks"))
def _decode_attention_chunked(q, k, v, lengths, *, scale: float,
                              window: int | None, n_s_chunks: int,
                              n_t_chunks: int):
    """(m, n)-streamed single-query attention.  See :func:`decode_attention`
    for shapes.  ``lengths`` is traced (per-slot cache fill); chunk loops are
    Python-unrolled, so no chunk can be pruned at trace time."""
    s, hkv, g, d = q.shape
    t = k.shape[2]
    dv = v.shape[3]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    lens = lengths.astype(jnp.int32)

    sc = -(-s // n_s_chunks)
    tc = -(-t // n_t_chunks)
    outs = []
    for i in range(n_s_chunks):
        q_blk = qf[i * sc:(i + 1) * sc]
        bs = q_blk.shape[0]
        if bs == 0:
            continue
        l_blk = lens[i * sc:i * sc + bs]                  # [bs]
        acc = _mn_init(bs, hkv, g, dv)
        for j in range(n_t_chunks):
            lo, hi = j * tc, min(t, (j + 1) * tc)
            if lo >= hi:
                continue
            acc = _mn_mask_update(
                acc, q_blk, kf[i * sc:i * sc + bs, :, lo:hi],
                vf[i * sc:i * sc + bs, :, lo:hi], jnp.arange(lo, hi),
                l_blk, scale=scale, window=window)
        # Fully-masked slots (length 0: a free pool slot) have m_acc == 0;
        # the max() guard turns their output into exact zeros, not NaN.
        outs.append(acc[0] / jnp.maximum(acc[1], 1e-37))
    return jnp.concatenate(outs, axis=0).astype(q.dtype)


def _gather_scale_chunk(scale_leaf, pt, bs, npg, ps, hkv):
    """Gather one t-chunk's scale rows through the page table and shape
    them to broadcast against the ``[bs, hkv, g, t]`` scores: ``[bs, 1, 1,
    t]`` for "page" scales (``[P, ps]`` sidecar), ``[bs, hkv, 1, t]`` for
    "page_head" (``[P, ps, Hkv]``)."""
    sch = scale_leaf[pt]                             # [bs, npg, ps(, hkv)]
    if scale_leaf.ndim == 2:
        return sch.reshape(bs, 1, 1, npg * ps)
    return sch.reshape(bs, npg * ps, hkv).transpose(0, 2, 1)[:, :, None, :]


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "n_s_chunks", "n_t_chunks"))
def _decode_attention_paged_chunked(q, k_pages, v_pages, page_table, lengths,
                                    k_scale=None, v_scale=None,
                                    *, scale: float, window: int | None,
                                    n_s_chunks: int, n_t_chunks: int):
    """Paged variant of :func:`_decode_attention_chunked`: K/V live in a
    shared page arena and are gathered per t-chunk through the per-slot page
    table, so only a chunk's worth of contiguous KV ever materializes.  The
    (m, n) accumulation is order-free (power-of-two rescales), which is what
    lets the sweep visit arena pages in whatever order the table holds.

    With ``k_scale``/``v_scale`` (int8 arenas + fp32 sidecars) the chunk's
    scale rows are gathered through the same table and folded into the
    sweep as per-column multipliers (:func:`_mn_mask_update`): the int8
    pages are cast per-chunk on their way into the dot products, never as
    a whole-arena full-precision copy."""
    s, hkv, g, d = q.shape
    ps = k_pages.shape[1]                 # tokens per page
    pmax = page_table.shape[1]            # pages per slot (logical T / ps)
    dv = v_pages.shape[3]
    qf = q.astype(jnp.float32)
    lens = lengths.astype(jnp.int32)

    sc = -(-s // n_s_chunks)
    pc = -(-pmax // n_t_chunks)           # whole pages per t-chunk
    outs = []
    for i in range(n_s_chunks):
        q_blk = qf[i * sc:(i + 1) * sc]
        bs = q_blk.shape[0]
        if bs == 0:
            continue
        l_blk = lens[i * sc:i * sc + bs]
        pt_blk = page_table[i * sc:i * sc + bs]          # [bs, pmax]
        acc = _mn_init(bs, hkv, g, dv)
        for j in range(n_t_chunks):
            p0, p1 = j * pc, min(pmax, (j + 1) * pc)
            if p0 >= p1:
                continue
            npg = p1 - p0
            # Gather this chunk's pages: [bs, npg, ps, hkv, *] -> the
            # contiguous [bs, hkv, npg * ps, *] layout the sweep consumes.
            # Free/trash pages surface garbage, killed by the length mask.
            pt = pt_blk[:, p0:p1]
            kc = k_pages[pt].reshape(bs, npg * ps, hkv, d)
            vc = v_pages[pt].reshape(bs, npg * ps, hkv, dv)
            ksc = vsc = None
            if k_scale is not None:
                ksc = _gather_scale_chunk(k_scale, pt, bs, npg, ps, hkv)
                vsc = _gather_scale_chunk(v_scale, pt, bs, npg, ps, hkv)
            acc = _mn_mask_update(
                acc, q_blk, kc.transpose(0, 2, 1, 3).astype(jnp.float32),
                vc.transpose(0, 2, 1, 3).astype(jnp.float32),
                jnp.arange(p0 * ps, p1 * ps), l_blk,
                scale=scale, window=window, k_scale=ksc, v_scale=vsc)
        outs.append(acc[0] / jnp.maximum(acc[1], 1e-37))
    return jnp.concatenate(outs, axis=0).astype(q.dtype)


def _kernel_path(policy, use_kernel) -> bool:
    """Decode-op dispatch.  Explicit ``use_kernel`` wins unconditionally
    (tests/tuner callers pick their path knowingly); otherwise the
    policy's ``use_kernels`` switch routes to the Pallas kernels ONLY on
    backends that can run them — TPU for real, CPU in interpret mode.
    The decode kernels' scalar-prefetch grid spec is TPU-specific, so a
    GPU policy falls back to the jnp (m, n) forms instead of failing to
    lower in the serving hot path (matching
    ``autotune.decode_kernel_path``, which tunes the jnp path there)."""
    if use_kernel is not None:
        return bool(use_kernel)
    if policy is None or not policy.use_kernels:
        return False
    return jax.default_backend() in ("cpu", "tpu")


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, scale: float | None = None,
                     window: int | None = None,
                     block_s: int | None = None,
                     block_t: int | None = None,
                     policy=None, use_kernel: bool | None = None
                     ) -> jax.Array:
    """Single-query attention against a length-masked KV cache.

    q: [S, Hkv, G, D] (one query per slot, grouped heads); k: [S, Hkv, T, D];
    v: [S, Hkv, T, Dv]; lengths: [S] int32 — valid cache prefix per slot
    (position ``lengths - 1`` holds the slot's own query token; 0 marks a
    free slot, whose output is exact zeros).  Returns [S, Hkv, G, Dv].

    Registry resolution: rows = S (slots), cols = T (cache positions).
    ``block_s``/``block_t`` are explicit overrides (what the autotuner
    sweeps); ``policy`` carries attn overrides + the autotune cache
    setting.  Dispatch (``policy.use_kernels`` / explicit ``use_kernel``):
    the Pallas kernel streams KV in ``block_t`` VMEM tiles with the length
    mask fused into the sweep (``block_s`` does not apply — the kernel
    grid is one row per slot); the jnp fallback uses the resolved blocks
    as chunk lengths for the unrolled (m, n) loop, capped by
    ``MAX_SLOT_CHUNKS``/``MAX_T_CHUNKS``.
    """
    s, hkv, _, d = q.shape
    t = k.shape[2]
    kernel = _kernel_path(policy, use_kernel)
    shards, mesh = _tp_shards(hkv) if kernel else (1, None)
    bs, bt = _blocks("decode_attention", s, t, q.dtype, block_s, block_t,
                     policy, shards=shards)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if kernel:
        fn = functools.partial(_da.decode_attention_pallas, scale=scale,
                               window=window, block_t=bt)
        if shards > 1:
            # Head axis (dim 1 of q/k/v) over model; lengths replicated.
            hs = P(None, "model", None, None)
            fn = jax.shard_map(fn, mesh=mesh,
                               in_specs=(hs, hs, hs, P(None)),
                               out_specs=hs, check_vma=False)
        return fn(q, k, v, lengths)
    return _decode_attention_chunked(
        q, k, v, lengths, scale=scale, window=window,
        n_s_chunks=min(MAX_SLOT_CHUNKS, -(-s // bs)),
        n_t_chunks=min(MAX_T_CHUNKS, -(-t // bt)))


def decode_attention_paged(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *, scale: float | None = None,
                           window: int | None = None,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           block_s: int | None = None,
                           block_t: int | None = None,
                           policy=None, use_kernel: bool | None = None
                           ) -> jax.Array:
    """Single-query attention against a PAGED KV cache.

    q: [S, Hkv, G, D]; k_pages: [P, ps, Hkv, D]; v_pages: [P, ps, Hkv, Dv]
    (the shared page arenas of ``kv_cache.init_paged_pool``, one row per
    page of ``ps`` tokens); page_table: [S, Pmax] int32 — arena page ids
    backing each slot's logical positions ``[p * ps, (p + 1) * ps)``;
    lengths: [S] int32 valid-prefix per slot (position ``lengths - 1`` holds
    the slot's own query; 0 marks a free slot, output exact zeros).  Returns
    [S, Hkv, G, Dv], identical to :func:`decode_attention` over the
    contiguous cache the table describes.

    Registry resolution: rows = S, cols = Pmax * ps (logical positions);
    the resolved col block is rounded DOWN to whole pages so every t-chunk
    gathers full pages through the table.  Entries of the table that back
    no valid position (a free slot, or pages past ``lengths``) may point
    anywhere — the length mask makes their content invisible.

    Dispatch (``policy.use_kernels`` / explicit ``use_kernel``): the Pallas
    kernel gathers the arena pages tile-by-tile in VMEM through the
    scalar-prefetched table (``pages_per_tile = block_t // ps``, capped by
    ``decode_attention.MAX_PAGES_PER_TILE``); the jnp fallback gathers
    whole page chunks via ``jnp.take`` into the shared (m, n) sweep.

    Quantized arenas (``kv_cache.init_paged_pool(page_dtype="int8")``) pass
    int8 ``k_pages``/``v_pages`` plus ``k_scale``/``v_scale`` fp32 sidecars
    (``[P, ps]`` "page" granularity or ``[P, ps, Hkv]`` "page_head");
    dequantization is fused into the (m, n) sweep — scale rows are gathered
    through the same page table and applied as per-column multipliers
    inside each tile, so no full-precision copy of the arena is ever
    materialized (the ``kv_page_quant`` registry op tunes the geometry).
    """
    s, hkv, _, d = q.shape
    ps = k_pages.shape[1]
    pmax = page_table.shape[1]
    t = pmax * ps
    kernel = _kernel_path(policy, use_kernel)
    shards, mesh = _tp_shards(hkv) if kernel else (1, None)
    bs, bt = _blocks("decode_attention_paged", s, t, q.dtype, block_s,
                     block_t, policy, shards=shards)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    pages_per_chunk = max(1, bt // ps)
    if kernel:
        fn = functools.partial(_da.decode_attention_paged_pallas,
                               scale=scale, window=window,
                               pages_per_tile=pages_per_chunk)
        if shards > 1:
            # q heads (dim 1) and arena heads (dim 2 of [P, ps, Hkv, D])
            # over model; the table and lengths replicated so every shard
            # gathers its own heads of each page.  "page" scales carry no
            # head axis (replicated); "page_head" scales split with the
            # arena heads.
            sc_spec = ()
            if k_scale is not None:
                one = (P(None, None) if k_scale.ndim == 2
                       else P(None, None, "model"))
                sc_spec = (one, one)
            fn = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P(None, "model", None, None),
                          P(None, None, "model", None),
                          P(None, None, "model", None),
                          P(None, None), P(None)) + sc_spec,
                out_specs=P(None, "model", None, None), check_vma=False)
        if k_scale is not None:
            return fn(q, k_pages, v_pages, page_table, lengths, k_scale,
                      v_scale)
        return fn(q, k_pages, v_pages, page_table, lengths)
    return _decode_attention_paged_chunked(
        q, k_pages, v_pages, page_table, lengths, k_scale, v_scale,
        scale=scale, window=window,
        n_s_chunks=min(MAX_SLOT_CHUNKS, -(-s // bs)),
        n_t_chunks=min(MAX_T_CHUNKS, -(-pmax // pages_per_chunk)))


def logsumexp_stats(x: jax.Array, block_rows: int | None = None,
                    block_cols: int | None = None, policy=None):
    """Pass-1 stats (m_sum, n_sum) for 2-D x via the Pallas kernel."""
    rows, cols = x.shape
    br, bc = _blocks("logsumexp", rows, cols, x.dtype, block_rows,
                     block_cols, policy)
    pr, pc = _round_up(rows, br), _round_up(cols, bc)
    padded = jnp.full((pr, pc), -jnp.inf, x.dtype)
    padded = jax.lax.dynamic_update_slice(padded, x, (0, 0))
    m, n = _tp2.twopass_stats_2d(padded, block_rows=br, block_cols=bc)
    return m[:rows], n[:rows]


# Attach kernel entry points to the registry specs (introspection surface
# for benchmarks/docs; the wrappers above remain the public API).
registry.bind("softmax", _tp2.twopass_softmax_2d)
registry.bind("logsumexp", _tp2.twopass_stats_2d)
registry.bind("xent", _xent.xent_fwd_2d)
registry.bind("flash_attention", _fa.flash_attention_gqa)
registry.bind("flash_attention_bwd", _fa.flash_attention_bwd_gqa)
registry.bind("lmhead_xent", _xent.lmhead_xent_fwd_2d)
registry.bind("decode_attention", _da.decode_attention_pallas)
registry.bind("decode_attention_paged", _da.decode_attention_paged_pallas)

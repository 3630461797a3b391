"""Plain float32 forward of a dense decoder, as the published description
has it: RMSNorm, rotary positions (rotate-half), grouped-query attention
with optional QKV bias and an optional sliding window, a SwiGLU MLP and
an untied LM head.  It imports nothing of the program under test and reads
only the benchmark's seeded weights (``weights.py``), one layer at a time.

``served_gaps`` reads, at each position of a served request, how far the
served token lies below the best logit.  ``fp8=True`` is the precision
control: every matrix product takes its two operands rounded to
float8_e4m3 (absmax-scaled per row of activations and per weight tensor),
the step below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
PAD = 256          # sequences are padded to a multiple of this (causal:
#                    a pad tail cannot reach an earlier position)
Q_BLOCK = 512      # query rows per attention block
V_BLOCK = 512      # positions per LM-head block


def _q8(x, axis):
    """Round to float8_e4m3 after scaling the absmax along ``axis`` (None:
    the whole tensor) onto the format's largest value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    """``a @ b`` for activations ``a`` [..., k] and weights ``b`` [k, n]."""
    if fp8:
        a, b = _q8(a, -1), _q8(b, None)
    return jnp.matmul(a, b, precision=HI)


def _bmm(spec, a, b, fp8: bool):
    """einsum of two activations (attention scores and values)."""
    if fp8:
        a, b = _q8(a, -1), _q8(b, -1)
    return jnp.einsum(spec, a, b, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """x: [S, H, hd]; rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(w, x, *, m: dict, eps: float, theta: float, window, fp8: bool):
    """One decoder layer over one sequence ``x`` [S, d]."""
    s = x.shape[0]
    h, kv, hd = m["h"], m["kv"], m["hd"]
    a = rmsnorm(x, w["ln1"], eps)
    q = _mm(a, w["wq"], fp8) + w.get("bq", 0.0)
    k = _mm(a, w["wk"], fp8) + w.get("bk", 0.0)
    v = _mm(a, w["wv"], fp8) + w.get("bv", 0.0)
    pos = jnp.arange(s)
    q = rope(q.reshape(s, h, hd), pos, theta)
    k = rope(k.reshape(s, kv, hd), pos, theta)
    v = v.reshape(s, kv, hd)
    rep = h // kv                                # query head i reads kv i//rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(s, lo + Q_BLOCK)
        sc = _bmm("qhd,khd->hqk", q[lo:hi], k, fp8) * hd ** -0.5
        qp, kp = pos[lo:hi, None], pos[None, :]
        mask = kp <= qp
        if window is not None:
            mask &= kp > qp - window
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_bmm("hqk,khd->qhd", p, v, fp8))
    o = jnp.concatenate(outs, 0).reshape(s, h * hd)
    x = x + _mm(o, w["wo"], fp8)
    a = rmsnorm(x, w["ln2"], eps)
    g = _mm(a, w["w_gate"], fp8)
    u = _mm(a, w["w_up"], fp8)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], fp8)


def _gap_block(x, w_head, targets, fp8):
    """Per position: (best logit, logit of ``targets``, argmax)."""
    lg = _mm(x, w_head, fp8)
    t = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[:, None], 1)[:, 0]
    return jnp.max(lg, -1), t, jnp.argmax(lg, -1)


class Forward:
    """The reference for one configuration and one weight seed."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.m = weights.dims(cfg)
        win = cfg.get("sliding_window")
        self._layer = {
            fp8: jax.jit(functools.partial(
                layer, m=self.m, eps=cfg["rms_norm_eps"],
                theta=cfg["rope_theta"], window=win, fp8=fp8))
            for fp8 in (False, True)}
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        self._weights = jax.jit(
            lambda i: weights.layer_weights(cfg, seed, i, self.dtype))
        self._gap = {fp8: jax.jit(functools.partial(_gap_block, fp8=fp8))
                     for fp8 in (False, True)}

    def hidden(self, seqs, fp8: bool):
        """Final-norm hidden states of each sequence, padded."""
        with jax.default_matmul_precision("highest"):
            emb = weights.global_weight(self.cfg, self.seed, "embed",
                                        self.dtype)
            xs = []
            for s in seqs:
                n = -(-len(s) // PAD) * PAD
                tok = np.zeros(n, np.int32)
                tok[:len(s)] = s
                xs.append(emb[tok])
            del emb
            for i in range(self.m["layers"]):
                w = self._weights(i)
                xs = [self._layer[fp8](w, x) for x in xs]
            nf = weights.global_weight(self.cfg, self.seed, "norm_f",
                                       self.dtype)
            return [rmsnorm(x, nf, self.cfg["rms_norm_eps"]) for x in xs]

    def head_stats(self, xs, targets, fp8: bool = False):
        """Per hidden-state sequence: arrays (best logit, logit of the
        target, argmax) at each position (a target < 0 reads token 0)."""
        head = weights.global_weight(self.cfg, self.seed, "lm_head",
                                     self.dtype)
        out = []
        with jax.default_matmul_precision("highest"):
            for x, t in zip(xs, targets):
                tt = np.zeros(x.shape[0], np.int32)
                tt[:len(t)] = t
                parts = [self._gap[fp8](x[lo:lo + V_BLOCK], head,
                                        tt[lo:lo + V_BLOCK])
                         for lo in range(0, x.shape[0], V_BLOCK)]
                out.append(tuple(
                    np.concatenate([np.asarray(p[j]) for p in parts])[
                        :len(t)] for j in range(3)))
        return out


def served_gaps(fwd: Forward, prompts, served, control: bool = False):
    """Widest gap per request between the reference's best logit and the
    logit of the token served at each position: ``(program, control)``.
    ``control`` also reads the gaps of the tokens that the fp8 reference
    puts first at the same positions (else it is None); every gap is read
    in float32."""
    seqs = [list(p) + list(t[:-1]) for p, t in zip(prompts, served)]
    starts = [len(p) - 1 for p in prompts]
    targets = [[-1] * a + list(t) for a, t in zip(starts, served)]
    xs = fwd.hidden(seqs, fp8=False)

    def widest(stats):
        return [float(np.max(best[a:] - tl[a:]))
                for a, (best, tl, _) in zip(starts, stats)]

    prog = widest(fwd.head_stats(xs, targets))
    if not control:
        return prog, None
    ctl = fwd.head_stats(fwd.hidden(seqs, fp8=True), targets, fp8=True)
    picks = [[-1] * a + list(am[a:]) for a, (_, _, am) in zip(starts, ctl)]
    return prog, widest(fwd.head_stats(xs, picks))

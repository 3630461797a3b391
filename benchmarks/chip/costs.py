"""Operations and bytes of the work the served model must do, from its
shapes alone: what the algorithm needs, not what a kernel happens to
fetch or a padded program happens to compute.

``m`` is ``weights.dims(config)``: d, h, kv, hd, ff, v, layers.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: every layer's projections
    and MLP, and the LM head (the embedding is a lookup)."""
    d, hd = m["d"], m["hd"]
    attn = d * (m["h"] + 2 * m["kv"]) * hd + m["h"] * hd * d
    return m["layers"] * (attn + 3 * d * m["ff"]) + d * m["v"]


def attention_flops(m: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, all
    layers."""
    return 4 * m["layers"] * m["h"] * m["hd"] * context


def decode_flops(m: dict, contexts) -> int:
    """Model FLOPs of decode steps: one token per entry of ``contexts``,
    each attending that many positions (itself included)."""
    contexts = list(contexts)
    return (2 * matmul_params(m) * len(contexts)
            + sum(attention_flops(m, c) for c in contexts))


def prefill_flops(m: dict, start: int, tokens: int) -> int:
    """Model FLOPs of prefilling ``tokens`` real prompt tokens after
    ``start`` cached ones (causal: position p attends p + 1 keys)."""
    ctx = tokens * start + tokens * (tokens + 1) // 2
    return 2 * matmul_params(m) * tokens + attention_flops(m, 1) * ctx


def paged_decode_cost(m: dict, context: int, kv_bytes: int = 2,
                      q_bytes: int = 2, shards: int = 1) -> tuple[int, int]:
    """(FLOPs, bytes) the paged decode kernel needs for one query token
    over ``context`` cached positions, all layers, on one of ``shards``
    chips that split the heads: every cached key and value read once, the
    query read and the output written once."""
    h, kv = m["h"] // shards, max(1, m["kv"] // shards)
    flops = 4 * m["layers"] * h * m["hd"] * context
    nbytes = m["layers"] * (2 * context * kv * m["hd"] * kv_bytes
                            + 2 * h * m["hd"] * q_bytes)
    return flops, nbytes


def softmax_cost(rows: int, cols: int, in_bytes: int = 4,
                 out_bytes: int = 4) -> tuple[int, int]:
    """(FLOPs, bytes) of a row softmax: the scores read once and the
    probabilities written once; about five operations an element."""
    return 5 * rows * cols, rows * cols * (in_bytes + out_bytes)


def least_time(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """The roofline's least time and the bound that sets it."""
    tc, tm = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")

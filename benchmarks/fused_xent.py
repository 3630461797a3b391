"""Beyond-paper table: fused two-pass cross-entropy vs unfused
softmax->log->gather on LM-head shapes.  Time + compiled bytes accessed
(the memory win is the point: probabilities never hit memory)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.core import twopass


def _fused(logits, labels):
    lse = twopass.twopass_logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll)


def _unfused(logits, labels):
    p = jax.nn.softmax(logits, axis=-1)
    logp = jnp.log(p)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def run(t=256, vocabs=(49152, 152064)):
    rows = []
    for v in vocabs:
        logits = jax.random.normal(jax.random.PRNGKey(0), (t, v)) * 4
        labels = jax.random.randint(jax.random.PRNGKey(1), (t,), 0, v)
        for name, fn in (("fused_twopass", _fused), ("unfused", _unfused)):
            jf = jax.jit(fn)
            sec = time_fn(jf, logits, labels)
            ca = jf.lower(logits, labels).compile().cost_analysis()
            rows.append((f"fused_xent/{name}/vocab={v}",
                         round(sec * 1e6, 2),
                         f"bytes={float(ca.get('bytes accessed', 0))/1e6:.0f}MB"))
        # gradient path (training): fused bwd recomputes, unfused saves probs
        for name, fn in (("fused_twopass_grad", _fused),
                         ("unfused_grad", _unfused)):
            jf = jax.jit(jax.grad(fn))
            sec = time_fn(jf, logits, labels)
            ca = jf.lower(logits, labels).compile().cost_analysis()
            rows.append((f"fused_xent/{name}/vocab={v}",
                         round(sec * 1e6, 2),
                         f"bytes={float(ca.get('bytes accessed', 0))/1e6:.0f}MB"))
    return emit(rows)


if __name__ == "__main__":
    run()

"""Paper Table 2: memory reads/writes/bandwidth cost per algorithm.

Two measurements:

(a) **Pallas kernel traffic (structural)** — sum of pallas_call operand +
    result bytes over each algorithm's kernel pipeline, extracted from the
    jaxpr.  This is the HBM traffic the TPU kernels perform by construction
    and must match the paper's 4N : 5N : 3N.

(b) **XLA-CPU compiled bytes (informational)** — `cost_analysis()` of the
    jnp forms.  Honest finding: XLA CPU *fuses* the three-pass pipeline
    (exp folded into the reduce) while materializing the two-pass (m, n)
    pair, so the CPU ratio INVERTS (~0.5x).  The paper's claim is about
    explicitly-staged memory passes, which only the kernel pipeline (a)
    preserves; (b) is reported to document the fusion effect.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core.softmax_api import SoftmaxAlgorithm, softmax as softmax_jnp
from repro.kernels import ops

THEORY = {
    SoftmaxAlgorithm.THREE_PASS_RECOMPUTE: ("3N reads + 1N writes", 4),
    SoftmaxAlgorithm.THREE_PASS_RELOAD: ("3N reads + 2N writes", 5),
    SoftmaxAlgorithm.TWO_PASS: ("2N reads + 1N writes", 3),
}


def _pallas_traffic_bytes(algo, n) -> int:
    """Sum pallas_call in/out aval bytes over the kernel pipeline."""
    x = jax.ShapeDtypeStruct((1, n), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda t: ops.softmax(t, algorithm=algo))(x)

    total = 0

    def walk(jx):
        nonlocal total
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                for v in list(eqn.invars) + list(eqn.outvars):
                    aval = v.aval
                    total += aval.size * aval.dtype.itemsize
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)
                if isinstance(sub, (list, tuple)):
                    for s_ in sub:
                        if hasattr(s_, "jaxpr"):
                            walk(s_.jaxpr)

    walk(jaxpr.jaxpr)
    return total


def run(n=2 ** 22):
    rows = []
    kernel = {a: _pallas_traffic_bytes(a, n) for a in SoftmaxAlgorithm}
    base = kernel[SoftmaxAlgorithm.TWO_PASS] / 3.0     # bytes per N-pass
    x = jax.ShapeDtypeStruct((1, n), jnp.float32)
    for algo in SoftmaxAlgorithm:
        desc, cost = THEORY[algo]
        ratio = kernel[algo] / (3 * base)
        c = jax.jit(lambda t, a=algo: softmax_jnp(t, algorithm=a)).lower(
            x).compile()
        cpu_bytes = float(c.cost_analysis().get("bytes accessed", 0))
        rows.append((
            f"memory_traffic/{algo.value}", 0,
            f"theory={desc}({cost}N);"
            f"pallas_kernel={kernel[algo] / 1e6:.1f}MB"
            f"={ratio:.2f}x_vs_2pass(theory {cost / 3:.2f}x);"
            f"xla_cpu_fused={cpu_bytes / 1e6:.1f}MB"))
    # assertion-grade check: the kernel pipeline must realize the paper table
    for algo in SoftmaxAlgorithm:
        got = kernel[algo] / base
        want = THEORY[algo][1]
        assert abs(got - want) / want < 0.05, (algo, got, want)
    return emit(rows)


if __name__ == "__main__":
    run()

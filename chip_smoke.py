#!/usr/bin/env python3
"""Prove that the system runs on a TPU: its kernels and its serving path.

    python chip_smoke.py             # one chip: phases `kernel` and `serve`
    python chip_smoke.py --chips 4   # four chips: phase `sharded` only

Phases, all in this one process (a chip belongs to one process):

* ``kernel``: the Two-Pass softmax Pallas kernel at 4096 x 32768 f32
  against ``jax.nn.softmax``.
* ``serve``: h2o-danube-3-4b at its published width (24 layers, d 3840,
  32/8 heads of 120, vocab 32000, bf16 params, random weights from a seed)
  served by the engine ``python -m repro.launch.serve`` builds: paged pool
  of 128-token pages, Pallas kernels on, 8 greedy requests of 256-1024
  prompt tokens, 4 of them sharing a 512-token prefix.  A cold run
  (compiles included) and a warm run of the same shapes, then the
  kernel path's logits against the jnp (m, n) path's at one decode step
  on the same pool state.
* ``sharded`` (``--chips 4``): qwen2.5-14b at its published width (29.5 GB
  of bf16 params, more than one chip holds) over a 1x4 ('data', 'model')
  mesh, params initialised straight into their shardings, with the same
  logits comparison under the mesh.

Wall times printed here are set-up facts (compiles included or not), not
speed measurements.  The last line of stdout is one JSON object, printed
only when every phase passed.  Without a TPU, or without the repo's
``src/`` beside it, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# kernel phase: softmax outputs lie in [0, 1] and both sides are f32 with
# exp and divide accurate to a few ulp (2^-23 relative), so the error is a
# few ulp of the largest output, under 1e-6; allow ten times that.
SOFTMAX_TOL = 1e-5

# serve phases: the kernel and jnp decode paths differ only in f32
# summation order inside attention, which moves a bf16-rounded attention
# output by at most one bf16 step (2^-8 relative) per layer.  Through 24-48
# residual layers that stays within a few percent of the logit range.
LOGIT_TOL = 0.05


class SmokeFailure(RuntimeError):
    """A phase's result failed its check."""


def require(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


class Compiles:
    """Counts backend compiles (or persistent-cache loads) and cache hits
    through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n = self.secs = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return (self.n, self.secs, self.hits, self.misses)

    def since(self, snap):
        n, s, h, m = (a - b for a, b in zip(self.snap(), snap))
        return (f"{n} programs compiled or loaded in {s:.1f}s "
                f"(persistent cache: {h} hits, {m} misses)")


def kernel_phase():
    import jax
    import jax.numpy as jnp

    from repro.kernels.twopass_softmax import twopass_softmax_2d

    x = jax.random.normal(jax.random.PRNGKey(0), (4096, 32768),
                          jnp.float32) * 4.0
    y = twopass_softmax_2d(x)
    ref = jax.nn.softmax(x, axis=-1)
    err = float(jnp.max(jnp.abs(y - ref)))
    print(f"kernel: twopass_softmax_2d 4096x32768 f32 vs jax.nn.softmax: "
          f"max abs err {err:.3e} (bound {SOFTMAX_TOL:.0e})")
    require(bool(jnp.all(jnp.isfinite(y))), "kernel: non-finite output")
    require(err <= SOFTMAX_TOL, f"kernel: max abs err {err} > {SOFTMAX_TOL}")


def make_requests(vocab, n, lengths, *, shared, new_tokens, seed, rid0=0):
    """``n`` greedy requests with seeded prompt lengths in ``lengths``; the
    first ``shared[0]`` of them open with one ``shared[1]``-token prefix
    (their lengths drawn above it).  Lengths come from a fixed seed and
    tokens from ``seed``, so every call gives the same shapes."""
    import numpy as np

    from repro.serving.scheduler import Request

    n_shared, plen_shared = shared
    lens = np.random.default_rng(0)
    plens = [int(lens.integers(plen_shared + 128 if i < n_shared
                               else lengths[0], lengths[1] + 1))
             for i in range(n)]
    rng = np.random.default_rng(seed)
    head = tuple(rng.integers(0, vocab, plen_shared))
    return [Request(rid=rid0 + i, max_new_tokens=new_tokens,
                    prompt=(head if i < n_shared else ())
                    + tuple(rng.integers(0, vocab, plen - (
                        plen_shared if i < n_shared else 0))))
            for i, plen in enumerate(plens)]


def decode_logits_check(eng, pool, toks, active):
    """Logits of the Pallas and the jnp (m, n) paged decode paths at one
    decode step on the same pool state; returns (max abs diff, max abs
    logit) over the active slots and the number of Mosaic kernel calls in
    each compiled step (kernel path, jnp path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import autoshard
    from repro.serving import engine

    rows = np.asarray(active)
    out, mosaic = {}, {}
    for kernels in (True, False):
        cfg = dataclasses.replace(eng.cfg, use_kernels=kernels)

        def step(params, pool, toks, active, cfg=cfg):
            return engine.decode_step_ragged(params, pool, toks, cfg=cfg,
                                             tp=eng.model.tp,
                                             active=active)[0]

        with (autoshard.hints(eng.mesh) if eng.mesh is not None
              else contextlib.nullcontext()):
            args = (eng.params, pool, toks, active)
            compiled = jax.jit(step).lower(*args).compile()
            logits = compiled(*args)
        mosaic[kernels] = compiled.as_text().count("tpu_custom_call")
        logits = np.asarray(logits[:, :eng.cfg.vocab].astype(jnp.float32))
        out[kernels] = logits[rows]
    require(np.isfinite(out[True]).all() and np.isfinite(out[False]).all(),
            "non-finite decode logits")
    if jax.default_backend() == "tpu":
        require(mosaic[True] > 0 and mosaic[False] == 0,
                f"Mosaic calls in the kernel/jnp decode steps: "
                f"{mosaic[True]}/{mosaic[False]}")
    diff = float(np.max(np.abs(out[True] - out[False])))
    return diff, float(np.max(np.abs(out[False]))), mosaic[True]


def serve_once(eng, reqs, *, check=False):
    """Serve ``reqs`` to completion through the engine; with ``check``,
    the engine's first decode step is intercepted (every request admitted
    and prefilled, nothing decoded yet) for :func:`decode_logits_check` on
    that step's own inputs, outside the timed wall."""
    eng.reset_stats()
    result = []
    paused = 0.0
    step = eng._step
    if check:
        def first_step(params, pool, toks, key, active):
            nonlocal paused
            if not result:
                t1 = time.perf_counter()
                result.append(decode_logits_check(eng, pool, toks, active))
                paused = time.perf_counter() - t1
            return step(params, pool, toks, key, active)

        eng._step = first_step
    t0 = time.perf_counter()
    try:
        comps = eng.run(reqs)
    finally:
        eng._step = step
    wall = time.perf_counter() - t0 - paused
    st = eng.stats
    require(len(comps) == len(reqs), f"{len(comps)}/{len(reqs)} completed")
    for c in comps:
        require(len(c.tokens) == c.max_new_tokens
                and c.reason == "max_tokens",
                f"request {c.rid}: {len(c.tokens)} tokens, {c.reason}")
    require(st["preempted"] == 0, f"{st['preempted']} preempted")
    return wall, (result[0] if result else None)


def print_peak_memory(name):
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"{name}: peak device memory (device 0) "
              f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
              f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")


def check_logits(name, result, layers):
    diff, scale, mosaic = result
    print(f"{name}: kernel ({mosaic} Mosaic calls) vs jnp paged decode "
          f"logits, one step, {layers} layers: max abs diff {diff:.4g}, "
          f"max abs logit {scale:.4f}, ratio {diff / scale:.4g} "
          f"(bound {LOGIT_TOL})")
    require(diff <= LOGIT_TOL * scale,
            f"{name}: logits differ by {diff} > {LOGIT_TOL} x {scale}")


def serve_phase(compiles):
    import jax

    from repro.launch.serve import build_engine

    lengths, new_tokens = (256, 1024), 32
    snap = compiles.snap()
    t0 = time.perf_counter()
    eng = build_engine("h2o-danube-3-4b", slots=8,
                       max_len=lengths[1] + new_tokens, temperature=0.0,
                       page_size=128, seed=0)
    cfg = eng.cfg
    print(f"serve: {cfg.name} L{cfg.n_layers} d{cfg.d_model} "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.resolved_head_dim()} "
          f"vocab {cfg.vocab}, params {cfg.param_dtype}, kernels "
          f"{cfg.use_kernels}; {eng.allocator.usable_pages} pages x "
          f"{eng.page_size} tok; built in {time.perf_counter() - t0:.1f}s, "
          f"{compiles.since(snap)}")
    if jax.default_backend() == "tpu":
        require(cfg.use_kernels, "serve: the TPU engine must run kernels")
    kw = dict(n=8, lengths=lengths, shared=(4, lengths[1] // 2),
              new_tokens=new_tokens)
    snap = compiles.snap()
    cold, _ = serve_once(eng, make_requests(cfg.vocab, seed=1, **kw))
    hits = eng.stats["prefix_hits"]
    print(f"serve: cold run 8/8 requests x {new_tokens} tokens in "
          f"{cold:.1f}s wall, {compiles.since(snap)}; prefix hits {hits}")
    require(hits > 0, "serve: no prefix-cache hit")
    snap = compiles.snap()
    warm, result = serve_once(
        eng, make_requests(cfg.vocab, seed=2, rid0=100, **kw), check=True)
    print(f"serve: warm run 8/8 requests in {warm:.1f}s wall; "
          f"{compiles.since(snap)} (the logits check's two included); "
          f"prefix hits {eng.stats['prefix_hits']}")
    require(eng.stats["prefix_hits"] > 0, "serve: no prefix-cache hit")
    check_logits("serve", result, cfg.n_layers)
    print_peak_memory("serve")


def sharded_phase(compiles):
    """One prompt bucket and no shared prefix keep the compiles (and the
    four-chip time) few."""
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import build_engine

    lengths, new_tokens = (200, 256), 16
    mesh = make_serving_mesh((1, 4))
    snap = compiles.snap()
    t0 = time.perf_counter()
    eng = build_engine("qwen2.5-14b", mesh=mesh, slots=8,
                       max_len=lengths[1] + new_tokens, temperature=0.0,
                       page_size=128, seed=0)
    cfg = eng.cfg
    tpd = eng.throughput()
    print(f"sharded: {cfg.name} L{cfg.n_layers} d{cfg.d_model} heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads} vocab {cfg.vocab}, params "
          f"{cfg.param_dtype}, kernels {cfg.use_kernels}; mesh "
          f"{tpd['mesh_axes']}, kv arena split {tpd['kv_shards']}x; built "
          f"in {time.perf_counter() - t0:.1f}s, {compiles.since(snap)}")
    snap = compiles.snap()
    wall, result = serve_once(
        eng, make_requests(cfg.vocab, n=8, lengths=lengths, shared=(0, 0),
                           new_tokens=new_tokens, seed=3), check=True)
    print(f"sharded: 8/8 requests x {new_tokens} tokens in {wall:.1f}s "
          f"wall, {compiles.since(snap)}")
    check_logits("sharded", result, cfg.n_layers)
    print_peak_memory("sharded")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: kernel + serve phases on one chip; 4: only "
                        "the sharded serving phase over four")
    args = p.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    count = len(jax.devices())
    print(f"device: {dev.device_kind}, {count} device(s), jax "
          f"{jax.__version__}")
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {count}", file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {Path(__file__).name}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    compiles = Compiles()
    if args.chips == 4:
        sharded_phase(compiles)
    else:
        kernel_phase()
        serve_phase(compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving launcher: continuous-batching engine over a slot pool.

``python -m repro.launch.serve --arch qwen2.5-14b --reduced --slots 4``

Requests stream in (optionally Poisson — ``--arrival-rate``), join the pool
by prefilling into a free slot, decode raggedly in one jitted step, and
free their slot on completion.  Prefill and decode tok/s are reported
SEPARATELY: the phases sit at different arithmetic intensities, and the
paper's bandwidth argument is about the decode one.

encdec (whisper) runs through the engine too: each request carries encoder
frames, whose projected cross-KV is adopted as read-only arena pages at
admission (``--enc-chunk`` encodes long audio in fixed windows so one long
request can't head-of-line-block admission).  ``--stream`` drives the
engine's streaming generator — tokens print as decode bursts complete
instead of after the run.  Only vlm (prompts carry patch inputs the
scheduler has no Request field for) still falls back to a phase-timed
lockstep prefill+decode loop.

The platform picks the implementation, not a flag: on a TPU every softmax
site runs its Pallas kernel (``use_kernels``), elsewhere the jnp (m, n)
forms.  Params are held in the compute dtype (bf16 at published widths:
h2o-danube-3-4b's 3.96 B params take 7.9 GB instead of 15.9 GB in f32).
"""

from __future__ import annotations

import argparse
import dataclasses


def load_model(arch: str, *, reduced: bool = False, mesh=None,
               softmax: str = "two_pass"):
    """``(model, params)`` as the serve launcher runs them: kernels on where
    the backend is a TPU, params in the compute dtype and initialised by
    one jitted ``model.init``.  Under ``mesh`` the params are initialised
    straight into their tensor-parallel shardings
    (``param_specs(fsdp=False)`` of ``model.init_shape()``), so no device
    ever holds the whole model."""
    import jax

    from repro.distributed import sharding
    from repro.launch.mesh import mesh_tp
    from repro.models import build_model

    model = build_model(arch, tp=1 if mesh is None else mesh_tp(mesh),
                        reduced=reduced, softmax_algorithm=softmax,
                        use_kernels=jax.default_backend() == "tpu")
    model.cfg = dataclasses.replace(model.cfg, param_dtype=model.cfg.dtype)
    out = None
    if mesh is not None:
        specs = sharding.param_specs(model.init_shape(), model.cfg, mesh,
                                     fsdp=False)
        out = sharding.named(specs, mesh)
    params = jax.jit(model.init, out_shardings=out)(jax.random.PRNGKey(0))
    return model, params


def build_engine(arch: str, *, reduced: bool = False, mesh=None,
                 softmax: str = "two_pass", **engine_kw):
    """The continuous-batching engine ``python -m repro.launch.serve``
    serves with: :func:`load_model`, then ``model.serving_engine`` with
    ``engine_kw`` (slots, max_len, temperature, pool options)."""
    model, params = load_model(arch, reduced=reduced, mesh=mesh,
                               softmax=softmax)
    return model.serving_engine(params, mesh=mesh, **engine_kw)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--slots", type=int, default=4,
                   help="cache-slot pool size (concurrent sequences)")
    p.add_argument("--strip", action="store_true",
                   help="force the slot-major strip pool (paged pool is "
                        "the default wherever the family supports it)")
    p.add_argument("--page-size", type=int, default=None,
                   help="tokens per KV page (default: kernel-registry "
                        "resolution, 128-token heuristic)")
    p.add_argument("--pages", type=int, default=None,
                   help="arena page count incl. the trash page (default: "
                        "full provisioning; fewer = oversubscribe, "
                        "preempt on OOM)")
    p.add_argument("--kv-dtype", default=None, choices=["int8"],
                   help="quantize the page arenas (int8 pages + fp32 "
                        "scale sidecars, dequant fused into the decode "
                        "sweep; default: the model dtype)")
    p.add_argument("--scale-granularity", default=None,
                   choices=["page", "page_head"],
                   help="int8 scale granularity: one scale per page "
                        "position, or per (position, kv head) "
                        "(default: kv_page_quant registry resolution)")
    p.add_argument("--host-swap-bytes", type=int, default=None,
                   help="host-RAM swap budget: under page pressure cold "
                        "slots demote their pages to host RAM "
                        "(bit-lossless) instead of being preempted and "
                        "recomputed (default: swap tier off)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--arrival-rate", type=float, default=None,
                   help="Poisson request arrivals per second "
                        "(default: all offered at t=0)")
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--shared-prefix-len", type=int, default=0,
                   help="give every request the same first N prompt tokens "
                        "(exercises the prefix cache: whole matched pages "
                        "are adopted by reference, only the tail prefills)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable prefix sharing (default: on wherever the "
                        "family supports exact tail prefill)")
    p.add_argument("--steps", type=int, default=32,
                   help="max new tokens per request")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--softmax", default="two_pass")
    p.add_argument("--enc-frames", type=int, default=None,
                   help="encdec: encoder frames per request "
                        "(default: prompt-len)")
    p.add_argument("--enc-chunk", type=int, default=None,
                   help="encdec: encode frames in fixed windows of this "
                        "size, one window per scheduler step (default: "
                        "whole-sequence encode)")
    p.add_argument("--stream", action="store_true",
                   help="drive the streaming generator: print per-request "
                        "token deltas as decode bursts complete")
    p.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                   help="serve sharded over a ('data', 'model') device "
                        "mesh, e.g. --mesh 2x4: KV heads of every arena "
                        "page tensor-parallel over 'model', params TP, "
                        "page tables replicated (docs/serving.md)")
    args = p.parse_args()

    import numpy as np

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_serving_mesh

        try:
            d, m = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            p.error("--mesh wants DATAxMODEL, e.g. 2x4")
        mesh = make_serving_mesh((d, m))
        print(f"mesh: {d}x{m} over {jax.device_count()} devices "
              f"(axes data={d}, model={m})")

    key = jax.random.PRNGKey(1)
    if get_config(args.arch).family == "vlm":
        # No continuous-batching path (prompts carry patch inputs the
        # scheduler has no Request field for) — lockstep loop, phase-timed.
        from repro.serving import engine

        model, params = load_model(args.arch, reduced=args.reduced,
                                   mesh=mesh, softmax=args.softmax)
        cfg = model.cfg
        prompt = jax.random.randint(key, (args.slots, args.prompt_len), 0,
                                    cfg.vocab)
        kw = {"patches": jax.random.normal(
            key, (args.slots, cfg.n_patches, cfg.d_model))}
        _, st = engine.generate_timed(
            params, prompt, cfg=cfg, steps=args.steps, key=key, tp=model.tp,
            temperature=args.temperature,
            max_len=prompt.shape[1] + args.steps + 8, **kw)
        print(f"{args.arch}: lockstep batch={args.slots} (no "
              f"continuous-batching path for family={cfg.family})")
    else:
        from repro.serving.scheduler import Request

        encdec = get_config(args.arch).family == "encdec"
        n_frames = args.enc_frames or args.prompt_len
        eng = build_engine(
            args.arch, reduced=args.reduced, mesh=mesh, softmax=args.softmax,
            slots=args.slots, max_len=args.prompt_len + args.steps + 8,
            temperature=args.temperature, seed=2,
            paged=False if args.strip else "auto",
            page_size=args.page_size, pages=args.pages,
            prefix_cache=False if args.no_prefix_cache else "auto",
            page_dtype=args.kv_dtype,
            scale_granularity=args.scale_granularity,
            host_swap_bytes=args.host_swap_bytes,
            **(dict(max_cross_len=n_frames, enc_chunk=args.enc_chunk)
               if encdec else {}))
        cfg = eng.cfg
        rng = np.random.default_rng(0)
        arrivals = (np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                              args.requests))
                    if args.arrival_rate else np.zeros(args.requests))
        head = tuple(rng.integers(0, cfg.vocab, args.shared_prefix_len))
        reqs = [Request(rid=i,
                        prompt=head + tuple(rng.integers(
                            0, cfg.vocab,
                            args.prompt_len - len(head))),
                        max_new_tokens=args.steps,
                        arrival_s=float(arrivals[i]),
                        frames=(rng.standard_normal(
                            (n_frames, cfg.d_model)).astype(np.float32)
                            if encdec else None))
                for i in range(args.requests)]
        if args.stream:
            first_delta = {}
            n_events = 0
            for rid, toks in eng.stream(reqs):
                n_events += 1
                first_delta.setdefault(rid, n_events)
            comps = eng.completions
            print(f"streamed: {n_events} delta events; first delta per "
                  f"request (event #): "
                  f"{dict(sorted(first_delta.items()))}")
        else:
            comps = eng.run(reqs)
        st = eng.stats
        quant = (f", int8/{eng.scale_granularity} scales"
                 if eng.page_dtype else "")
        pool = (f"paged pool ({eng.allocator.usable_pages} pages x "
                f"{eng.page_size} tok{quant}, peak {st['peak_pages']} in "
                f"use, {st['preempted']} preempted)" if eng.paged
                else "strip pool")
        print(f"{args.arch}: served {len(comps)} requests over "
              f"{args.slots} slots / {pool} ({st['steps']} ragged decode "
              f"steps, {st['admitted']} admissions, "
              f"{len(eng._prefill_shapes)} prefill bucket compiles)")
        if mesh is not None:
            tpd = eng.throughput()
            print(f"sharded: mesh {tpd['mesh_axes']}, kv arena split "
                  f"{tpd['kv_shards']}x over 'model'")
        if eng.prefix_cache is not None:
            print(f"prefix cache: {st['prefix_hits']} hits, "
                  f"{st['prefix_tokens_reused']} prompt tok adopted by "
                  f"reference, {st['cow_copies']} copy-on-write page "
                  f"copies, {st['prefix_evictions']} evictions, "
                  f"{eng.prefix_cache.n_pages} pages indexed")
        elif not args.no_prefix_cache and eng.paged:
            print("prefix cache: off (family needs full-prompt prefill)")
        if eng.host_swap is not None:
            print(f"host swap: {st['demoted']} demoted, "
                  f"{st['prefetched']} prefetched back, "
                  f"{eng.host_swap.bytes_used} bytes resident")
        ttfts = sorted(c.ttft_s for c in comps if c.ttft_s is not None)
        if ttfts:
            print(f"ttft: p50 {ttfts[len(ttfts) // 2] * 1e3:.2f}ms  "
                  f"max {ttfts[-1] * 1e3:.2f}ms")
        print("sample row:", comps[0].tokens[:16])

    pre = st["prefill_tokens"] / max(st["prefill_s"], 1e-9)
    dec = st["decode_tokens"] / max(st["decode_s"], 1e-9)
    print(f"prefill: {st['prefill_tokens']} tok in {st['prefill_s']:.2f}s "
          f"({pre:.1f} tok/s)")
    print(f"decode:  {st['decode_tokens']} tok in {st['decode_s']:.2f}s "
          f"({dec:.1f} tok/s) via {args.softmax} sampler")


if __name__ == "__main__":
    main()

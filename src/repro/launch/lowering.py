"""Dry-run lowering + compiled-artifact analysis.

Builds the (train | prefill | decode) step for any (arch x shape x mesh)
cell, lowers with ShapeDtypeStruct inputs (no allocation), compiles under
SPMD, and extracts:

  * memory_analysis()  — proves the cell fits per device
  * cost_analysis()    — HLO FLOPs / bytes
  * collective bytes   — parsed from the optimized HLO text (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute result
    sizes, async -start variants included once)

Scan-trip-count correction (methodology, see EXPERIMENTS.md): XLA counts a
``lax.scan`` body ONCE in cost_analysis.  We therefore compile small
UNROLLED variants (L=1, L=2 python-loop layers) of the same cell and
extrapolate linearly: per-layer slope = f(2) - f(1); total = f(1) +
(L-1) * slope.  The full scanned compile is still what memory_analysis and
the deliverable "lower+compile succeeds" come from.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeCell
from repro.distributed import autoshard, sharding
from repro.models.model_zoo import Model, cell_supported, input_specs
from repro.serving import engine
from repro.training import step_fn, train_state

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result sizes per collective kind over the optimized module."""
    out: dict[str, float] = {}
    count: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        ty, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0) + _type_bytes(ty)
        count[kind] = count.get(kind, 0) + 1
    out["total"] = sum(out.values())
    out["counts"] = count
    return out


# ---------------------------------------------------------------------------
# Cell construction.
# ---------------------------------------------------------------------------
def _specs_to_shardings(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_cell(arch: str, cell: ShapeCell | str, mesh, *,
               unrolled_layers: int | None = None,
               moe_impl: str = "dispatch", seq_shard_decode: bool = False,
               microbatches: int = 4, grad_compression: str = "none",
               cfg_overrides: dict | None = None, use_reduced: bool = False,
               logits_sharded: bool = False, decode_no_fsdp: bool = False):
    """Returns (jitted_fn, example_args_shapes) ready to ``.lower()``.

    ``unrolled_layers``: replace the scan with a python loop over this many
    layers (cost-model variants).  ``use_reduced``: the smoke-size config
    (mesh-logic tests on small fake-device grids).
    """
    if isinstance(cell, str):
        cell = SHAPES[cell]
    cfg = get_config(arch)
    if use_reduced:
        cfg = cfg.reduced()
    changes: dict[str, Any] = dict(cfg_overrides or {})
    if unrolled_layers is not None:
        changes.update(n_layers=unrolled_layers, scan_layers=False)
        if cfg.n_enc_layers:
            changes["n_enc_layers"] = unrolled_layers
    if changes:
        cfg = dataclasses.replace(cfg, **changes)
    tp = sharding._tp(mesh)
    model = Model(cfg, tp)

    specs = input_specs(cfg, cell, tp)
    params_shape = model.init_shape()
    pspecs = sharding.param_specs(params_shape, cfg, mesh)

    if cell.kind == "train":
        state_shape = jax.eval_shape(train_state.init_state, params_shape)
        sspecs = train_state.state_specs(pspecs)
        bspecs = sharding.batch_specs(specs["batch"], mesh)
        fn = step_fn.make_train_step(model, microbatches=microbatches,
                                     grad_compression=grad_compression,
                                     moe_impl=moe_impl)
        jitted = jax.jit(
            fn,
            in_shardings=(_specs_to_shardings(sspecs, mesh),
                          _specs_to_shardings(bspecs, mesh)),
            out_shardings=(_specs_to_shardings(sspecs, mesh), None),
            donate_argnums=(0,),            # state updated in place (TPU)
        )
        return jitted, (state_shape, specs["batch"])

    if cell.kind == "prefill":
        bspecs = sharding.batch_specs(specs, mesh)
        fn = functools.partial(engine.prefill, cfg=cfg, tp=tp,
                               moe_impl=moe_impl)

        def prefill_fn(params, inputs):
            return fn(params, **inputs)

        jitted = jax.jit(
            prefill_fn,
            in_shardings=(_specs_to_shardings(pspecs, mesh),
                          _specs_to_shardings(bspecs, mesh)),
        )
        return jitted, (params_shape, specs)

    # decode
    if decode_no_fsdp:
        pspecs = sharding.param_specs(params_shape, cfg, mesh, fsdp=False)
    cspecs = sharding.cache_specs(specs["cache"], cfg, mesh,
                                  seq_shard=seq_shard_decode)
    tok_spec = sharding.batch_specs(specs["tokens"], mesh)
    fn = functools.partial(engine.decode_step, cfg=cfg, tp=tp,
                           moe_impl=moe_impl)

    def decode_fn(params, cache, tokens, pos):
        return fn(params, cache, tokens, pos)

    dp = tuple(a for a in mesh.axis_names if a != "model") or None
    batch_ok = cell.global_batch % sharding._axes_size(mesh, dp) == 0
    logits_sh = (NamedSharding(mesh, P(dp if batch_ok else None, "model"))
                 if logits_sharded else None)
    jitted = jax.jit(
        decode_fn,
        in_shardings=(_specs_to_shardings(pspecs, mesh),
                      _specs_to_shardings(cspecs, mesh),
                      _specs_to_shardings(tok_spec, mesh),
                      NamedSharding(mesh, P())),
        out_shardings=(logits_sh, _specs_to_shardings(cspecs, mesh)),
        donate_argnums=(1,),                     # cache updated in place
    )
    return jitted, (params_shape, specs["cache"], specs["tokens"],
                    specs["pos"])


def lower_and_analyze(arch: str, cell: ShapeCell | str, mesh, *,
                      with_cost_model: bool = True, **kw) -> dict:
    """The full dry-run for one cell: compile + memory + roofline inputs."""
    if isinstance(cell, str):
        cell = SHAPES[cell]
    cfg = get_config(arch)
    ok, why = cell_supported(cfg, cell)
    if not ok:
        return {"arch": arch, "cell": cell.name, "skipped": True,
                "reason": why}

    with mesh, autoshard.hints(mesh):
        jitted, args = build_cell(arch, cell, mesh, **kw)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())

    result = {
        "arch": arch, "cell": cell.name, "skipped": False,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "memory": {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
        },
        "scanned": {
            "flops": ca.get("flops"),
            "bytes": ca.get("bytes accessed"),
            "collective_bytes": coll["total"],
            "collective_counts": coll["counts"],
        },
    }

    if with_cost_model:
        result["extrapolated"] = extrapolate_cost(arch, cell, mesh, **kw)
    return result


def extrapolate_cost(arch: str, cell: ShapeCell | str, mesh, **kw) -> dict:
    """Scan-correct flop/byte/collective totals via L=1 and L=2 unrolled
    compiles: total(L) = f(1) + (L-1) * (f(2) - f(1))."""
    if isinstance(cell, str):
        cell = SHAPES[cell]
    cfg = get_config(arch)
    vals = {}
    with mesh, autoshard.hints(mesh):
        for lcount in (1, 2):
            jitted, args = build_cell(arch, cell, mesh,
                                      unrolled_layers=lcount, **kw)
            compiled = jitted.lower(*args).compile()
            ca = compiled.cost_analysis()
            coll = collective_bytes(compiled.as_text())
            vals[lcount] = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes": float(ca.get("bytes accessed", 0.0)),
                "collective_bytes": float(coll["total"]),
            }
    out = {}
    ls = cfg.n_layers
    for key in ("flops", "bytes", "collective_bytes"):
        f1, f2 = vals[1][key], vals[2][key]
        slope = max(0.0, f2 - f1)   # fixed overheads can make f2 < f1 on
        out[key] = f1 + (ls - 1) * slope   # tiny cells; clamp at L=1 cost
        out[key + "_per_layer"] = slope
        out[key + "_base"] = f1 - slope
    out["n_layers"] = ls
    return out

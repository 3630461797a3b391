"""Seeded weights of a dense decoder, made by the benchmark.

Each tensor is a function of the seed, its name and its layer alone, so
the served parameters (every layer at once, on the device, in the served
dtype) and the plain reference (one layer at a time, in float32 from the
same rounded values) read the same numbers without sharing an array.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# per-layer tensors: name -> (shape builder, kind)
LAYER = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2", "w_gate",
         "w_up", "w_down")
GLOBAL = ("embed", "norm_f", "lm_head")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, h=h, kv=kv, hd=hd, ff=cfg["intermediate_size"],
                v=cfg["vocab_size"], layers=cfg["num_hidden_layers"])


def shapes(cfg: dict) -> dict:
    """Name -> shape of one layer's tensors and of the global ones."""
    m = dims(cfg)
    d, q, k = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    out = dict(ln1=(d,), wq=(d, q), wk=(d, k), wv=(d, k), wo=(q, d),
               ln2=(d,), w_gate=(d, m["ff"]), w_up=(d, m["ff"]),
               w_down=(m["ff"], d), embed=(m["v"], d), norm_f=(d,),
               lm_head=(d, m["v"]))
    if cfg["attention_bias"]:
        out.update(bq=(q,), bk=(k,), bv=(k,))
    return out


def root_key(seed: int):
    """A key from a seed of any size up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def tensor(key, name: str, layer, shape, dtype):
    """One tensor: norms near 1, biases small, matrices at the usual
    ``fan_in ** -0.5`` scale (embeddings at ``d ** -0.5``)."""
    idx = (GLOBAL.index(name) + 1000 if name in GLOBAL
           else LAYER.index(name))
    k = jax.random.fold_in(jax.random.fold_in(key, idx), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if name in ("ln1", "ln2", "norm_f"):
        x = 1.0 + 0.1 * z
    elif name in ("bq", "bk", "bv"):
        x = 0.1 * z
    elif name == "embed":
        x = z * shape[1] ** -0.5
    else:
        x = z * shape[0] ** -0.5
    return x.astype(dtype)


def layer_weights(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16):
    """One layer's tensors as served (``dtype``), upcast to float32."""
    sh = shapes(cfg)
    key = root_key(seed)
    return {n: tensor(key, n, layer, sh[n], dtype).astype(jnp.float32)
            for n in LAYER if n in sh}


def global_weight(cfg: dict, seed: int, name: str, dtype=jnp.bfloat16):
    return tensor(root_key(seed), name, 0, shapes(cfg)[name],
                  dtype).astype(jnp.float32)


# the program's parameter tree (``model.init_shape()``): path -> name
PROGRAM_PATHS = {
    ("embed", "table"): "embed",
    ("norm_f", "scale"): "norm_f",
    ("lm_head", "w"): "lm_head",
    ("blocks", "ln1", "scale"): "ln1",
    ("blocks", "ln2", "scale"): "ln2",
    ("blocks", "attn", "wq", "w"): "wq",
    ("blocks", "attn", "wq", "b"): "bq",
    ("blocks", "attn", "wk", "w"): "wk",
    ("blocks", "attn", "wk", "b"): "bk",
    ("blocks", "attn", "wv", "w"): "wv",
    ("blocks", "attn", "wv", "b"): "bv",
    ("blocks", "attn", "wo", "w"): "wo",
    ("blocks", "mlp", "gate", "w"): "w_gate",
    ("blocks", "mlp", "up", "w"): "w_up",
    ("blocks", "mlp", "down", "w"): "w_down",
}


def _path(kp) -> tuple:
    return tuple(getattr(k, "key", getattr(k, "name", k)) for k in kp)


def program_params(abstract, cfg: dict, seed: int, shardings=None):
    """The program's parameter tree, made on the device in one jitted
    call from ``seed``.  ``abstract`` is the program's ``init_shape()``;
    a leaf the benchmark cannot name, or whose shape differs from the
    configuration's, is an error."""
    sh = shapes(cfg)
    n_layers = dims(cfg)["layers"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    plan = []
    for kp, leaf in leaves:
        path = _path(kp)
        name = PROGRAM_PATHS.get(path)
        if name is None or name not in sh:
            raise ValueError(f"program parameter {'/'.join(path)} has no "
                             "counterpart in the configuration")
        want = ((n_layers,) if path[0] == "blocks" else ()) + sh[name]
        if tuple(leaf.shape) != want:
            raise ValueError(f"program parameter {'/'.join(path)} is "
                             f"{leaf.shape}, the configuration says {want}")
        plan.append((name, path[0] == "blocks", leaf.dtype))

    def make(key):
        out = []
        for name, stacked, dtype in plan:
            if stacked:
                out.append(jax.vmap(lambda i, n=name, t=dtype: tensor(
                    key, n, i, sh[n], t))(jnp.arange(n_layers)))
            else:
                out.append(tensor(key, name, 0, sh[name], dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make, out_shardings=shardings)(root_key(seed))

"""A configuration file as the benchmark reads it, and the weights it makes.

``dims(spec)`` is the plain description of the model that both the served
program and the reference are built from: published keys are read through
the file's ``model.keys`` map, the rest comes from its ``model`` block.
``make_params`` draws the weights from the seed on the device, in the
served dtype, in one jitted call. The reference is run on these same
arrays, so nothing it compares against was made by the program.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def find(kind: str, name: str, roots=(HERE,), ext: str = ".json") -> str:
    """The file of a configuration, traffic mix or metric, by its name."""
    for root in roots:
        path = os.path.join(root, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind[:-1] if kind.endswith('s') else kind}"
                            f" named {name!r} under {list(roots)}")


def load(kind: str, name: str, roots=(HERE,)) -> Dict:
    with open(find(kind, name, roots)) as f:
        return json.load(f)


def dims(spec: Dict) -> Dict:
    """Normalised sizes: n_layers, d_model, n_heads, n_kv_heads, head_dim,
    d_ff, vocab_size, norm_eps, rope_theta, rotary_dims, mlp, dtype."""
    m = spec["model"]
    out = {k: v for k, v in m.items() if k != "keys"}
    for name, key in m.get("keys", {}).items():
        out[name] = spec[key]
    out.setdefault("head_dim", out["d_model"] // out["n_heads"])
    for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "rotary_dims"):
        out[k] = int(out[k])
    out["norm_eps"] = float(out["norm_eps"])
    out["rope_theta"] = float(out["rope_theta"])
    return out


def param_shapes(d: Dict) -> Dict:
    """The served parameter tree: layers stacked on a leading axis."""
    L, D, F, V = d["n_layers"], d["d_model"], d["d_ff"], d["vocab_size"]
    hq, hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    mlp = ({"w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}
           if d["mlp"] == "swiglu" else
           {"w_up": (L, D, F), "w_down": (L, F, D)})
    return {
        "embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
        "layers": {
            "attn_norm": (L, D), "mlp_norm": (L, D),
            "attn": {"wq": (L, D, hq * hd), "wk": (L, D, hkv * hd),
                     "wv": (L, D, hkv * hd), "wo": (L, hq * hd, D)},
            "mlp": mlp,
        },
    }


def _scale(path: str, shape) -> float:
    """Standard deviation of a leaf: 0.02 for the embedding, 0.1 for the
    norm-gain offsets, 1/sqrt(fan_in) for every projection."""
    if path == "embed":
        return 0.02
    if path.endswith("norm"):
        return 0.1
    return 1.0 / math.sqrt(shape[-2])


def make_params(d: Dict, seed: int):
    """Weights from ``seed``, made on the default device in ``d['dtype']``
    by one jitted call (the f32 draws fuse into the cast)."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(d)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(getattr(k, "key", k)) for k in p).split("/")[-1]
             for p, _ in flat]
    dtype = jnp.dtype(d["dtype"])

    def init(key):
        keys = jax.random.split(key, len(flat))
        leaves = [(jax.random.normal(k, s, jnp.float32)
                   * _scale(p, s)).astype(dtype)
                  for k, p, (_, s) in zip(keys, paths, flat)]
        return jax.tree_util.tree_unflatten(tree, leaves)

    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(init)(key)

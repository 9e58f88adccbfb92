"""Files of the benchmark found by name, and the weights it makes.

``make_params`` draws the weights of a parameter tree, given as shape
tuples by the cell's architecture module (``archs/<name>.py``), from the
seed on the device, in the served dtype, in one jitted call. The
reference is run on these same arrays, so nothing it compares against was
made by the program.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def find(kind: str, name: str, roots=(HERE,), ext: str = ".json") -> str:
    """The file of a configuration, traffic mix, metric or architecture,
    by its name."""
    for root in roots:
        path = os.path.join(root, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind[:-1] if kind.endswith('s') else kind}"
                            f" named {name!r} under {list(roots)}")


def load(kind: str, name: str, roots=(HERE,)) -> Dict:
    with open(find(kind, name, roots)) as f:
        return json.load(f)


def _scale(path: str, shape) -> float:
    """Standard deviation of a leaf: 0.02 for the embedding, 0.1 for the
    norm-gain offsets, 1/sqrt(fan_in) for every projection."""
    if path == "embed":
        return 0.02
    if path.endswith("norm"):
        return 0.1
    return 1.0 / math.sqrt(shape[-2])


def make_params(shapes: Dict, dtype: str, seed: int):
    """Weights of the tree ``shapes`` (shape tuples) from ``seed``, made on
    the default device in ``dtype`` by one jitted call (the f32 draws fuse
    into the cast). Leaf ``i`` in flatten order draws from the ``i``-th
    split of the seed's key."""
    import jax
    import jax.numpy as jnp

    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(getattr(k, "key", k)) for k in p).split("/")[-1]
             for p, _ in flat]
    dtype = jnp.dtype(dtype)

    def init(key):
        keys = jax.random.split(key, len(flat))
        leaves = [(jax.random.normal(k, s, jnp.float32)
                   * _scale(p, s)).astype(dtype)
                  for k, p, (_, s) in zip(keys, paths, flat)]
        return jax.tree_util.tree_unflatten(tree, leaves)

    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(init)(key)

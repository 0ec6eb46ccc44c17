"""Weights made on the device from the seed, in one jitted call.

The tree's layout (names and shapes) is the program's, read with
``jax.eval_shape`` of its init; every value is the benchmark's own: norm
scales 1 + N(0, 0.1^2), the embedding N(0, 0.02^2), every projection
N(0, 1/fan_in). The plain reference reads the same tree by name.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# projection leaf -> how many axes after the (stacked) layer axis form
# its fan-in: wq (L, d, H, hd) contracts d; wo (L, H, hd, d) contracts H*hd
FAN_IN_AXES = {"wq": 1, "wk": 1, "wv": 1, "wo": 2,
               "w_gate": 1, "w_up": 1, "w_down": 1}


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def init_leaf(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    stacked = name.startswith("blocks/")
    if last == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if last in ("embed", "lm_head"):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if last in FAN_IN_AXES:
        lead = 1 if stacked else 0
        fan = math.prod(shape[lead:lead + FAN_IN_AXES[last]])
        return jax.random.normal(key, shape, dtype) * jnp.asarray(
            fan ** -0.5, dtype)
    raise ValueError(f"no rule for weight {name!r} {shape}")


def make(key, abstract):
    """A tree shaped like ``abstract`` (ShapeDtypeStructs), filled on the
    device from ``key`` in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = [(leaf_name(p), x.shape, x.dtype) for p, x in flat]

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            init_leaf(jax.random.fold_in(key, i), n, s, d)
            for i, (n, s, d) in enumerate(specs)])

    return jax.jit(build)(key)


def named(tree) -> dict:
    """{leaf name: array} of a weight tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {leaf_name(p): x for p, x in flat}

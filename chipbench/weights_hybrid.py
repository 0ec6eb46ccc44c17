"""Weights of a hybrid Mamba2 / attention model, made on the device from
the seed in one jitted call, as ``weights.make`` makes a dense decoder's.

Every leaf ``weights.init_leaf`` knows keeps its rule there. The Mamba2
mixer's own leaves follow its published initialisation (the
``mamba_ssm`` reference code): A = -exp(A_log) with A uniform in [1, 16];
dt_bias the inverse softplus of dt, log-uniform in [0.001, 0.1]; D = 1;
the depthwise conv's weights N(0, 1/d_conv) and its bias uniform in
+-1/sqrt(d_conv) (PyTorch's Conv1d bound); in_proj and out_proj
N(0, 1/fan_in). With the projections' rule on A_log and dt_bias every
head would forget its state within a few tokens, and a program that
dropped the state between chunks would still agree with the reference.

The embedding is N(0, (0.02 / embedding_multiplier)^2), so that the
scaled input ``embedding_multiplier * E[tok]`` has the spread 0.02 that
``weights.py`` gives a dense decoder's embedding. At 0.02 itself the
tied output head's product of a token's embedding with itself (x12)
would outweigh everything the layers add, the model would repeat its
input token, and the comparison of served tokens could not see a fault
in the layers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights

DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def init_leaf(key, name: str, shape, dtype, c: dict):
    """One leaf of the tree, by its name; ``c`` the configuration file."""
    last = name.rsplit("/", 1)[-1]
    lead = 1 if name.startswith("blocks/") else 0
    d_conv = c["mamba_d_conv"]
    if last == "embed":
        return (0.02 / c["embedding_multiplier"] * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)
    if last == "A_log":
        a = jax.random.uniform(key, shape, jnp.float32, A_MIN, A_MAX)
        return jnp.log(a).astype(dtype)
    if last == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(math.log(DT_MIN) + u * (math.log(DT_MAX)
                                             - math.log(DT_MIN)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if last == "D":
        return jnp.ones(shape, dtype)
    if last == "conv_w":
        return (jax.random.normal(key, shape, jnp.float32)
                * d_conv ** -0.5).astype(dtype)
    if last == "conv_b":
        bound = d_conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    if last in ("in_proj", "out_proj"):
        return (jax.random.normal(key, shape, jnp.float32)
                * shape[lead] ** -0.5).astype(dtype)
    return weights.init_leaf(key, name, shape, dtype)


def make(key, abstract, c: dict):
    """A tree shaped like ``abstract`` (ShapeDtypeStructs), filled on the
    device from ``key`` in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = [(weights.leaf_name(p), x.shape, x.dtype) for p, x in flat]

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            init_leaf(jax.random.fold_in(key, i), n, s, d, c)
            for i, (n, s, d) in enumerate(specs)])

    return jax.jit(build)(key)

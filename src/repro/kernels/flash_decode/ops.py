"""Jitted public wrapper around the paged flash decode Pallas kernel.

Normalizes dtypes of the runtime page maps / position / live-mask args
and resolves ``interpret`` (False when the default backend is a TPU,
True on any other), mirroring ``flash_attention/ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
from repro.kernels.runtime import default_interpret


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def flash_decode(q: jnp.ndarray, kpool: jnp.ndarray, vpool: jnp.ndarray,
                 page_map: jnp.ndarray, pos: jnp.ndarray,
                 live: jnp.ndarray, *, interpret: bool = None,
                 scale: float = None) -> jnp.ndarray:
    """q: (B,H,D); kpool/vpool: (N,K,ps,D); page_map: (B,P) int32 with
    entries >= N meaning 'no page'; pos: (B,) last valid position per
    row; live: (B,) row mask -> (B,H,D). ``scale``: the softmax scale
    (static; ``D ** -0.5`` when not given)."""
    interpret = default_interpret(interpret)
    return flash_decode_pallas(
        q, kpool, vpool,
        page_map.astype(jnp.int32),
        pos.astype(jnp.int32),
        live.astype(jnp.int32),
        interpret=interpret, scale=scale)

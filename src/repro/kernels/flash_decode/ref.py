"""Pure-jnp oracle for the paged flash decode kernel.

The reference materializes the gather the kernel avoids: clamp the page
map, gather pages into a (B, P*ps, K, D) linear view, and run masked
softmax attention in f32. Rows with no valid key (dead rows) return
exact zeros, matching the kernel's l>=eps guard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_decode_ref(q: jnp.ndarray, kpool: jnp.ndarray, vpool: jnp.ndarray,
                     page_map: jnp.ndarray, pos: jnp.ndarray,
                     live: jnp.ndarray, scale: float = None) -> jnp.ndarray:
    """q: (B,H,D); kpool/vpool: (N,K,ps,D); page_map: (B,P) int32 with
    entries >= N meaning 'no page'; pos: (B,) int32 last valid position
    per row; live: (B,) int32/bool row mask -> (B,H,D). ``scale``: the
    softmax scale (``D ** -0.5`` when not given)."""
    B, H, D = q.shape
    N, K, ps, _ = kpool.shape
    P = page_map.shape[1]
    G = H // K
    pm = jnp.clip(page_map, 0, N - 1)
    # (B, P, K, ps, D) -> linear (B, P*ps, K, D)
    k = kpool[pm].transpose(0, 1, 3, 2, 4).reshape(B, P * ps, K, D)
    v = vpool[pm].transpose(0, 1, 3, 2, 4).reshape(B, P * ps, K, D)
    t = jnp.arange(P * ps, dtype=jnp.int32)
    page_ok = jnp.repeat(page_map < N, ps, axis=1)            # (B, P*ps)
    valid = (t[None, :] <= pos[:, None]) & page_ok \
        & (live.astype(jnp.int32) != 0)[:, None]
    qf = q.astype(jnp.float32).reshape(B, K, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k.astype(jnp.float32)) \
        * (D ** -0.5 if scale is None else scale)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    any_valid = valid.any(axis=-1)                            # (B,)
    o = jnp.where(any_valid[:, None, None, None], o, 0.0)
    return o.reshape(B, H, D).astype(q.dtype)

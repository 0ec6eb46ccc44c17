"""Attention: MHA/GQA/MQA with RoPE, qk-norm, sliding windows and KV caches.

Layouts:
  q projections    (d, H, hd)        H = query heads
  k/v projections  (d, K, hd)        K = kv heads (GQA groups G = H/K)
  out projection   (H, hd, d)
  activations      (B, S, H, hd)

KV caches store *post-RoPE* keys so decode never re-rotates history. A
sliding-window cache is a ring buffer of size ``window`` with an absolute-
position array ``kpos`` for validity/recency masking — this is what makes
``long_500k`` decode O(window) state instead of O(seq).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rmsnorm

Params = Dict[str, Any]
NEG_INF = -1e30


# ---------------------------------------------------------------------------
def init_attention(key, d: int, n_heads: int, n_kv_heads: int, head_dim: int,
                   qk_norm: bool = False, bias: bool = False,
                   dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p: Params = {
        "wq": (jax.random.normal(ks[0], (d, n_heads, head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, n_kv_heads, head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, n_kv_heads, head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_heads, head_dim, d))
               * (n_heads * head_dim) ** -0.5).astype(dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv_heads, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv_heads, head_dim), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    if qk_norm:
        p["q_norm"] = {"scale": jnp.ones((head_dim,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((head_dim,), dtype)}
    return p


def _project_qkv(p: Params, xq: jnp.ndarray, xkv: jnp.ndarray):
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", xkv, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", xkv, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if "q_norm" in p:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    return q, k, v


def _out_proj(p: Params, o: jnp.ndarray) -> jnp.ndarray:
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if "bo" in p:
        out = out + p["bo"]
    return out


def grouped_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mask: Optional[jnp.ndarray],
                   scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B,S,H,hd), k/v: (B,T,K,hd), mask broadcastable to (B,1,1,S,T).
    ``scale`` is the softmax scale (None: ``hd ** -0.5``).

    Returns (B,S,H,hd). GQA via a group axis — no kv repetition in memory.
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    # f32 ACCUMULATION, bf16 operands: casting k/v to f32 materializes a
    # full-size copy of the KV cache (2x HBM + observed 1GiB/layer
    # all-gathers in the decode dry-run); preferred_element_type gets the
    # same numerics from the MXU without the copies.
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) \
        * (hd ** -0.5 if scale is None else scale)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v)
    return o.reshape(B, S, H, hd)


def make_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool,
              window: int = 0) -> jnp.ndarray:
    """(B?,S),(B?,T) -> bool (.., 1, 1, S, T) for grouped_attend."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        m = m & (kp <= qp)
    if window:
        m = m & (kp > qp - window)
    # insert head/group broadcast axes: (..., S, T) -> (..., 1, 1, S, T)
    return jnp.expand_dims(jnp.expand_dims(m, -3), -3)


# ---------------------------------------------------------------------------
# Full-sequence (train / encoder) attention
# ---------------------------------------------------------------------------
def attention(p: Params, x: jnp.ndarray, positions: jnp.ndarray, *,
              causal: bool = True, window: int = 0, use_rope: bool = True,
              rope_theta: float = 10000.0,
              xkv: Optional[jnp.ndarray] = None,
              kv_positions: Optional[jnp.ndarray] = None,
              scale: Optional[float] = None) -> jnp.ndarray:
    """Self-attention (xkv=None) or cross-attention (xkv=encoder states)."""
    xkv = x if xkv is None else xkv
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(p, x, xkv)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, kv_positions, rope_theta)
    mask = make_mask(positions, kv_positions, causal, window) \
        if (causal or window) else None
    o = grouped_attend(q, k, v, mask, scale)
    return _out_proj(p, o)


# ---------------------------------------------------------------------------
# KV-cache path (prefill + decode)
# ---------------------------------------------------------------------------
def init_cache(batch: int, cache_len: int, n_kv: int, head_dim: int,
               dtype=jnp.bfloat16) -> Params:
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        # absolute position held by each slot; NEG -> empty
        "kpos": jnp.full((cache_len,), -1, jnp.int32),
    }


def cache_spec(batch: int, cache_len: int, n_kv: int, head_dim: int,
               dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree mirroring init_cache (dry-run, no allocation)."""
    sd = jax.ShapeDtypeStruct
    return {
        "k": sd((batch, cache_len, n_kv, head_dim), dtype),
        "v": sd((batch, cache_len, n_kv, head_dim), dtype),
        "kpos": sd((cache_len,), jnp.int32),
    }


def attention_prefill(p: Params, x: jnp.ndarray, positions: jnp.ndarray, *,
                      cache: Params, window: int = 0, use_rope: bool = True,
                      rope_theta: float = 10000.0
                      ) -> Tuple[jnp.ndarray, Params]:
    """Full forward over (B,S) writing post-RoPE k/v into the cache.

    Assumes S <= cache_len and prefill starts at slot 0 (positions 0..S-1).
    """
    q, k, v = _project_qkv(p, x, x)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    mask = make_mask(positions, positions, True, window)
    o = grouped_attend(q, k, v, mask)
    S = x.shape[1]
    T = cache["k"].shape[1]
    if S == T:
        new_cache = {"k": k.astype(cache["k"].dtype),
                     "v": v.astype(cache["v"].dtype),
                     "kpos": positions[0] if positions.ndim > 1 else positions}
        new_cache["kpos"] = new_cache["kpos"].astype(jnp.int32)
    else:
        pos1d = (positions[0] if positions.ndim > 1 else positions).astype(jnp.int32)
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
            "kpos": jax.lax.dynamic_update_slice(cache["kpos"], pos1d, (0,)),
        }
    return _out_proj(p, o), new_cache


def attention_decode(p: Params, x: jnp.ndarray, pos: jnp.ndarray, *,
                     cache: Params, window: int = 0, use_rope: bool = True,
                     rope_theta: float = 10000.0
                     ) -> Tuple[jnp.ndarray, Params]:
    """One-token decode. x: (B,1,d); pos: scalar int32 absolute position.

    The cache is a ring buffer when ``window>0`` (cache_len == window);
    otherwise slot == pos.
    """
    B = x.shape[0]
    T = cache["k"].shape[1]
    q, k, v = _project_qkv(p, x, x)
    posb = jnp.full((B, 1), pos, jnp.int32)
    if use_rope:
        q = apply_rope(q, posb, rope_theta)
        k = apply_rope(k, posb, rope_theta)
    slot = jnp.where(window > 0, pos % T, pos).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    kpos = jax.lax.dynamic_update_slice(
        cache["kpos"], jnp.full((1,), pos, jnp.int32), (slot,))
    valid = (kpos >= 0) & (kpos <= pos)
    if window:
        valid = valid & (kpos > pos - window)
    mask = valid[None, None, None, None, :]                 # (1,1,1,1,T)
    o = grouped_attend(q, ck, cv, mask)
    return _out_proj(p, o), {"k": ck, "v": cv, "kpos": kpos}


def attention_decode_ragged(p: Params, x: jnp.ndarray, pos: jnp.ndarray, *,
                            cache: Params, live: jnp.ndarray,
                            use_rope: bool = True,
                            rope_theta: float = 10000.0
                            ) -> Tuple[jnp.ndarray, Params]:
    """One-token decode with PER-ROW positions — the serving engine's slot
    cache (docs/serving.md). x: (B,1,d); pos: (B,) int32 absolute position
    of each row's current token; live: (B,) bool slot mask.

    The cache here is LINEAR (slot t holds position t; no sliding-window
    ring) and carries no ``kpos``: row b is valid exactly on ``[0, pos_b]``
    after this call's write, so the mask is just ``t <= pos_b``. Stale
    entries from a slot's previous occupant are only ever re-exposed at
    ``t == pos_b`` — the very index this step overwrites — so the engine
    never needs to scrub freed rows. Dead rows are masked out of the write
    by scattering to an out-of-bounds batch index (dropped), and their
    query attends only to slot 0 so the (ignored) output stays finite.
    """
    B = x.shape[0]
    T = cache["k"].shape[1]
    q, k, v = _project_qkv(p, x, x)
    posb = pos[:, None].astype(jnp.int32)                    # (B,1)
    if use_rope:
        q = apply_rope(q, posb, rope_theta)
        k = apply_rope(k, posb, rope_theta)
    slot = jnp.clip(posb[:, 0], 0, T - 1)
    bidx = jnp.where(live, jnp.arange(B), B)                 # dead -> dropped
    ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    t = jnp.arange(T, dtype=jnp.int32)
    valid = t[None, :] <= jnp.where(live, posb[:, 0], 0)[:, None]  # (B,T)
    mask = valid[:, None, None, None, :]                     # (B,1,1,1,T)
    o = grouped_attend(q, ck, cv, mask)
    return _out_proj(p, o), {"k": ck, "v": cv}


def _pow2_kv_block(cache_len: int) -> int:
    """Page size for viewing a DENSE (B, T, ..) slot cache as kernel
    pages: the largest power of two dividing ``cache_len``, capped at
    128 (the TPU-friendly tile). Power-of-two by construction, so the
    block count never fragments the flash-decode grid."""
    block = cache_len & (-cache_len)
    return min(block, 128)


def attention_decode_ragged_flash(p: Params, x: jnp.ndarray,
                                  pos: jnp.ndarray, *, cache: Params,
                                  live: jnp.ndarray, use_rope: bool = True,
                                  rope_theta: float = 10000.0
                                  ) -> Tuple[jnp.ndarray, Params]:
    """``attention_decode_ragged`` with the attention contraction done by
    the fused Pallas flash-decode kernel (repro.kernels.flash_decode).

    The cache WRITE is byte-identical to the oracle path (same RoPE, same
    OOB-dropped dead-row scatter), so cache state stays bit-exact; only
    the softmax-matmul is computed by the kernel, which views the dense
    ``(B, T, ..)`` row as ``T // block`` contiguous pages under an
    identity page map — the degenerate case of the paged kernel. Dead
    rows are skipped inside the kernel and return exact zeros (finite,
    discarded — same contract as the oracle's slot-0 attend)."""
    from repro.kernels.flash_decode import flash_decode
    B = x.shape[0]
    T = cache["k"].shape[1]
    q, k, v = _project_qkv(p, x, x)
    posb = pos[:, None].astype(jnp.int32)                    # (B,1)
    if use_rope:
        q = apply_rope(q, posb, rope_theta)
        k = apply_rope(k, posb, rope_theta)
    slot = jnp.clip(posb[:, 0], 0, T - 1)
    bidx = jnp.where(live, jnp.arange(B), B)                 # dead -> dropped
    ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    ps = _pow2_kv_block(T)
    nb = T // ps
    K, hd = ck.shape[2:]

    def as_pool(c):                     # (B, T, K, hd) -> (B*nb, K, ps, hd)
        return c.reshape(B, nb, ps, K, hd).transpose(0, 1, 3, 2, 4) \
            .reshape(B * nb, K, ps, hd)

    kpool, vpool = as_pool(ck), as_pool(cv)
    idmap = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    qpos = jnp.where(live, posb[:, 0], 0).astype(jnp.int32)
    o = flash_decode(q[:, 0], kpool, vpool, idmap, qpos,
                     live.astype(jnp.int32))
    return _out_proj(p, o.astype(q.dtype)[:, None]), {"k": ck, "v": cv}


def attention_decode_ragged_paged_flash(p: Params, x: jnp.ndarray,
                                        pos: jnp.ndarray, *,
                                        kbuf: jnp.ndarray, vbuf: jnp.ndarray,
                                        live: jnp.ndarray,
                                        rmap: jnp.ndarray, wmap: jnp.ndarray,
                                        use_rope: bool = True,
                                        rope_theta: float = 10000.0,
                                        scale: Optional[float] = None
                                        ) -> Tuple[jnp.ndarray,
                                                   Tuple[jnp.ndarray,
                                                         jnp.ndarray]]:
    """Ragged one-token decode DIRECTLY over the paged KV pool: no
    ``gather_kv_pages`` materialization, no scatter-back round trip.

    ``kbuf``/``vbuf``: one layer's ``(n_pages, K, page_size, hd)`` pool;
    ``rmap``/``wmap``: ``(B, P)`` int32 page maps with entries
    ``>= n_pages`` meaning no page (read: kernel skips; write: scatter
    drops — the engine's frozen/COW and dead-row convention unchanged).
    The new token's k/v lands on exactly one (page, offset) cell via the
    write map — equivalent to the gather -> oracle-write -> scatter
    composition because every non-frozen page is uniquely owned and the
    scatter-back of unchanged pages is the identity. ``scale`` is the
    softmax scale (None: the kernel's ``hd ** -0.5``). Returns
    ``(out (B,1,d), (new kbuf, new vbuf))``."""
    from repro.kernels.flash_decode import flash_decode
    ps = kbuf.shape[2]
    P = rmap.shape[1]
    q, k, v = _project_qkv(p, x, x)
    posb = pos[:, None].astype(jnp.int32)                    # (B,1)
    if use_rope:
        q = apply_rope(q, posb, rope_theta)
        k = apply_rope(k, posb, rope_theta)
    pidx = jnp.clip(posb[:, 0] // ps, 0, P - 1)
    wpage = jnp.take_along_axis(wmap, pidx[:, None], axis=1)[:, 0]
    woff = posb[:, 0] % ps
    nk = kbuf.at[wpage, :, woff].set(k[:, 0].astype(kbuf.dtype))
    nv = vbuf.at[wpage, :, woff].set(v[:, 0].astype(vbuf.dtype))
    qpos = jnp.where(live, posb[:, 0], 0).astype(jnp.int32)
    o = flash_decode(q[:, 0], nk, nv, rmap, qpos, live.astype(jnp.int32),
                     scale=scale)
    return _out_proj(p, o.astype(q.dtype)[:, None]), (nk, nv)


def attention_prefill_chunk(p: Params, x: jnp.ndarray, off: jnp.ndarray,
                            clen: jnp.ndarray, *, cache: Params,
                            use_rope: bool = True,
                            rope_theta: float = 10000.0,
                            scale: Optional[float] = None
                            ) -> Tuple[jnp.ndarray, Params]:
    """One CHUNK of a chunked ragged prefill — the serving engine's path
    for prompts longer than its largest prefill bucket (docs/serving.md).
    x: (B,C,d); row b's chunk occupies absolute positions
    ``[off_b, off_b + clen_b)`` of its slot, with ``clen_b <= C`` valid
    tokens and the rest padding. The cache is the engine's LINEAR slot
    cache (``{"k","v"}`` of (B,T,..), no ``kpos`` — same contract as
    ``attention_decode_ragged``): columns ``[0, off_b)`` hold the
    already-prefilled prefix, post-RoPE.

    The chunk's k/v are scattered at columns ``off_b + i`` (padding
    scatters out of bounds and is dropped), then query ``i`` attends
    ``t <= off_b + i`` — the prefix plus the in-chunk causal triangle in
    one mask, since in-chunk keys sit at exactly those columns. Stale
    columns past ``off_b + clen_b`` are masked to exact zeros, so a
    chunked prefill is bit-exact vs one unpadded full-prompt prefill.
    Padding queries attend only ``t == 0`` (finite, discarded).
    """
    B, C, _ = x.shape
    T = cache["k"].shape[1]
    q, k, v = _project_qkv(p, x, x)
    i = jnp.arange(C, dtype=jnp.int32)
    qpos = off[:, None].astype(jnp.int32) + i[None, :]       # (B,C) absolute
    if use_rope:
        q = apply_rope(q, qpos, rope_theta)
        k = apply_rope(k, qpos, rope_theta)
    valid_q = i[None, :] < clen[:, None]                     # (B,C)
    col = jnp.where(valid_q, qpos, T)                        # pad -> dropped
    bidx = jnp.arange(B)[:, None]
    ck = cache["k"].at[bidx, col].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, col].set(v.astype(cache["v"].dtype))
    t = jnp.arange(T, dtype=jnp.int32)
    lim = jnp.where(valid_q, qpos, 0)                        # (B,C)
    mask = (t[None, None, :] <= lim[..., None])[:, None, None, :, :]
    o = grouped_attend(q, ck, cv, mask, scale)
    return _out_proj(p, o), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross-attention KV (whisper decoder): computed once per sequence
# ---------------------------------------------------------------------------
def cross_kv(p: Params, enc: jnp.ndarray) -> Params:
    k = jnp.einsum("btd,dhk->bthk", enc, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return {"k": k, "v": v}


def cross_attend(p: Params, x: jnp.ndarray, kv: Params) -> jnp.ndarray:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    o = grouped_attend(q, kv["k"], kv["v"], None)
    return _out_proj(p, o)

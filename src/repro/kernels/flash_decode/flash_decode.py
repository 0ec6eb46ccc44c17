"""Fused paged flash-decode Pallas kernel — TPU TARGET (validated
interpret=True).

Single-token ragged decode attention read DIRECTLY off the paged KV pool:
no `gather_kv_pages` materialization, no (B, max_seq) linear copy. Each
row's page map is a runtime scalar-prefetch argument, so the kernel's
K/V BlockSpecs dereference `page_map[b, j]` to DMA exactly the live
pages — the page-table indirection the serving engine already maintains
becomes the kernel's addressing mode:

  - grid (B, K, P) with the page axis minor-most, so the per-row online
    softmax scratch (m/l/acc in VMEM) carries across a row's page scan
    (TPU grid steps execute sequentially on a core);
  - `pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=3)` prefetches
    (page_map, pos, live) into SMEM; the page map drives the K/V block
    index_maps and all three drive the per-step skip predicate;
  - pages past `pos_b // page_size`, pages mapped out-of-bounds
    (page_map >= n_pages: the engine's freed/COW convention), and dead
    rows are skipped entirely — the DMA still issues (clamped to a real
    page) but the flops and softmax update do not;
  - GQA: grid axis 1 walks KV heads; each step computes the whole
    G = H // K query-head group against that kv head's page.

Dead rows (live == 0) never update l, so the final l==0 guard emits
exact zeros for them.

Layouts: q (B, H, D); kpool/vpool (n_pages, K, page_size, D) — the
KV-head axis ahead of the page-slot axis, so one (page, kv head) block is
a whole (page_size, D) tile; page_map (B, P) int32 with entries >=
n_pages meaning "no page"; pos (B,) int32 last valid position; live (B,)
int32. Out: (B, H, D). Inside the call q and the output are viewed as
(B, K, G, D), so every block's two trailing dims are whole array dims —
what the TPU's tiling asks of a block at any width.

The dense slot cache is the degenerate case: view (B, T, K, D) as
(B*nb, K, T//nb, D) with the identity page map — one kernel serves both
serving cache layouts (ops.py / models.attention wire this up).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_decode_kernel(pm_ref, pos_ref, live_ref, q_ref, k_ref, v_ref,
                         o_ref, m_scr, l_scr, acc_scr, *,
                         page_size: int, n_pages: int, n_page_blocks: int,
                         scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    needed = (live_ref[b] != 0) & (pm_ref[b, j] < n_pages) \
        & (j * page_size <= pos)

    @pl.when(needed)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (ps, D)
        v = v_ref[0, 0].astype(jnp.float32)               # (ps, D)
        G = q.shape[0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (G, page_size), 1)
        mask = k_pos <= pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_page_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def grid(batch: int, kv_heads: int, page_blocks: int):
    """The kernel's grid: every (row, KV head, page block), live or not."""
    return (batch, kv_heads, page_blocks)


def pages_walked(batch: int, page_blocks: int) -> int:
    """KV pages the grid visits per layer and KV head: the DMA of every
    (row, page block) step issues, dead pages included."""
    rows, _, pages = grid(batch, 1, page_blocks)
    return rows * pages


def flash_decode_pallas(q: jnp.ndarray, kpool: jnp.ndarray,
                        vpool: jnp.ndarray, page_map: jnp.ndarray,
                        pos: jnp.ndarray, live: jnp.ndarray, *,
                        interpret: bool = True,
                        scale: float = None) -> jnp.ndarray:
    """q: (B,H,D); kpool/vpool: (N,K,ps,D); page_map: (B,P) int32 (>=N
    means no page); pos/live: (B,) int32 -> (B,H,D). ``scale`` is the
    softmax scale, ``D ** -0.5`` when not given."""
    B, H, D = q.shape
    N, K, ps, _ = kpool.shape
    P = page_map.shape[1]
    G = H // K
    if scale is None:
        scale = D ** -0.5

    kernel = functools.partial(
        _flash_decode_kernel, page_size=ps, n_pages=N, n_page_blocks=P,
        scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid(B, K, P),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, kh, j, pm, pos, live: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, ps, D),
                         lambda b, kh, j, pm, pos, live, N=N:
                         (jnp.minimum(pm[b, j], N - 1), kh, 0, 0)),
            pl.BlockSpec((1, 1, ps, D),
                         lambda b, kh, j, pm, pos, live, N=N:
                         (jnp.minimum(pm[b, j], N - 1), kh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, kh, j, pm, pos, live:
                               (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(page_map, pos, live, q.reshape(B, K, G, D), kpool, vpool)
    return out.reshape(B, H, D)

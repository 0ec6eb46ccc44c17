"""Model assembly: block definitions, layer-stacked scans, train/prefill/
decode entry points for every assigned architecture family.

Param layout (uniform stacks carry a leading L axis, consumed by lax.scan):
  dense/moe/vlm : {embed, blocks, final_norm[, lm_head]}
  ssm           : {embed, blocks, final_norm}
  hybrid        : {embed, super: {ssm (Ns,P-1,...), attn (Ns,...)},
                   tail (Nt,...), final_norm}
  layer_types   : {embed, blocks: {mamba (Nm,...), attn (Na,...)},
                   final_norm}: layer i is the next of its kind's stack
  audio(encdec) : {embed, encoder, enc_final_norm, blocks(dec), final_norm}

Decode "cache" trees mirror the block structure with leading L axes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.activation_sharding import constrain_batch
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_norm, dtype_of, embed, init_embed,
                                 init_layernorm, init_mlp, init_rmsnorm, mlp,
                                 unembed)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------
def scan_apply(body, carry, xs, unroll: bool = False):
    """lax.scan or an unrolled python loop (identical math).

    Unrolling exists for the dry-run's cost probes: XLA's cost_analysis
    counts a while-loop body ONCE regardless of trip count, so roofline
    numbers come from small-L unrolled lowers extrapolated linearly
    (launch/dryrun.py).
    """
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    L = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def sinusoidal_pos(positions: jnp.ndarray, d: int) -> jnp.ndarray:
    """Whisper-style sinusoidal embedding; length-agnostic (adapts the
    paper-model's learned table, which caps at 448, to assigned shapes)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Per-block init
# ---------------------------------------------------------------------------
def _init_norm(cfg: ArchConfig, dtype):
    return (init_layernorm if cfg.attn_bias else init_rmsnorm)(cfg.d_model, dtype)


def init_attn_block(key, cfg: ArchConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": _init_norm(cfg, dtype),
        "attn": attn_mod.init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qk_norm=cfg.qk_norm, bias=cfg.attn_bias, dtype=dtype),
        "ln2": _init_norm(cfg, dtype),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act,
                        bias=cfg.attn_bias, dtype=dtype),
    }


def init_moe_block(key, cfg: ArchConfig, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": _init_norm(cfg, dtype),
        "attn": attn_mod.init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qk_norm=cfg.qk_norm, bias=cfg.attn_bias, dtype=dtype),
        "ln2": _init_norm(cfg, dtype),
        "moe": moe_mod.init_moe(k2, cfg.d_model, cfg.moe, dtype),
    }
    if cfg.moe.shared_expert:
        p["shared"] = init_mlp(k3, cfg.d_model, cfg.moe.d_ff_expert,
                               "silu", dtype=dtype)
    if cfg.moe.dense_residual:
        p["dense"] = init_mlp(k4, cfg.d_model, cfg.d_ff, "silu", dtype=dtype)
    return p


def init_ssm_block(key, cfg: ArchConfig, dtype) -> Params:
    return {
        "ln": init_rmsnorm(cfg.d_model, dtype),
        "ssm": ssm_mod.init_ssm(key, cfg.d_model, cfg.ssm, dtype),
    }


def init_decoder_xblock(key, cfg: ArchConfig, dtype) -> Params:
    """Enc-dec decoder block: self-attn + cross-attn + MLP (whisper)."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": _init_norm(cfg, dtype),
        "self_attn": attn_mod.init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            bias=cfg.attn_bias, dtype=dtype),
        "ln2": _init_norm(cfg, dtype),
        "cross_attn": attn_mod.init_attention(
            k2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            bias=cfg.attn_bias, dtype=dtype),
        "ln3": _init_norm(cfg, dtype),
        "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.mlp_act,
                        bias=cfg.attn_bias, dtype=dtype),
    }


def init_mamba_layer(key, cfg: ArchConfig, dtype) -> Params:
    """A ``layer_types`` Mamba2 layer: the mixer and a dense MLP, each
    behind its own pre-norm."""
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "ssm": ssm_mod.init_ssm(k1, cfg.d_model, cfg.ssm, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype=dtype),
    }


def _stack_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ArchConfig) -> Params:
    dtype = dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    p: Params = {"embed": init_embed(keys[0], cfg.vocab_size, cfg.d_model, dtype),
                 "final_norm": _init_norm(cfg, dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = init_embed(keys[1], cfg.vocab_size, cfg.d_model, dtype)

    cfg.block_pattern()          # validates the arch family eagerly
    if cfg.layer_types:
        counts = layer_counts(cfg)
        inits = {MAMBA: init_mamba_layer, ATTENTION: init_attn_block}
        p["blocks"] = {
            STACK[t]: _stack_init(lambda k, f=inits[t]: f(k, cfg, dtype),
                                  jax.random.fold_in(keys[2], i), counts[t])
            for i, t in enumerate((MAMBA, ATTENTION)) if counts[t]}
    elif cfg.arch_type in ("dense", "vlm"):
        p["blocks"] = _stack_init(lambda k: init_attn_block(k, cfg, dtype),
                                  keys[2], cfg.n_layers)
    elif cfg.arch_type == "moe":
        p["blocks"] = _stack_init(lambda k: init_moe_block(k, cfg, dtype),
                                  keys[2], cfg.n_layers)
    elif cfg.arch_type == "ssm":
        p["blocks"] = _stack_init(lambda k: init_ssm_block(k, cfg, dtype),
                                  keys[2], cfg.n_layers)
    elif cfg.arch_type == "hybrid":
        period = cfg.hybrid_attn_period
        n_super = cfg.n_layers // period
        n_tail = cfg.n_layers % period

        def init_super(k):
            ka, kb = jax.random.split(k)
            return {"ssm": _stack_init(
                        lambda kk: init_ssm_block(kk, cfg, dtype), ka, period - 1),
                    "attn": init_attn_block(kb, cfg, dtype)}

        p["super"] = _stack_init(init_super, keys[2], n_super)
        if n_tail:
            p["tail"] = _stack_init(lambda k: init_ssm_block(k, cfg, dtype),
                                    keys[3], n_tail)
    elif cfg.arch_type == "audio":
        p["encoder"] = _stack_init(lambda k: init_attn_block(k, cfg, dtype),
                                   keys[2], cfg.n_encoder_layers)
        p["enc_final_norm"] = _init_norm(cfg, dtype)
        p["blocks"] = _stack_init(lambda k: init_decoder_xblock(k, cfg, dtype),
                                  keys[3], cfg.n_layers)
    else:
        raise ValueError(f"unknown arch_type {cfg.arch_type}")
    return p


def abstract_params(cfg: ArchConfig) -> Params:
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    return jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Block application — full-sequence (train / encoder / prefill-less)
# ---------------------------------------------------------------------------
def apply_attn_block(bp: Params, x, positions, cfg: ArchConfig, *,
                     causal=True) -> jnp.ndarray:
    h = apply_norm(bp["ln1"], x, cfg.norm_eps)
    x = x + attn_mod.attention(
        bp["attn"], h, positions, causal=causal, window=cfg.sliding_window,
        use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
    h = apply_norm(bp["ln2"], x, cfg.norm_eps)
    return constrain_batch(x + mlp(bp["mlp"], h, cfg.mlp_act))


def apply_moe_block(bp: Params, x, positions, cfg: ArchConfig
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = apply_norm(bp["ln1"], x, cfg.norm_eps)
    x = x + attn_mod.attention(
        bp["attn"], h, positions, causal=True, window=cfg.sliding_window,
        use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
    h = apply_norm(bp["ln2"], x, cfg.norm_eps)
    moe_fn = moe_mod.moe_ffn_sorted if cfg.moe.impl == "sort" \
        else moe_mod.moe_ffn
    y, aux = moe_fn(bp["moe"], h, cfg.moe)
    if "shared" in bp:
        y = y + mlp(bp["shared"], h, "silu")
    if "dense" in bp:
        y = y + mlp(bp["dense"], h, "silu")
    return constrain_batch(x + y), aux


def apply_ssm_block(bp: Params, x, cfg: ArchConfig) -> jnp.ndarray:
    h = apply_norm(bp["ln"], x, cfg.norm_eps)
    return constrain_batch(
        x + ssm_mod.ssm_forward(bp["ssm"], h, cfg.d_model, cfg.ssm))


def _head(params: Params, cfg: ArchConfig, x) -> jnp.ndarray:
    """Final norm and the output head: fp32 logits (divided by Granite's
    ``logits_scaling`` where it is set)."""
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(head, x)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# ---------------------------------------------------------------------------
# Per-layer hybrids (``cfg.layer_types``, Granite-4.0-H): every layer is a
# Mamba2 or an attention mixer, then a dense MLP, each branch scaled by
# ``residual_multiplier``. Each kind's layers form one stack; a run of
# same-kind Mamba layers is one scan, the (few) attention layers are
# unrolled so each owns its KV page buffers.
# ---------------------------------------------------------------------------
MAMBA, ATTENTION = "mamba", "attention"
STACK = {MAMBA: "mamba", ATTENTION: "attn"}


def layer_counts(cfg: ArchConfig) -> Dict[str, int]:
    return {t: sum(1 for x in cfg.layer_types if x == t)
            for t in (MAMBA, ATTENTION)}


def layer_runs(cfg: ArchConfig) -> List[Tuple[str, int, int]]:
    """Runs of same-kind layers in order: (kind, index of the run's first
    layer in its kind's stack, length)."""
    runs: List[Tuple[str, int, int]] = []
    seen = {MAMBA: 0, ATTENTION: 0}
    for t in cfg.layer_types:
        if runs and runs[-1][0] == t:
            runs[-1] = (t, runs[-1][1], runs[-1][2] + 1)
        else:
            runs.append((t, seen[t], 1))
        seen[t] += 1
    return runs


def _layer(stack: Params, i) -> Params:
    """Layer ``i`` (static or traced) of a stacked tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        stack)


def _residual(cfg: ArchConfig, x, y):
    return x + jnp.asarray(cfg.residual_multiplier, x.dtype) * y.astype(
        x.dtype)


def _mlp_branch(lp: Params, x, cfg: ArchConfig):
    h = apply_norm(lp["ln2"], x, cfg.norm_eps)
    return _residual(cfg, x, mlp(lp["mlp"], h, cfg.mlp_act))


def _hybrid_embed(params: Params, cfg: ArchConfig, tokens) -> jnp.ndarray:
    adt = dtype_of(cfg.activ_dtype)
    x = embed(params["embed"], tokens).astype(adt)
    return x * jnp.asarray(cfg.embedding_multiplier, adt)


def hybrid_forward(params: Params, cfg: ArchConfig, x, positions, scan,
                   maybe_ckpt) -> jnp.ndarray:
    """The training path's layers over embedded ``x`` (B,S,d)."""
    blocks = params["blocks"]
    for kind, start, n in layer_runs(cfg):
        if kind == MAMBA:
            @maybe_ckpt
            def body(h, i):
                lp = _layer(blocks["mamba"], i)
                hh = apply_norm(lp["ln1"], h, cfg.norm_eps)
                h = _residual(cfg, h, ssm_mod.ssm_forward(
                    lp["ssm"], hh, cfg.d_model, cfg.ssm))
                return constrain_batch(_mlp_branch(lp, h, cfg)), None
            x, _ = scan(body, x, jnp.arange(start, start + n))
            continue
        for j in range(start, start + n):
            lp = _layer(blocks["attn"], j)
            hh = apply_norm(lp["ln1"], x, cfg.norm_eps)
            x = _residual(cfg, x, attn_mod.attention(
                lp["attn"], hh, positions, causal=True,
                use_rope=cfg.use_rope, rope_theta=cfg.rope_theta,
                scale=cfg.attn_scale))
            x = constrain_batch(_mlp_branch(lp, x, cfg))
    return x


def hybrid_cache(cfg: ArchConfig, slots: int, n_pages: int,
                 page_size: int) -> Params:
    """The serving engine's cache for a ``layer_types`` model: one page
    buffer pair ``(n_pages, n_kv, page_size, head_dim)`` per attention
    layer, and a state pool by slot for each run of Mamba layers: ``h``
    (n, slots, nh, hd, N) float32 and the conv ring (n, slots, W-1,
    conv_dim) in the activation dtype, ``n`` the run's length. (One pool
    a run, so that each buffer passes through one loop only: a pool
    carried through several loops with attention between them is copied
    by the compiler.) Zeros."""
    adt = dtype_of(cfg.activ_dtype)
    shape = (n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    kv = [{"k": jnp.zeros(shape, adt), "v": jnp.zeros(shape, adt)}
          for _ in range(layer_counts(cfg)[ATTENTION])]
    spec = ssm_mod.ssm_states_spec(slots, cfg.d_model, cfg.ssm, adt)
    state = [{k: jnp.zeros((n,) + v.shape, v.dtype) for k, v in spec.items()}
             for kind, _, n in layer_runs(cfg) if kind == MAMBA]
    return {"kv": kv, "state": state}


def reset_slot_state(state: Params, slots: jnp.ndarray) -> Params:
    """Zero the state of ``slots`` (entries past the pool are dropped)."""
    return jax.tree.map(lambda v: v.at[:, slots].set(0, mode="drop"), state)


def hybrid_prefill_chunk_paged(params: Params, cfg: ArchConfig,
                               tokens: jnp.ndarray, off: jnp.ndarray,
                               clen: jnp.ndarray, cache: Params,
                               rmap: jnp.ndarray, wmap: jnp.ndarray,
                               slots: jnp.ndarray, unroll: bool = False
                               ) -> Tuple[jnp.ndarray, Params]:
    """One chunk of a chunked ragged prefill of a ``layer_types`` model
    (the shapes of ``prefill_chunk_paged``; ``slots`` (B,) is each row's
    slot, the pool size for padding rows). A Mamba layer gathers its
    rows' carried state, runs ``ssm_prefill_chunk`` and writes the state
    at each row's last valid column back in place; an attention layer
    runs ``prefill_chunk``'s math on its page buffers. Returns the
    logits at each row's last valid column (B,1,V) and the cache."""
    scan = functools.partial(scan_apply, unroll=unroll)
    blocks = params["blocks"]
    x = _hybrid_embed(params, cfg, tokens)
    state, kv = list(cache["state"]), list(cache["kv"])
    rows = jnp.clip(slots, 0, state[0]["h"].shape[1] - 1)
    runs = [r for r in layer_runs(cfg) if r[0] == MAMBA]
    for kind, start, n in layer_runs(cfg):
        if kind == MAMBA:
            def body(carry, i):
                h, st = carry
                lp = _layer(blocks["mamba"], start + i)
                with jax.named_scope("serve/ssm"):
                    mine = {k: _layer(v, i)[rows] for k, v in st.items()}
                    y, new = ssm_mod.ssm_prefill_chunk(
                        lp["ssm"], apply_norm(lp["ln1"], h, cfg.norm_eps),
                        clen, mine, cfg.d_model, cfg.ssm)
                    h = _residual(cfg, h, y)
                    st = {k: v.at[i, slots].set(new[k].astype(v.dtype),
                                                mode="drop")
                          for k, v in st.items()}
                with jax.named_scope("serve/mlp"):
                    h = _mlp_branch(lp, h, cfg)
                return (h, st), None
            r = runs.index((kind, start, n))
            (x, state[r]), _ = scan(body, (x, state[r]), jnp.arange(n))
            continue
        for j in range(start, start + n):
            lp = _layer(blocks["attn"], j)
            # one layer's page buffers relaid out at a time (see the decode)
            x, kv[j] = jax.lax.optimization_barrier((x, kv[j]))
            with jax.named_scope("serve/attn"):
                view = {m: gather_kv_pages(kv[j][m][None], rmap)[0]
                        for m in ("k", "v")}
                a, view = attn_mod.attention_prefill_chunk(
                    lp["attn"], apply_norm(lp["ln1"], x, cfg.norm_eps),
                    off, clen, cache=view, use_rope=cfg.use_rope,
                    rope_theta=cfg.rope_theta, scale=cfg.attn_scale)
                x = _residual(cfg, x, a)
                kv[j] = {m: scatter_kv_pages(kv[j][m][None], wmap,
                                             view[m][None])[0]
                         for m in ("k", "v")}
            with jax.named_scope("serve/mlp"):
                x = _mlp_branch(lp, x, cfg)
    idx = jnp.clip(clen.astype(jnp.int32) - 1, 0, x.shape[1] - 1)
    x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    return _head(params, cfg, x), {"kv": kv, "state": state}


def hybrid_decode_paged_flash(params: Params, cfg: ArchConfig,
                              token: jnp.ndarray, pos: jnp.ndarray,
                              cache: Params, live: jnp.ndarray,
                              rmap: jnp.ndarray, wmap: jnp.ndarray,
                              unroll: bool = False
                              ) -> Tuple[jnp.ndarray, Params]:
    """One-token ragged decode of a ``layer_types`` model over every slot
    (row == slot): each Mamba layer reads and writes the whole state pool
    in place and changes only ``live`` rows; each attention layer runs
    the paged flash-decode kernel on its page buffers."""
    scan = functools.partial(scan_apply, unroll=unroll)
    blocks = params["blocks"]
    x = _hybrid_embed(params, cfg, token)
    state, kv = list(cache["state"]), list(cache["kv"])
    runs = [r for r in layer_runs(cfg) if r[0] == MAMBA]
    for kind, start, n in layer_runs(cfg):
        if kind == MAMBA:
            def body(carry, i):
                h, st = carry
                lp = _layer(blocks["mamba"], start + i)
                with jax.named_scope("serve/ssm"):
                    y, new = ssm_mod.ssm_decode(
                        lp["ssm"], apply_norm(lp["ln1"], h, cfg.norm_eps),
                        _layer(st, i), cfg.d_model, cfg.ssm, live=live)
                    h = _residual(cfg, h, y)
                    st = {k: jax.lax.dynamic_update_index_in_dim(
                        v, new[k].astype(v.dtype), i, 0)
                        for k, v in st.items()}
                with jax.named_scope("serve/mlp"):
                    h = _mlp_branch(lp, h, cfg)
                return (h, st), None
            r = runs.index((kind, start, n))
            (x, state[r]), _ = scan(body, (x, state[r]), jnp.arange(n))
            continue
        for j in range(start, start + n):
            lp = _layer(blocks["attn"], j)
            # tie the layer's page buffers to its input: the compiler
            # relays the buffers out for the kernel (head_dim 64 pages are
            # not stored row-major), and would otherwise relay every
            # layer's at once, before the first layer runs
            x, kv[j] = jax.lax.optimization_barrier((x, kv[j]))
            with jax.named_scope("serve/attn"):
                a, (nk, nv) = attn_mod.attention_decode_ragged_paged_flash(
                    lp["attn"], apply_norm(lp["ln1"], x, cfg.norm_eps), pos,
                    kbuf=kv[j]["k"], vbuf=kv[j]["v"], live=live, rmap=rmap,
                    wmap=wmap, use_rope=cfg.use_rope,
                    rope_theta=cfg.rope_theta, scale=cfg.attn_scale)
                x = _residual(cfg, x, a)
                kv[j] = {"k": nk, "v": nv}
            with jax.named_scope("serve/mlp"):
                x = _mlp_branch(lp, x, cfg)
    return _head(params, cfg, x), {"kv": kv, "state": state}


# ---------------------------------------------------------------------------
# Full forward (training path) -> (logits, aux_loss)
# ---------------------------------------------------------------------------
def forward(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
            prefix: Optional[jnp.ndarray] = None,
            frames: Optional[jnp.ndarray] = None,
            remat: bool = True,
            unroll: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scan = functools.partial(scan_apply, unroll=unroll)
    adt = dtype_of(cfg.activ_dtype)
    x = embed(params["embed"], tokens).astype(adt)
    if cfg.arch_type == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, adt)        # gemma convention
        assert prefix is not None, "vlm needs patch embeddings"
        x = jnp.concatenate([prefix.astype(adt), x], axis=1)
    x = constrain_batch(x)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    aux_total = jnp.zeros((), jnp.float32)

    def maybe_ckpt(fn):
        return jax.checkpoint(fn) if remat else fn

    if cfg.arch_type == "audio":
        # ---- encoder over precomputed frame embeddings ----
        assert frames is not None, "audio needs frame embeddings"
        F = frames.shape[1]
        enc = frames.astype(adt) + sinusoidal_pos(
            jnp.arange(F, dtype=jnp.int32), cfg.d_model).astype(adt)

        @maybe_ckpt
        def enc_body(h, bp):
            return apply_attn_block(bp, h, jnp.arange(F, dtype=jnp.int32),
                                    cfg, causal=False), None
        enc, _ = scan(enc_body, enc, params["encoder"])
        enc = apply_norm(params["enc_final_norm"], enc, cfg.norm_eps)

        x = x + sinusoidal_pos(positions, cfg.d_model).astype(adt)

        @maybe_ckpt
        def dec_body(h, bp):
            hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
            h = h + attn_mod.attention(
                bp["self_attn"], hh, positions, causal=True,
                use_rope=False)
            hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
            h = h + attn_mod.attention(
                bp["cross_attn"], hh, positions, causal=False, use_rope=False,
                xkv=enc, kv_positions=jnp.arange(F, dtype=jnp.int32))
            hh = apply_norm(bp["ln3"], h, cfg.norm_eps)
            return constrain_batch(h + mlp(bp["mlp"], hh, cfg.mlp_act)), None
        x, _ = scan(dec_body, x, params["blocks"])

    elif cfg.arch_type in ("dense", "vlm"):
        @maybe_ckpt
        def body(h, bp):
            return apply_attn_block(bp, h, positions, cfg), None
        x, _ = scan(body, x, params["blocks"])

    elif cfg.arch_type == "moe":
        @maybe_ckpt
        def body(carry, bp):
            h, aux = carry
            h, a = apply_moe_block(bp, h, positions, cfg)
            return (h, aux + a), None
        (x, aux_total), _ = scan(body, (x, aux_total), params["blocks"])

    elif cfg.arch_type == "ssm":
        @maybe_ckpt
        def body(h, bp):
            return apply_ssm_block(bp, h, cfg), None
        x, _ = scan(body, x, params["blocks"])

    elif cfg.layer_types:
        x = hybrid_forward(params, cfg, x * jnp.asarray(
            cfg.embedding_multiplier, adt), positions, scan, maybe_ckpt)

    elif cfg.arch_type == "hybrid":
        @maybe_ckpt
        def super_body(h, sp):
            def inner(hh, bp):
                return apply_ssm_block(bp, hh, cfg), None
            h, _ = scan(inner, h, sp["ssm"])
            return apply_attn_block(sp["attn"], h, positions, cfg), None
        x, _ = scan(super_body, x, params["super"])
        if "tail" in params:
            @maybe_ckpt
            def tail_body(h, bp):
                return apply_ssm_block(bp, h, cfg), None
            x, _ = scan(tail_body, x, params["tail"])
    else:
        raise ValueError(cfg.arch_type)

    if cfg.arch_type == "vlm":                               # loss on text only
        x = x[:, -tokens.shape[1]:, :]
    return _head(params, cfg, x), aux_total


# ---------------------------------------------------------------------------
# Prefill / decode (serving path)
# ---------------------------------------------------------------------------
def init_decode_cache(cfg: ArchConfig, batch: int, cache_len: int,
                      abstract: bool = False) -> Params:
    """Cache tree with leading per-stack L axes. ``cache_len`` is the KV
    length; sliding-window archs get a ring of min(window, cache_len)."""
    if cfg.layer_types:
        raise ValueError(f"{cfg.name}: layer_types models keep their cache "
                         f"in the serving engine (hybrid_cache)")
    adt = dtype_of(cfg.activ_dtype)
    eff = min(cfg.sliding_window, cache_len) if cfg.sliding_window else cache_len
    sd = jax.ShapeDtypeStruct

    def attn_cache():
        return attn_mod.cache_spec(batch, eff, cfg.n_kv_heads, cfg.head_dim, adt)

    def ssm_state():
        return ssm_mod.ssm_states_spec(batch, cfg.d_model, cfg.ssm, adt)

    def stack(tree, n):
        return jax.tree.map(lambda s: sd((n,) + s.shape, s.dtype), tree)

    if cfg.arch_type in ("dense", "vlm", "moe"):
        spec = {"layers": stack(attn_cache(), cfg.n_layers)}
    elif cfg.arch_type == "ssm":
        spec = {"layers": stack(ssm_state(), cfg.n_layers)}
    elif cfg.arch_type == "hybrid":
        period = cfg.hybrid_attn_period
        n_super = cfg.n_layers // period
        n_tail = cfg.n_layers % period
        spec = {"super": {"ssm": stack(stack(ssm_state(), period - 1), n_super),
                          "attn": stack(attn_cache(), n_super)}}
        if n_tail:
            spec["tail"] = stack(ssm_state(), n_tail)
    elif cfg.arch_type == "audio":
        spec = {"self": stack(attn_cache(), cfg.n_layers),
                "cross": stack({"k": sd((batch, cfg.encoder_seq,
                                         cfg.n_kv_heads, cfg.head_dim), adt),
                                "v": sd((batch, cfg.encoder_seq,
                                         cfg.n_kv_heads, cfg.head_dim), adt)},
                               cfg.n_layers)}
    else:
        raise ValueError(cfg.arch_type)
    if abstract:
        return spec
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype) if s.dtype
                        != jnp.int32 else jnp.full(s.shape, -1, s.dtype), spec)


def decode_step(params: Params, cfg: ArchConfig, token: jnp.ndarray,
                pos: jnp.ndarray, cache: Params,
                frames_enc: Optional[jnp.ndarray] = None,
                unroll: bool = False) -> Tuple[jnp.ndarray, Params]:
    """ONE-token decode. token: (B,1) int32; pos: scalar int32 (same for all
    rows — continuous batching with per-row positions is a serving-layer
    concern handled by repro.serve). Returns (logits (B,1,V), new cache)."""
    if cfg.layer_types:
        raise ValueError(f"{cfg.name}: layer_types models decode through "
                         f"decode_step_ragged_paged_flash")
    scan = functools.partial(scan_apply, unroll=unroll)
    adt = dtype_of(cfg.activ_dtype)
    x = embed(params["embed"], token).astype(adt)
    if cfg.arch_type == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, adt)
    w = cfg.sliding_window

    if cfg.arch_type in ("dense", "vlm", "moe"):
        is_moe = cfg.arch_type == "moe"

        def body(h, xs):
            bp, cl = xs
            hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
            a, new_c = attn_mod.attention_decode(
                bp["attn"], hh, pos, cache=cl, window=w,
                use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
            h = h + a
            hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
            if is_moe:
                moe_fn = moe_mod.moe_ffn_sorted if cfg.moe.impl == "sort" \
                    else moe_mod.moe_ffn
                y, _ = moe_fn(bp["moe"], hh, cfg.moe)
                if "shared" in bp:
                    y = y + mlp(bp["shared"], hh, "silu")
                if "dense" in bp:
                    y = y + mlp(bp["dense"], hh, "silu")
            else:
                y = mlp(bp["mlp"], hh, cfg.mlp_act)
            return h + y, new_c
        x, new_layers = scan(body, x, (params["blocks"],
                                               cache["layers"]))
        new_cache = {"layers": new_layers}

    elif cfg.arch_type == "ssm":
        def body(h, xs):
            bp, st = xs
            hh = apply_norm(bp["ln"], h, cfg.norm_eps)
            y, st2 = ssm_mod.ssm_decode(bp["ssm"], hh, st, cfg.d_model, cfg.ssm)
            return h + y, st2
        x, new_layers = scan(body, x, (params["blocks"],
                                               cache["layers"]))
        new_cache = {"layers": new_layers}

    elif cfg.arch_type == "hybrid":
        def ssm_body(h, xs):
            bp, st = xs
            hh = apply_norm(bp["ln"], h, cfg.norm_eps)
            y, st2 = ssm_mod.ssm_decode(bp["ssm"], hh, st, cfg.d_model, cfg.ssm)
            return h + y, st2

        def super_body(h, xs):
            sp, sc = xs
            h, new_ssm = scan(ssm_body, h, (sp["ssm"], sc["ssm"]))
            bp = sp["attn"]
            hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
            a, new_attn = attn_mod.attention_decode(
                bp["attn"], hh, pos, cache=sc["attn"], window=w,
                use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
            h = h + a
            hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
            h = h + mlp(bp["mlp"], hh, cfg.mlp_act)
            return h, {"ssm": new_ssm, "attn": new_attn}
        x, new_super = scan(super_body, x,
                                    (params["super"], cache["super"]))
        new_cache = {"super": new_super}
        if "tail" in cache:
            x, new_tail = scan(ssm_body, x,
                                       (params["tail"], cache["tail"]))
            new_cache["tail"] = new_tail

    elif cfg.arch_type == "audio":
        x = x + sinusoidal_pos(jnp.full((1,), pos, jnp.int32),
                               cfg.d_model).astype(adt)

        def body(h, xs):
            bp, cl, xkv = xs
            hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
            a, new_c = attn_mod.attention_decode(
                bp["self_attn"], hh, pos, cache=cl, window=w, use_rope=False)
            h = h + a
            hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
            h = h + attn_mod.cross_attend(bp["cross_attn"], hh, xkv)
            hh = apply_norm(bp["ln3"], h, cfg.norm_eps)
            return h + mlp(bp["mlp"], hh, cfg.mlp_act), new_c
        x, new_self = scan(body, x, (params["blocks"], cache["self"],
                                             cache["cross"]))
        new_cache = {"self": new_self, "cross": cache["cross"]}
    else:
        raise ValueError(cfg.arch_type)

    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(head, x), new_cache


def decode_step_ragged(params: Params, cfg: ArchConfig, token: jnp.ndarray,
                       pos: jnp.ndarray, cache: Params, live: jnp.ndarray,
                       unroll: bool = False, flash: bool = False
                       ) -> Tuple[jnp.ndarray, Params]:
    """ONE-token decode with PER-ROW positions and a live-slot mask — the
    continuous-batching step (repro.serving). token: (B,1) int32; pos: (B,)
    int32 per-row absolute positions; live: (B,) bool. The cache is the
    engine's slot cache: ``{"layers": {"k","v"}}`` with fixed
    ``(B, max_seq)`` buffers and NO kpos (validity is ``t <= pos_b``).
    ``flash=True`` routes the attention contraction through the fused
    Pallas flash-decode kernel (identical cache writes, kernel softmax).
    Returns (logits (B,1,V), new cache). Attention-cached archs only."""
    assert cfg.arch_type in ("dense", "vlm", "moe"), \
        f"ragged decode needs an attention cache, not {cfg.arch_type}"
    scan = functools.partial(scan_apply, unroll=unroll)
    adt = dtype_of(cfg.activ_dtype)
    x = embed(params["embed"], token).astype(adt)
    if cfg.arch_type == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, adt)
    is_moe = cfg.arch_type == "moe"
    attn_fn = attn_mod.attention_decode_ragged_flash if flash \
        else attn_mod.attention_decode_ragged

    def body(h, xs):
        bp, cl = xs
        hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
        a, new_c = attn_fn(
            bp["attn"], hh, pos, cache=cl, live=live,
            use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
        h = h + a
        hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
        if is_moe:
            moe_fn = moe_mod.moe_ffn_sorted if cfg.moe.impl == "sort" \
                else moe_mod.moe_ffn
            y, _ = moe_fn(bp["moe"], hh, cfg.moe)
            if "shared" in bp:
                y = y + mlp(bp["shared"], hh, "silu")
            if "dense" in bp:
                y = y + mlp(bp["dense"], hh, "silu")
        else:
            y = mlp(bp["mlp"], hh, cfg.mlp_act)
        return h + y, new_c
    x, new_layers = scan(body, x, (params["blocks"], cache["layers"]))
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(head, x), {"layers": new_layers}


def prefill_chunk(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
                  off: jnp.ndarray, clen: jnp.ndarray, cache: Params,
                  unroll: bool = False, all_logits: bool = False
                  ) -> Tuple[jnp.ndarray, Params]:
    """One chunk of a CHUNKED ragged prefill into the serving engine's
    slot cache (docs/serving.md). tokens: (B,C) int32 — row b's valid
    tokens are ``tokens[b, :clen_b]``, occupying absolute positions
    ``[off_b, off_b + clen_b)`` of its slot row; cache: ``{"layers":
    {"k","v"}}`` with leading L axes over (B, T, ..) slot segments whose
    columns ``[0, off_b)`` were written by earlier chunks.

    Returns (per-row logits at the chunk's last VALID column (B,1,V),
    updated cache). The logits are only meaningful on a request's FINAL
    chunk (they are the next-token logits of the full prompt — bit-exact
    vs an unpadded single-shot prefill, the same argument as ragged
    ``prefill(lengths=)``); earlier chunks' logits are discarded by the
    engine. ``all_logits=True`` instead returns the WHOLE chunk's logits
    (B,C,V) — the speculative-verification shape, where every drafted
    position's next-token distribution is needed (final_norm and unembed
    are per-position maps, so column ``clen-1`` of the full output equals
    the default path's single column bit-for-bit). Attention-cached archs
    only, like every ragged path."""
    assert cfg.arch_type in ("dense", "moe"), \
        f"chunked prefill needs an attention slot cache, not {cfg.arch_type}"
    scan = functools.partial(scan_apply, unroll=unroll)
    adt = dtype_of(cfg.activ_dtype)
    x = embed(params["embed"], tokens).astype(adt)
    is_moe = cfg.arch_type == "moe"

    def body(h, xs):
        bp, cl = xs
        hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
        a, new_c = attn_mod.attention_prefill_chunk(
            bp["attn"], hh, off, clen, cache=cl,
            use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
        h = h + a
        hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
        if is_moe:
            moe_fn = moe_mod.moe_ffn_sorted if cfg.moe.impl == "sort" \
                else moe_mod.moe_ffn
            y, _ = moe_fn(bp["moe"], hh, cfg.moe)
            if "shared" in bp:
                y = y + mlp(bp["shared"], hh, "silu")
            if "dense" in bp:
                y = y + mlp(bp["dense"], hh, "silu")
        else:
            y = mlp(bp["mlp"], hh, cfg.mlp_act)
        return h + y, new_c
    x, new_layers = scan(body, x, (params["blocks"], cache["layers"]))
    if not all_logits:
        idx = jnp.clip(clen.astype(jnp.int32) - 1, 0, x.shape[1] - 1)
        x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(head, x), {"layers": new_layers}


# ---------------------------------------------------------------------------
# Paged KV cache views (repro.serving paged mode; docs/serving.md §8)
# ---------------------------------------------------------------------------
def gather_kv_pages(buf: jnp.ndarray, page_map: jnp.ndarray) -> jnp.ndarray:
    """Assemble per-row LINEAR cache views from a pooled page buffer.

    ``buf``: ``(L, n_pages, n_kv, page_size, head_dim)`` — ONE pool
    shared by every request; ``page_map``: ``(B, P)`` int32, row b's
    ordered page ids (entries ``>= n_pages`` mark unused tail pages —
    the gather CLAMPS them onto the last real page, whose junk is masked
    downstream exactly like a dense row's stale columns). Returns the
    ``(L, B, P*page_size, n_kv, head_dim)`` view that ``prefill_chunk``
    / ``decode_step_ragged`` consume unchanged — paging is invisible to
    the attention math, which is the whole bit-exactness argument."""
    L, N, K, ps, hd = buf.shape
    B, P = page_map.shape
    g = buf[:, jnp.clip(page_map, 0, N - 1)]          # (L, B, P, K, ps, hd)
    return g.transpose(0, 1, 2, 4, 3, 5).reshape(L, B, P * ps, K, hd)


def scatter_kv_pages(buf: jnp.ndarray, page_map: jnp.ndarray,
                     view: jnp.ndarray) -> jnp.ndarray:
    """Scatter a linear view back into the pooled page buffer. Entries of
    ``page_map`` at or beyond ``n_pages`` are OOB and the write DROPS —
    that single mechanism expresses every protection the pool needs:
    padding rows, unused tail pages, and FROZEN shared pages (the engine
    maps them all OOB in the write map, so copy-on-write needs no copy
    and no mask arithmetic inside the trace)."""
    L, N, K, ps, hd = buf.shape
    B, P = page_map.shape
    upd = view.reshape(L, B, P, ps, K, hd).transpose(0, 1, 2, 4, 3, 5)
    return buf.at[:, page_map].set(upd.astype(buf.dtype))


def prefill_chunk_paged(params: Params, cfg: ArchConfig,
                        tokens: jnp.ndarray, off: jnp.ndarray,
                        clen: jnp.ndarray, pool: Params,
                        rmap: jnp.ndarray, wmap: jnp.ndarray,
                        unroll: bool = False, all_logits: bool = False,
                        slots: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, Params]:
    """``prefill_chunk`` through a page table: gather each row's pages
    into a linear view (``rmap``), run the IDENTICAL chunk math, scatter
    the updated view back through ``wmap`` (frozen/shared/padding
    entries OOB -> dropped). With ``P*page_size == max_seq`` the inner
    program is the same as the dense engine's, so outputs are bit-exact
    vs the dense slot cache (tests/test_paging.py). A ``layer_types``
    model (``hybrid_prefill_chunk_paged``) also takes each row's
    ``slots``, whose carried state its Mamba layers continue."""
    if cfg.layer_types:
        assert not all_logits, "a recurrent state cannot verify drafts"
        return hybrid_prefill_chunk_paged(params, cfg, tokens, off, clen,
                                          pool, rmap, wmap, slots,
                                          unroll=unroll)
    view = {"layers": {n: gather_kv_pages(pool["layers"][n], rmap)
                       for n in ("k", "v")}}
    logits, view = prefill_chunk(params, cfg, tokens, off, clen, view,
                                 unroll=unroll, all_logits=all_logits)
    new = {n: scatter_kv_pages(pool["layers"][n], wmap, view["layers"][n])
           for n in ("k", "v")}
    return logits, {"layers": new}


def decode_step_ragged_paged(params: Params, cfg: ArchConfig,
                             token: jnp.ndarray, pos: jnp.ndarray,
                             pool: Params, live: jnp.ndarray,
                             rmap: jnp.ndarray, wmap: jnp.ndarray,
                             unroll: bool = False
                             ) -> Tuple[jnp.ndarray, Params]:
    """``decode_step_ragged`` through a page table (see
    ``prefill_chunk_paged``). One fixed ``(B, P)`` map shape keeps this a
    single trace regardless of how pages are laid out."""
    view = {"layers": {n: gather_kv_pages(pool["layers"][n], rmap)
                       for n in ("k", "v")}}
    logits, view = decode_step_ragged(params, cfg, token, pos, view, live,
                                      unroll=unroll)
    new = {n: scatter_kv_pages(pool["layers"][n], wmap, view["layers"][n])
           for n in ("k", "v")}
    return logits, {"layers": new}


def decode_step_ragged_paged_flash(params: Params, cfg: ArchConfig,
                                   token: jnp.ndarray, pos: jnp.ndarray,
                                   pool: Params, live: jnp.ndarray,
                                   rmap: jnp.ndarray, wmap: jnp.ndarray,
                                   unroll: bool = False
                                   ) -> Tuple[jnp.ndarray, Params]:
    """Ragged one-token decode reading the page pool DIRECTLY through
    the fused Pallas flash-decode kernel — the gather/scatter round trip
    of ``decode_step_ragged_paged`` disappears entirely: each layer's
    attention dereferences ``rmap`` inside the kernel and the new
    token's KV lands on one (page, offset) cell through ``wmap``. Same
    trace-shape contract (fixed ``(B, P)`` maps -> single trace). A
    ``layer_types`` model runs ``hybrid_decode_paged_flash``."""
    if cfg.layer_types:
        return hybrid_decode_paged_flash(params, cfg, token, pos, pool, live,
                                         rmap, wmap, unroll=unroll)
    assert cfg.arch_type in ("dense", "vlm", "moe"), \
        f"ragged decode needs an attention cache, not {cfg.arch_type}"
    scan = functools.partial(scan_apply, unroll=unroll)
    adt = dtype_of(cfg.activ_dtype)
    x = embed(params["embed"], token).astype(adt)
    if cfg.arch_type == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, adt)
    is_moe = cfg.arch_type == "moe"

    def body(h, xs):
        bp, cl = xs
        hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
        a, (nk, nv) = attn_mod.attention_decode_ragged_paged_flash(
            bp["attn"], hh, pos, kbuf=cl["k"], vbuf=cl["v"], live=live,
            rmap=rmap, wmap=wmap,
            use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
        h = h + a
        hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
        if is_moe:
            moe_fn = moe_mod.moe_ffn_sorted if cfg.moe.impl == "sort" \
                else moe_mod.moe_ffn
            y, _ = moe_fn(bp["moe"], hh, cfg.moe)
            if "shared" in bp:
                y = y + mlp(bp["shared"], hh, "silu")
            if "dense" in bp:
                y = y + mlp(bp["dense"], hh, "silu")
        else:
            y = mlp(bp["mlp"], hh, cfg.mlp_act)
        return h + y, {"k": nk, "v": nv}
    x, new_layers = scan(body, x, (params["blocks"], pool["layers"]))
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(head, x), {"layers": new_layers}


def prefill(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
            prefix: Optional[jnp.ndarray] = None,
            frames: Optional[jnp.ndarray] = None,
            cache_len: Optional[int] = None,
            unroll: bool = False,
            lengths: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Params]:
    """Forward over the prompt, building a decode cache of ``cache_len``
    slots (default: prompt + 64 so decode can continue immediately).
    Returns (last-token logits (B,1,V), cache).

    ``lengths`` (B,) int32 enables RAGGED prompts in one batch: row b's
    true prompt is ``tokens[b, :lengths[b]]`` and the rest is padding.
    Causal masking makes valid positions blind to the padded tail, so the
    returned logits are row b's ``lengths[b]-1`` column — for dense
    attention, exactly what an unpadded prefill would produce. MoE is
    exact only while expert capacity does not bind: per-row capacity
    ``ceil(S*k/E*cf)`` is computed from the PADDED length and the junk
    tail is routed too, so with a tight ``capacity_factor`` a padded row
    can drop tokens an unpadded run would keep (generous capacity — e.g.
    ``reduced()``'s 4.0 — sees no drops and stays exact). Only
    attention-cached archs support ragged prefill at all (an SSM/hybrid
    recurrent state would have consumed the padding); the padded tail's
    cache entries are overwritten by ragged decode before they can ever
    be attended (models/attention.py).
    """
    scan = functools.partial(scan_apply, unroll=unroll)
    if lengths is not None:
        assert cfg.arch_type in ("dense", "vlm", "moe"), \
            f"ragged prefill needs an attention cache, not {cfg.arch_type}"
    adt = dtype_of(cfg.activ_dtype)
    x = embed(params["embed"], tokens).astype(adt)
    if cfg.arch_type == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, adt)
        if prefix is not None:
            x = jnp.concatenate([prefix.astype(adt), x], axis=1)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    w = cfg.sliding_window
    if cache_len is None:
        cache_len = S + 64
    if w:
        assert S <= min(w, cache_len), \
            "sliding-window prefill longer than the window is unsupported " \
            "(decode-only shape); prefill chunking is a serving-layer feature"
    cache = init_decode_cache(cfg, B, cache_len)

    if cfg.arch_type in ("dense", "vlm", "moe"):
        is_moe = cfg.arch_type == "moe"

        def body(h, xs):
            bp, cl = xs
            hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
            a, new_c = attn_mod.attention_prefill(
                bp["attn"], hh, positions, cache=cl, window=w,
                use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
            h = h + a
            hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
            if is_moe:
                moe_fn = moe_mod.moe_ffn_sorted if cfg.moe.impl == "sort" \
                    else moe_mod.moe_ffn
                y, _ = moe_fn(bp["moe"], hh, cfg.moe)
                if "shared" in bp:
                    y = y + mlp(bp["shared"], hh, "silu")
                if "dense" in bp:
                    y = y + mlp(bp["dense"], hh, "silu")
            else:
                y = mlp(bp["mlp"], hh, cfg.mlp_act)
            return constrain_batch(h + y), new_c
        x, new_layers = scan(body, x, (params["blocks"],
                                               cache["layers"]))
        new_cache = {"layers": new_layers}

    elif cfg.arch_type == "ssm":
        def body(h, bp):
            hh = apply_norm(bp["ln"], h, cfg.norm_eps)
            y, st = ssm_mod.ssm_prefill(bp["ssm"], hh, cfg.d_model, cfg.ssm)
            return constrain_batch(h + y), st
        x, new_layers = scan(body, x, params["blocks"])
        new_cache = {"layers": new_layers}

    elif cfg.arch_type == "hybrid":
        def ssm_body(h, bp):
            hh = apply_norm(bp["ln"], h, cfg.norm_eps)
            y, st = ssm_mod.ssm_prefill(bp["ssm"], hh, cfg.d_model, cfg.ssm)
            return h + y, st

        def super_body(h, xs):
            sp, cl = xs
            h, new_ssm = scan(ssm_body, h, sp["ssm"])
            bp = sp["attn"]
            hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
            a, new_attn = attn_mod.attention_prefill(
                bp["attn"], hh, positions, cache=cl["attn"], window=w,
                use_rope=cfg.use_rope, rope_theta=cfg.rope_theta)
            h = h + a
            hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
            h = constrain_batch(h + mlp(bp["mlp"], hh, cfg.mlp_act))
            return h, {"ssm": new_ssm, "attn": new_attn}
        x, new_super = scan(super_body, x,
                                    (params["super"], cache["super"]))
        new_cache = {"super": new_super}
        if "tail" in cache:
            x, new_tail = scan(ssm_body, x, params["tail"])
            new_cache["tail"] = new_tail

    elif cfg.arch_type == "audio":
        assert frames is not None
        F = frames.shape[1]
        fpos = jnp.arange(F, dtype=jnp.int32)
        enc = frames.astype(adt) + sinusoidal_pos(fpos, cfg.d_model).astype(adt)

        def enc_body(h, bp):
            return apply_attn_block(bp, h, fpos, cfg, causal=False), None
        enc, _ = scan(enc_body, enc, params["encoder"])
        enc = apply_norm(params["enc_final_norm"], enc, cfg.norm_eps)
        x = x + sinusoidal_pos(positions, cfg.d_model).astype(adt)

        def body(h, xs):
            bp, cl = xs
            hh = apply_norm(bp["ln1"], h, cfg.norm_eps)
            a, new_c = attn_mod.attention_prefill(
                bp["self_attn"], hh, positions, cache=cl, window=w,
                use_rope=False)
            h = h + a
            hh = apply_norm(bp["ln2"], h, cfg.norm_eps)
            h = h + attn_mod.attention(
                bp["cross_attn"], hh, positions, causal=False, use_rope=False,
                xkv=enc, kv_positions=fpos)
            hh = apply_norm(bp["ln3"], h, cfg.norm_eps)
            return constrain_batch(h + mlp(bp["mlp"], hh, cfg.mlp_act)), new_c
        x, new_self = scan(body, x, (params["blocks"], cache["self"]))

        def xkv_body(_, bp):
            return None, attn_mod.cross_kv(bp["cross_attn"], enc)
        _, cross = scan(xkv_body, None, params["blocks"])
        new_cache = {"self": new_self, "cross": cross}
    else:
        raise ValueError(cfg.arch_type)

    if lengths is None:
        x = x[:, -1:, :]
    else:
        off = cfg.n_prefix_tokens if cfg.arch_type == "vlm" else 0
        idx = jnp.clip(lengths.astype(jnp.int32) + off - 1, 0, x.shape[1] - 1)
        x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(head, x), new_cache

"""Plain reference of the dense decoders the benchmark runs.

Written from the published description of a Qwen3-style decoder: RMSNorm
before attention and MLP, grouped-query attention with rotary embeddings
(half-split rotation), per-head RMSNorm of q and k where ``qk_norm`` is
set, SwiGLU MLP, final RMSNorm and an output head tied to the embedding.
Sizes come from the configuration file's keys; weights are read by name
from the tree the benchmark made. It imports nothing of the program.

Precision: ``"f32"`` computes in float32 with every matrix product at
``highest`` precision (the reference); ``"bf16"`` computes in bfloat16 at
the default precision, and ``"fp8"`` rounds both operands of every
weight matrix product to float8_e4m3fn (weights scaled per output
channel, activations per token) and computes the rest in float32 (the
controls).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

LAYER_KEYS = {
    "ln1": "blocks/ln1/scale", "ln2": "blocks/ln2/scale",
    "wq": "blocks/attn/wq", "wk": "blocks/attn/wk", "wv": "blocks/attn/wv",
    "wo": "blocks/attn/wo", "qn": "blocks/attn/q_norm/scale",
    "kn": "blocks/attn/k_norm/scale", "wg": "blocks/mlp/w_gate",
    "wu": "blocks/mlp/w_up", "wd": "blocks/mlp/w_down"}
MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
# the contraction axes of each weight matrix (after the layer axis); the
# rest are its output channels
IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1), "wg": (0,),
           "wu": (0,), "wd": (0,)}


def dtype_of(precision: str):
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def fp8_round(w: jnp.ndarray, in_axes) -> jnp.ndarray:
    """``w`` rounded to float8_e4m3fn, scaled per output channel."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def fp8_act(x: jnp.ndarray, precision: str) -> jnp.ndarray:
    """Activations entering a weight product: rounded to float8_e4m3fn
    (scaled per token) in the fp8 control, unchanged otherwise."""
    return fp8_round(x, (-1,)) if precision == "fp8" else x


def prep(lp: Dict[str, jnp.ndarray], precision: str) -> dict:
    dt = dtype_of(precision)
    out = {}
    for k, v in lp.items():
        if precision == "fp8" and k in MATRICES:
            out[k] = fp8_round(v, IN_AXES[k])
        else:
            out[k] = v.astype(dt)
    return out


def rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """x (N, T, H, hd), pos (T,)"""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def block(x, lp: dict, c: dict, precision: str = "f32"):
    """One decoder layer over x (N, T, d), causal, positions 0..T-1."""
    eps = c["rms_norm_eps"]
    N, T, _ = x.shape
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["head_dim"]
    pos = jnp.arange(T)
    h = fp8_act(rms(x, lp["ln1"], eps), precision)
    q = jnp.einsum("ntd,dhk->nthk", h, lp["wq"])
    k = jnp.einsum("ntd,dhk->nthk", h, lp["wk"])
    v = jnp.einsum("ntd,dhk->nthk", h, lp["wv"])
    if c.get("qk_norm"):
        q = rms(q, lp["qn"], eps)
        k = rms(k, lp["kn"], eps)
    q = rope(q, pos, c["rope_theta"]).reshape(N, T, K, H // K, hd)
    k = rope(k, pos, c["rope_theta"])
    s = jnp.einsum("ntkgd,nskd->nkgts", q, k).astype(jnp.float32)
    s = s / math.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("nkgts,nskd->ntkgd", p, v).reshape(N, T, H, hd)
    x = x + jnp.einsum("nthk,hkd->ntd", fp8_act(o, precision), lp["wo"])
    h = fp8_act(rms(x, lp["ln2"], eps), precision)
    u = jax.nn.silu(h @ lp["wg"]) * (h @ lp["wu"])
    return x + fp8_act(u, precision) @ lp["wd"]


def layer_params(w: Dict[str, jnp.ndarray], c: dict, i=None) -> dict:
    keys = dict(LAYER_KEYS)
    if not c.get("qk_norm"):
        keys.pop("qn"), keys.pop("kn")
    return {k: (w[n] if i is None else w[n][i]) for k, n in keys.items()}


def head(w: Dict[str, jnp.ndarray], c: dict) -> jnp.ndarray:
    return w["embed"] if c["tie_word_embeddings"] else w["lm_head"]


def matmul_precision(precision: str) -> str:
    return "highest" if precision == "f32" else "default"


# ---------------------------------------------------------------------------
# serving: logits at chosen positions, one layer at a time
# ---------------------------------------------------------------------------
def logits_at(w: Dict[str, jnp.ndarray], c: dict, tokens: jnp.ndarray,
              rows: jnp.ndarray, cols: jnp.ndarray,
              precision: str = "f32") -> jnp.ndarray:
    """Logits (M, V) of sequences ``tokens`` (N, T) at positions
    ``(rows[m], cols[m])``. Layers run one at a time, each cast (or
    rounded) from the served weights on its own, so the whole model is
    never held in float32."""
    mp = matmul_precision(precision)
    dt = dtype_of(precision)

    @jax.jit
    def embed(tok, table):
        return jnp.take(table.astype(dt), tok, axis=0)

    @jax.jit
    def layer(x, lp):
        with jax.default_matmul_precision(mp):
            return block(x, prep(lp, precision), c, precision)

    @jax.jit
    def out(x, norm, table, rows, cols):
        with jax.default_matmul_precision(mp):
            h = fp8_act(rms(x[rows, cols], norm.astype(dt),
                            c["rms_norm_eps"]), precision)
            t = (fp8_round(table, (1,)) if precision == "fp8"
                 else table.astype(dt))
            return jnp.einsum("md,vd->mv", h, t).astype(jnp.float32)

    x = embed(tokens, w["embed"])
    for i in range(c["num_hidden_layers"]):
        x = layer(x, layer_params(w, c, i))
    return out(x, w["final_norm/scale"], head(w, c), rows, cols)


# ---------------------------------------------------------------------------
# training: the loss, its gradient, and AdaGrad
# ---------------------------------------------------------------------------
def nll_sum(params: Dict[str, jnp.ndarray], c: dict, tokens, labels):
    """Summed next-token negative log-likelihood over a block of rows."""
    x = jnp.take(params["embed"], tokens, axis=0)
    stacked = layer_params(params, c)

    def body(x, lp):
        return block(x, lp, c), None

    x, _ = jax.lax.scan(body, x, stacked)
    h = rms(x, params["final_norm/scale"], c["rms_norm_eps"])
    logits = jnp.einsum("ntd,vd->ntv", h, head(params, c)).astype(
        jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


def train_trajectory(p0: Dict[str, jnp.ndarray], c: dict, batches,
                     lr: float, eps: float, init_accum: float,
                     block_rows: int, precision: str = "f32"):
    """AdaGrad steps over ``batches`` from ``p0``, the mean loss over all
    tokens of each batch. Returns (losses, first gradient's norm per leaf,
    norm of each leaf's change after the last step). The first gradient
    is read back from the accumulator after step 1, less ``init_accum``,
    as the program's is, so that both carry the same rounding."""
    dt = dtype_of(precision)
    mp = matmul_precision(precision)
    params = {k: v.astype(dt) for k, v in p0.items()}

    @jax.jit
    def grad_block(params, tok, lab):
        with jax.default_matmul_precision(mp):
            return jax.value_and_grad(nll_sum)(params, c, tok, lab)

    @jax.jit
    def update(params, acc, g):
        g = {k: v.astype(jnp.float32) for k, v in g.items()}
        acc = {k: acc[k] + g[k] * g[k] for k in params}
        new = {k: params[k] - (lr * g[k] / (jnp.sqrt(acc[k]) + eps)
                               ).astype(dt) for k in params}
        return new, acc

    # the accumulator stays float32 in every precision, as the program's
    acc = {k: jnp.full(v.shape, init_accum, jnp.float32)
           for k, v in params.items()}
    losses, g1 = [], None
    for b in batches:
        tok, lab = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
        rows = tok.shape[0]
        total, grads = 0.0, None
        for r in range(0, rows, block_rows):
            l, g = grad_block(params, tok[r:r + block_rows],
                              lab[r:r + block_rows])
            total += float(l)
            grads = g if grads is None else {k: grads[k] + g[k] for k in g}
        count = float(tok.size)
        grads = {k: (v / count).astype(dt) for k, v in grads.items()}
        losses.append(total / count)
        params, acc = update(params, acc, grads)
        if g1 is None:
            g1 = leaf_norms(jax.tree.map(
                lambda a: jnp.sqrt(jnp.maximum(a - init_accum, 0)), acc))
    change = leaf_norms({k: params[k].astype(jnp.float32)
                         - p0[k].astype(jnp.float32) for k in params})
    return losses, g1, change


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def leaf_norms(tree: Dict[str, jnp.ndarray]) -> Dict[str, float]:
    """{leaf name: its float32 2-norm}."""
    return {k: float(v) for k, v in _norms(tree).items()}

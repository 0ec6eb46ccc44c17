"""Plain reference of a hybrid Mamba2 / attention decoder
(``granitemoehybrid`` with no experts: Granite-4.0-H-Micro).

Written from the published description (the Hugging Face
``GraniteMoeHybrid`` decoder and the Mamba2 paper, arXiv:2405.21060):

    x = embedding_multiplier * E[tok]
    h = x + residual_multiplier * mixer(RMSNorm(x))
    x = h + residual_multiplier * MLP(RMSNorm(h))        (SwiGLU)
    logits = RMSNorm(x) . E^T / logits_scaling

The mixer is the layer's ``layer_types`` entry. ``attention``: grouped
query attention with no positional encoding (``position_embedding_type``
nope), causal, softmax scale ``attention_multiplier``. ``mamba``:
in_proj -> [z | xBC | dt]; a causal depthwise conv of width
``mamba_d_conv`` with bias over xBC, then SiLU; xBC -> [x | B | C] (one
group); dt = softplus(dt + dt_bias), A = -exp(A_log); the recurrence
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t, run
step by step from a zero state (not the chunked form); then the gated
RMSNorm of y * SiLU(z) and out_proj.

Sizes come from the configuration file's keys; weights are read by name
from the tree the benchmark made, in the program's layout (one stack per
layer kind, layer i of a kind at index i; the conv weight (W, channels)
with its last tap on the current token). It imports nothing of the
program.

Departures from the published description: none. The float32 state and
the order of the recurrence are the description's own; the conv ring,
the chunking and the page pool are the program's and do not appear here.

Precision: as ``refmodel``: ``"f32"`` in float32 at ``highest`` matrix
precision (the reference), ``"fp8"`` with every weight matrix product's
operands rounded to float8_e4m3fn (the control); the recurrence runs in
float32 in both.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.refmodel import (dtype_of, fp8_act, fp8_round,
                                matmul_precision, rms)

MAMBA_KEYS = {
    "ln1": "ln1/scale", "in_proj": "ssm/in_proj", "conv_w": "ssm/conv_w",
    "conv_b": "ssm/conv_b", "A_log": "ssm/A_log", "D": "ssm/D",
    "dt_bias": "ssm/dt_bias", "gate": "ssm/gate_norm/scale",
    "out_proj": "ssm/out_proj", "ln2": "ln2/scale", "wg": "mlp/w_gate",
    "wu": "mlp/w_up", "wd": "mlp/w_down"}
ATTN_KEYS = {
    "ln1": "ln1/scale", "wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv",
    "wo": "attn/wo", "ln2": "ln2/scale", "wg": "mlp/w_gate",
    "wu": "mlp/w_up", "wd": "mlp/w_down"}
STACK = {"mamba": "blocks/mamba/", "attention": "blocks/attn/"}
KEYS = {"mamba": MAMBA_KEYS, "attention": ATTN_KEYS}
# the contraction axes of each weight matrix; the rest are its outputs
IN_AXES = {"in_proj": (0,), "out_proj": (0,), "wq": (0,), "wk": (0,),
           "wv": (0,), "wo": (0, 1), "wg": (0,), "wu": (0,), "wd": (0,)}
# kept in float32 in every precision: the recurrence's own parameters
STATE_PARAMS = ("A_log", "D", "dt_bias")


def prep(lp: Dict[str, jnp.ndarray], precision: str) -> dict:
    dt = dtype_of(precision)
    out = {}
    for k, v in lp.items():
        if precision == "fp8" and k in IN_AXES:
            out[k] = fp8_round(v, IN_AXES[k])
        elif k in STATE_PARAMS:
            out[k] = v.astype(jnp.float32)
        else:
            out[k] = v.astype(dt)
    return out


def layer_params(w: Dict[str, jnp.ndarray], c: dict, i: int) -> dict:
    """(kind, weights) of layer ``i`` of ``c["layer_types"]``."""
    kind = c["layer_types"][i]
    j = c["layer_types"][:i].count(kind)
    return kind, {k: w[STACK[kind] + n][j] for k, n in KEYS[kind].items()}


def swiglu(h, lp, precision):
    u = jax.nn.silu(h @ lp["wg"]) * (h @ lp["wu"])
    return fp8_act(u, precision) @ lp["wd"]


def mamba(x, lp: dict, c: dict, precision: str = "f32"):
    """The Mamba2 mixer over normalized x (N, T, d)."""
    N, T, _ = x.shape
    nh, hd = c["mamba_n_heads"], c["mamba_d_head"]
    ds, W = c["mamba_d_state"], c["mamba_d_conv"]
    di = nh * hd
    proj = fp8_act(x, precision) @ lp["in_proj"]
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * ds], \
        proj[..., 2 * di + 2 * ds:]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + T] * lp["conv_w"][i] for i in range(W)) \
        + lp["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :di].reshape(N, T, nh, hd).astype(jnp.float32)
    b = xbc[..., di:di + ds].astype(jnp.float32)
    cm = xbc[..., di + ds:].astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    a = -jnp.exp(lp["A_log"])

    def step(h, inp):
        xt, dtt, bt, ct = inp          # (N,nh,hd) (N,nh) (N,ds) (N,ds)
        h = h * jnp.exp(dtt * a)[..., None, None] + jnp.einsum(
            "nh,nhp,nd->nhpd", dtt, xt, bt)
        return h, jnp.einsum("nhpd,nd->nhp", h, ct)

    h0 = jnp.zeros((N, nh, hd, ds), jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (xs, dt, b, cm)))
    y = jnp.moveaxis(ys, 0, 1) + lp["D"][:, None] * xs
    y = y.reshape(N, T, di).astype(x.dtype) * jax.nn.silu(z)
    y = rms(y, lp["gate"], c["rms_norm_eps"])
    return fp8_act(y, precision) @ lp["out_proj"]


def attention(x, lp: dict, c: dict, precision: str = "f32",
              block: int = 256):
    """Causal NoPE grouped-query attention over normalized x (N, T, d),
    queries in blocks of ``block`` (only to bound the score matrix)."""
    N, T, _ = x.shape
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    h = fp8_act(x, precision)
    q = jnp.einsum("ntd,dhk->nthk", h, lp["wq"]).reshape(N, T, K, H // K,
                                                         hd)
    k = jnp.einsum("ntd,dhk->nthk", h, lp["wk"])
    v = jnp.einsum("ntd,dhk->nthk", h, lp["wv"])
    kpos = jnp.arange(T)
    outs = []
    for s in range(0, T, block):
        qb = q[:, s:s + block]
        sc = jnp.einsum("ntkgd,nskd->nkgts", qb, k).astype(jnp.float32)
        sc = sc * c["attention_multiplier"]
        causal = kpos[None, :] <= (s + jnp.arange(qb.shape[1]))[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("nkgts,nskd->ntkgd", p.astype(x.dtype), v))
    o = jnp.concatenate(outs, axis=1).reshape(N, T, H, hd)
    return jnp.einsum("nthk,hkd->ntd", fp8_act(o, precision), lp["wo"])


def layer(x, kind: str, lp: dict, c: dict, precision: str = "f32"):
    r = c["residual_multiplier"]
    eps = c["rms_norm_eps"]
    mixer = mamba if kind == "mamba" else attention
    x = x + r * mixer(rms(x, lp["ln1"], eps), lp, c, precision)
    h = fp8_act(rms(x, lp["ln2"], eps), precision)
    return x + r * swiglu(h, lp, precision)


# ---------------------------------------------------------------------------
def logits_at(w: Dict[str, jnp.ndarray], c: dict, tokens: jnp.ndarray,
              rows: jnp.ndarray, cols: jnp.ndarray,
              precision: str = "f32") -> jnp.ndarray:
    """Logits (M, V) of sequences ``tokens`` (N, T) at positions
    ``(rows[m], cols[m])``, one layer at a time, each cast (or rounded)
    from the served weights on its own."""
    mp = matmul_precision(precision)
    dt = dtype_of(precision)

    @jax.jit
    def embed(tok, table):
        return jnp.take(table.astype(dt), tok, axis=0) \
            * c["embedding_multiplier"]

    def run(kind):
        @jax.jit
        def fn(x, lp):
            with jax.default_matmul_precision(mp):
                return layer(x, kind, prep(lp, precision), c, precision)
        return fn

    fns = {k: run(k) for k in ("mamba", "attention")}

    @jax.jit
    def out(x, norm, table, rows, cols):
        with jax.default_matmul_precision(mp):
            h = fp8_act(rms(x[rows, cols], norm.astype(dt),
                            c["rms_norm_eps"]), precision)
            t = (fp8_round(table, (1,)) if precision == "fp8"
                 else table.astype(dt))
            return jnp.einsum("md,vd->mv", h, t).astype(jnp.float32) \
                / c["logits_scaling"]

    x = embed(tokens, w["embed"])
    for i in range(c["num_hidden_layers"]):
        kind, lp = layer_params(w, c, i)
        x = fns[kind](x, lp)
    return out(x, w["final_norm/scale"], w["embed"], rows, cols)


def logits(w: Dict[str, jnp.ndarray], c: dict, tokens: jnp.ndarray,
           precision: str = "f32") -> jnp.ndarray:
    """Logits (N, T, V) at every position of ``tokens`` (N, T)."""
    N, T = tokens.shape
    rows = jnp.repeat(jnp.arange(N), T)
    cols = jnp.tile(jnp.arange(T), N)
    return logits_at(w, c, tokens, rows, cols, precision).reshape(N, T, -1)

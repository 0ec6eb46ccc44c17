"""Mamba2 (state-space duality) blocks.

Training/prefill uses the chunked SSD algorithm [arXiv:2405.21060 §6]:
intra-chunk quadratic attention-like einsums (MXU-friendly) plus an
inter-chunk recurrence over per-chunk states. Decode carries an O(1)
recurrent state (B, nh, hd, N) + a (d_conv-1)-deep conv ring — this is why
SSM archs run ``long_500k`` natively.

Shapes: d_inner = expand*d_model, nh = d_inner/head_dim, N = d_state,
groups g=1 (B/C shared across heads).

in_proj packs [z (di) | x (di) | B (g*N) | C (g*N) | dt (nh)];
x,B,C pass through a causal depthwise conv (width d_conv) + SiLU.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.layers import rmsnorm

Params = Dict[str, Any]


def init_ssm(key, d: int, cfg: SSMConfig, dtype=jnp.float32) -> Params:
    di = cfg.d_inner(d)
    nh = cfg.n_heads(d)
    g = cfg.n_groups
    conv_dim = di + 2 * g * cfg.d_state
    proj_out = 2 * di + 2 * g * cfg.d_state + nh
    ks = jax.random.split(key, 4)
    return {
        "in_proj": (jax.random.normal(ks[0], (d, proj_out)) * d ** -0.5).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.d_conv, conv_dim))
                   * cfg.d_conv ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "gate_norm": {"scale": jnp.ones((di,), dtype)},
        "out_proj": (jax.random.normal(ks[2], (di, d)) * di ** -0.5).astype(dtype),
    }


def _split_proj(proj: jnp.ndarray, d: int, cfg: SSMConfig):
    di = cfg.d_inner(d)
    gN = cfg.n_groups * cfg.d_state
    z = proj[..., :di]
    xc = proj[..., di:di + di + 2 * gN]      # conv input: [x|B|C]
    dt = proj[..., di + di + 2 * gN:]
    return z, xc, dt


def causal_conv(w: jnp.ndarray, b: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv. w: (W, C), x: (B, S, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    S = x.shape[1]
    out = sum(xp[:, i:i + S, :] * w[i] for i in range(W))
    return out + b


def _xbc_split(xc: jnp.ndarray, d: int, cfg: SSMConfig):
    di = cfg.d_inner(d)
    gN = cfg.n_groups * cfg.d_state
    nh = cfg.n_heads(d)
    xs = xc[..., :di]
    Bm = xc[..., di:di + gN]
    Cm = xc[..., di + gN:]
    shp = xs.shape[:-1]
    xs = xs.reshape(*shp, nh, cfg.head_dim)
    Bm = Bm.reshape(*shp, cfg.n_groups, cfg.d_state)
    Cm = Cm.reshape(*shp, cfg.n_groups, cfg.d_state)
    return xs, Bm, Cm


# ---------------------------------------------------------------------------
# Core SSD — chunked (training) and sequential (oracle / decode)
# ---------------------------------------------------------------------------
def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, h0=None):
    """x:(B,S,nh,hd) dt:(B,S,nh) A:(nh,) Bm/Cm:(B,S,g,N), g==1.

    Returns (y (B,S,nh,hd), h_final (B,nh,hd,N)). All math float32.
    """
    Bsz, S, nh, hd = x.shape
    N = Bm.shape[-1]
    S_orig = S
    if S % chunk:
        # pad with dt=0 steps: zero decay-delta and zero input contribution,
        # so the final state and real-position outputs are unaffected.
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc, Q = S // chunk, chunk
    f32 = jnp.float32
    x = x.astype(f32)
    dt = dt.astype(f32)
    Bm = Bm[..., 0, :].astype(f32)           # (B,S,N) g=1
    Cm = Cm[..., 0, :].astype(f32)

    xc = x.reshape(Bsz, nc, Q, nh, hd)
    dtc = dt.reshape(Bsz, nc, Q, nh)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    dA = dtc * A                              # (B,nc,Q,nh), <= 0
    cum = jnp.cumsum(dA, axis=2)              # (B,nc,Q,nh)

    # --- intra-chunk (quadratic, MXU) ---
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)              # (B,nc,Q,Q)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # (B,nc,Q,Q,nh)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # clamp BEFORE exp: masked (i<j) entries have seg>0 and exp can
    # overflow to inf; where(inf*0) NaNs the backward pass
    seg = jnp.minimum(seg, 0.0)
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    xdt = xc * dtc[..., None]                               # (B,nc,Q,nh,hd)
    y_intra = jnp.einsum("bcij,bcijh,bcjhp->bcihp", CB, L, xdt)

    # --- per-chunk input state ---
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)         # (B,nc,Q,nh)
    S_c = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", Bc, dtc * decay_to_end, xc)

    # --- inter-chunk recurrence ---
    gamma = jnp.exp(cum[:, :, -1, :])                       # (B,nc,nh)
    h_init = jnp.zeros((Bsz, nh, hd, N), f32) if h0 is None else h0.astype(f32)

    def step(h, inp):
        g_c, s_c = inp                                      # (B,nh), (B,nh,hd,N)
        h_out = h                                           # state entering chunk
        h_next = h * g_c[:, :, None, None] + s_c
        return h_next, h_out

    gamma_t = jnp.moveaxis(gamma, 1, 0)                     # (nc,B,nh)
    S_t = jnp.moveaxis(S_c, 1, 0)                           # (nc,B,nh,hd,N)
    h_final, h_starts = jax.lax.scan(step, h_init, (gamma_t, S_t))
    h_starts = jnp.moveaxis(h_starts, 0, 1)                 # (B,nc,nh,hd,N)

    y_inter = jnp.einsum("bcin,bchi,bchpn->bcihp",
                         Cc, jnp.moveaxis(jnp.exp(cum), 2, 3), h_starts)
    y = (y_intra + y_inter).reshape(Bsz, S, nh, hd)
    return y[:, :S_orig], h_final


def ssd_sequential(x, dt, A, Bm, Cm, h0=None):
    """Oracle: step-by-step recurrence. Same signature/returns as chunked."""
    Bsz, S, nh, hd = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    Bm = Bm[..., 0, :].astype(f32)
    Cm = Cm[..., 0, :].astype(f32)
    h = jnp.zeros((Bsz, nh, hd, N), f32) if h0 is None else h0.astype(f32)

    def step(h, inp):
        xt, dtt, bt, ct = inp                # (B,nh,hd),(B,nh),(B,N),(B,N)
        decay = jnp.exp(dtt * A)             # (B,nh)
        h = h * decay[:, :, None, None] + jnp.einsum(
            "bh,bhp,bn->bhpn", dtt, xt, bt)
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    h, ys = jax.lax.scan(step, h, xs)
    return jnp.moveaxis(ys, 0, 1), h


# ---------------------------------------------------------------------------
# Block-level entry points
# ---------------------------------------------------------------------------
def ssm_states_spec(batch: int, d: int, cfg: SSMConfig, dtype=jnp.float32):
    nh, hd = cfg.n_heads(d), cfg.head_dim
    conv_dim = cfg.d_inner(d) + 2 * cfg.n_groups * cfg.d_state
    sd = jax.ShapeDtypeStruct
    return {"h": sd((batch, nh, hd, cfg.d_state), jnp.float32),
            "conv": sd((batch, cfg.d_conv - 1, conv_dim), dtype)}


def init_ssm_state(batch: int, d: int, cfg: SSMConfig, dtype=jnp.float32):
    spec = ssm_states_spec(batch, d, cfg, dtype)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def _pre_core(p: Params, proj: jnp.ndarray, conv_out: jnp.ndarray,
              d: int, cfg: SSMConfig):
    z, _, dt_raw = _split_proj(proj, d, cfg)
    xs, Bm, Cm = _xbc_split(jax.nn.silu(conv_out), d, cfg)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    return z, xs, Bm, Cm, dt, A


def ssm_forward(p: Params, x: jnp.ndarray, d: int, cfg: SSMConfig,
                sequential: bool = False) -> jnp.ndarray:
    """Training-path full-sequence forward (no state I/O). x: (B,S,d)."""
    proj = x @ p["in_proj"]
    _, xc, _ = _split_proj(proj, d, cfg)
    conv_out = causal_conv(p["conv_w"], p["conv_b"], xc)
    z, xs, Bm, Cm, dt, A = _pre_core(p, proj, conv_out, d, cfg)
    core = ssd_sequential if sequential else \
        (lambda *a: ssd_chunked(*a, chunk=cfg.chunk))
    y, _ = core(xs, dt, A, Bm, Cm)
    y = y + p["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(*x.shape[:-1], -1).astype(x.dtype)
    y = rmsnorm(p["gate_norm"], y * jax.nn.silu(z))
    return y @ p["out_proj"]


def ssm_prefill(p: Params, x: jnp.ndarray, d: int, cfg: SSMConfig
                ) -> Tuple[jnp.ndarray, Params]:
    """Forward + emit decode state {h, conv}."""
    proj = x @ p["in_proj"]
    _, xc, _ = _split_proj(proj, d, cfg)
    conv_out = causal_conv(p["conv_w"], p["conv_b"], xc)
    z, xs, Bm, Cm, dt, A = _pre_core(p, proj, conv_out, d, cfg)
    y, h = ssd_chunked(xs, dt, A, Bm, Cm, chunk=cfg.chunk)
    y = y + p["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(*x.shape[:-1], -1).astype(x.dtype)
    y = rmsnorm(p["gate_norm"], y * jax.nn.silu(z))
    conv_tail = xc[:, -(cfg.d_conv - 1):, :]
    return y @ p["out_proj"], {"h": h, "conv": conv_tail}


def ssm_prefill_chunk(p: Params, x: jnp.ndarray, clen: jnp.ndarray,
                      state: Params, d: int, cfg: SSMConfig
                      ) -> Tuple[jnp.ndarray, Params]:
    """One chunk of a chunked ragged prefill, from each row's carried
    state. x: (B,C,d), row b's valid tokens ``x[b, :clen_b]`` continuing
    the sequence whose state is ``state`` ({h (B,nh,hd,N) f32, conv
    (B,W-1,conv_dim)}: the last W-1 conv inputs, zero for a fresh row).

    The conv runs over the carried ring followed by the chunk; the SSD
    starts from ``h``. Padding columns (``t >= clen_b``) get ``dt = 0``
    (no decay, no input), so the returned state is the state after
    column ``clen_b - 1``, and the returned ring is the last W-1 conv
    inputs up to that column; a row with ``clen_b == 0`` keeps its
    state. Returns (y (B,C,d), new state)."""
    C = x.shape[1]
    W = cfg.d_conv
    proj = x @ p["in_proj"]
    _, xc, _ = _split_proj(proj, d, cfg)
    hist = jnp.concatenate([state["conv"].astype(xc.dtype), xc], axis=1)
    conv_out = sum(hist[:, i:i + C, :] * p["conv_w"][i]
                   for i in range(W)) + p["conv_b"]
    z, xs, Bm, Cm, dt, A = _pre_core(p, proj, conv_out, d, cfg)
    valid = jnp.arange(C)[None, :] < clen[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    y, h = ssd_chunked(xs, dt, A, Bm, Cm, chunk=min(cfg.chunk, C),
                       h0=state["h"])
    y = y + p["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(*x.shape[:-1], -1).astype(x.dtype)
    y = rmsnorm(p["gate_norm"], y * jax.nn.silu(z))
    ring = jax.vmap(lambda hr, c: jax.lax.dynamic_slice_in_dim(
        hr, c, W - 1, axis=0))(hist, clen.astype(jnp.int32))
    return y @ p["out_proj"], {"h": h, "conv": ring.astype(
        state["conv"].dtype)}


def keep_live(live: jnp.ndarray, new: Params, old: Params) -> Params:
    """``new`` state on live rows, ``old`` on the rest (B leading)."""
    def pick(n, o):
        m = live.reshape(live.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(pick, new, old)


def ssm_decode(p: Params, x: jnp.ndarray, state: Params, d: int,
               cfg: SSMConfig, live: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, Params]:
    """One-token decode. x: (B,1,d); state: {h (B,nh,hd,N), conv (B,W-1,C)}.
    With ``live`` (B,) bool, rows not live keep their state."""
    proj = x @ p["in_proj"]                                 # (B,1,P)
    _, xc, _ = _split_proj(proj, d, cfg)                    # (B,1,C)
    hist = jnp.concatenate([state["conv"], xc], axis=1)     # (B,W,C)
    conv_out = jnp.einsum("bwc,wc->bc", hist, p["conv_w"]) + p["conv_b"]
    conv_out = conv_out[:, None, :]
    z, xs, Bm, Cm, dt, A = _pre_core(p, proj, conv_out, d, cfg)
    xt, dtt = xs[:, 0], dt[:, 0]                            # (B,nh,hd),(B,nh)
    bt, ct = Bm[:, 0, 0, :], Cm[:, 0, 0, :]                 # (B,N)
    decay = jnp.exp(dtt * A)
    h = state["h"] * decay[:, :, None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dtt, xt.astype(jnp.float32), bt.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", h, ct.astype(jnp.float32))
    y = y + p["D"][:, None] * xt.astype(jnp.float32)
    y = y.reshape(x.shape[0], 1, -1).astype(x.dtype)
    y = rmsnorm(p["gate_norm"], y * jax.nn.silu(z))
    new_state = {"h": h, "conv": hist[:, 1:, :]}
    if live is not None:
        new_state = keep_live(live, new_state, state)
    return y @ p["out_proj"], new_state

"""Operations and bytes of a hybrid Mamba2 / attention decoder, from the
configuration file's published sizes alone (the rules of ``flops.py``: a
multiply-add is 2 operations, attention counts the causal half).

A Mamba2 layer counts in_proj and out_proj, the depthwise conv, and the
recurrence per token: the state's decay and its input outer product
(2 multiply-adds a state element) and the read-out C h (1). The gated
norm, the SiLUs and softplus are not counted, nor are norms anywhere.
"""
from __future__ import annotations

from dataclasses import dataclass

from chipbench.flops import causal_keys


@dataclass(frozen=True)
class Hybrid:
    mamba_layers: int
    attn_layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    d_conv: int
    groups: int
    act_bytes: int = 2              # bf16 weights, activations, conv ring
    state_bytes: int = 4            # the h state is float32

    @classmethod
    def of(cls, c: dict) -> "Hybrid":
        types = c["layer_types"]
        return cls(types.count("mamba"), types.count("attention"),
                   c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"],
                   c["hidden_size"] // c["num_attention_heads"],
                   c["intermediate_size"], c["vocab_size"],
                   c["mamba_n_heads"], c["mamba_d_head"],
                   c["mamba_d_state"], c["mamba_d_conv"],
                   c["mamba_n_groups"])

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.d_state

    @property
    def in_proj(self) -> int:
        return self.d * (self.d_inner + self.conv_dim + self.ssm_heads)

    @property
    def mixer_params(self) -> int:
        """One Mamba2 mixer with its pre-norm: in_proj, conv weights and
        bias, A_log, D, dt_bias, the gate norm, out_proj."""
        return (self.in_proj + (self.d_conv + 1) * self.conv_dim
                + 3 * self.ssm_heads + self.d_inner
                + self.d_inner * self.d + self.d)

    @property
    def attn_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (2 * q + 2 * kv) + self.d

    @property
    def mlp_params(self) -> int:
        return 3 * self.d * self.ff + self.d

    @property
    def params(self) -> int:
        return (self.mamba_layers * (self.mixer_params + self.mlp_params)
                + self.attn_layers * (self.attn_params + self.mlp_params)
                + self.vocab * self.d + self.d)

    @property
    def state_elems(self) -> int:
        return self.ssm_heads * self.ssm_head_dim * self.d_state

    def token_flops(self) -> float:
        """Every layer's operations for one token, but attention's scores
        and mixing (``attn_flops``) and the output head."""
        mamba = (2.0 * (self.in_proj + self.d_inner * self.d)
                 + 2.0 * self.d_conv * self.conv_dim
                 + 6.0 * self.state_elems)
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        attn = 2.0 * self.d * (2 * q + 2 * kv)
        mlp = 6.0 * self.d * self.ff
        return (self.mamba_layers * (mamba + mlp)
                + self.attn_layers * (attn + mlp))

    def attn_flops(self, keys: float) -> float:
        """QK^T and PV over ``keys`` keys in all, every attention layer."""
        return 4.0 * self.attn_layers * self.heads * self.head_dim * keys

    def state_row_bytes(self) -> float:
        """One slot's state in one Mamba layer: h and the conv ring."""
        return (self.state_elems * self.state_bytes
                + (self.d_conv - 1) * self.conv_dim * self.act_bytes)


def forward_flops(m: Hybrid, tokens: int, key_sum: float,
                  logits: int) -> float:
    return (m.token_flops() * tokens + m.attn_flops(key_sum)
            + 2.0 * m.d * m.vocab * logits)


def serve_request_flops(m: Hybrid, prompt: int, out: int) -> float:
    """One request: its prompt and the ``out - 1`` tokens fed back, with
    logits at the ``out`` positions that produce served tokens."""
    n = prompt + out - 1
    return forward_flops(m, n, causal_keys(n), out)


def ssm_decode_bytes(m: Hybrid, live_rows: int) -> float:
    """Bytes one decode dispatch's Mamba mixers need: their weights once
    (A_log, D and dt_bias float32, the rest bf16) and the h state and
    conv ring of ``live_rows`` rows, read and written, every layer."""
    f32 = 3 * m.ssm_heads
    weights = (m.mixer_params - f32) * m.act_bytes + f32 * 4
    return m.mamba_layers * (weights + 2.0 * live_rows * m.state_row_bytes())

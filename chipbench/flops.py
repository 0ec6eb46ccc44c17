"""Operations and bytes the algorithms need, from shapes alone.

Counted from the configuration file's published sizes, never from the
program's padding, buckets or page sizes. A multiply-add is 2 operations.
Attention counts the causal half: a token at position p attends p + 1 keys.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dense:
    """A dense decoder's sizes, read from a configuration file."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qk_norm: bool = False

    @classmethod
    def of(cls, c: dict) -> "Dense":
        return cls(c["num_hidden_layers"], c["hidden_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"], c["intermediate_size"], c["vocab_size"],
                   c["tie_word_embeddings"], c.get("qk_norm", False))

    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (2 * q + 2 * kv) + 3 * self.d * self.ff

    @property
    def params(self) -> int:
        norms = 2 * self.d + 2 * self.head_dim * int(self.qk_norm)
        emb = self.vocab * self.d * (1 if self.tied else 2)
        return self.layers * (self.layer_matmul_params + norms) + emb \
            + self.d


def attn_flops(m: Dense, keys: int) -> float:
    """QK^T and PV of one query over ``keys`` keys, all layers."""
    return 4.0 * m.layers * m.heads * m.head_dim * keys


def forward_flops(m: Dense, tokens: int, key_sum: float,
                  logits: int) -> float:
    """Forward pass over ``tokens`` positions attending ``key_sum`` keys
    in all, with the output head applied at ``logits`` positions."""
    return (2.0 * m.layers * m.layer_matmul_params * tokens
            + attn_flops(m, 1) * key_sum + 2.0 * m.d * m.vocab * logits)


def causal_keys(n: int, start: int = 0) -> float:
    """Keys attended by positions start .. start+n-1 (p attends p+1)."""
    return n * start + n * (n + 1) / 2.0


def train_flops_per_token(m: Dense, seq: int) -> float:
    """Forward and backward (3x forward) per trained token of a ``seq``
    sequence; recomputation is not counted."""
    return 3.0 * forward_flops(m, seq, causal_keys(seq), seq) / seq


def serve_request_flops(m: Dense, prompt: int, out: int) -> float:
    """One request: its prompt and the ``out - 1`` tokens fed back, with
    logits at the ``out`` positions that produce served tokens."""
    n = prompt + out - 1
    return forward_flops(m, n, causal_keys(n), out)


def decode_bytes(m: Dense, kv_tokens: int, rows: int,
                 act_bytes: int = 2) -> float:
    """Bytes one decode attention dispatch needs over all layers: K and V
    of ``kv_tokens`` cached positions, plus q read and out written for
    ``rows`` rows."""
    kv = 2.0 * kv_tokens * m.kv_heads * m.head_dim * act_bytes
    qo = 2.0 * rows * m.heads * m.head_dim * act_bytes
    return m.layers * (kv + qo)


def topk_kernel_bytes(n: int, workers: int, k: int, block: int) -> float:
    """The fused top-k kernel over ``workers`` flat f32 buffers of ``n``:
    gradients and residuals read, residuals written, and ``k`` packed
    (value, index) pairs of 4 bytes each written per ``block`` entries."""
    rows = -(-n // block)
    return 4.0 * workers * (3 * n + 2 * k * rows)


def reduce_step_bytes(n: int, workers: int) -> float:
    """One master step over ``workers`` messages on an ``n``-entry f32
    buffer: gradients and residuals read, residuals written; parameters
    and the accumulator read and written; the reduced gradient written
    once."""
    return 4.0 * (3 * workers * n + 4 * n + n)

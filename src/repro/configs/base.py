"""Architecture configuration system.

Every assigned architecture is a frozen dataclass instance registered in
``ARCH_REGISTRY`` under its public id (``--arch <id>``). Configs are pure
data: models consume them, the launcher looks them up, and smoke tests call
``.reduced()`` to get a CPU-sized variant of the same family.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Block kinds (layer-stack pattern language)
# ---------------------------------------------------------------------------
ATTN = "attn"          # attention + dense MLP block
MOE = "moe"            # attention + MoE block
SSM = "ssm"            # Mamba2 block (attention-free)
HYBRID_ATTN = "hattn"  # shared attention block inside an SSM stack (Zamba2)

# the published ``layer_types`` names (Granite-4.0-H) -> block kinds: each
# such layer is a mixer (Mamba2 or attention) followed by a dense MLP
LAYER_TYPES = {"mamba": SSM, "attention": ATTN}


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    experts_per_token: int          # top-k
    d_ff_expert: int
    capacity_factor: float = 1.25
    shared_expert: bool = False     # llama4-style shared expert
    dense_residual: bool = False    # arctic-style parallel dense FFN
    router_aux_weight: float = 0.01
    impl: str = "einsum"            # einsum (GShard) | sort (§Perf H2)


@dataclass(frozen=True)
class SSMConfig:
    d_state: int
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 128
    n_groups: int = 1

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str                  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int                    # query heads (0 for attention-free)
    n_kv_heads: int
    d_ff: int                       # dense MLP hidden (0 if none)
    vocab_size: int
    head_dim: int = 0               # 0 -> d_model // n_heads
    citation: str = ""

    # attention details
    qk_norm: bool = False
    attn_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    sliding_window: int = 0         # 0 = full attention (long_500k may override)
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    mlp_act: str = "silu"           # silu (SwiGLU) | gelu (plain MLP)

    # mixture-of-experts / ssm sub-configs
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid_attn_period: int = 0     # zamba2: every Nth block is shared attention
    # per-layer mixer order as published ("mamba" | "attention"), each
    # layer followed by a SwiGLU MLP (granitemoehybrid); () = not this kind
    layer_types: Tuple[str, ...] = ()
    # Granite's scalars (layer_types models only; the defaults are no-ops):
    # x = embedding_multiplier * E[tok]; each branch adds
    # residual_multiplier * f(RMSNorm(x)); the softmax scale is
    # attention_multiplier (None: head_dim ** -0.5); logits are divided
    # by logits_scaling
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # encoder-decoder (whisper backbone)
    enc_dec: bool = False
    n_encoder_layers: int = 0
    encoder_seq: int = 0            # fixed encoder length (audio frames)

    # modality frontend stubs
    frontend: str = "none"          # none | vision | audio
    n_prefix_tokens: int = 0        # vision patches prepended to text

    # numerics
    param_dtype: str = "bfloat16"
    activ_dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.n_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        # a tuple, however given (a JSON round trip gives a list)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            bad = set(self.layer_types) - set(LAYER_TYPES)
            if bad or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"{self.name}: layer_types must list n_layers="
                    f"{self.n_layers} of {sorted(LAYER_TYPES)}")
        elif (self.embedding_multiplier, self.attention_multiplier,
              self.residual_multiplier, self.logits_scaling) \
                != (1.0, None, 1.0, 1.0):
            raise ValueError(f"{self.name}: Granite's multipliers apply to "
                             f"layer_types models only")

    @property
    def attn_scale(self) -> float:
        """The attention softmax scale."""
        if self.attention_multiplier is not None:
            return float(self.attention_multiplier)
        return self.head_dim ** -0.5

    @property
    def attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def supports_long_decode(self) -> bool:
        """True if a 500k-token decode is representable (sub-quadratic state).

        SSM/hybrid natively; attention archs via the sliding-window variant.
        The enc-dec audio backbone has no long-decode analogue.
        """
        return not self.enc_dec

    def block_pattern(self) -> Tuple[str, ...]:
        """Per-layer block kinds, length == n_layers (decoder stack)."""
        if self.layer_types:
            return tuple(LAYER_TYPES[t] for t in self.layer_types)
        if self.arch_type == "moe":
            return (MOE,) * self.n_layers
        if self.arch_type == "ssm":
            return (SSM,) * self.n_layers
        if self.arch_type == "hybrid":
            p = self.hybrid_attn_period
            out = []
            for i in range(self.n_layers):
                out.append(HYBRID_ATTN if (i % p == p - 1) else SSM)
            return tuple(out)
        return (ATTN,) * self.n_layers

    # ------------------------------------------------------------------
    def n_params(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        d, hd = self.d_model, self.head_dim
        total = self.vocab_size * d                       # embed
        if not self.tie_embeddings:
            total += self.vocab_size * d                  # lm head
        total += d                                        # final norm

        def attn_params() -> int:
            n = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
            n += self.n_heads * hd * d
            n += 2 * d                                    # pre norms
            if self.qk_norm:
                n += 2 * hd
            return n

        def mlp_params(ff: int) -> int:
            mult = 3 if self.mlp_act == "silu" else 2
            return mult * d * ff

        def ssm_params() -> int:
            assert self.ssm is not None
            di = self.ssm.d_inner(d)
            nh = self.ssm.n_heads(d)
            g = self.ssm.n_groups
            n = d * (2 * di + 2 * g * self.ssm.d_state + nh)   # in_proj
            n += (self.ssm.d_conv + 1) * (di + 2 * g * self.ssm.d_state)
            #    ^ depthwise conv weights and bias
            n += nh * 2 + nh                               # A_log, D, dt_bias
            n += di * d                                    # out_proj
            n += d + di                                    # pre-norm + gate norm
            return n

        if self.layer_types:                              # mixer + MLP
            for kind in self.block_pattern():
                total += mlp_params(self.d_ff)
                # attn_params counts both pre-norms; ssm_params its own
                total += attn_params() if kind == ATTN else ssm_params() + d
            return total
        for kind in self.block_pattern():
            if kind == ATTN:
                total += attn_params() + mlp_params(self.d_ff)
            elif kind == MOE:
                assert self.moe is not None
                total += attn_params()
                total += self.moe.n_experts * 3 * d * self.moe.d_ff_expert
                total += d * self.moe.n_experts            # router
                if self.moe.shared_expert:
                    total += 3 * d * self.moe.d_ff_expert
                if self.moe.dense_residual:
                    total += mlp_params(self.d_ff)
            elif kind == SSM:
                total += ssm_params()
            elif kind == HYBRID_ATTN:
                total += attn_params() + mlp_params(self.d_ff)
        if self.enc_dec:
            # encoder self-attn + MLP blocks, plus decoder cross-attn already
            # counted? (decoder blocks get an extra cross-attn each)
            enc = self.n_encoder_layers * (attn_params() + mlp_params(self.d_ff))
            cross = self.n_layers * attn_params()
            total += enc + cross + self.encoder_seq * d    # enc pos embed
        return total

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE top-k instead of all experts)."""
        if self.moe is None:
            return self.n_params()
        d = self.d_model
        all_exp = self.moe.n_experts * 3 * d * self.moe.d_ff_expert
        act_exp = self.moe.experts_per_token * 3 * d * self.moe.d_ff_expert
        return self.n_params() - self.n_layers * (all_exp - act_exp)

    # ------------------------------------------------------------------
    def reduced(self) -> "ArchConfig":
        """CPU-sized smoke variant of the same family (<=512 d_model etc.)."""
        changes: Dict = dict(
            n_layers=2,
            d_model=min(self.d_model, 128),
            vocab_size=min(self.vocab_size, 512),
            param_dtype="float32",
            activ_dtype="float32",
        )
        if self.n_heads:
            hd = 32
            nh = max(2, min(4, self.n_heads))
            nkv = 1 if self.n_kv_heads < self.n_heads else nh
            changes.update(n_heads=nh, n_kv_heads=nkv, head_dim=hd)
        if self.d_ff:
            changes["d_ff"] = 256
        if self.moe is not None:
            changes["moe"] = dataclasses.replace(
                self.moe, n_experts=min(4, self.moe.n_experts),
                experts_per_token=min(2, self.moe.experts_per_token),
                d_ff_expert=128,
                # generous capacity so smoke/consistency tests see no drops
                capacity_factor=4.0)
        if self.ssm is not None:
            changes["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=32, chunk=16)
        if self.hybrid_attn_period:
            changes["hybrid_attn_period"] = 2
            changes["n_layers"] = 4
        if self.layer_types:
            changes["layer_types"] = ("mamba", "mamba", "attention", "mamba")
            changes["n_layers"] = 4
        if self.enc_dec:
            changes.update(n_encoder_layers=2, encoder_seq=16)
        if self.n_prefix_tokens:
            changes["n_prefix_tokens"] = 4
        if self.sliding_window:
            changes["sliding_window"] = 16
        return dataclasses.replace(self, **changes)

    def with_sliding_window(self, window: int) -> "ArchConfig":
        return dataclasses.replace(self, sliding_window=window)


# ---------------------------------------------------------------------------
ARCH_REGISTRY: Dict[str, Callable[[], ArchConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], ArchConfig]):
        ARCH_REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> ArchConfig:
    if name not in ARCH_REGISTRY:
        # import side-effect registration
        from repro.configs import all_configs  # noqa: F401
    if name not in ARCH_REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCH_REGISTRY)}")
    return ARCH_REGISTRY[name]()


def list_archs() -> Sequence[str]:
    from repro.configs import all_configs  # noqa: F401
    return sorted(ARCH_REGISTRY)

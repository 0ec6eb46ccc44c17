"""Granite-4.0-H-Micro — 40 layers: 36 Mamba2 and 4 NoPE GQA attention
layers, each mixer followed by a SwiGLU MLP (``granitemoehybrid`` with no
experts).

[https://huggingface.co/ibm-granite/granite-4.0-h-micro, config.json] —
hidden 2048, MLP 8192, 32 query / 8 KV heads of 64 with no positional
encoding, Mamba2 with 64 heads of 64, d_state 128, one group, conv 4
(with bias), chunk 256; tied vocabulary of 100,352; embedding x12, the
softmax scale 1/64, residual branches x0.22, logits /8.
"""
from repro.configs.base import ArchConfig, SSMConfig, register

# attention at 5, 15, 25 and 35, as the published list has it
LAYER_TYPES = tuple("attention" if i in (5, 15, 25, 35) else "mamba"
                    for i in range(40))


@register("granite-4.0-h-micro")
def granite_4_0_h_micro() -> ArchConfig:
    return ArchConfig(
        name="granite-4.0-h-micro",
        arch_type="hybrid",
        n_layers=40,
        d_model=2048,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        d_ff=8192,
        vocab_size=100_352,
        use_rope=False,
        tie_embeddings=True,
        norm_eps=1e-5,
        layer_types=LAYER_TYPES,
        ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                      chunk=256, n_groups=1),
        embedding_multiplier=12.0,
        attention_multiplier=0.015625,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        citation="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
    )

"""What the drivers share: the program's configuration object built from
the benchmark's configuration file, and the gap of per-leaf norms."""
from __future__ import annotations

import statistics
from typing import Dict, List

import jax


def arch(c: dict):
    """The program's ``ArchConfig`` for a dense decoder, from the file's
    published keys (so the file, not the program's registry, says what
    runs)."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=c["name"], arch_type="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=bool(c.get("qk_norm", False)), rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"], param_dtype=c["torch_dtype"],
        activ_dtype=c["torch_dtype"])


def abstract(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def worst_norm_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves: List[str]) -> float:
    """The largest gap between the program's and the reference's norm of
    a leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    med = statistics.median(ref[k] for k in leaves)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)

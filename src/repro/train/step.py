"""Step builders: train / prefill / decode functions for any ArchConfig.

``build_train_step`` implements the paper's algorithm as ONE sharded
program: every virtual worker (data-shard) contributes the gradient SUM
over its masked-in samples, and the division by the GLOBAL masked token
count is the master's weighted average (MLitB step c). The optimizer
update (AdaGrad by default) is the master's step, executed on fully-
sharded state.

The ``mask`` is the elasticity hook: the adaptive scheduler widens or
zeroes per-worker row ranges without recompiling (see core/mesh_engine).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as tf
from repro.models.layers import softmax_xent
from repro.optim.base import Optimizer

PyTree = Any


def make_train_state(params: PyTree, optimizer: Optimizer) -> PyTree:
    return {"params": params, "opt": optimizer.init(params)}


def build_train_step(cfg: ArchConfig, optimizer: Optimizer, *,
                     remat: bool = True, aux_weight: float = 0.01,
                     unroll: bool = False
                     ) -> Callable[[PyTree, Dict[str, jnp.ndarray]],
                                   Tuple[PyTree, Dict[str, jnp.ndarray]]]:
    def loss_fn(params, batch):
        kw = {}
        if cfg.arch_type == "vlm":
            kw["prefix"] = batch["prefix"]
        if cfg.arch_type == "audio":
            kw["frames"] = batch["frames"]
        logits, aux = tf.forward(params, cfg, batch["tokens"], remat=remat,
                                 unroll=unroll, **kw)
        sum_nll, count = softmax_xent(logits, batch["labels"], batch["mask"])
        # weighted reduce: gradient of (global sum / global count) ==
        # (sum_w grad_sum_w) / (sum_w n_w) — the master's weighted average.
        count = jnp.maximum(count, 1.0)
        loss = sum_nll / count + aux_weight * aux
        return loss, (sum_nll, count, aux)

    def train_step(state, batch):
        (loss, (sum_nll, count, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"], batch)
        new_params, new_opt = optimizer.update(state["params"], grads,
                                               state["opt"])
        metrics = {"loss": sum_nll / count, "tokens": count,
                   "aux_loss": aux, "total_loss": loss}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


# ---------------------------------------------------------------------------
# Serving programs — ONE factory for every serving step function
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ServePrograms:
    """The complete set of (unjitted) serving step functions for one
    ``(cfg, paged, decode_kernel)`` choice — built once by
    ``build_serve_programs`` and jitted/bucketed by the caller
    (repro.serving.engine wraps them with its trace counter and sampler;
    launch/serve.py jits them directly).

    Signatures (``B``=batch, ``C``=chunk, ``P``=pages per row):

      prefill(params, batch)                      -> (logits (B,1,V), cache)
      prefill_chunk  dense: (params, tokens (B,C), off, clen, cache)
                     paged: (params, tokens, off, clen, pool, rmap, wmap,
                     slots (B,)): slots picks the rows' recurrent state
                     (layer_types only; other models ignore it)
      verify         same as prefill_chunk but returns ALL chunk logits
                     (B,C,V) — the speculative-verification program
                     (None for layer_types: state cannot roll back)
      decode         dense: (params, token (B,1), pos (B,), cache, live)
                     paged: (params, token, pos, pool, live, rmap, wmap)
      decode_lockstep(params, token, pos_scalar, cache)   [dense only]
    """
    cfg: ArchConfig
    paged: bool
    decode_kernel: str
    prefill: Callable
    prefill_chunk: Callable
    verify: Callable
    decode: Callable
    decode_lockstep: Optional[Callable]


def build_serve_programs(cfg: ArchConfig, *, paged: bool,
                         unroll: bool = False,
                         decode_kernel: str = "xla",
                         prefill_cache_len: Optional[int] = None
                         ) -> ServePrograms:
    """Build every serving step function in one place. ``paged`` selects
    the KV layout (dense slot cache vs paged pool + page maps);
    ``decode_kernel`` selects the decode attention implementation:
    ``"xla"`` (the ``attention_decode_ragged`` oracle) or ``"flash"``
    (the fused Pallas flash-decode kernel — paged mode reads the page
    pool directly with no gather). ``prefill_cache_len`` pins the
    single-shot prefill's cache length (bucketed shapes).

    Replaces the five historical ``build_*_step`` factories (removed
    after their one deprecation cycle — docs/serving.md §1 has the
    migration table)."""
    if decode_kernel not in ("xla", "flash"):
        raise ValueError(f"decode_kernel={decode_kernel!r}: expected "
                         f"'xla' or 'flash'")
    if cfg.layer_types and (not paged or decode_kernel != "flash"):
        # recurrent state beside KV pages: one layout, one decode path
        raise ValueError(
            f"{cfg.name}: a model with recurrent (Mamba) layers serves "
            f"from the paged pool with decode_kernel='flash' only; "
            f"{'the dense slot layout' if not paged else 'XLA decode'} "
            f"is refused")

    def prefill(params, batch):
        kw = {}
        if cfg.arch_type == "vlm":
            kw["prefix"] = batch.get("prefix")
        if cfg.arch_type == "audio":
            kw["frames"] = batch.get("frames")
        return tf.prefill(params, cfg, batch["tokens"], unroll=unroll,
                          cache_len=prefill_cache_len,
                          lengths=batch.get("lengths"), **kw)

    if paged:
        def prefill_chunk(params, tokens, off, clen, pool, rmap, wmap,
                          slots=None):
            return tf.prefill_chunk_paged(params, cfg, tokens, off, clen,
                                          pool, rmap, wmap, unroll=unroll,
                                          slots=slots)

        def verify(params, tokens, off, clen, pool, rmap, wmap):
            return tf.prefill_chunk_paged(params, cfg, tokens, off, clen,
                                          pool, rmap, wmap, unroll=unroll,
                                          all_logits=True)

        if decode_kernel == "flash":
            def decode(params, token, pos, pool, live, rmap, wmap):
                return tf.decode_step_ragged_paged_flash(
                    params, cfg, token, pos, pool, live, rmap, wmap,
                    unroll=unroll)
        else:
            def decode(params, token, pos, pool, live, rmap, wmap):
                return tf.decode_step_ragged_paged(
                    params, cfg, token, pos, pool, live, rmap, wmap,
                    unroll=unroll)
        if cfg.layer_types:
            # no single-shot prefill, and no verify: state cannot roll
            # back rejected drafts
            prefill = verify = None
        return ServePrograms(cfg=cfg, paged=True,
                             decode_kernel=decode_kernel, prefill=prefill,
                             prefill_chunk=prefill_chunk, verify=verify,
                             decode=decode, decode_lockstep=None)

    def prefill_chunk(params, tokens, off, clen, cache):
        return tf.prefill_chunk(params, cfg, tokens, off, clen, cache,
                                unroll=unroll)

    def verify(params, tokens, off, clen, cache):
        return tf.prefill_chunk(params, cfg, tokens, off, clen, cache,
                                unroll=unroll, all_logits=True)

    def decode(params, token, pos, cache, live):
        return tf.decode_step_ragged(params, cfg, token, pos, cache, live,
                                     unroll=unroll,
                                     flash=decode_kernel == "flash")

    def decode_lockstep(params, token, pos, cache):
        return tf.decode_step(params, cfg, token, pos, cache, unroll=unroll)

    return ServePrograms(cfg=cfg, paged=False, decode_kernel=decode_kernel,
                         prefill=prefill, prefill_chunk=prefill_chunk,
                         verify=verify, decode=decode,
                         decode_lockstep=decode_lockstep)


def build_draft_program(cfg: ArchConfig, *, k: int, window: int):
    """Speculative-decoding DRAFT program (part of the consolidated
    serving-program API; docs/serving.md §9): a cacheless greedy k-token
    proposer over a fixed ``(B, window)`` token buffer.

    ``(params, window_toks (B,W) int32, hlen (B,) int32) -> (B,k) int32``
    — row b's history is ``window_toks[b, :hlen_b]`` (left-aligned, the
    caller truncates to the last ``window - k`` tokens so all k writes
    fit); the program unrolls k greedy forwards, writing each proposal at
    column ``hlen + i``. Causal masking makes the padding tail invisible,
    so proposals depend only on the visible history. ONE trace per
    (B, W) shape; draft quality moves the ACCEPTANCE RATE only — the
    engine's accept rule keeps the emitted stream equal to the target
    model's greedy output regardless (repro.serving.engine)."""
    def draft(params, window_toks, hlen):
        B, W = window_toks.shape
        rows = jnp.arange(B)
        toks = window_toks
        hl = hlen.astype(jnp.int32)
        outs = []
        for i in range(k):
            logits, _ = tf.forward(params, cfg, toks, remat=False)
            col = jnp.clip(hl - 1 + i, 0, W - 1)
            step = jnp.take_along_axis(logits, col[:, None, None],
                                       axis=1)[:, 0, :]
            nxt = jnp.argmax(step, axis=-1).astype(jnp.int32)
            outs.append(nxt)
            wcol = jnp.clip(hl + i, 0, W - 1)
            toks = toks.at[rows, wcol].set(nxt)
        return jnp.stack(outs, axis=1)
    return draft

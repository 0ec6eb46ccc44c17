"""Serve path of a hybrid Mamba2 / attention model: ``ServingEngine``
under an open loop, as ``drivers/serve.py`` runs a dense decoder.

What differs from ``serve.py``: the program's configuration is built
from the hybrid's published keys (``arch``); the weights follow Mamba2's
published initialisation (``weights_hybrid``); set-up warms the prefill
buckets up to the rows the engine feeds a hybrid's prefill dispatch
(``eng.prefill_rows``, not ``max_batch``), the decode program and the
admission's state reset; the window's operations are counted by
``flops_hybrid``; and the comparison
runs ``refhybrid``, whose Mamba layers run the recurrence step by step
over each whole sequence. The window, the sample the comparison checks
and its measure (``logit_gap``) are ``serve.py``'s.
"""
from __future__ import annotations

import jax
import numpy as np

from chipbench import flops_hybrid, refhybrid, traffic, weights, \
    weights_hybrid
from chipbench.drivers import serve

# the reference's sequences are padded to a multiple of this
REF_PAD = 256


def arch(c: dict):
    """The program's ``ArchConfig`` for a ``layer_types`` hybrid, from the
    file's published keys."""
    from repro.configs.base import ArchConfig, SSMConfig
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return ArchConfig(
        name=c["name"], arch_type="hybrid",
        n_layers=c["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=c["num_key_value_heads"], head_dim=d // heads,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        use_rope=c["position_embedding_type"] != "nope",
        rope_theta=c["rope_theta"], tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"], layer_types=tuple(c["layer_types"]),
        ssm=SSMConfig(d_state=c["mamba_d_state"], d_conv=c["mamba_d_conv"],
                      expand=c["mamba_expand"], head_dim=c["mamba_d_head"],
                      chunk=c["mamba_chunk_size"],
                      n_groups=c["mamba_n_groups"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        param_dtype=c["torch_dtype"], activ_dtype=c["torch_dtype"])


def _engine(run, params):
    from repro.serving import (PagingConfig, SamplingConfig, ServingConfig,
                               ServingEngine)
    sv = run.config["serving"]
    return ServingEngine(params, arch(run.config), serving=ServingConfig(
        max_batch=sv["max_batch"], max_seq=sv["max_seq"],
        prompt_bucket_min=sv["prompt_bucket_min"],
        prompt_cap=sv["prompt_cap"], decode_kernel=sv["decode_kernel"],
        paging=PagingConfig(page_size=sv["page_size"],
                            prefix_reuse=sv["prefix_reuse"]),
        sampling=SamplingConfig(temperature=0.0)))


def _weights(run, shape):
    return weights_hybrid.make(run.key("weights"), shape, run.config)


def warm(run, eng, vocab: int) -> None:
    """Every (batch, chunk) prefill bucket a dispatch of at most
    ``eng.prefill_rows`` rows can take, then the decode program."""
    from repro.serving import ServeRequest
    sv = run.config["serving"]
    rng = run.rng("warm")
    rid = serve.WARM_RID
    rows = min(sv["max_batch"], eng.prefill_rows)
    for b, c in serve.buckets(dict(sv, max_batch=rows)) + [(1, 0)]:
        for _ in range(b):
            plen, new = (c, 1) if c else (8, 3)    # (1, 0): the decode
            eng.submit(ServeRequest(rid, rng.integers(0, vocab, plen,
                                                      dtype=np.int32), new))
            rid += 1
        while eng.has_work:
            eng.step()


def setup(run):
    from repro.models import transformer as tf
    cfg = arch(run.config)
    shape = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    with run.span("cb:serve.build"):
        params = _weights(run, shape)
        eng = _engine(run, params)
    del params
    with run.span("cb:serve.warm"):
        warm(run, eng, cfg.vocab_size)
    mix = run.traffic
    reqs = traffic.requests(run.rng("requests"), mix, cfg.vocab_size,
                            run.seconds, run.seconds + mix["drain_max_s"])
    return {"eng": eng, "shape": shape, "reqs": reqs,
            "traces": eng.trace_count}


def window(run, st):
    counters = serve.window(run, st)
    # serve.window counts a dense decoder's operations: count the hybrid's
    model = flops_hybrid.Hybrid.of(run.config)
    by_rid = {r.rid: r for r in st["reqs"]}
    counters["window_flops"] = sum(
        flops_hybrid.serve_request_flops(model, len(by_rid[rid].prompt),
                                         len(toks))
        for rid, (t, toks) in counters["finished"].items()
        if t <= counters["window_s"] and rid in by_rid)
    return counters


release = serve.release


def gaps(run, st, counters, precision: str = None):
    """Widest gap below the reference's best of the served tokens (or,
    with ``precision``, of the tokens that precision puts first)."""
    picked = serve.sample(run, st, counters)
    longest = max(len(p) + len(g) - 1 for p, g in picked)
    toks, rows, cols, served = serve.positions(
        picked, -(-longest // REF_PAD) * REF_PAD)
    w = weights.named(_weights(run, st["shape"]))
    ref = np.asarray(refhybrid.logits_at(w, run.config, toks, rows, cols))
    if precision is not None:
        low = refhybrid.logits_at(w, run.config, toks, rows, cols, precision)
        served = np.asarray(jax.numpy.argmax(low, -1))
    gap = (ref.max(-1) - ref[np.arange(len(served)), served]) / ref.std(-1)
    return float(np.max(gap)), len(served)


def check(run, st, counters):
    gap, _ = gaps(run, st, counters)
    return [("logit_gap", gap, run.limits["logit_gap"]["limit"])]


def control(run, st, counters):
    """The control's reading: the reference with float8 weights in the
    program's place, over the same prompts and served tokens."""
    return {"logit_gap": gaps(run, st, counters, "fp8")[0]}

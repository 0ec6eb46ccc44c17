"""A ``layer_types`` hybrid (Mamba2 and attention layers, Granite-4.0-H)
served through ``ServingEngine`` (paged, flash decode in interpret mode)
against the plain float32 reference ``chipbench/refhybrid.py``, whose
Mamba layers run the recurrence step by step over the whole sequence.

The tiny model is float32 with seeded random weights and Mamba2's
published A / dt initialisation (``chipbench/weights_hybrid.py``), so
heads keep their state over tens of tokens and a lost carry shows.

Tolerance: logits agree to 1e-4 of their spread (standard deviation at
that position). Program and reference are both float32; what differs is
the order of the sums: the chunked SSD against the step-by-step
recurrence, the ring-fed conv against the padded one, the kernel's
online softmax against a full one. That rounding reads about 1e-6 here;
a state lost or taken at the wrong column reads 1e-2 and more.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tf
from repro.serving import (PagingConfig, SamplingConfig, ServeRequest,
                           ServingConfig, ServingEngine, SpeculativeConfig)
from repro.serving import engine as engine_mod
from repro.train.step import build_serve_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import refhybrid, weights, weights_hybrid  # noqa: E402
from chipbench.drivers import serve_hybrid  # noqa: E402

# the published keys of a tiny hybrid: mamba, mamba, attention, mamba
TINY = {
    "name": "tiny-hybrid", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "position_embedding_type": "nope", "rope_theta": 10000,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_d_head": 32, "mamba_n_heads": 8, "mamba_n_groups": 1,
    "mamba_chunk_size": 8, "embedding_multiplier": 12,
    "attention_multiplier": 0.05, "residual_multiplier": 0.22,
    "logits_scaling": 8, "torch_dtype": "float32"}
CFG = serve_hybrid.arch(TINY)
TOL = 1e-4


def _params(seed=0):
    shape = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0),
                                                  CFG))
    return weights_hybrid.make(jax.random.PRNGKey(seed), shape, TINY)


PARAMS = _params()
NAMED = weights.named(PARAMS)


def _ref_logits(seqs):
    """Reference logits (N, T, V) of token rows padded to one length."""
    T = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    return np.asarray(refhybrid.logits(NAMED, TINY, jnp.asarray(toks)))


def _gap(got, want):
    """Largest |got - want| in units of the reference's spread there."""
    return float(np.max(np.abs(got - want) / want.std(-1, keepdims=True)))


def _engine(max_batch=4, **kw):
    sv = dict(max_batch=max_batch, max_seq=64, prompt_bucket_min=8,
              prompt_cap=12, decode_kernel="flash",
              paging=PagingConfig(page_size=8, prefix_reuse=False),
              sampling=SamplingConfig(temperature=0.0))
    sv.update(kw)
    return ServingEngine(PARAMS, CFG, serving=ServingConfig(**sv))


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


def _served(eng, reqs, stagger=False):
    """Tokens by rid; with ``stagger`` each request is submitted one
    engine step after the one before it."""
    out = {}
    pending = list(reqs)
    while pending or eng.has_work:
        if pending:
            for r in (pending[:1] if stagger else pending):
                eng.submit(r)
            pending = pending[1:] if stagger else []
        for c in eng.step().completed:
            out[c.rid] = c.tokens.tolist()
    return out


def _served_gap(reqs, tokens):
    """Widest gap below the reference's best of the served tokens, in
    units of the reference's spread (chipbench's ``logit_gap``)."""
    seqs = [np.concatenate([r.prompt, tokens[r.rid][:-1]]) for r in reqs]
    ref = _ref_logits(seqs)
    worst = 0.0
    for i, r in enumerate(reqs):
        P = len(r.prompt)
        for j, t in enumerate(tokens[r.rid]):
            row = ref[i, P - 1 + j]
            worst = max(worst, (row.max() - row[t]) / row.std())
    return worst


# ---------------------------------------------------------------------------
# the programs: chunked prefill and decode through the state against the
# reference's full forward, logits compared
# ---------------------------------------------------------------------------
def test_chunked_prefill_and_decode_logits_match_reference():
    """Three rows in one program: a prompt longer than three chunks, one
    of one short chunk, one that decodes while another still prefills
    (its state must stay as it was: it is not live). Chunks of 12 cut
    the SSD's chunks of 8 in the middle, and every row ends on a chunk
    with ``clen < C``."""
    rng = np.random.default_rng(3)
    B, C, cap, ps, P = 3, 16, 12, 8, 8
    prompts = [_prompt(rng, n) for n in (41, 7, 20)]
    conts = [_prompt(rng, 6) for _ in range(B)]
    ref = _ref_logits([np.concatenate([p, c]) for p, c in zip(prompts,
                                                             conts)])
    progs = build_serve_programs(CFG, paged=True, decode_kernel="flash")
    chunk = jax.jit(progs.prefill_chunk)
    decode = jax.jit(progs.decode)
    cache = tf.hybrid_cache(CFG, B, B * P, ps)
    pmap = jnp.asarray(np.arange(B * P, dtype=np.int32).reshape(B, P))
    filled = [0] * B
    pos = np.zeros(B, np.int32)
    tok = np.zeros(B, np.int32)
    live = np.zeros(B, bool)
    n_dec = [0] * B
    got = {b: [] for b in range(B)}
    while any(n < len(conts[b]) for b, n in enumerate(n_dec)):
        todo = [b for b in range(B) if filled[b] < len(prompts[b])]
        if todo:
            toks = np.zeros((B, C), np.int32)
            off = np.zeros(B, np.int32)
            clen = np.zeros(B, np.int32)
            slots = np.full(B, B, np.int32)
            for b in todo:
                n = min(cap, len(prompts[b]) - filled[b])
                toks[b, :n] = prompts[b][filled[b]:filled[b] + n]
                off[b], clen[b], slots[b] = filled[b], n, b
            logits, cache = chunk(PARAMS, jnp.asarray(toks),
                                  jnp.asarray(off), jnp.asarray(clen), cache,
                                  pmap, pmap, jnp.asarray(slots))
            for b in todo:
                filled[b] += int(clen[b])
                if filled[b] == len(prompts[b]):
                    got[b].append(np.asarray(logits[b, 0]))
                    live[b], pos[b] = True, filled[b]
                    tok[b] = conts[b][0]
        if live.any():
            # rows not live write no KV (the engine's write map)
            wmap = jnp.where(jnp.asarray(live)[:, None], pmap, B * P)
            logits, cache = decode(PARAMS, jnp.asarray(tok[:, None]),
                                   jnp.asarray(pos), cache,
                                   jnp.asarray(live), pmap, wmap)
            for b in np.flatnonzero(live):
                got[b].append(np.asarray(logits[b, 0]))
                n_dec[b] += 1
                pos[b] += 1
                if n_dec[b] < len(conts[b]):
                    tok[b] = conts[b][n_dec[b]]
                else:
                    live[b] = False
    for b in range(B):
        L = len(prompts[b])
        want = ref[b, L - 1:L - 1 + len(got[b])]
        assert len(got[b]) == len(conts[b]) + 1
        assert _gap(np.stack(got[b]), want) < TOL, b


def test_training_forward_matches_reference():
    rng = np.random.default_rng(4)
    toks = np.stack([_prompt(rng, 30) for _ in range(2)])
    logits, _ = tf.forward(PARAMS, CFG, jnp.asarray(toks), remat=False)
    assert _gap(np.asarray(logits), _ref_logits(list(toks))) < TOL


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
def test_engine_serves_the_reference_greedy_tokens(monkeypatch):
    """Prompts longer than ``prompt_cap`` (several chunks), staggered so
    rows decode while others prefill; at most two rows a prefill
    dispatch (the engine's row cap, lowered from 4 to bite here)."""
    rng = np.random.default_rng(5)
    reqs = [ServeRequest(i, _prompt(rng, n), g) for i, (n, g) in
            enumerate([(30, 6), (5, 9), (25, 4), (13, 7), (40, 5)])]
    monkeypatch.setattr(engine_mod, "HYBRID_PREFILL_ROWS", 2)
    eng = _engine()
    assert eng.prefill_rows == 2
    tokens = _served(eng, reqs, stagger=True)
    assert sorted(tokens) == list(range(len(reqs)))
    assert _served_gap(reqs, tokens) < TOL
    # buckets never exceed the cap; decode + state reset: two programs
    assert max(b for b, _ in eng.buckets_seen) <= 2
    assert eng.trace_count == len(eng.buckets_seen) + 2


def test_reused_slot_serves_what_a_fresh_engine_serves():
    rng = np.random.default_rng(6)
    first = ServeRequest(0, _prompt(rng, 27), 8)
    second = ServeRequest(1, _prompt(rng, 19), 8)
    eng = _engine(max_batch=1)
    _served(eng, [first])
    reused = _served(eng, [second])
    fresh = _served(_engine(max_batch=1), [second])
    assert reused == fresh


def test_logits_do_not_depend_on_batch_companions():
    """A request alone and among companions that prefill long prompts
    around its decode steps: the same tokens (its state is its own, and
    dead rows' state is left alone)."""
    rng = np.random.default_rng(7)
    mine = ServeRequest(0, _prompt(rng, 9), 10)
    others = [ServeRequest(i, _prompt(rng, n), 3)
              for i, n in ((1, 38), (2, 26), (3, 45))]
    alone = _served(_engine(), [mine])
    crowd = _served(_engine(), [mine] + others, stagger=True)
    assert crowd[0] == alone[0]
    solo = {r.rid: _served(_engine(), [r])[r.rid] for r in others}
    assert all(crowd[r.rid] == solo[r.rid] for r in others)


def test_hot_swap_serves_each_request_under_its_version():
    """A swap while requests are in flight: each completion is what a
    fresh engine serves under the version it was admitted with (two
    version groups decode side by side; each touches its own rows'
    state)."""
    rng = np.random.default_rng(8)
    other = _params(seed=1)
    early = [ServeRequest(i, _prompt(rng, n), 8)
             for i, n in ((0, 30), (1, 6))]
    late = [ServeRequest(i, _prompt(rng, n), 6)
            for i, n in ((2, 20), (3, 9))]
    eng = _engine()
    for r in early:
        eng.submit(r)
    out = {}
    for _ in range(2):
        out.update({c.rid: c.tokens.tolist() for c in eng.step().completed})
    eng.swap_params(other)
    out.update(_served(eng, late))
    assert eng.swap_count == 1
    for r in early:
        assert out[r.rid] == _served(_engine(), [r])[r.rid]
    swapped = ServingEngine(other, CFG, serving=_engine().serving)
    for r in late:
        assert out[r.rid] == _served(swapped, [r])[r.rid]


@pytest.mark.parametrize("kw,words", [
    (dict(paging=PagingConfig(page_size=8)), "prefix_reuse"),
    (dict(paging=None), "dense slot layout"),
    (dict(decode_kernel="xla"), "XLA decode"),
])
def test_engine_refuses_what_state_cannot_serve(kw, words):
    with pytest.raises(ValueError, match=words):
        _engine(**kw)


def test_engine_refuses_speculative_decoding():
    draft = get_config("qwen3-4b").reduced()
    spec = SpeculativeConfig(
        draft_params=tf.init_params(jax.random.PRNGKey(1), draft),
        draft_cfg=draft, k=2, window=8)
    with pytest.raises(ValueError, match="roll back"):
        _engine(speculative=spec)


# ---------------------------------------------------------------------------
# the published configuration
# ---------------------------------------------------------------------------
def test_published_layer_types_and_parameter_count():
    import math
    cfg = get_config("granite-4.0-h-micro")
    attn = [i for i, t in enumerate(cfg.layer_types) if t == "attention"]
    assert attn == [5, 15, 25, 35] and cfg.n_layers == 40
    shapes = tf.abstract_params(cfg)
    assert cfg.n_params() == sum(math.prod(x.shape)
                                 for x in jax.tree.leaves(shapes))
    assert 3.15e9 < cfg.n_params() < 3.25e9
    assert tf.layer_runs(cfg) == [("mamba", 0, 5), ("attention", 0, 1),
                                  ("mamba", 5, 9), ("attention", 1, 1),
                                  ("mamba", 14, 9), ("attention", 2, 1),
                                  ("mamba", 23, 9), ("attention", 3, 1),
                                  ("mamba", 32, 4)]
    # the benchmark's configuration builds the same program
    import json
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        built = serve_hybrid.arch(json.load(f))
    assert dataclasses.replace(built, citation="") == \
        dataclasses.replace(cfg, citation="")

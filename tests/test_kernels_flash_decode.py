"""Fused Pallas flash-decode kernel + speculative decoding + the
consolidated serving-program API (docs/serving.md §9).

Three layers of guarantees:

  - KERNEL: ``flash_decode`` (interpret=True executes the Pallas body on
    CPU) matches the pure-jnp oracle ``flash_decode_ref`` over ragged
    ``pos``, dead rows, OOB page-map rows and every GQA shape — and dead
    / no-valid-key rows come out EXACTLY zero, never NaN;
  - ENGINE: a ServingEngine running the flash kernel (dense AND paged)
    serves byte-identical token streams to the XLA-oracle engine on
    identical schedules (dense + moe), and speculative decoding emits
    the EXACT greedy stream of the non-speculative engine while keeping
    the trace discipline (one draft trace + prefill buckets + ONE verify
    bucket);
  - API: ``serving=ServingConfig(...)`` is the engine's only
    construction form (the flat kwargs and the five historical
    ``build_*_step`` factories finished their deprecation cycle and are
    gone — both removals pinned here), and invalid configs fail AT
    CONSTRUCTION with messages naming the offending values.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.kernels.flash_decode import flash_decode, flash_decode_ref
from repro.models import transformer as tf
from repro.serving import (PagingConfig, SamplingConfig, ServeRequest,
                           ServingConfig, ServingEngine,
                           SpeculativeConfig)
from repro.train.step import build_serve_programs

TINY_DENSE = ArchConfig(
    name="tiny-dense", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=61, head_dim=16,
    param_dtype="float32", activ_dtype="float32", tie_embeddings=True)

TINY_MOE = ArchConfig(
    name="tiny-moe", arch_type="moe", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=61, head_dim=16,
    param_dtype="float32", activ_dtype="float32", tie_embeddings=True,
    moe=MoEConfig(n_experts=4, experts_per_token=2, d_ff_expert=32,
                  capacity_factor=4.0))


def _params(cfg, seed=0):
    return tf.init_params(jax.random.PRNGKey(seed), cfg)


def _mk_requests(cfg, rng, n, max_prompt=10, max_new=6):
    reqs = []
    for rid in range(n):
        p = int(rng.randint(1, max_prompt + 1))
        g = int(rng.randint(2, max_new + 1))
        reqs.append(ServeRequest(
            rid=rid, prompt=rng.randint(0, cfg.vocab_size, p).astype(
                np.int32), max_new=g))
    return reqs


def _live_kv(reqs, page_size=None):
    """Live KV tokens (or, with ``page_size``, pages) the decode
    dispatches read: a request of prompt P and n tokens decodes n - 1
    times, at positions P .. P + n - 2, each row holding pos + 1
    tokens."""
    out = 0
    for r in reqs:
        P = len(r.prompt)
        for pos in range(P, P + r.max_new - 1):
            out += pos + 1 if page_size is None else pos // page_size + 1
    return out


def _tokens_by_rid(stats):
    return {c.rid: c.tokens.tolist() for c in stats.completions}


# ---------------------------------------------------------------------------
# kernel: ref vs Pallas interpret parity
# ---------------------------------------------------------------------------
def _mk_case(key, B, H, K, D, n_pages, ps, P, seed_pos=None):
    """Random pool + a page map with live pages up front and OOB (==
    n_pages) everywhere past each row's allocation — the engine's rmap
    contract."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kpool = jax.random.normal(ks[1], (n_pages, K, ps, D), jnp.float32)
    vpool = jax.random.normal(ks[2], (n_pages, K, ps, D), jnp.float32)
    rng = np.random.RandomState(
        int(jax.random.randint(ks[3], (), 0, 2**31 - 1)))
    pos = rng.randint(0, P * ps, size=B).astype(np.int32) \
        if seed_pos is None else np.asarray(seed_pos, np.int32)
    pm = np.full((B, P), n_pages, np.int32)
    for b in range(B):
        used = int(pos[b]) // ps + 1
        pm[b, :used] = rng.choice(n_pages, size=used, replace=False)
    live = np.ones(B, np.int32)
    return q, kpool, vpool, jnp.asarray(pm), jnp.asarray(pos), \
        jnp.asarray(live)


CASES = [
    # B, H, K, D, n_pages, ps, P
    (4, 4, 2, 16, 16, 4, 4),      # GQA
    (2, 4, 4, 32, 8, 8, 2),       # MHA
    (3, 8, 1, 16, 32, 4, 8),      # MQA
    (1, 2, 2, 64, 4, 16, 2),      # single row, big pages
]


@pytest.mark.parametrize("B,H,K,D,NP,ps,P", CASES)
def test_flash_decode_matches_ref(B, H, K, D, NP, ps, P):
    case = _mk_case(jax.random.PRNGKey(B * 100 + H), B, H, K, D, NP, ps, P)
    out = flash_decode(*case, interpret=True)
    ref = flash_decode_ref(*case)
    assert out.shape == (B, H, D)
    assert jnp.abs(out - ref).max() < 2e-5


@pytest.mark.parametrize("scale", [1 / 64, 0.3])
def test_flash_decode_scale_matches_ref(scale):
    """A softmax scale other than ``D ** -0.5`` (Granite's 1/64 at
    head_dim 64) reaches the kernel's logits as the reference's."""
    case = _mk_case(jax.random.PRNGKey(64), 3, 8, 2, 64, 12, 8, 4)
    out = flash_decode(*case, interpret=True, scale=scale)
    ref = flash_decode_ref(*case, scale=scale)
    assert jnp.abs(out - ref).max() < 2e-5
    # and it is not the default's answer
    assert jnp.abs(out - flash_decode_ref(*case)).max() > 1e-3


def test_flash_decode_default_scale_is_bit_exact():
    case = _mk_case(jax.random.PRNGKey(65), 3, 8, 2, 64, 12, 8, 4)
    out = flash_decode(*case, interpret=True)
    explicit = flash_decode(*case, interpret=True, scale=64 ** -0.5)
    assert jnp.array_equal(out, explicit)


def test_flash_decode_ragged_pos_and_dead_rows():
    """Rows at every fill level incl. pos=0, plus dead rows: dead rows
    must come out EXACTLY zero (the engine discards them, but NaN would
    poison the out-projection of live rows in a fused batch)."""
    B, H, K, D, NP, ps, P = 5, 4, 2, 16, 12, 4, 3
    q, kp, vp, pm, pos, _ = _mk_case(
        jax.random.PRNGKey(0), B, H, K, D, NP, ps, P,
        seed_pos=[0, 3, 7, 11, 5])
    live = jnp.asarray([1, 1, 0, 1, 0], jnp.int32)
    out = flash_decode(q, kp, vp, pm, pos, live, interpret=True)
    ref = flash_decode_ref(q, kp, vp, pm, pos, live)
    assert jnp.abs(out - ref).max() < 2e-5
    assert bool((out[2] == 0.0).all()) and bool((out[4] == 0.0).all())
    assert bool(jnp.isfinite(out).all())


def test_flash_decode_oob_page_rows_are_skipped():
    """Pages past a row's allocation are marked OOB (== n_pages) in the
    map; flipping them to arbitrary VALID page ids holding garbage must
    not change the output, because pos masks those columns anyway —
    while flipping a page the row DOES read must."""
    B, H, K, D, NP, ps, P = 2, 4, 2, 16, 8, 4, 4
    q, kp, vp, pm, pos, live = _mk_case(
        jax.random.PRNGKey(5), B, H, K, D, NP, ps, P, seed_pos=[5, 2])
    base = flash_decode(q, kp, vp, pm, pos, live, interpret=True)
    # row 0 uses pages [0..1], rows beyond are OOB: point them anywhere
    pm_alias = pm.at[0, 3].set(0).at[1, 2].set(1)
    out = flash_decode(q, kp, vp, pm_alias, pos, live, interpret=True)
    assert jnp.abs(out - base).max() == 0.0
    pm_swap = pm.at[0, 0].set(pm[1, 0])      # a page row 0 DOES read
    out2 = flash_decode(q, kp, vp, pm_swap, pos, live, interpret=True)
    assert jnp.abs(out2 - base).max() > 1e-3


def test_flash_decode_identity_map_is_dense_attention():
    """With the identity page map the pool is just a dense (B, T) cache
    — the kernel must reproduce plain masked attention over it."""
    B, H, K, D, ps, nb = 3, 4, 2, 16, 4, 4
    T = ps * nb
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    ck = jax.random.normal(ks[1], (B, T, K, D), jnp.float32)
    cv = jax.random.normal(ks[2], (B, T, K, D), jnp.float32)
    pos = jnp.asarray([3, 9, 15], jnp.int32)
    live = jnp.ones(B, jnp.int32)
    idmap = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)

    def as_pool(c):
        return c.reshape(B, nb, ps, K, D).transpose(0, 1, 3, 2, 4) \
            .reshape(B * nb, K, ps, D)

    out = flash_decode(q, as_pool(ck), as_pool(cv), idmap, pos, live,
                       interpret=True)
    # plain grouped attention oracle over the dense cache
    G = H // K
    qg = q.reshape(B, K, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, ck.astype(jnp.float32))
    s = s / jnp.sqrt(D)
    mask = jnp.arange(T)[None, None, None, :] <= pos[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bkgt,btkd->bkgd", p,
                     cv.astype(jnp.float32)).reshape(B, H, D)
    assert jnp.abs(out - ref).max() < 2e-5


# ---------------------------------------------------------------------------
# engine: flash kernel serves bit-identical streams (dense + paged)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cfg", [TINY_DENSE, TINY_MOE],
                         ids=["dense", "moe"])
def test_engine_flash_dense_matches_oracle_bit_exact(cfg):
    params = _params(cfg)
    rng = np.random.RandomState(21)
    reqs = _mk_requests(cfg, rng, 12, max_prompt=12, max_new=6)
    base = ServingEngine(params, cfg,
                         serving=ServingConfig.from_flat(max_batch=4,
                                                         max_seq=32,
                                                         prompt_cap=8))
    flash = ServingEngine(params, cfg, serving=ServingConfig(
        max_batch=4, max_seq=32, prompt_cap=8, decode_kernel="flash"))
    ref = _tokens_by_rid(base.run_closed_loop(reqs))
    got = flash.run_closed_loop(reqs)
    assert _tokens_by_rid(got) == ref
    # the kernel's pos-bounded scan reads fewer KV tokens than the dense
    # rectangle — the counter the cost model charges must show it
    assert 0 < got.decode_kv_tokens < got.decode_rows_total * 32
    assert got.decode_kv_tokens == _live_kv(reqs)
    assert flash.trace_count == 1 + len(flash.buckets_seen)


@pytest.mark.parametrize("cfg", [TINY_DENSE, TINY_MOE],
                         ids=["dense", "moe"])
def test_engine_flash_paged_matches_oracle_bit_exact(cfg):
    params = _params(cfg)
    rng = np.random.RandomState(22)
    reqs = _mk_requests(cfg, rng, 12, max_prompt=12, max_new=6)
    base = ServingEngine(params, cfg,
                         serving=ServingConfig.from_flat(max_batch=4,
                                                         max_seq=32,
                                                         prompt_cap=8))
    flash = ServingEngine(params, cfg, serving=ServingConfig(
        max_batch=4, max_seq=32, prompt_cap=8, decode_kernel="flash",
        paging=PagingConfig(page_size=8)))
    ref = _tokens_by_rid(base.run_closed_loop(reqs))
    got = flash.run_closed_loop(reqs)
    assert _tokens_by_rid(got) == ref
    assert flash.trace_count == 1 + len(flash.buckets_seen)
    # paged mode counts live KV tokens per dispatch too, beside the live
    # pages and the pages the kernel's grid walks (every row, every page)
    assert got.decode_kv_tokens == _live_kv(reqs)
    assert got.decode_pages_live == _live_kv(reqs, page_size=8)
    assert got.decode_pages_walked == got.decode_dispatches * 4 * (32 // 8)


def test_engine_flash_paged_prefix_reuse_still_exact():
    """Flash decode reads through the SHARED (frozen) prefix pages too —
    reuse + COW must stay bit-exact under the kernel."""
    from repro.core.simulation import generate_requests
    cfg = TINY_DENSE
    params = _params(cfg)
    reqs = generate_requests(
        14, rate_rps=200.0, vocab_size=cfg.vocab_size, prompt_rng=(4, 8),
        gen_short=(2, 4), gen_long=(4, 6), long_frac=0.3,
        shared_prefix=(2, 16, 0.8), seed=9)
    base = ServingEngine(params, cfg,
                         serving=ServingConfig.from_flat(max_batch=4,
                                                         max_seq=64))
    flash = ServingEngine(params, cfg, serving=ServingConfig(
        max_batch=4, max_seq=64, decode_kernel="flash",
        paging=PagingConfig(page_size=8)))
    ref = _tokens_by_rid(base.run_closed_loop(reqs))
    stats = flash.run_closed_loop(reqs)
    assert _tokens_by_rid(stats) == ref
    assert stats.prefix_hits > 0          # reuse actually fired


# ---------------------------------------------------------------------------
# speculative decoding: exact greedy stream, one verify bucket
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_speculative_emits_exact_greedy_stream(paged):
    cfg = TINY_DENSE
    params = _params(cfg)
    rng = np.random.RandomState(31)
    reqs = _mk_requests(cfg, rng, 10, max_prompt=10, max_new=8)
    base = ServingEngine(params, cfg,
                         serving=ServingConfig.from_flat(max_batch=4,
                                                         max_seq=64))
    ref = _tokens_by_rid(base.run_closed_loop(reqs))
    # a DIFFERENT-SEED draft: disagrees with the target often, so the
    # accept rule is exercised on real rejections — output must not move
    spec = SpeculativeConfig(draft_params=_params(cfg, seed=3),
                             draft_cfg=cfg, k=3, window=16)
    eng = ServingEngine(params, cfg, serving=ServingConfig(
        max_batch=4, max_seq=64, speculative=spec,
        paging=PagingConfig(page_size=16) if paged else None))
    stats = eng.run_closed_loop(reqs)
    assert _tokens_by_rid(stats) == ref
    assert stats.drafted > 0
    # trace discipline: one DRAFT trace + one per prefill bucket + the
    # single pinned verify bucket (vcap = pow2_bucket(k+1)); there is NO
    # plain decode trace in speculative mode
    assert eng.verify_buckets_seen == [(4, 4)]
    assert eng.trace_count == 1 + len(eng.buckets_seen) \
        + len(eng.verify_buckets_seen)


def test_speculative_perfect_draft_accepts_everything():
    """Draft == target: every draft token matches the target's argmax,
    so the accept rule must take all k + the bonus token every round."""
    cfg = TINY_DENSE
    params = _params(cfg)
    rng = np.random.RandomState(32)
    reqs = _mk_requests(cfg, rng, 8, max_prompt=8, max_new=8)
    base = ServingEngine(params, cfg,
                         serving=ServingConfig.from_flat(max_batch=4,
                                                         max_seq=64))
    ref = _tokens_by_rid(base.run_closed_loop(reqs))
    spec = SpeculativeConfig(draft_params=params, draft_cfg=cfg, k=4,
                             window=32)
    eng = ServingEngine(params, cfg, serving=ServingConfig(
        max_batch=4, max_seq=64, speculative=spec))
    stats = eng.run_closed_loop(reqs)
    assert _tokens_by_rid(stats) == ref
    assert stats.drafted > 0 and stats.accepted == stats.drafted
    # accepting k+1 tokens per round needs far fewer dispatches than
    # one-token-at-a-time decode — the speculative win the bench gates
    assert stats.decode_dispatches < base.decode_dispatches


def test_speculative_moe_and_cross_arch_draft():
    """A dense draft can speculate for a moe target (vocab superset);
    the stream stays the moe engine's exact greedy output."""
    cfg = TINY_MOE
    params = _params(cfg)
    rng = np.random.RandomState(33)
    reqs = _mk_requests(cfg, rng, 8, max_prompt=8, max_new=6)
    base = ServingEngine(params, cfg,
                         serving=ServingConfig.from_flat(max_batch=4,
                                                         max_seq=64))
    ref = _tokens_by_rid(base.run_closed_loop(reqs))
    spec = SpeculativeConfig(draft_params=_params(TINY_DENSE, seed=5),
                             draft_cfg=TINY_DENSE, k=2, window=16)
    eng = ServingEngine(params, cfg, serving=ServingConfig(
        max_batch=4, max_seq=64, speculative=spec))
    assert _tokens_by_rid(eng.run_closed_loop(reqs)) == ref


# ---------------------------------------------------------------------------
# ServingConfig: grouped == flat, validation at construction
# ---------------------------------------------------------------------------
def test_serving_config_equals_flat_kwargs():
    cfg = TINY_DENSE
    params = _params(cfg)
    rng = np.random.RandomState(41)
    reqs = _mk_requests(cfg, rng, 8)
    flat = ServingEngine(params, cfg,
                         serving=ServingConfig.from_flat(max_batch=4,
                                                         max_seq=32,
                                                         prompt_cap=8,
                                                         temperature=0.7,
                                                         top_k=5,
                                                         sample_seed=3,
                                                         page_size=8))
    grouped = ServingEngine(params, cfg, serving=ServingConfig(
        max_batch=4, max_seq=32, prompt_cap=8,
        sampling=SamplingConfig(temperature=0.7, top_k=5, sample_seed=3),
        paging=PagingConfig(page_size=8)))
    assert _tokens_by_rid(flat.run_closed_loop(reqs)) \
        == _tokens_by_rid(grouped.run_closed_loop(reqs))


def test_mixing_serving_and_flat_kwargs_rejected():
    # the flat kwargs finished their deprecation cycle, so "mixing" is
    # no longer a ValueError at the disambiguation layer — the engine's
    # signature simply has no flat kwargs left to mix in
    cfg = TINY_DENSE
    params = _params(cfg)
    with pytest.raises(TypeError):
        ServingEngine(params, cfg,
                      serving=ServingConfig(max_batch=4, max_seq=32),
                      max_batch=4)


def test_page_size_divisibility_rejected_with_both_values_named():
    with pytest.raises(ValueError, match="max_seq=40.*page_size=16"):
        ServingConfig(max_batch=4, max_seq=40,
                      paging=PagingConfig(page_size=16))


def test_speculative_k_exceeding_prompt_cap_rejected():
    spec = SpeculativeConfig(draft_params={}, draft_cfg=TINY_DENSE,
                             k=8, window=16)
    with pytest.raises(ValueError, match="k=8.*prompt_cap=8"):
        ServingConfig(max_batch=4, max_seq=64, prompt_cap=8,
                      speculative=spec)


def test_speculative_requires_greedy():
    spec = SpeculativeConfig(draft_params={}, draft_cfg=TINY_DENSE,
                             k=2, window=8)
    with pytest.raises(ValueError, match="temperature=0"):
        ServingConfig(max_batch=4, max_seq=64,
                      sampling=SamplingConfig(temperature=0.5),
                      speculative=spec)


def test_more_construction_rejections():
    with pytest.raises(ValueError, match="decode_kernel='turbo'"):
        ServingConfig(max_batch=4, max_seq=32, decode_kernel="turbo")
    with pytest.raises(ValueError, match="window=2 must exceed k=2"):
        SpeculativeConfig(draft_params={}, draft_cfg=None, k=2, window=2)
    with pytest.raises(ValueError, match="n_pages requires page_size"):
        ServingConfig.from_flat(max_batch=4, max_seq=32, n_pages=8)


# ---------------------------------------------------------------------------
# the one-cycle deprecations are GONE: grouped construction is the API
# ---------------------------------------------------------------------------
def test_flat_constructions_removed():
    # the five build_*_step wrappers completed their deprecation cycle
    # (docs/serving.md §1 maps each to build_serve_programs)
    import repro.train.step as step_mod
    for old in ("build_prefill_step", "build_prefill_chunk_step",
                "build_paged_prefill_chunk_step", "build_paged_decode_step",
                "build_decode_step"):
        assert not hasattr(step_mod, old)
    # ...and so did the ServingEngine flat-kwarg constructor: the grouped
    # config is now required, flat kwargs are a TypeError
    cfg = TINY_DENSE
    with pytest.raises(TypeError):
        ServingEngine(_params(cfg), cfg, max_batch=4, max_seq=32)
    with pytest.raises(TypeError):
        ServingEngine(_params(cfg), cfg)

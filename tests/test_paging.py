"""Paged KV cache tests (docs/serving.md §8):

  - the paged engine is BIT-EXACT vs the dense slot cache on identical
    schedules (dense + moe) — max_seq is whole pages, so the gathered
    page view has exactly the dense row shape and the inner program is
    identical;
  - cross-request prefix reuse: a shared-prefix workload completes
    bit-exact with the trie ON, and the hit counters prove pages were
    actually reused (tokens never re-prefilled);
  - page lifecycle: after a drain every page is either free or held by
    the trie (no leaks), ``flush_prefix_cache`` returns the pool to
    empty, and a rerun on the same engine stays exact;
  - copy-on-write isolation: a forked request's prefill/decode NEVER
    mutates the frozen pages it shares with its parent (writes to
    frozen pages are OOB-dropped);
  - trace discipline carries over: paged trace count is still
    1 + distinct prefill buckets;
  - version-pinned page validity: trie generations are keyed on the
    param version, survive ``swap_params`` for pinned slots, and drop
    when the ring retires the version.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.core.simulation import ServeCostModel, generate_requests
from repro.models import transformer as tf
from repro.serving import ServeRequest, ServingConfig, ServingEngine

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
        g = int(rng.randint(1, max_new + 1))
        reqs.append(ServeRequest(
            rid=rid, prompt=rng.randint(0, cfg.vocab_size, p).astype(
                np.int32), max_new=g))
    return reqs


def _tokens_by_rid(stats):
    return {c.rid: c.tokens.tolist() for c in stats.completions}


# ---------------------------------------------------------------------------
# paged vs dense: bit-exact oracle on identical schedules
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cfg", [TINY_DENSE, TINY_MOE],
                         ids=["dense", "moe"])
def test_paged_matches_dense_bit_exact(cfg):
    params = _params(cfg)
    rng = np.random.RandomState(11)
    reqs = _mk_requests(cfg, rng, 12, max_prompt=12, max_new=6)
    dense = ServingEngine(params, cfg,
                          serving=ServingConfig.from_flat(max_batch=4,
                                                          max_seq=32,
                                                          prompt_cap=8))
    paged = ServingEngine(params, cfg,
                          serving=ServingConfig.from_flat(max_batch=4,
                                                          max_seq=32,
                                                          prompt_cap=8,
                                                          page_size=8))
    ref = _tokens_by_rid(dense.run_closed_loop(reqs))
    stats = paged.run_closed_loop(reqs)
    got = _tokens_by_rid(stats)
    assert got == ref
    # trace discipline is unchanged by paging: one decode trace plus one
    # per distinct prefill bucket, regardless of requests served
    assert paged.trace_count == 1 + len(paged.buckets_seen)
    # live KV tokens per decode dispatch: sum over its rows of pos + 1
    # (a request decodes n - 1 times, at positions P .. P + n - 2)
    assert stats.decode_kv_tokens == sum(
        pos + 1 for r in reqs
        for pos in range(len(r.prompt), len(r.prompt) + r.max_new - 1))


def test_prefix_reuse_is_bit_exact_and_actually_fires():
    cfg = TINY_DENSE
    params = _params(cfg)
    reqs = generate_requests(
        16, rate_rps=200.0, vocab_size=cfg.vocab_size, prompt_rng=(4, 8),
        gen_short=(2, 4), gen_long=(4, 6), long_frac=0.3,
        shared_prefix=(2, 16, 0.8), seed=5)
    dense = ServingEngine(params, cfg,
                          serving=ServingConfig.from_flat(max_batch=4,
                                                          max_seq=64))
    paged = ServingEngine(params, cfg,
                          serving=ServingConfig.from_flat(max_batch=4,
                                                          max_seq=64,
                                                          page_size=8))
    ref = _tokens_by_rid(dense.run_closed_loop(reqs))
    stats = paged.run_closed_loop(reqs)
    assert _tokens_by_rid(stats) == ref
    # the workload repeats 16-token system prompts: reuse must fire
    assert stats.prefix_hits > 0
    assert stats.reused_tokens >= stats.prefix_hits * paged.page_size


def test_no_reuse_mode_is_still_bit_exact():
    cfg = TINY_DENSE
    params = _params(cfg)
    reqs = generate_requests(
        10, rate_rps=200.0, vocab_size=cfg.vocab_size, prompt_rng=(4, 8),
        gen_short=(2, 4), gen_long=(4, 6), long_frac=0.3,
        shared_prefix=(2, 16, 0.8), seed=6)
    dense = ServingEngine(params, cfg,
                          serving=ServingConfig.from_flat(max_batch=4,
                                                          max_seq=64))
    paged = ServingEngine(params, cfg,
                          serving=ServingConfig.from_flat(max_batch=4,
                                                          max_seq=64,
                                                          page_size=8,
                                                          prefix_reuse=False))
    ref = _tokens_by_rid(dense.run_closed_loop(reqs))
    stats = paged.run_closed_loop(reqs)
    assert _tokens_by_rid(stats) == ref
    assert stats.prefix_hits == 0 and paged.trie_pages == 0


# ---------------------------------------------------------------------------
# page lifecycle: no leaks, flush empties, engine reuse stays exact
# ---------------------------------------------------------------------------
def test_pages_freed_on_drain_and_engine_reuse_exact():
    cfg = TINY_DENSE
    params = _params(cfg)
    reqs = generate_requests(
        12, rate_rps=200.0, vocab_size=cfg.vocab_size, prompt_rng=(4, 8),
        gen_short=(2, 4), gen_long=(4, 6), long_frac=0.3,
        shared_prefix=(2, 16, 0.8), seed=7)
    engine = ServingEngine(params, cfg,
                           serving=ServingConfig.from_flat(max_batch=4,
                                                           max_seq=64,
                                                           page_size=8))
    first = _tokens_by_rid(engine.run_closed_loop(reqs))
    # mirror of the dense slot-reuse test: every slot-held page was
    # released at completion — residual pages are all trie-held prefixes
    assert engine.n_live == 0
    assert engine.pages_free + engine.trie_pages == engine.n_pages
    held = engine.trie_pages
    assert held > 0                         # prefixes stayed cached
    assert engine.flush_prefix_cache() == held
    assert engine.trie_pages == 0
    assert engine.pages_free == engine.n_pages
    # a second run on the SAME engine (pool + trie repopulated from
    # scratch) reproduces the first bit-exactly
    second = _tokens_by_rid(engine.run_closed_loop(reqs))
    assert second == first


def test_request_too_big_for_pool_raises_at_submit():
    cfg = TINY_DENSE
    engine = ServingEngine(_params(cfg), cfg,
                           serving=ServingConfig.from_flat(max_batch=2,
                                                           max_seq=32,
                                                           page_size=8,
                                                           n_pages=2))
    rng = np.random.RandomState(0)
    big = ServeRequest(rid=0, prompt=rng.randint(
        0, cfg.vocab_size, 20).astype(np.int32), max_new=8)
    with pytest.raises(ValueError, match="never be admitted"):
        engine.submit(big)


def test_paged_ctor_validation():
    cfg = TINY_DENSE
    params = _params(cfg)
    with pytest.raises(ValueError, match="whole pages"):
        ServingEngine(params, cfg,
                      serving=ServingConfig.from_flat(max_batch=2, max_seq=40,
                                                      page_size=16))
    with pytest.raises(ValueError, match="page_size"):
        ServingEngine(params, cfg,
                      serving=ServingConfig.from_flat(max_batch=2, max_seq=32,
                                                      page_size=0))
    with pytest.raises(ValueError, match="n_pages"):
        ServingEngine(params, cfg,
                      serving=ServingConfig.from_flat(max_batch=2, max_seq=32,
                                                      page_size=8, n_pages=0))
    with pytest.raises(ValueError, match="page_size"):
        ServingEngine(params, cfg,
                      serving=ServingConfig.from_flat(max_batch=2, max_seq=32,
                                                      n_pages=4))


# ---------------------------------------------------------------------------
# copy-on-write: forks never mutate their parent's frozen pages
# ---------------------------------------------------------------------------
def test_cow_fork_never_mutates_shared_pages():
    cfg = TINY_DENSE
    params = _params(cfg)
    rng = np.random.RandomState(21)
    prefix = rng.randint(0, cfg.vocab_size, 16).astype(np.int32)
    parent = ServeRequest(rid=0, prompt=prefix, max_new=2)
    tail = rng.randint(0, cfg.vocab_size, 5).astype(np.int32)
    child = ServeRequest(rid=1,
                         prompt=np.concatenate([prefix, tail]), max_new=6)
    engine = ServingEngine(params, cfg,
                           serving=ServingConfig.from_flat(max_batch=2,
                                                           max_seq=64,
                                                           page_size=8))
    engine.submit(parent)
    while engine.has_work:
        engine.step()
    # the parent published its two full prompt pages to the trie
    frozen = [p for p in range(engine.n_pages) if engine._pool.frozen[p]]
    assert len(frozen) == 2 and engine.trie_pages == 2
    snap_k = np.asarray(engine.cache["layers"]["k"][:, frozen])
    snap_v = np.asarray(engine.cache["layers"]["v"][:, frozen])
    engine.submit(child)
    done = []
    while engine.has_work:
        done += engine.step().completed
    assert engine.prefix_hits == 1
    assert engine.reused_tokens == 16       # both prefix pages forked
    # the child prefilled its tail and decoded 6 tokens — none of which
    # may have touched the frozen prefix KV it read through
    np.testing.assert_array_equal(
        np.asarray(engine.cache["layers"]["k"][:, frozen]), snap_k)
    np.testing.assert_array_equal(
        np.asarray(engine.cache["layers"]["v"][:, frozen]), snap_v)
    # and the fork's output is bit-equal to a solo dense run
    solo = ServingEngine(params, cfg,
                         serving=ServingConfig.from_flat(max_batch=1,
                                                         max_seq=64))
    ref = solo.run_closed_loop([ServeRequest(
        rid=1, prompt=child.prompt, max_new=child.max_new)])
    assert done[0].tokens.tolist() == ref.completions[0].tokens.tolist()


# ---------------------------------------------------------------------------
# version-pinned page validity across hot-swaps
# ---------------------------------------------------------------------------
def test_trie_generations_follow_the_version_ring():
    cfg = TINY_DENSE
    p0, p1 = _params(cfg, 0), _params(cfg, 1)
    reqs = generate_requests(
        14, rate_rps=40.0, vocab_size=cfg.vocab_size, prompt_rng=(4, 8),
        gen_short=(2, 4), gen_long=(4, 6), long_frac=0.3,
        shared_prefix=(2, 16, 0.8), seed=9)
    engine = ServingEngine(p0, cfg,
                           serving=ServingConfig.from_flat(max_batch=4,
                                                           max_seq=64,
                                                           page_size=8))
    t_mid = sorted(r.arrival for r in reqs)[len(reqs) // 2]
    stats = engine.run_simulated(reqs, ServeCostModel(),
                                 swaps=[(t_mid, p1, 1)])
    assert stats.swap_count == 1
    # every completion replays bit-exactly SOLO under its pinned version
    # — pages written under v0 stayed valid for v0-pinned slots after
    # the swap, and v1 admissions never read a v0 prefix
    by_rid = {r.rid: r for r in reqs}
    solos = {0: ServingEngine(p0, cfg,
                              serving=ServingConfig.from_flat(max_batch=1,
                                                              max_seq=64)),
             1: ServingEngine(p1, cfg,
                              serving=ServingConfig.from_flat(max_batch=1,
                                                              max_seq=64))}
    for c in stats.completions:
        ref = solos[c.version].run_closed_loop([ServeRequest(
            rid=c.rid, prompt=by_rid[c.rid].prompt,
            max_new=by_rid[c.rid].max_new)])
        assert c.tokens.tolist() == ref.completions[0].tokens.tolist(), \
            f"rid {c.rid} diverged under pinned v{c.version}"
    # the drained ring holds only the latest version, and the trie
    # dropped the retired generation with it
    assert engine.live_versions == [1]
    assert set(engine._trie.versions) <= {1}


def test_decode_time_paged_calibration():
    # a full dense batch read through the page table costs EXACTLY the
    # dense decode charge — the paged arm's advantage in bench_serve
    # comes from admitting more rows, never from a cheaper clock
    cost = ServeCostModel()
    for batch, pages_per_row in [(8, 16), (4, 4), (64, 16)]:
        assert cost.decode_time_paged(batch * pages_per_row,
                                      pages_per_row) \
            == pytest.approx(cost.decode_time(batch))

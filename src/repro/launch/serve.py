"""Serving launcher: continuous-batching inference over a slot KV cache.

Thin CLI over ``repro.serving.ServingEngine`` (docs/serving.md) — the
MLitB "prediction to the public at large" path. A seeded open-loop
request schedule (Poisson arrivals, mixed prompt/generation lengths,
heterogeneous client latencies — core/simulation.py) streams through the
engine's admission queue; requests of arbitrary length join and leave
mid-flight without retracing, because step fns are keyed on power-of-two
``(batch_cap, prompt_cap)`` buckets and decode runs one fixed
``(max_batch, max_seq)`` shape.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 32 --max-batch 8 --max-seq 256
  PYTHONPATH=src python -m repro.launch.serve --closure model.json \
      --requests 16 --simulate

``--simulate`` times the run on the discrete-event ``ServeCostModel``
clock (deterministic; what bench_serve.py gates); the default measures
real wall-clock. ``--page-size`` switches the KV cache to the PAGED
pool with cross-request prefix reuse (docs/serving.md §8) —
``--shared-prefix N`` generates the matching system-prompt-heavy
workload. ``serve_batch`` below is the one-batch-at-a-time reference
path the engine is benchmarked against.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.closure import ResearchClosure, jaxify
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.train.step import build_serve_programs


def greedy_sample(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)


def serve_batch(params, cfg, prompts: jnp.ndarray, gen: int,
                prefix=None, frames=None):
    """REFERENCE one-shot path: one fixed-shape batch, every row padded
    to the same prompt length and decoded for the same ``gen`` steps.
    prompts: (B, P) int32 -> generated (B, gen) int32.

    This is the baseline the continuous-batching engine is gated against
    (benchmarks/bench_serve.py) and the oracle the engine's per-request
    outputs are tested against (tests/test_serving.py)."""
    B, P = prompts.shape
    progs = build_serve_programs(cfg, paged=False)
    prefill = jax.jit(progs.prefill)
    decode = jax.jit(progs.decode_lockstep)
    batch = {"tokens": prompts}
    if prefix is not None:
        batch["prefix"] = prefix
    if frames is not None:
        batch["frames"] = frames
    logits, cache = prefill(params, batch)
    offset = cfg.n_prefix_tokens if cfg.arch_type == "vlm" else 0
    tok = greedy_sample(logits)
    out = [tok]
    for t in range(gen - 1):
        pos = jnp.asarray(P + offset + t, jnp.int32)
        logits, cache = decode(params, tok, pos, cache)
        tok = greedy_sample(logits)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def _serve_oneshot(params, cfg, args):
    """Fallback for arch families without engine support: the reference
    one-shot batch (random prompts + prefix/frames as the family needs)."""
    import time

    import numpy as np

    batch, prompt_len, gen = 4, 24, 12
    ks = jax.random.split(jax.random.PRNGKey(args.seed + 1), 2)
    prompts = jax.random.randint(ks[0], (batch, prompt_len), 0,
                                 cfg.vocab_size)
    kw = {}
    if cfg.arch_type == "vlm":
        kw["prefix"] = jax.random.normal(
            ks[1], (batch, cfg.n_prefix_tokens, cfg.d_model)) * 0.02
    if cfg.arch_type == "audio":
        kw["frames"] = jax.random.normal(
            ks[1], (batch, cfg.encoder_seq, cfg.d_model)) * 0.02
    t0 = time.time()
    out = serve_batch(params, cfg, prompts, gen, **kw)
    dt = time.time() - t0
    print(f"arch={cfg.name} [{cfg.arch_type}] one-shot reference path "
          f"(no continuous-batching engine for this family yet)")
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({batch * gen / dt:.1f} tok/s incl. compile)")
    print("sample:", np.asarray(out[0][:12]))
    return 0


def main(argv=None):
    """CLI entry point. Returns the engine run's ``ServeStats`` (0 for
    the one-shot families)."""
    from repro.core.simulation import ServeCostModel, generate_requests
    from repro.serving import (PagingConfig, SamplingConfig, ServingConfig,
                               ServingEngine, SpeculativeConfig)

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--closure", default=None)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=60.0,
                    help="open-loop arrival rate (requests/s)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--prompt-cap", type=int, default=None,
                    help="largest prefill bucket; longer prompts prefill "
                         "in chunks (default: max_seq, no chunking)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples with per-request keys")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=0,
                    help=">0 enables the PAGED KV cache: fixed-size "
                         "pages in one pooled buffer with cross-request "
                         "prefix reuse (docs/serving.md §8); must divide "
                         "max_seq. 0 = dense slot cache")
    ap.add_argument("--pages", type=int, default=0,
                    help="with --page-size: pool size in pages (default "
                         "max_batch * max_seq / page_size — dense parity)")
    ap.add_argument("--no-prefix-reuse", action="store_true",
                    help="with --page-size: disable the prefix trie "
                         "(pure paging, no cross-request sharing)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend one of 3 fixed system prompts of this "
                         "many tokens to ~70%% of requests (the "
                         "'millions of users, one system prompt' mix)")
    ap.add_argument("--decode-kernel", choices=("xla", "flash"),
                    default="xla",
                    help="decode attention implementation: 'flash' runs "
                         "the fused Pallas flash-decode kernel "
                         "(interpret-mode on CPU; docs/serving.md §9)")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help=">0 enables speculative decoding: a 1-layer "
                         "draft drafts K tokens per round and the served "
                         "model verifies them in one chunk dispatch "
                         "(greedy only; docs/serving.md §9)")
    ap.add_argument("--draft-window", type=int, default=32,
                    help="with --speculative: the draft LM's cacheless "
                         "context window")
    ap.add_argument("--simulate", action="store_true",
                    help="discrete-event clock instead of wall-clock")
    ap.add_argument("--swap-every", type=float, default=0.0,
                    help="with --simulate: hot-swap the params in as a "
                         "new version every S simulated seconds (the "
                         "same tree — exercises in-flight version "
                         "pinning; the histogram shows who saw what)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.closure:
        clo = ResearchClosure.load(args.closure)
        cfg, params = clo.config, jaxify(clo.params)
        print(f"loaded closure {args.closure} (arch={clo.arch}, "
              f"step={clo.step})")
    else:
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        # under jit: eagerly, each stacked weight is drawn in f32 before
        # its cast, several GB at published widths
        params = jax.jit(tf.init_params, static_argnums=1)(
            jax.random.PRNGKey(args.seed), cfg)

    if cfg.arch_type not in ("dense", "moe") and not cfg.layer_types:
        # vlm/audio/ssm and period hybrids: no slot-cache engine yet
        # (ROADMAP follow-up) — serve one reference batch through
        # serve_batch so every arch family the old launcher handled still
        # serves. layer_types hybrids take the engine (paged, flash
        # decode, --no-prefix-reuse)
        return _serve_oneshot(params, cfg, args)

    max_seq = args.max_seq
    if cfg.sliding_window:
        max_seq = min(max_seq, cfg.sliding_window)
    # size the workload so every draw fits prompt + max_new <= max_seq,
    # whatever --max-seq (or a window clamp) left us with
    g_long_hi = max(2, max_seq // 2)
    g_long_lo = max(1, max_seq // 4)
    p_hi = max(1, min(max(8, max_seq // 8), max_seq - g_long_hi))
    shared = None
    if args.shared_prefix > 0:
        # keep prefix + tail + generation within max_seq
        g_long_hi = min(g_long_hi, max(1, (max_seq - args.shared_prefix
                                           - p_hi) // 2))
        g_long_lo = min(g_long_lo, g_long_hi)
        shared = (3, args.shared_prefix, 0.7)
    reqs = generate_requests(
        args.requests, rate_rps=args.rate, vocab_size=cfg.vocab_size,
        prompt_rng=(min(4, p_hi), p_hi),
        gen_short=(1, min(12, g_long_lo)),
        gen_long=(g_long_lo, g_long_hi),
        shared_prefix=shared,
        seed=args.seed + 1)
    speculative = None
    if args.speculative > 0:
        # the draft is a 1-layer sibling of the served model, freshly
        # initialized: draft quality only moves the acceptance rate, so
        # even an untrained draft serves the EXACT greedy stream
        import dataclasses as _dc

        draft_cfg = _dc.replace(cfg, name=cfg.name + "-draft", n_layers=1)
        draft_params = tf.init_params(jax.random.PRNGKey(args.seed + 2),
                                      draft_cfg)
        speculative = SpeculativeConfig(
            draft_params=draft_params, draft_cfg=draft_cfg,
            k=args.speculative, window=args.draft_window)
    paging = None
    if args.page_size:
        paging = PagingConfig(page_size=args.page_size,
                              n_pages=args.pages or None,
                              prefix_reuse=not args.no_prefix_reuse)
    engine = ServingEngine(params, cfg, serving=ServingConfig(
        max_batch=args.max_batch, max_seq=max_seq,
        prompt_cap=args.prompt_cap, decode_kernel=args.decode_kernel,
        sampling=SamplingConfig(temperature=args.temperature,
                                top_k=args.top_k, sample_seed=args.seed),
        paging=paging, speculative=speculative))
    if args.simulate:
        swaps = []
        if args.swap_every > 0:
            horizon = max(r.arrival for r in reqs) + 4.0
            t, ver = args.swap_every, 1
            while t < horizon:
                swaps.append((t, params, ver))
                t += args.swap_every
                ver += 1
        stats = engine.run_simulated(reqs, ServeCostModel(), swaps=swaps)
        mode = "simulated"
    else:
        stats = engine.run_closed_loop(reqs)
        mode = "wall-clock"
    print(f"arch={cfg.name} requests={stats.n_requests} "
          f"max_batch={args.max_batch} max_seq={max_seq}")
    print(f"{mode}: {stats.gen_tokens} tokens in {stats.makespan:.2f}s "
          f"({stats.tokens_per_s:.1f} tok/s), p50={stats.p50_latency:.3f}s "
          f"p95={stats.p95_latency:.3f}s")
    print(f"engine: {stats.engine_steps} steps, "
          f"{stats.decode_rows_live}/{stats.decode_rows_total} live decode "
          f"rows, {stats.trace_count} traces over buckets "
          f"{engine.buckets_seen}, peak concurrency "
          f"{stats.concurrency_peak}")
    if engine.paged:
        print(f"paged: {engine.n_pages} pages x {engine.page_size} tok, "
              f"peak resident {stats.pages_peak}, prefix hits "
              f"{stats.prefix_hits} ({stats.reused_tokens} tokens never "
              f"re-prefilled), {engine.trie_pages} pages cached for reuse")
    if engine.paged:
        print(f"decode KV: {stats.decode_kv_tokens} live tokens in "
              f"{stats.decode_pages_live} live pages, of "
              f"{stats.decode_pages_walked} pages walked")
    elif engine.decode_kernel == "flash":
        print(f"decode KV: {stats.decode_kv_tokens} live tokens streamed "
              f"(vs {stats.decode_rows_total * max_seq} dense)")
    if stats.ttft_p50 is not None:
        gap = stats.token_gap_mean
        print(f"{mode}: time to first token p50={stats.ttft_p50:.3f}s "
              f"p95={stats.ttft_p95:.3f}s (queue wait p95="
              f"{stats.queue_wait_p95:.3f}s), mean gap between tokens "
              f"{'n/a' if gap is None else f'{1e3 * gap:.1f} ms'}")
    if engine.serving.speculative is not None:
        rate = stats.accepted / max(stats.drafted, 1)
        print(f"speculative: drafted {stats.drafted}, accepted "
              f"{stats.accepted} ({100 * rate:.0f}%) over "
              f"{stats.spec_rounds} rounds, verify buckets "
              f"{engine.verify_buckets_seen}")
    if args.simulate:
        from repro.launch.train_serve import format_version_histogram
        print(f"served version histogram ({stats.swap_count} in-flight "
              f"swaps applied):")
        for line in format_version_histogram(stats):
            print(line)
    first = min(stats.completions, key=lambda c: c.rid)
    print("sample:", first.tokens[:12])
    return stats


if __name__ == "__main__":
    main()

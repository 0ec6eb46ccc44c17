"""Serve path: ``ServingEngine.submit`` and ``step(now)`` under an open loop.

Set-up makes the weights on the chip from the seed, builds the engine as
the configuration's ``serving`` group says, and warms every prefill bucket
the traffic can reach (each power-of-two batch up to ``max_batch`` with
each chunk size from ``prompt_bucket_min`` to ``prompt_cap``) and the
decode program, through ``submit``/``step`` with prompts of their own.

The window submits each request when it is due and steps the engine in a
loop; a request's latency runs from when it was due to the step that
completed it. When the window closes the loop keeps offering load and
stepping until every request due in the window has finished, or
``drain_max_s`` has passed (those still open count as failed, with the
latency they had reached).

The comparison takes a sample of finished requests drawn from the seed,
the longest among them, and runs the plain reference (float32, highest
precision) once over each prompt with its served tokens: the number is
the widest gap by which a served token's logit lies below the reference's
best at that position, in units of the spread (standard deviation) of
the reference's logits there, so that it reads alike at any width.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from chipbench import flops, refmodel, traffic, weights
from chipbench.drivers import common

WARM_RID = 1 << 30


def _engine(run, params):
    from repro.serving import (PagingConfig, SamplingConfig, ServingConfig,
                               ServingEngine)
    sv = run.config["serving"]
    return ServingEngine(params, common.arch(run.config), serving=ServingConfig(
        max_batch=sv["max_batch"], max_seq=sv["max_seq"],
        prompt_bucket_min=sv["prompt_bucket_min"],
        prompt_cap=sv["prompt_cap"], decode_kernel=sv["decode_kernel"],
        paging=PagingConfig(page_size=sv["page_size"],
                            prefix_reuse=sv["prefix_reuse"]),
        sampling=SamplingConfig(temperature=0.0)))


def buckets(sv: dict):
    """Every (batch, chunk) prefill bucket the engine can dispatch."""
    b, out = 1, []
    while b <= sv["max_batch"]:
        c = sv["prompt_bucket_min"]
        while True:
            out.append((b, min(c, sv["prompt_cap"])))
            if c >= sv["prompt_cap"]:
                break
            c *= 2
        b *= 2
    return out


def warm(run, eng, vocab: int) -> None:
    from repro.serving import ServeRequest
    rng = run.rng("warm")
    rid = WARM_RID
    for b, c in buckets(run.config["serving"]) + [(1, 0)]:
        for _ in range(b):
            plen, new = (c, 1) if c else (8, 3)    # (1, 0): the decode
            eng.submit(ServeRequest(rid, rng.integers(0, vocab, plen,
                                                      dtype=np.int32), new))
            rid += 1
        while eng.has_work:
            eng.step()
    eng.flush_prefix_cache()


def setup(run):
    from repro.models import transformer as tf
    cfg = common.arch(run.config)
    shape = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    with run.span("cb:serve.build"):
        params = weights.make(run.key("weights"), shape)
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
    from repro.serving import ServeRequest
    eng, reqs = st["eng"], st["reqs"]
    due = [r for r in reqs if r.arrival < run.seconds]
    want = {r.rid for r in due}
    finished = {}            # rid -> (finish s, tokens)
    late = []                # how late each submission was (s)
    steps = []               # (t0 ns, t1 ns, decode KV pages)
    refused = set()
    i, closed = 0, False
    horizon = run.seconds + run.traffic["drain_max_s"]
    run.open_window()
    while True:
        now = run.tick()
        if not closed and now >= run.seconds:
            run.close_window()
            closed = True
        if closed and (want <= finished.keys() | refused or now >= horizon):
            break
        with run.span("cb:serve.submit"):
            while i < len(reqs) and reqs[i].arrival <= now:
                r = reqs[i]
                if not eng.submit(ServeRequest(r.rid, r.prompt, r.max_new,
                                               arrival=r.arrival), now=now):
                    refused.add(r.rid)
                late.append(now - r.arrival)
                i += 1
        if eng.has_work:
            t0 = time.time_ns()
            with run.span("cb:serve.step"):
                rep = eng.step(now)
            t = run.elapsed()
            steps.append((t0, time.time_ns(), sum(rep.decode_pages)))
            for c in rep.completed:
                finished[c.rid] = (t, c.tokens)
            refused.update(s.rid for s in rep.shed)
        else:
            nxt = reqs[i].arrival if i < len(reqs) else horizon
            with run.span("cb:serve.idle"):
                time.sleep(min(max(nxt - run.elapsed(), 0.0), 0.002))
    run.seal()
    end = run.elapsed()
    lat = []
    for r in due:
        done = finished.get(r.rid)
        lat.append((done[0] if done else end) - r.arrival)
    missing = sum(r.rid not in finished for r in due)
    win = run.window_s
    by_rid = {r.rid: r for r in reqs}
    in_win = [(rid, toks) for rid, (t, toks) in finished.items()
              if t <= win and rid in by_rid]
    model = flops.Dense.of(run.config)
    return {
        "metrics": {
            "serve_latency_p95_s": float(np.percentile(lat, 95))},
        "completed_tokens_per_s": sum(len(t) for _, t in in_win) / win,
        "attempted": len(due), "failed": missing,
        "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_max_s": float(np.max(lat)),
        "late_p95_s": float(np.percentile(late, 95)) if late else 0.0,
        "late_max_s": float(np.max(late)) if late else 0.0,
        "window_s": win, "drain_s": end - win,
        "engine_steps": len(steps),
        "retraces": eng.trace_count - st["traces"],
        "window_flops": sum(flops.serve_request_flops(
            model, len(by_rid[rid].prompt), len(t)) for rid, t in in_win),
        "steps": steps, "finished": finished,
        "page_size": run.config["serving"]["page_size"],
        "max_batch": run.config["serving"]["max_batch"]}


def release(run, st):
    st["eng"] = None
    gc.collect()


def sample(run, st, counters):
    """Finished requests for the comparison, drawn from the seed: the one
    with most served tokens, then others until the sample holds
    ``check.requests`` requests and ``check.min_tokens`` served tokens."""
    chk = run.traffic["check"]
    by_rid = {r.rid: r for r in st["reqs"]}
    done = sorted((rid, toks) for rid, (_, toks) in
                  counters["finished"].items() if rid in by_rid)
    if not done:
        raise RuntimeError("no request finished")
    longest = max(done, key=lambda x: (len(x[1]), -x[0]))
    rest = [done[j] for j in run.rng("check").permutation(len(done))
            if done[j][0] != longest[0]]
    pick, total = [longest], len(longest[1])
    for d in rest:
        if len(pick) >= chk["requests"] and total >= chk["min_tokens"]:
            break
        pick.append(d)
        total += len(d[1])
    return [(by_rid[rid].prompt, np.asarray(toks)) for rid, toks in pick]


def positions(picked, length: int):
    """Token rows (N, length) of prompt + served tokens, and the (row,
    column) of every position whose logits chose a served token."""
    toks = np.zeros((len(picked), length), np.int32)
    rows, cols, served = [], [], []
    for n, (p, g) in enumerate(picked):
        seq = np.concatenate([p, g[:-1]])
        toks[n, :len(seq)] = seq
        rows += [n] * len(g)
        cols += list(range(len(p) - 1, len(p) - 1 + len(g)))
        served += list(g)
    return toks, np.asarray(rows), np.asarray(cols), np.asarray(served)


def gaps(run, st, counters, precision: str = None):
    """Widest gap below the reference's best of the served tokens (or,
    with ``precision``, of the tokens that precision puts first)."""
    picked = sample(run, st, counters)
    toks, rows, cols, served = positions(picked,
                                         run.config["serving"]["max_seq"])
    w = weights.named(weights.make(run.key("weights"), st["shape"]))
    ref = np.asarray(refmodel.logits_at(w, run.config, toks, rows, cols))
    if precision is not None:
        low = refmodel.logits_at(w, run.config, toks, rows, cols, precision)
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

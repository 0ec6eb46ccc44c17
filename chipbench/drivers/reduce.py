"""Reduce path: ``MasterReducer.reduce_and_step`` (fused flat buffer,
blocktopk channel with error feedback, AdaGrad).

Set-up makes the parameters and W gradient trees on the chip from the
seed and builds the reducer; its first calls compile. The window calls
``reduce_and_step`` on the same messages again and again (the residuals
change what each call sends) and waits for the parameters each call
returns, as the master does before it broadcasts them.

Every block of 128 entries is independent of the others: its top-k, its
residual, its entries of the reduced gradient and of the AdaGrad state.
So the comparison takes a sample of blocks drawn from the seed and
replays the whole run (set-up and window calls) on them with a plain
reference: the residuals must be equal bit for bit, and the parameters
close to the reference's, against how far the reference moved them.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, weights
from chipbench.drivers import common


def _names(mix):
    return [f"w{i}" for i in range(mix["workers"])]


def _inputs(run, shape):
    """(parameters, [gradient trees]) made on the chip from the seed."""
    mix = run.traffic
    p0 = weights.make(run.key("weights"), shape)
    scale = mix["grad_scale"]

    @jax.jit
    def grads(key):
        leaves, treedef = jax.tree.flatten(shape)
        out = []
        for w in range(mix["workers"]):
            ks = jax.random.split(jax.random.fold_in(key, w), len(leaves))
            out.append(jax.tree.unflatten(treedef, [
                scale * jax.random.normal(k, x.shape, jnp.float32)
                for k, x in zip(ks, leaves)]))
        return out

    return p0, grads(run.key("grads"))


def setup(run):
    from repro.core.compression import GradientCompressor
    from repro.core.reducer import MasterReducer
    from repro.models import transformer as tf
    from repro.optim import get_optimizer
    mix = run.traffic
    cfg = common.arch(run.config)
    shape = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    with run.span("cb:reduce.build"):
        p0, gs = _inputs(run, shape)
        red = MasterReducer(
            p0, get_optimizer(mix["optimizer"], lr=mix["lr"],
                              eps=mix["eps"]),
            GradientCompressor("blocktopk",
                               frac=mix["block_k"] / mix["block_w"],
                               block_w=mix["block_w"]))
    del p0
    msgs = {w: (g, c) for w, g, c in zip(_names(mix), gs, mix["counts"])}
    for _ in range(mix["warmup_calls"]):
        with run.span("cb:reduce.step"):
            jax.block_until_ready(red.reduce_and_step(msgs))
    return {"red": red, "msgs": msgs, "shape": shape,
            "calls": mix["warmup_calls"]}


def window(run, st):
    red, msgs = st["red"], st["msgs"]
    calls = 0
    run.open_window()
    while run.window_open():
        with run.span("cb:reduce.step"):
            out = red.reduce_and_step(msgs)
        with run.span("cb:reduce.wait"):
            jax.block_until_ready(out)
        calls += 1
    secs = run.close_window()
    st["calls"] += calls
    mix = run.traffic
    n = red.flat_n
    return {"metrics": {"reduce_steps_per_s": calls / secs},
            "attempted": calls, "failed": 0, "window_s": secs,
            "calls_total": st["calls"], "flat_n": n,
            "step_bytes": flops.reduce_step_bytes(n, mix["workers"]),
            "kernel_bytes": flops.topk_kernel_bytes(
                n, mix["workers"], mix["block_k"], mix["block_w"])}


def release(run, st):
    """Keep the sampled blocks of the final state; free the reducer."""
    red, mix = st["red"], run.traffic
    n, bw = red.flat_n, mix["block_w"]
    blocks = np.sort(run.rng("check").choice(
        n // bw, size=min(mix["sample_blocks"], n // bw), replace=False))
    idx = jnp.asarray((blocks[:, None] * bw + np.arange(bw)).reshape(-1))
    st["idx"] = idx
    st["final"] = {
        "params": np.asarray(red.flat_params[idx]),
        "accum": np.asarray(red.opt_state["accum"][idx]),
        "res": np.stack([np.asarray(red._residuals[w][idx])
                         if w in red._residuals else
                         np.zeros(idx.shape, np.float32)
                         for w in _names(mix)])}
    st["red"] = st["msgs"] = None
    gc.collect()


def flat(tree) -> jnp.ndarray:
    """The flat layout of a message: leaves in ``jax.tree.leaves`` order,
    each raveled, as float32."""
    return jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


def replay(run, st, precision: str = "f32"):
    """The reference run on the sampled blocks: ``calls`` master steps of
    error feedback, block top-k (first largest |.| first, k times),
    weighted mean and AdaGrad. Returns (params, accum, residuals)."""
    mix = run.traffic
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    p0, gs = _inputs(run, st["shape"])
    idx = st["idx"]
    p = flat(p0)[idx].astype(dt)
    g = jnp.stack([flat(x)[idx] for x in gs]).astype(dt)
    del p0, gs
    W, bw, k = mix["workers"], mix["block_w"], mix["block_k"]
    g = g.reshape(W, -1, bw)
    total = jnp.asarray(sum(mix["counts"]), dt)
    lr, eps = mix["lr"], mix["eps"]

    def call(_, carry):
        p, acc, r = carry
        c = g + r
        rem, sent = c, jnp.zeros_like(c)
        pos = jnp.arange(bw)
        for _ in range(k):
            mag = jnp.abs(rem)
            first = jnp.argmax(mag, axis=-1)        # first of the largest
            hit = pos == first[..., None]
            sent = jnp.where(hit, rem, sent)
            rem = jnp.where(hit, 0, rem)
        gbar = (sent.sum(0) / total).reshape(-1)
        acc = acc + gbar * gbar
        p = p - lr * gbar / (jnp.sqrt(acc) + eps)
        return p, acc, rem

    init = (p, jnp.zeros_like(p), jnp.zeros_like(g))
    p, acc, r = jax.jit(lambda c: jax.lax.fori_loop(
        0, st["calls"], call, c))(init)
    return (np.asarray(p, np.float32), np.asarray(acc, np.float32),
            np.asarray(r.reshape(W, -1), np.float32),
            np.asarray(flat(weights.make(run.key("weights"), st["shape"]))
                       [idx]))


def readings(got: dict, ref) -> dict:
    p, acc, r, p0 = ref
    moved = float(np.max(np.abs(p - p0)))
    return {"residual_mismatch": float(np.sum(got["res"] != r)),
            "param_err": float(np.max(np.abs(got["params"] - p))) / moved}


def check(run, st, counters):
    got = readings(st["final"], replay(run, st))
    return [(k, v, run.limits[k]["limit"]) for k, v in got.items()]


def control(run, st, counters):
    """The control's readings: the reference in bfloat16 in the program's
    place."""
    p, acc, r, _ = replay(run, st, "bf16")
    return readings({"params": p, "res": r}, replay(run, st))

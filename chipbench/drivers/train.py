"""Train path: the mesh trainer's ``ElasticMeshSGD.step``.

Set-up builds the engine with the program's own ``run_mesh_training``
(no steps), puts the benchmark's weights into its state, and drives that
one engine through its first three steps with the window's own call and
batches (every row drawn afresh from the seed). Those steps compile the
program. The window then calls ``step`` on the same engine until
``--seconds`` have passed; ``step`` returns host floats, so each step has
finished when it returns.

The comparison replays the first three steps with the plain reference
(float32 at highest precision) from the same weights and batches, and
compares each step's loss, the norm of the first gradient as AdaGrad got
it (read from the accumulator after step 1) and the norm of each leaf's
change after step 3 (the state step 4 starts from). Leaves whose
reference gradient is under a thousandth of the median leaf's are left
out of the change: they move by round-off alone.
"""
from __future__ import annotations

import gc
import math
import statistics

import jax
import jax.numpy as jnp

from chipbench import flops, refmodel, traffic, weights
from chipbench.drivers import common

STEPS_COMPARED = 3
QUIET_LEAF = 1e-3


def _batches(run, vocab: int):
    tr = run.traffic
    return traffic.lm_batches(run.rng("batches"), tr["tokens"], vocab,
                              tr["rows_per_chip"] * run.chips, tr["seq"])


def _optimizer(tr: dict):
    from repro.optim import get_optimizer
    return get_optimizer(tr["optimizer"], lr=tr["lr"], eps=tr["eps"],
                         init_accum=tr["init_accum"])


def setup(run):
    from repro.launch.train import run_mesh_training
    from repro.train.step import make_train_state

    tr = run.traffic
    cfg = common.arch(run.config)
    opt = _optimizer(tr)
    rows = tr["rows_per_chip"] * run.chips
    with run.span("cb:train.build"):
        eng, _ = run_mesh_training(
            cfg, opt, steps=0, batch=rows, seq=tr["seq"],
            n_workers=tr["virtual_workers_per_chip"] * run.chips,
            devices=run.devices)
        shape = common.abstract(eng.state["params"])
        p0 = weights.make(run.key("weights"), shape)
        placement = jax.tree.map(lambda x: x.sharding, eng.state)
        eng.state = jax.device_put(make_train_state(p0, opt), placement)
    gen = _batches(run, cfg.vocab_size)
    losses, g1 = [], None
    for i in range(STEPS_COMPARED):
        with run.span("cb:train.step"):
            losses.append(eng.step(next(gen))["loss"])
        if i == 0:       # AdaGrad's accumulator now holds init + g1^2
            acc = weights.named(eng.state["opt"]["accum"])
            g1 = refmodel.leaf_norms({
                k: jnp.sqrt(jnp.maximum(a - tr["init_accum"], 0.0))
                for k, a in acc.items()})
    pn, p0n = weights.named(eng.state["params"]), weights.named(p0)
    change = refmodel.leaf_norms({k: pn[k] - p0n[k] for k in pn})
    del p0, p0n, pn
    return {"eng": eng, "gen": gen, "shape": shape, "losses": losses,
            "g1": g1, "change": change}


def window(run, st):
    eng, gen = st["eng"], st["gen"]
    tokens = steps = failed = 0
    run.open_window()
    while run.window_open():
        with run.span("cb:train.batch"):
            batch = next(gen)
        with run.span("cb:train.step"):
            m = eng.step(batch)
        tokens += m["tokens"]
        steps += 1
        failed += not math.isfinite(m["loss"])
    secs = run.close_window()
    tr = run.traffic
    per_token = flops.train_flops_per_token(flops.Dense.of(run.config),
                                            tr["seq"])
    return {"metrics": {"train_tokens_per_s": tokens / secs},
            "attempted": steps, "failed": failed, "tokens": tokens,
            "window_s": secs, "flops_per_token": per_token,
            "chips": run.chips}


def release(run, st):
    st["eng"] = st["gen"] = None
    gc.collect()


def reference(run, st, precision: str = "f32", lr=None, rows=None):
    """(losses, first-gradient norms, change norms) of the plain
    reference over the first three batches, from the same weights
    (``lr`` and the first ``rows`` of each batch plant a fault)."""
    tr = run.traffic
    p0 = weights.named(weights.make(run.key("weights"), st["shape"]))
    gen = _batches(run, run.config["vocab_size"])
    batches = [{k: v[:rows] for k, v in next(gen).items()}
               for _ in range(STEPS_COMPARED)]
    return refmodel.train_trajectory(
        p0, run.config, batches, tr["lr"] if lr is None else lr, tr["eps"],
        tr["init_accum"], tr["reference_block_rows"], precision)


def readings(prog, ref):
    """The compared numbers of a trajectory ``prog`` against ``ref``:
    each of (losses, g1, change)."""
    losses, g1, change = prog
    rl, rg, rc = ref
    loss = max(abs(a - b) / abs(b) for a, b in zip(losses, rl))
    leaves = sorted(rg)
    grad = common.worst_norm_gap(g1, rg, leaves)
    med = statistics.median(rg[k] for k in leaves)
    moving = [k for k in leaves if rg[k] >= QUIET_LEAF * med]
    chg = common.worst_norm_gap(change, rc, moving)
    return {"loss": loss, "grad_norm": grad, "param_change": chg}


def check(run, st, counters):
    ref = reference(run, st)
    got = readings((st["losses"], st["g1"], st["change"]), ref)
    return [(k, v, run.limits[k]["limit"]) for k, v in got.items()]


def control(run, st, counters=None):
    """The control's readings: the reference in bfloat16 in the program's
    place."""
    return readings(reference(run, st, "bf16"), reference(run, st))


def faults(run, st, counters=None):
    """Readings of the reference with a fault planted, in the program's
    place: a step that returns its state unchanged (it reads 1 on the
    gradient and the change by their measure; its losses are those of
    the first weights) and half of each batch left out."""
    ref = reference(run, st)
    rows = run.traffic["rows_per_chip"] * run.chips // 2
    kept = readings(reference(run, st, lr=0.0), ref)
    return {"unchanged": dict(kept, grad_norm=1.0, param_change=1.0),
            "half_batch": readings(reference(run, st, rows=rows), ref)}

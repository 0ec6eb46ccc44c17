"""Training launcher: elastic distributed SGD on synthetic LM data.

Runs the REAL production path end-to-end on whatever devices exist:
config -> model -> sharded train step -> ElasticMeshSGD (the paper's
event semantics) -> research-closure checkpoint.

Examples:
  # ~100M model, a few hundred steps (CPU-hours scale)
  PYTHONPATH=src python -m repro.launch.train --arch mlitb-lm-100m \
      --steps 300 --batch 8 --seq 256 --closure-out model.json

  # any assigned arch, reduced variant (smoke scale)
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
      --steps 20

  # with simulated worker churn (paper scenario)
  ... --churn "10:leave:1,15:join:1"

  # discrete-event simulated fleet instead of the mesh engine, flat or
  # hierarchical (docs/hierarchy.md; grouped TrainingConfig surface)
  PYTHONPATH=src python -m repro.launch.train --reduced --simulate \
      --steps 8 --regions 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import obs
from repro.core.closure import ResearchClosure
from repro.core.mesh_engine import ElasticMeshSGD
from repro.data.datasets import synthetic_lm
from repro.distributed.sharding import (param_specs, to_shardings,
                                        train_batch_specs)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.optim import get_optimizer
from repro.train.step import build_train_step, make_train_state


def data_stream(vocab: int, batch: int, seq: int, seed: int = 0):
    toks = synthetic_lm(2_000_000, vocab=min(vocab, 65_536), seed=seed)
    rng = np.random.RandomState(seed)
    while True:
        starts = rng.randint(0, len(toks) - seq - 1, size=batch)
        x = np.stack([toks[s:s + seq] for s in starts])
        y = np.stack([toks[s + 1:s + seq + 1] for s in starts])
        yield {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}


def build_mesh_training(cfg, opt, *, batch: int, n_workers: int,
                        seed: int = 0, devices=None) -> ElasticMeshSGD:
    """The engine of the paper's synchronous data-parallel SGD over a
    ``(data, model=1)`` mesh of ``devices`` (default: every local device).
    The train state is placed by ``param_specs(..., "train")`` (FSDP over
    ``data``), the batch is split over ``data``, and each ``data`` slice
    holds ``n_workers / len(devices)`` virtual workers."""
    devices = list(devices if devices is not None else jax.devices())
    mesh = make_host_mesh(data=len(devices), model=1, devices=devices)
    params = jax.jit(tf.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    state = make_train_state(params, opt)
    state_sh = to_shardings(param_specs(state, cfg, mesh, "train"), mesh)
    batch_sh = to_shardings(train_batch_specs(mesh, batch), mesh)
    return ElasticMeshSGD(
        train_step=build_train_step(cfg, opt, remat=False),
        state=jax.device_put(state, state_sh), n_workers=n_workers,
        global_batch=batch,
        jit_kwargs=dict(in_shardings=(state_sh, batch_sh),
                        out_shardings=(state_sh, None)),
        mesh=mesh)


def run_mesh_training(cfg, opt, *, steps: int, batch: int, seq: int,
                      n_workers: int, seed: int = 0, churn=None,
                      log_every: int = 10, devices=None):
    """Build the engine (``build_mesh_training``) and train it for
    ``steps`` steps of synthetic LM data, applying ``churn`` events.
    Returns ``(engine, losses)``.

    Spans: ``mlitb.train.build`` around the build and ``mlitb.train.loop``
    (arg ``steps``) around the loop. The printed tok/s counts from the
    end of the first step, which compiles the program."""
    with obs.span("train.build"):
        eng = build_mesh_training(cfg, opt, batch=batch,
                                  n_workers=n_workers, seed=seed,
                                  devices=devices)
    churn = churn or {}
    stream = data_stream(cfg.vocab_size, batch, seq, seed)
    losses = []
    t0 = None
    with obs.span("train.loop", steps=steps):
        for i in range(steps):
            for kind, idx in churn.get(i, []):
                getattr(eng, kind)(idx)
                print(f"step {i}: worker {idx} {kind}s "
                      f"({eng.n_live}/{eng.n_workers} live)")
            metrics = eng.step(next(stream))
            losses.append(metrics["loss"])
            if i % log_every == 0 or i == steps - 1:
                if t0 is None:       # the first step: compile and run
                    rate = "compile step"
                else:                # steps 1..i since the first ended
                    secs = max(time.time() - t0, 1e-9)
                    rate = f"{metrics['tokens'] * i / secs:.0f} tok/s"
                print(f"step {i:5d} loss {metrics['loss']:.4f} "
                      f"tokens {int(metrics['tokens'])} live {eng.n_live} "
                      f"({rate})")
            if t0 is None:
                t0 = time.time()
    return eng, losses


def parse_churn(spec: str):
    """'10:leave:1,15:join:1' -> {step: [(kind, worker_idx)]}"""
    out = {}
    if not spec:
        return out
    for item in spec.split(","):
        step, kind, idx = item.split(":")
        out.setdefault(int(step), []).append((kind, int(idx)))
    return out


def run_simulated(cfg, *, steps: int, regions: int, T: float,
                  seed: int) -> int:
    """The discrete-event path behind ``--simulate``: the grouped
    ``TrainingConfig`` construction surface end-to-end, flat
    (``regions=1``) or two-tier (docs/hierarchy.md)."""
    from repro.core import HierarchyConfig, TrainingConfig
    from repro.core.config import DeadlineConfig
    from repro.launch.train_serve import build_training

    hier = None if regions <= 1 else HierarchyConfig(
        n_regions=regions, inner_steps=4, gossip=True, gossip_frac=0.25)
    training = TrainingConfig(T=T, deadline=DeadlineConfig(quantile=0.5),
                              hierarchy=hier)
    master, cluster, _ = build_training(cfg, training=training, seed=seed)
    if hier is None:
        logs = master.run(steps)
        losses = [lg.loss for lg in logs if lg.loss == lg.loss]
        print(f"flat: {len(logs)} iterations, clock={master.clock:.2f}s, "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
        return 0
    outer = max(1, steps // hier.inner_steps)
    logs = master.run(outer)
    losses = [lg.loss for lg in logs if lg.loss == lg.loss]
    s = master.summary()
    print(f"hierarchy: {regions} regions x {hier.inner_steps} inner, "
          f"{outer} outer steps, clock={master.clock:.2f}s, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    print(f"wan: {s['wan_bytes']} bytes "
          f"({100 * s['wan_bytes_frac']:.2f}% of gradient traffic), "
          f"comm ratio {s['communication_ratio']:.3f}")
    return 0


def main(argv=None):
    """CLI entry point. Returns the per-step losses (``--simulate``: 0)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="mlitb-lm-100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="adagrad",
                    choices=["adagrad", "adam", "sgd"])
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--n-workers", type=int, default=4,
                    help="virtual workers (data slices)")
    ap.add_argument("--churn", default="",
                    help="step:leave|join:worker_idx,...")
    ap.add_argument("--closure-out", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--simulate", action="store_true",
                    help="drive the discrete-event simulated fleet "
                         "(build_training) instead of the mesh engine")
    ap.add_argument("--regions", type=int, default=1,
                    help="with --simulate: >1 builds the two-tier "
                         "hierarchy (docs/hierarchy.md)")
    ap.add_argument("--T", type=float, default=0.5,
                    help="with --simulate: iteration budget (s)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.simulate:
        return run_simulated(cfg, steps=args.steps, regions=args.regions,
                             T=args.T, seed=args.seed)
    lr = args.lr if args.lr is not None else \
        {"adagrad": 0.05, "adam": 3e-4, "sgd": 0.1}[args.optimizer]
    opt = get_optimizer(args.optimizer, lr=lr)

    print(f"arch={cfg.name} params={cfg.n_params()/1e6:.1f}M "
          f"devices={len(jax.devices())}")
    assert args.batch % args.n_workers == 0
    eng, losses = run_mesh_training(
        cfg, opt, steps=args.steps, batch=args.batch, seq=args.seq,
        n_workers=args.n_workers, seed=args.seed,
        churn=parse_churn(args.churn), log_every=args.log_every)

    if args.closure_out:
        clo = ResearchClosure(
            arch=cfg.name, config=cfg,
            algorithm={"optimizer": args.optimizer, "lr": lr,
                       "reduce": "weighted-mean", "steps": args.steps},
            params=jax.tree.map(np.asarray, eng.state["params"]),
            metrics=[{"step": i, "loss": float(v)}
                     for i, v in enumerate(losses)],
            step=args.steps)
        clo.save(args.closure_out)
        print(f"saved research closure -> {args.closure_out} "
              f"(digest {clo.digest})")
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    main()

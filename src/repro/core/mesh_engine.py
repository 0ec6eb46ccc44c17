"""ElasticMeshSGD — the paper's runtime mapped onto a TPU mesh.

Each slice of the ``data`` mesh axis is a *virtual worker* (DESIGN.md §2):

  - the adaptive scheduler's per-worker budgets become per-step SAMPLE
    budgets: worker w's contiguous row-slice of the global batch has its
    first ``budget_w`` rows mask=1, the rest 0;
  - worker churn (paper: closed tabs) = zeroing a worker's mask rows. No
    recompile, no resharding — the weighted reduce (sum/global-count baked
    into the train step) makes the math identical to the master dropping
    that client's message;
  - the master's reduce+AdaGrad step is the GSPMD-sharded optimizer
    update inside the same jit.

This is the production counterpart of core/simulation.py: same event
semantics, real gradients, collectives instead of WebSockets.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import obs
from repro.core.scheduler import AdaptiveScheduler
from repro.distributed.activation_sharding import activation_sharding
from repro.distributed.sharding import batch_axis, batch_spec

PyTree = Any


class ElasticMeshSGD:
    def __init__(self, *, train_step: Callable, state: PyTree,
                 n_workers: int, global_batch: int,
                 scheduler: Optional[AdaptiveScheduler] = None,
                 jit_kwargs: Optional[dict] = None,
                 mesh: Optional[Mesh] = None):
        """``mesh``: the step is traced and run on it (``jax.set_mesh``)
        with the activations' batch dim pinned to its batch axes; the
        placement itself comes from ``jit_kwargs`` shardings."""
        assert global_batch % n_workers == 0
        self.mesh = mesh
        self.n_workers = n_workers
        self.rows_per_worker = global_batch // n_workers
        self.global_batch = global_batch
        self.live = np.ones(n_workers, bool)
        self.scheduler = scheduler or AdaptiveScheduler(T=1.0)
        for w in self._names():
            self.scheduler.add_worker(w)
        self.state = state
        self._step = jax.jit(train_step, **(jit_kwargs or {}))
        self.history: List[Dict[str, float]] = []

    def _names(self) -> List[str]:
        return [f"vw{i}" for i in range(self.n_workers)]

    # ------------------------------------------------------------------
    # membership events (paper step b)
    # ------------------------------------------------------------------
    def leave(self, i: int) -> None:
        self.live[i] = False
        self.scheduler.remove_worker(f"vw{i}")

    def join(self, i: int) -> None:
        if not self.live[i]:
            self.live[i] = True
            self.scheduler.add_worker(f"vw{i}")

    @property
    def n_live(self) -> int:
        return int(self.live.sum())

    # ------------------------------------------------------------------
    def work_mask(self, seq_len: int) -> jnp.ndarray:
        """(B, S) mask from liveness + scheduler sample budgets."""
        rpw = self.global_batch // self.n_workers
        live_names = [f"vw{i}" for i in range(self.n_workers)
                      if self.live[i]]
        total_live_rows = rpw * len(live_names)
        budgets = self.scheduler.sample_budgets(total_live_rows)
        mask = np.zeros((self.global_batch,), np.float32)
        for i in range(self.n_workers):
            if not self.live[i]:
                continue
            b = min(budgets.get(f"vw{i}", 0), rpw)
            mask[i * rpw: i * rpw + b] = 1.0
        return jnp.asarray(np.broadcast_to(mask[:, None],
                                           (self.global_batch, seq_len)))

    def _masked(self, batch: Dict[str, jnp.ndarray]
                ) -> Dict[str, jnp.ndarray]:
        batch = dict(batch)
        batch["mask"] = self.work_mask(batch["tokens"].shape[1]) * \
            batch.get("mask", 1.0)
        return batch

    @contextlib.contextmanager
    def _on_mesh(self):
        if self.mesh is None:
            yield
            return
        bax = batch_axis(batch_spec(self.mesh, self.global_batch))
        with jax.set_mesh(self.mesh), activation_sharding(bax):
            yield

    def lower(self, batch: Dict[str, jnp.ndarray]):
        """The step program ``step(batch)`` runs, lowered for inspection
        (``.compile().as_text()`` shows its collectives)."""
        batch = self._masked(batch)
        with self._on_mesh():
            return self._step.lower(self.state, batch)

    # ------------------------------------------------------------------
    def step(self, batch: Dict[str, jnp.ndarray],
             measured_power: Optional[Dict[str, float]] = None
             ) -> Dict[str, float]:
        """One master-event-loop iteration on the mesh: (a/b) events were
        applied via join/leave, (c) weighted reduce + update inside the jit,
        (d) scheduler feedback from ``measured_power``, (e) broadcast is
        implicit (params stay sharded).

        Spans: ``mlitb.train.step`` (arg ``n_live``) around
        ``mlitb.train.mask`` (the host builds the work mask and uploads
        it), ``mlitb.train.dispatch`` (the jitted step, which returns once
        enqueued) and ``mlitb.train.readback`` (the metrics come back as
        host floats: the host waits here for the device to finish)."""
        n_live = self.n_live
        with obs.span("train.step", n_live=n_live):
            with obs.span("train.mask"):
                batch = self._masked(batch)  # built off the mesh
            with obs.span("train.dispatch"), self._on_mesh():
                self.state, metrics = self._step(self.state, batch)
            if measured_power:
                for w, p in measured_power.items():
                    if w in self.scheduler.stats:
                        self.scheduler.record(w, latency=0.0,
                                              vectors=max(1, int(p)),
                                              compute_time=1.0)
            with obs.span("train.readback"):
                out = {k: float(v) for k, v in metrics.items()}
        out["n_live"] = n_live
        self.history.append(out)
        return out

"""Weighted gradient reduction — MLitB §3.3(c) / §3.6 "Training Mode".

"The total gradient and the number of gradients is sent to the master,
which then in the reduce step computes a weighted average of gradients from
all workers and takes a gradient step using AdaGrad."

Workers send *gradient sums* g_w = sum_{i in batch_w} grad_i along with
their sample counts n_w. The reduce is

    g_bar = (sum_w g_w) / (sum_w n_w)

which equals the full-batch mean gradient over the union of worker batches
— the invariant that makes heterogeneous per-worker batch sizes exact
rather than approximate (tested in tests/test_reducer.py).

Two execution paths:

- **fused (default).** Every worker tree is raveled into one contiguous
  fp32 buffer (core.flatbuf), the per-worker channel (error-feedback add,
  sparsify, packed emission, residual update) runs over the stacked
  (num_workers, n) buffer, and the reduce is a segment-sum of the sent
  entries followed by the optimizer step — ALL inside one jitted
  function per (worker CAPACITY, max keep bucket). blocktopk picks are
  local to their 128-entry block, so its residual and segment-sum are
  rebuilt block by block with compare-and-select passes
  (``add_in_blocks``); topk/randk picks are global and scatter-add.
  O(1) dispatches per iteration instead of O(workers x leaves). Ragged
  per-worker keeps (bandwidth-adaptive ``frac_w``,
  core/adaptive_frac.py) ride the same dispatch: pad-to-the-largest-
  bucket plus a runtime mask, no retrace.

  The worker axis is CAPACITY-PADDED for churn (docs/elastic_training.md):
  the step fn is traced for ``W_cap`` = the next power of two >= the
  largest worker count seen (monotone non-decreasing), and the actual
  fleet occupies the first W rows. Vacant rows carry zero gradients,
  zero residuals, ``n_w = 0`` and ``k_w = 0``, so they are exact no-ops
  in the segment-sum. Joins/leaves/deaths therefore stop re-tracing the
  hot path: the trace cache is bounded by the number of distinct
  ``(W_cap, k bucket)`` pairs, not by the number of membership events.

  The same runtime mask implements DEADLINE-LATE workers (partial
  participation, core/event_loop.py): a worker named in ``defer=`` is
  stacked with the fleet but masked to ``k_w = 0`` and ``n_w = 0`` — it
  contributes exactly zero to the weighted average while its ENTIRE
  corrected gradient ``g + r`` lands in its error-feedback residual, so
  the excluded mass ships the next time the worker makes the deadline.

- **dense (``fused=False``).** The original per-worker Python loop over
  ``jax.tree.map`` with the leaf-wise compressor ``roundtrip`` — kept as
  the reference/compat path. The regression test pins the fused path to
  it numerically on the UNCOMPRESSED channel; with a compressor the two
  paths intentionally differ (flat-buffer-global vs per-leaf selection),
  and the fused channel is validated by its own oracle + convergence
  tests instead.

Optionally each worker message passes through a GradientCompressor (the
paper's §5.1 "partial gradient communication"), with per-worker error-
feedback residuals held master-side here (in the browser setting they live
on the client; the math is identical).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.core.compression import GradientCompressor
from repro.core.flatbuf import flat_spec
from repro.kernels.topk_compress import as_blocks, fused_block_topk_batched
from repro.optim.base import Optimizer

PyTree = Any

# step-fn cache key for the live-masked uncompressed variant (compressed
# variants key on their kmax >= 1; None keys the plain uncompressed fn)
MASKED_UNCOMPRESSED = -1

# blocktopk rebuilds its residual and segment-sum with one compare-and-
# select pass per pick (``add_in_blocks``) while W_cap * kmax is at most
# this, else scatter-adds. On a TPU v5e the rebuilt step ran 6.4-9.3x
# faster than the scatters at every W_cap * kmax from 16 to 1024 (PERF.md,
# "the crossover"), but its compile time grows with the picks (a first,
# compiling call of 64 s at 1024 against the scatters' 18 s) and nothing
# above 1024 was measured. A property of the traced shape, not a setting.
BLOCK_REBUILD_MAX_PICKS = 1024


def weighted_reduce(messages: Sequence[Tuple[PyTree, float]]) -> PyTree:
    """messages: [(grad_sum_tree, n_samples)] -> mean-gradient tree."""
    if not messages:
        raise ValueError("reduce step with no worker messages")
    total_n = sum(float(n) for _, n in messages)
    if total_n <= 0:
        raise ValueError("reduce step with zero samples")
    acc = jax.tree.map(lambda x: x.astype(jnp.float32), messages[0][0])
    for g, _ in messages[1:]:
        acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
    return jax.tree.map(lambda a: a / total_n, acc)


def scatter_add_rows(x: jnp.ndarray, idx: jnp.ndarray,
                     upd: jnp.ndarray) -> jnp.ndarray:
    """``x[w].ravel()[idx[w]] += upd[w]`` for every row ``w`` of ``x``, as
    ONE scatter over x's flat layout (``idx`` holds row-local flat
    indices). A scatter over several index dimensions is flattened by the
    compiler into one that carries no ``op_name``, so its time, and that
    of its index sort, would fall outside the caller's named scope."""
    if x.size >= 2 ** 31:
        raise ValueError(f"{x.shape} entries exceed int32 indexing")
    W = x.shape[0]
    rows = jnp.arange(W, dtype=jnp.int32).reshape((W,) + (1,) * (idx.ndim - 1))
    flat = (rows * (x.size // W) + idx).reshape(-1)
    return x.reshape(-1).at[flat].add(upd.reshape(-1)).reshape(x.shape)


def add_in_blocks(x: jnp.ndarray, at: jnp.ndarray, upd: jnp.ndarray,
                  where: jnp.ndarray) -> jnp.ndarray:
    """``x[..., r, c] += upd[..., r, j]`` for every pick ``j`` of row
    ``r`` with ``at[..., r, j] == where[..., r, c]`` (``where`` broadcast
    to ``x``): each pick addresses a lane of its own row. One statically
    unrolled compare-and-select pass a pick, bound by memory, where a
    scatter would sort its indices first. Lanes no pick names keep their
    bits; repeated picks add up, as a scatter-add's do."""
    for j in range(at.shape[-1]):
        hit = at[..., j:j + 1] == where
        x = jnp.where(hit, x + upd[..., j:j + 1], x)
    return x


class MasterReducer:
    """Owns optimizer state (the paper's master-held model) and applies the
    weighted reduce + optimizer step. Per-worker compressors implement the
    fixed-bandwidth-budget channel of §5.1."""

    def __init__(self, params: PyTree, optimizer: Optimizer,
                 compressor: Optional[GradientCompressor] = None,
                 fused: bool = True):
        self.optimizer = optimizer
        self.compressor = compressor
        self.fused = fused
        self._residuals: Dict[str, Any] = {}
        self.step = 0
        self.last_wire_bytes = 0
        self.last_per_worker_bytes: Dict[str, int] = {}
        if fused:
            self._spec = flat_spec(params)
            self._flat = self._spec.flatten(params)
            self.opt_state = optimizer.init(self._flat)
            self._unflatten = jax.jit(self._spec.unflatten)
            self._params_cache: Optional[PyTree] = None
            # jit trace cache, rebuilt lazily after a resume — never
            # checkpointed (trace_count restarting at 0 is asserted by
            # the churn/resume tests)
            # reprolint: exempt[RL005]
            self._step_fns: Dict[Tuple[int, Optional[int]], Any] = {}
            self._w_cap = 0              # monotone worker-axis capacity
            self._zero_tree: Optional[PyTree] = None
            self.trace_count = 0         # step-fn builds == jit traces
        else:
            self._params = params
            self.opt_state = optimizer.init(params)

    # ------------------------------------------------------------------
    @property
    def params(self) -> PyTree:
        if not self.fused:
            return self._params
        if self._params_cache is None:
            self._params_cache = self._unflatten(self._flat)
        return self._params_cache

    @property
    def flat_params(self) -> jnp.ndarray:
        """The master's (n,) fp32 parameter buffer (fused path only)."""
        if not self.fused:
            return flat_spec(self._params).flatten(self._params)
        return self._flat

    @property
    def flat_n(self) -> int:
        """Length of the flat gradient buffer a worker message addresses."""
        if not self.fused:
            return flat_spec(self._params).n
        return self._spec.n

    def drop_worker(self, worker: str) -> None:
        self._residuals.pop(worker, None)

    def apply_outer_delta(self, delta: jnp.ndarray) -> None:
        """Shift the flat parameter buffer by ``delta`` WITHOUT an
        optimizer step — the hierarchy's outer gossip correction
        (core/hierarchy.py): the sub-master's inner AdaGrad trajectory
        keeps its accumulator; only the point it continues from moves
        toward the cross-region consensus."""
        if not self.fused:
            raise ValueError("apply_outer_delta needs the fused flat "
                             "buffer (fused=True)")
        self._flat = self._flat + jnp.asarray(delta, jnp.float32)
        self._params_cache = None

    # ------------------------------------------------------------------
    # churn support: capacity bucketing + deadline deferral
    # ------------------------------------------------------------------
    def _capacity(self, W: int) -> int:
        """Power-of-two worker-axis capacity, monotone non-decreasing so
        fleet shrinkage never re-traces."""
        cap = 1 << max(0, (W - 1).bit_length())
        self._w_cap = max(self._w_cap, cap)
        return self._w_cap

    def _zero_gtree(self) -> PyTree:
        """Cached all-zeros gradient tree filling a vacant capacity row."""
        if self._zero_tree is None:
            self._zero_tree = jax.tree.unflatten(
                self._spec.treedef,
                [jnp.zeros(s, jnp.float32) for s in self._spec.shapes])
        return self._zero_tree

    def defer_to_residual(self, worker: str, grad: PyTree) -> None:
        """Fold a late/deadline-missed worker's ENTIRE gradient into its
        error-feedback residual without an optimizer step — used when no
        on-time message exists to anchor a reduce. The mass ships the
        next time the worker participates."""
        if self.fused:
            flat = self._spec.flatten(grad)
            res = self._residuals.get(worker)
            self._residuals[worker] = flat if res is None else res + flat
        else:
            res = self._residuals.get(worker)
            if res is None:
                self._residuals[worker] = jax.tree.map(
                    lambda g: g.astype(jnp.float32), grad)
            else:
                self._residuals[worker] = jax.tree.map(
                    lambda r, g: r + g.astype(jnp.float32), res, grad)

    # ------------------------------------------------------------------
    # dense reference path
    # ------------------------------------------------------------------
    def _channel(self, worker: str, grad: PyTree) -> PyTree:
        """Simulate the worker->master channel (compress + error feedback)."""
        if self.compressor is None:
            return grad
        res = self._residuals.get(worker)
        sent, new_res = self.compressor.roundtrip(grad, res, step=self.step)
        self._residuals[worker] = new_res
        return sent

    def _reduce_and_step_dense(
            self, messages: Dict[str, Tuple[PyTree, float]]) -> PyTree:
        chan = [(w, self._channel(w, g), n) for w, (g, n) in
                sorted(messages.items())]
        g_bar = weighted_reduce([(g, n) for _, g, n in chan])
        self._params, self.opt_state = self.optimizer.update(
            self._params, g_bar, self.opt_state)
        self.last_per_worker_bytes = {
            w: (self.compressor.wire_bytes(g) if self.compressor else
                4 * sum(leaf.size for leaf in jax.tree.leaves(g)))
            for w, g, _ in chan}
        self.last_wire_bytes = sum(self.last_per_worker_bytes.values())
        self.step += 1
        return self._params

    # ------------------------------------------------------------------
    # fused flat-buffer path
    # ------------------------------------------------------------------
    def _build_step_fn(self, W_cap: int, kmax: Optional[int]):
        """One jitted fn per (worker-axis capacity, padded keep count).
        EVERYTHING between receiving the worker trees and the new
        parameter buffer happens inside this single dispatch: per-worker
        ravel into the flat layout, the compression channel (error-
        feedback add + sparsify + packed emission + residual update), the
        segment-sum reduce, and the optimizer step.

        The worker axis is padded to ``W_cap`` (power-of-two, monotone
        across the reducer's lifetime): the live fleet occupies a prefix
        of the rows and every vacant row carries zero gradient/residual
        and ``ns = 0``/``k_arr = 0``, making it an exact no-op. Worker
        joins/leaves/deaths therefore re-trace only when the fleet
        outgrows its capacity bucket, never per membership event.

        Ragged per-worker message sizes (bandwidth-adaptive ``frac_w``,
        core/adaptive_frac.py) are handled WITHOUT retracing: the channel
        selects ``kmax`` candidates per worker (``kmax`` = the largest
        worker's bucketed keep; per-block for blocktopk) and a runtime
        ``k_arr`` masks each worker down to its own keep — selection
        emits in descending-|.| order, so the first ``k_arr[w]`` entries
        ARE worker w's top-k. Masked-off candidates carry value 0 into
        the segment-sum (no-ops, never on the wire) and are
        returned to the worker's error-feedback residual. ``k_arr[w] = 0``
        is the deadline-late/vacant live-mask: such a row sends nothing
        and its whole corrected gradient stays in the residual. ``kmax``
        is bucketed to the compressor's power-of-two lattice, so at most
        ~log2(n) variants of this function exist per (W_cap, layout).

        Each variant is a named program, so a profile shows it as
        ``jit_reduce_blocktopk_step``, ``jit_reduce_topk_step`` (topk and
        randk), ``jit_reduce_dense_step`` or ``jit_reduce_masked_step``.
        Inside the two compressed ones every op carries one of the named
        scopes ``reduce/pack`` (flatten and stack the worker rows),
        ``reduce/compress`` (selection and the keep mask),
        ``reduce/residual`` (unsent candidates added back to the
        residual), ``reduce/segment_sum`` (the sent entries summed over
        workers) and ``reduce/optimizer``.

        blocktopk's picks are local to their block, so while ``W_cap *
        kmax <= BLOCK_REBUILD_MAX_PICKS`` both of those are rebuilt over
        the ``(W_cap, R, block_w)`` layout by ``add_in_blocks``, one
        compare-and-select pass a pick; above it, and for topk/randk,
        whose picks are global, they are scatter-adds."""
        self.trace_count += 1
        opt = self.optimizer
        comp = self.compressor
        spec = self._spec
        n = spec.n

        if comp is None:
            if kmax is None:
                # plain uncompressed reduce: no deferral state in play,
                # so skip the residual stack + live-mask entirely (the
                # common case — keeps the hot path at PR-1 speed)
                @jax.jit
                def reduce_dense_step(flat, opt_state, gtrees, ns):
                    grads = jnp.stack([spec.flatten(t) for t in gtrees])
                    g_bar = jnp.sum(grads, axis=0) / jnp.sum(ns)
                    return opt.update(flat, g_bar, opt_state)

                return reduce_dense_step

            # masked variant (kmax == MASKED_UNCOMPRESSED): deferred
            # workers and pending residuals ride the live-mask
            @jax.jit
            def reduce_masked_step(flat, opt_state, gtrees, res_rows, ns):
                g = (jnp.stack([spec.flatten(t) for t in gtrees])
                     + jnp.stack(res_rows))
                live = (ns > 0).astype(jnp.float32)[:, None]
                g_bar = jnp.sum(g * live, axis=0) / jnp.sum(ns)
                new_res = g * (1.0 - live)
                new_flat, new_state = opt.update(flat, g_bar, opt_state)
                return (new_flat, new_state,
                        tuple(new_res[i] for i in range(W_cap)))

            return reduce_masked_step

        if comp.method == "blocktopk":
            block_w = comp.block_w
            in_blocks = W_cap * kmax <= BLOCK_REBUILD_MAX_PICKS

            @jax.jit
            def reduce_blocktopk_step(flat, opt_state, gtrees, res_rows, ns,
                                      step, k_arr):
                with jax.named_scope("reduce/pack"):
                    # (W_cap, R, block_w) stacks, built row-wise
                    grads = jnp.stack([as_blocks(spec.flatten(t), block_w)
                                       for t in gtrees])
                    res = jnp.stack([as_blocks(x, block_w)
                                     for x in res_rows])
                with jax.named_scope("reduce/compress"):
                    # (W_cap, R, kmax) candidates per worker, descending
                    # |.| per block; res_full assumes ALL kmax sent
                    vals, idx, res_full = fused_block_topk_batched(
                        grads, res, k=kmax)
                    mask = (jnp.arange(kmax, dtype=jnp.int32)[None, None, :]
                            < k_arr[:, None, None])
                    sent = jnp.where(mask, vals, 0.0)
                with jax.named_scope("reduce/residual"):
                    # candidates a worker did NOT send go back to its
                    # residual (idx is the row-local flat index)
                    unsent = vals - sent
                    if in_blocks:
                        # the rows that hold entries below n (past them
                        # lie zeros only) and each row's first entry
                        Rn = -(-n // block_w)
                        first = jnp.arange(Rn, dtype=jnp.int32)[:, None] \
                            * block_w
                        lanes = jnp.arange(block_w, dtype=jnp.int32)
                        new_res = [add_in_blocks(res_full[i, :Rn],
                                                 idx[i, :Rn] - first,
                                                 unsent[i, :Rn], lanes)
                                   for i in range(W_cap)]
                    else:
                        new_res = scatter_add_rows(res_full, idx, unsent)
                    rows = tuple(new_res[i].reshape(-1)[:n]
                                 for i in range(W_cap))
                with jax.named_scope("reduce/segment_sum"):
                    if in_blocks:
                        # picks against each entry's flat position: no
                        # compare is shared with the residual's, so none
                        # is kept in memory between the two passes
                        pos = first + lanes
                        g = jnp.zeros((Rn, block_w), jnp.float32)
                        for i in range(W_cap):
                            g = add_in_blocks(g, idx[i, :Rn], sent[i, :Rn],
                                              pos)
                        g_bar = g.reshape(-1)[:n] / jnp.sum(ns)
                    else:
                        g_bar = jnp.zeros((n,), jnp.float32).at[
                            idx.reshape(-1)].add(sent.reshape(-1),
                                                 mode="drop") / jnp.sum(ns)
                with jax.named_scope("reduce/optimizer"):
                    new_flat, new_state = opt.update(flat, g_bar, opt_state)
                return new_flat, new_state, rows

            return reduce_blocktopk_step

        method = comp.method
        seed = comp.seed

        @jax.jit
        def reduce_topk_step(flat, opt_state, gtrees, res_rows, ns, step,
                             k_arr):
            with jax.named_scope("reduce/pack"):
                grads = jnp.stack([spec.flatten(t) for t in gtrees])
                res = jnp.stack(res_rows)
            with jax.named_scope("reduce/compress"):
                c = grads + res
                if method == "topk":
                    _, idx = jax.lax.top_k(jnp.abs(c), kmax)
                else:                                          # randk
                    base = jax.random.fold_in(jax.random.PRNGKey(seed),
                                              step)
                    keys = jax.random.split(base, W_cap)
                    scores = jax.vmap(
                        lambda key: jax.random.uniform(key, (n,)))(keys)
                    _, idx = jax.lax.top_k(scores, kmax)
                idx = idx.astype(jnp.int32)
                vals = jnp.take_along_axis(c, idx, axis=1)
                mask = (jnp.arange(kmax, dtype=jnp.int32)[None, :]
                        < k_arr[:, None])
                sent = jnp.where(mask, vals, 0.0)
            with jax.named_scope("reduce/residual"):
                # zero exactly the sent entries out of c; unsent
                # candidates stay in the residual (per-row indices are
                # distinct)
                new_res = scatter_add_rows(c, idx, -sent)
            with jax.named_scope("reduce/segment_sum"):
                g_bar = jnp.zeros((n,), jnp.float32).at[
                    idx.reshape(-1)].add(sent.reshape(-1),
                                         mode="drop") / jnp.sum(ns)
            with jax.named_scope("reduce/optimizer"):
                new_flat, new_state = opt.update(flat, g_bar, opt_state)
            with jax.named_scope("reduce/residual"):
                rows = tuple(new_res[i] for i in range(W_cap))
            return new_flat, new_state, rows

        return reduce_topk_step

    def _reduce_and_step_fused(
            self, messages: Dict[str, Tuple[PyTree, float]],
            keep: Optional[Dict[str, int]] = None,
            defer: Optional[Any] = None) -> PyTree:
        """Spans: ``mlitb.reduce.step`` (args ``W``, ``W_cap``, ``kmax``
        -- 0 uncompressed, per block for blocktopk -- and ``wire_bytes``)
        around ``mlitb.reduce.pack`` (the host stacks the worker trees,
        residual rows, counts and keeps) and ``mlitb.reduce.dispatch``
        (the jitted call, which returns once the program is enqueued)."""
        if not messages:
            raise ValueError("reduce step with no worker messages")
        defer = frozenset(defer or ())
        names = sorted(messages)
        on_time = [w for w in names if w not in defer]
        total_n = sum(float(messages[w][1]) for w in on_time)
        if not on_time or total_n <= 0:
            raise ValueError("reduce step with no on-time samples "
                             "(use defer_to_residual for all-late rounds)")
        comp = self.compressor
        if comp is None and keep:
            raise ValueError("per-worker keep requires a compressor")
        n = self._spec.n
        W = len(names)
        W_cap = self._capacity(W)
        pad = W_cap - W
        kmax = 0
        with obs.span("reduce.step", W=W, W_cap=W_cap) as sp:
            with obs.span("reduce.pack"):
                gtrees = (tuple(messages[w][0] for w in names)
                          + (self._zero_gtree(),) * pad)
                # ns = 0 is the live-mask: vacant capacity rows AND
                # deferred (deadline-late) workers carry zero weight
                ns = np.zeros(W_cap, np.float32)
                for i, w in enumerate(names):
                    if w not in defer:
                        ns[i] = float(messages[w][1])
                zeros = jnp.zeros((n,), jnp.float32)
                res_rows = (tuple(self._residuals.get(w, zeros)
                                  for w in names) + (zeros,) * pad)
                if comp is not None:
                    # per-worker keep totals, snapped to the compressor's
                    # lattice (keep=None -> the uniform frac-derived
                    # default); deferred workers are masked to k = 0
                    # (nothing on the wire, all mass into the residual)
                    k_tot = {w: comp.flat_k(n, (keep or {}).get(w))
                             for w in on_time}
                    kmax_tot = max(k_tot.values())
                    if comp.method == "blocktopk":
                        rows = -(-n // comp.block_w)
                        kmax = kmax_tot // rows        # per-block keep
                        k_of = {w: k_tot[w] // rows for w in on_time}
                    else:
                        kmax = kmax_tot
                        k_of = dict(k_tot)
                    k_arr = jnp.asarray([k_of.get(w, 0) for w in names]
                                        + [0] * pad, jnp.int32)

            if comp is None:
                masked = bool(defer) or any(w in self._residuals
                                            for w in names)
                key = (W_cap, MASKED_UNCOMPRESSED if masked else None)
                fn = self._step_fns.get(key)
                if fn is None:
                    fn = self._step_fns[key] = self._build_step_fn(*key)
                with obs.span("reduce.dispatch"):
                    if masked:
                        self._flat, self.opt_state, new_res = fn(
                            self._flat, self.opt_state, gtrees, res_rows,
                            ns)
                    else:
                        self._flat, self.opt_state = fn(
                            self._flat, self.opt_state, gtrees, ns)
                if masked:
                    # on-time rows leave an all-zero residual: keep the
                    # dict sparse (only deferred mass is worth holding)
                    for i, w in enumerate(names):
                        if w in defer:
                            self._residuals[w] = new_res[i]
                        else:
                            self._residuals.pop(w, None)
                self.last_per_worker_bytes = {w: 4 * n for w in on_time}
                self.last_wire_bytes = len(on_time) * 4 * n
            else:
                fn = self._step_fns.get((W_cap, kmax))
                if fn is None:
                    fn = self._step_fns[(W_cap, kmax)] = \
                        self._build_step_fn(W_cap, kmax)
                with obs.span("reduce.dispatch"):
                    self._flat, self.opt_state, new_res = fn(
                        self._flat, self.opt_state, gtrees, res_rows, ns,
                        np.asarray(self.step, np.int32), k_arr)
                for i, w in enumerate(names):
                    self._residuals[w] = new_res[i]
                self.last_per_worker_bytes = {w: 8 * k_tot[w]
                                              for w in on_time}
                self.last_wire_bytes = sum(
                    self.last_per_worker_bytes.values())
            if obs.tracing():
                sp.set_metadata(kmax=kmax, wire_bytes=self.last_wire_bytes)
        self._params_cache = None
        self.step += 1
        return self.params

    # ------------------------------------------------------------------
    @property
    def supports_defer(self) -> bool:
        """Whether late/deadline-missed messages can be preserved in
        error-feedback residuals (fused flat buffers, or the dense path's
        compressor residual trees). The dense UNCOMPRESSED path has no
        residual channel — late mass there is simply dropped."""
        return self.fused or self.compressor is not None

    def reduce_and_step(
            self, messages: Dict[str, Tuple[PyTree, float]],
            keep: Optional[Dict[str, int]] = None,
            defer: Optional[Any] = None) -> PyTree:
        """messages: {worker: (grad_sum, n)}. Returns the new params
        (the broadcast payload of step (e)).

        ``keep`` maps worker -> per-message keep total (entries, not
        bytes) for bandwidth-adaptive per-worker compression; missing
        workers fall back to the compressor's uniform frac. Values are
        quantized onto ``GradientCompressor.k_lattice``; the actual
        bytes shipped per worker land in ``last_per_worker_bytes``.
        Requires the fused path AND a compressor (the dense path is the
        uniform-frac reference).

        ``defer`` names workers (a subset of ``messages``) whose reply
        missed the iteration deadline: they are live-masked out of the
        weighted average (zero contribution, zero wire bytes) and their
        whole corrected gradient is preserved in their error-feedback
        residual. Fused path only; at least one message must remain
        on-time."""
        if self.fused:
            return self._reduce_and_step_fused(messages, keep, defer)
        if keep:
            raise ValueError("per-worker keep requires fused=True")
        if defer:
            raise ValueError("defer requires fused=True (use "
                             "defer_to_residual on the dense path)")
        return self._reduce_and_step_dense(messages)

    # ------------------------------------------------------------------
    # full-state snapshot (TrainState resume contract,
    # docs/elastic_training.md; serialized by checkpoint/io.py)
    # ------------------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        """Everything mutable: params, optimizer state, per-worker
        error-feedback residuals, the step counter (randk's PRNG input),
        the capacity bucket, and the wire accounting. Arrays come out as
        numpy; structure is rebuilt against the live objects on load."""
        def leaves(tree):
            return [np.asarray(x) for x in jax.tree.leaves(tree)]

        st: Dict[str, Any] = {
            "fused": self.fused,
            "step": self.step,
            "opt_leaves": leaves(self.opt_state),
            "last_wire_bytes": self.last_wire_bytes,
            "last_per_worker_bytes": dict(self.last_per_worker_bytes),
        }
        if self.fused:
            st["flat"] = np.asarray(self._flat)
            st["w_cap"] = self._w_cap
            st["residuals"] = {w: np.asarray(r)
                               for w, r in self._residuals.items()}
        else:
            st["param_leaves"] = leaves(self._params)
            st["residuals"] = {w: leaves(r)
                               for w, r in self._residuals.items()}
        return st

    def load_state_dict(self, st: Dict[str, Any]) -> None:
        if bool(st["fused"]) != self.fused:
            raise ValueError("snapshot fused mode does not match reducer")

        def into(tree, leaf_list):
            return jax.tree.unflatten(
                jax.tree.structure(tree),
                [jnp.asarray(a) for a in leaf_list])

        self.step = int(st["step"])
        self.opt_state = into(self.opt_state, st["opt_leaves"])
        self.last_wire_bytes = int(st["last_wire_bytes"])
        self.last_per_worker_bytes = {
            w: int(b) for w, b in st["last_per_worker_bytes"].items()}
        if self.fused:
            self._flat = jnp.asarray(st["flat"], jnp.float32)
            self._w_cap = int(st["w_cap"])
            self._residuals = {w: jnp.asarray(r, jnp.float32)
                               for w, r in st["residuals"].items()}
            self._params_cache = None
        else:
            self._params = into(self._params, st["param_leaves"])
            self._residuals = {w: into(self._params, r)
                               for w, r in st["residuals"].items()}

"""The fused flat-buffer compressed-reduce pipeline:

- FlatSpec layout contract (ravel order, offsets, dtype round-trip);
- packed (values, indices) wire format round-trips to exactly the dense
  reconstruction for all three methods, incl. ragged tails
  (n % block_w != 0) and k >= buffer-size edge cases;
- MasterReducer fused path is numerically identical (fp32 tolerance) to
  the per-worker dense path on a 4-worker `mlitb_cnn` step;
- packed wire bytes match the compressor's accounting.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import (GradientCompressor, decompress_flat)
from repro.core.flatbuf import flat_spec
from repro.core.reducer import MasterReducer, scatter_add_rows
from repro.core.simulation import make_cnn_problem
from repro.data.datasets import synthetic_mnist
from repro.kernels.topk_compress import as_blocks, fused_block_topk_batched
from repro.optim import adagrad, sgd


# ---------------------------------------------------------------------------
# FlatSpec layout contract
# ---------------------------------------------------------------------------
def test_flatspec_roundtrip_and_layout():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((4,), jnp.bfloat16),
            "c": jnp.asarray(7.0)}
    spec = flat_spec(tree)
    assert spec.n == 11
    # leaves in jax.tree.leaves order, contiguous, C-order raveled
    flat = spec.flatten(tree)
    assert flat.dtype == jnp.float32 and flat.shape == (11,)
    leaves = jax.tree.leaves(tree)
    for off, size, leaf in zip(spec.offsets, spec.sizes, leaves):
        np.testing.assert_allclose(
            np.asarray(flat[off:off + size]),
            np.asarray(leaf, np.float32).reshape(-1))
    back = spec.unflatten(flat)
    assert back["b"].dtype == jnp.bfloat16
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
    # spec caching: same layout -> same object
    assert flat_spec(tree) is spec


def test_flatspec_stacked_matches_rowwise():
    tree = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.arange(5, dtype=jnp.float32)}
    spec = flat_spec(tree)
    stack = jax.tree.map(
        lambda x: jnp.stack([x, 2 * x, -x]), tree)
    flat = spec.flatten_stacked(stack)
    assert flat.shape == (3, spec.n)
    np.testing.assert_allclose(np.asarray(flat[0]),
                               np.asarray(spec.flatten(tree)))
    np.testing.assert_allclose(np.asarray(flat[2]),
                               -np.asarray(spec.flatten(tree)))


# ---------------------------------------------------------------------------
# packed wire format round-trips
# ---------------------------------------------------------------------------
def _dense_topk_oracle(c: np.ndarray, k: int) -> np.ndarray:
    """Keep the k largest-|.| entries (ties -> lowest index, matching
    lax.top_k), zero the rest."""
    k = min(k, c.size)
    order = np.argsort(-np.abs(c), kind="stable")[:k]
    out = np.zeros_like(c)
    out[order] = c[order]
    return out


@pytest.mark.parametrize("n", [7, 64, 1000, 4097])
@pytest.mark.parametrize("frac", [0.01, 0.3, 2.0])  # 2.0 -> k >= n
def test_topk_wire_roundtrip_exact(n, frac):
    rng = np.random.RandomState(n)
    g = rng.randn(n).astype(np.float32)
    r = rng.randn(n).astype(np.float32) * 0.5
    comp = GradientCompressor("topk", frac=frac)
    msg, res = comp.compress_flat(jnp.asarray(g), jnp.asarray(r))
    dense = np.asarray(msg.dense())
    np.testing.assert_array_equal(
        dense, _dense_topk_oracle(g + r, comp.flat_k(n)))
    # error feedback: dense + residual == g + r exactly
    np.testing.assert_allclose(dense + np.asarray(res), g + r, atol=0)
    assert msg.wire_bytes() == comp.packed_wire_bytes(n)


@pytest.mark.parametrize("n,block_w", [(64, 8), (1000, 16), (31786, 128),
                                       (5, 8), (130, 128)])
@pytest.mark.parametrize("frac", [1 / 128, 0.25, 1.0])
def test_blocktopk_wire_roundtrip_exact(n, block_w, frac):
    from repro.kernels.topk_compress import fused_compress_ref
    rng = np.random.RandomState(block_w + n)
    g = rng.randn(n).astype(np.float32)
    r = rng.randn(n).astype(np.float32) * 0.5
    comp = GradientCompressor("blocktopk", frac=frac, block_w=block_w)
    msg, res = comp.compress_flat(jnp.asarray(g), jnp.asarray(r))
    # oracle dense reconstruction: pad, per-block iterated first-max
    pad = (-n) % block_w
    gp = np.pad(g, (0, pad)).reshape(-1, block_w)
    rp = np.pad(r, (0, pad)).reshape(-1, block_w)
    vals, offs, rem = fused_compress_ref(gp, rp, comp._block_k())
    dense_oracle = ((gp + rp) - rem).reshape(-1)[:n]
    np.testing.assert_allclose(np.asarray(msg.dense()), dense_oracle,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(msg.dense()) + np.asarray(res),
                               g + r, atol=1e-6)
    assert msg.wire_bytes() == comp.packed_wire_bytes(n)


@pytest.mark.parametrize("n", [64, 1000])
@pytest.mark.parametrize("frac", [0.1, 2.0])
def test_randk_wire_roundtrip_exact(n, frac):
    rng = np.random.RandomState(17 * n)
    g = rng.randn(n).astype(np.float32)
    r = rng.randn(n).astype(np.float32)
    comp = GradientCompressor("randk", frac=frac, seed=5)
    msg, res = comp.compress_flat(jnp.asarray(g), jnp.asarray(r), step=3)
    k = comp.flat_k(n)
    dense = np.asarray(msg.dense())
    resid = np.asarray(res)
    c = g + r
    # selected set: residual zeroed there, untouched elsewhere; payload
    # is UNSCALED (error feedback corrects the shrinkage), so
    # dense + residual == c exactly
    np.testing.assert_allclose(dense + resid, c, atol=1e-5)
    sel = np.asarray(msg.indices).reshape(-1)
    assert len(np.unique(sel)) == k            # k distinct positions
    np.testing.assert_allclose(dense[sel], c[sel], atol=1e-5)
    assert msg.wire_bytes() == comp.packed_wire_bytes(n)


def test_decompress_drops_out_of_range_padding():
    vals = jnp.asarray([1.0, 0.0])
    idx = jnp.asarray([1, 9], jnp.int32)       # 9 >= n: padding pair
    out = np.asarray(decompress_flat(vals, idx, n=4))
    np.testing.assert_array_equal(out, [0.0, 1.0, 0.0, 0.0])


# ---------------------------------------------------------------------------
# fused reducer == dense reducer (satellite regression)
# ---------------------------------------------------------------------------
def test_fused_reducer_matches_dense_on_cnn_step():
    init_p, grad_fn, _ = make_cnn_problem()
    X, y = synthetic_mnist(256, seed=0)
    p0 = init_p(jax.random.PRNGKey(0))
    dense = MasterReducer(p0, adagrad(lr=0.02), fused=False)
    fused = MasterReducer(p0, adagrad(lr=0.02), fused=True)
    rng = np.random.RandomState(0)
    for _ in range(3):                          # multi-step: state carries
        msgs = {}
        for w in range(4):
            idx = rng.choice(256, 64, replace=False)
            g, _ = grad_fn(dense.params, X[idx], y[idx])
            msgs[f"w{w}"] = (g, 64)
        dense.reduce_and_step(msgs)
        fused.reduce_and_step(msgs)
    for a, b in zip(jax.tree.leaves(dense.params),
                    jax.tree.leaves(fused.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
    assert fused.step == dense.step == 3


@pytest.mark.parametrize("method", ["topk", "randk"])
def test_fused_reducer_compressed_converges_quadratic(method):
    """Error feedback through the PACKED channel still drives a quadratic
    to its optimum (same setting as the dense-path test). randk is the
    regression for the scaling+feedback mass-amplification bug: with the
    old n/k wire rescaling this setup diverged to ~1e12 within 150
    steps."""
    target = jnp.asarray(np.random.RandomState(0).randn(64))
    red = MasterReducer({"w": jnp.zeros(64)}, sgd(lr=0.1),
                        compressor=GradientCompressor(method, frac=0.1),
                        fused=True)
    for _ in range(600):
        g = {"w": (red.params["w"] - target)}
        red.reduce_and_step({"w0": (g, 1)})
    assert float(jnp.abs(red.params["w"] - target).max()) < 1e-2


def test_fused_reducer_wire_accounting_and_elasticity():
    """Wire bytes track worker count; residuals survive joins/leaves."""
    p0 = {"w": jnp.zeros((300,))}
    comp = GradientCompressor("blocktopk", frac=1 / 32, block_w=32)
    red = MasterReducer(p0, sgd(lr=0.1), compressor=comp)
    g = {"w": jnp.ones((300,))}
    red.reduce_and_step({"a": (g, 1), "b": (g, 1)})
    assert red.last_wire_bytes == 2 * comp.packed_wire_bytes(300)
    red.reduce_and_step({"a": (g, 1), "b": (g, 1), "c": (g, 1)})
    assert red.last_wire_bytes == 3 * comp.packed_wire_bytes(300)
    assert set(red._residuals) == {"a", "b", "c"}
    red.drop_worker("b")
    red.reduce_and_step({"a": (g, 1), "c": (g, 1)})
    assert set(red._residuals) == {"a", "c"}


def test_fused_reducer_rejects_empty_and_zero_samples():
    red = MasterReducer({"w": jnp.zeros(4)}, sgd(lr=0.1))
    with pytest.raises(ValueError):
        red.reduce_and_step({})
    with pytest.raises(ValueError):
        red.reduce_and_step({"w0": ({"w": jnp.zeros(4)}, 0)})


# ---------------------------------------------------------------------------
# capacity-padded worker axis: churn must not retrace the hot path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("compressed", [False, True])
def test_churn_traces_bounded_by_capacity_buckets(compressed):
    """Property: under an M-event churn schedule the number of jit
    traces is bounded by the number of distinct W_cap buckets (power-of-
    two capacities), NOT by M."""
    n = 256
    comp = GradientCompressor("topk", frac=0.05) if compressed else None
    red = MasterReducer({"w": jnp.zeros(n)}, sgd(lr=0.1), compressor=comp)
    g = {"w": jnp.ones(n)}
    rng = np.random.RandomState(4)
    M = 60
    caps = set()
    for _ in range(M):
        W = int(rng.randint(1, 9))          # fleet churns between 1..8
        red.reduce_and_step({f"w{i}": (g, 1) for i in range(W)})
        caps.add(red._w_cap)
    assert caps <= {1, 2, 4, 8}
    # capacity is monotone, so distinct (W_cap, kmax) pairs — and hence
    # traces — are bounded by the capacity buckets actually visited
    assert red.trace_count == len(red._step_fns) <= len(caps)
    assert red.trace_count < M // 4


def test_capacity_padding_is_numerically_invisible():
    """A 3-worker reduce on a capacity-4 axis equals the same reduce on
    a reducer that only ever saw 3 workers: vacant rows are exact
    no-ops."""
    n = 129
    rng = np.random.RandomState(9)
    g = {w: {"w": jnp.asarray(rng.randn(n), jnp.float32)}
         for w in ("a", "b", "c")}
    outs = []
    for warm_w in (8, None):        # warm_w=8 forces W_cap=8 first
        red = MasterReducer({"w": jnp.zeros(n)}, sgd(lr=1.0),
                            compressor=GradientCompressor("topk",
                                                          frac=0.2))
        if warm_w:
            z = {"w": jnp.zeros(n)}
            red.reduce_and_step({f"p{i}": (z, 1) for i in range(warm_w)})
        red.reduce_and_step({w: (g[w], 1) for w in g})
        outs.append(np.asarray(red.flat_params))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# deadline-late workers: live-masked to zero, mass kept in the residual
# ---------------------------------------------------------------------------
def test_deferred_worker_contributes_zero_topk_oracle():
    """defer={'b'}: params move exactly as if only a and c reduced, and
    b's whole corrected gradient lands in its residual."""
    n = 257
    rng = np.random.RandomState(21)
    g = {w: {"w": jnp.asarray(rng.randn(n), jnp.float32)}
         for w in ("a", "b", "c")}
    comp = GradientCompressor("topk", frac=0.1)

    red = MasterReducer({"w": jnp.zeros(n)}, sgd(lr=1.0), compressor=comp)
    red.reduce_and_step({w: (g[w], 1) for w in g}, defer=["b"])

    ctrl = MasterReducer({"w": jnp.zeros(n)}, sgd(lr=1.0), compressor=comp)
    ctrl.reduce_and_step({w: (g[w], 1) for w in ("a", "c")})

    np.testing.assert_array_equal(np.asarray(red.flat_params),
                                  np.asarray(ctrl.flat_params))
    # sum(ns) counted only on-time workers
    np.testing.assert_array_equal(np.asarray(red.last_wire_bytes),
                                  ctrl.last_wire_bytes)
    assert set(red.last_per_worker_bytes) == {"a", "c"}
    # b keeps ALL its mass: residual == corrected gradient, exactly
    np.testing.assert_array_equal(np.asarray(red._residuals["b"]),
                                  np.asarray(g["b"]["w"]))


@pytest.mark.parametrize("method", ["topk", "randk", "blocktopk"])
def test_deferred_mass_preserved_all_methods(method):
    """Feedback invariant under deferral, every channel: the deferred
    worker's residual carries g + r_prev (nothing reduced, nothing
    lost), while on-time workers keep sent + residual == g + r_prev."""
    n, block_w = 192, 32
    rng = np.random.RandomState(31)
    comp = GradientCompressor(method, frac=0.25, block_w=block_w)
    red = MasterReducer({"w": jnp.zeros(n)}, sgd(lr=1.0), compressor=comp)
    g1 = {w: {"w": jnp.asarray(rng.randn(n), jnp.float32)}
          for w in ("a", "b")}
    red.reduce_and_step({w: (g1[w], 1) for w in g1})   # grow residuals
    prev = {w: np.asarray(red._residuals[w]) for w in g1}
    g2 = {w: {"w": jnp.asarray(rng.randn(n), jnp.float32)}
          for w in ("a", "b")}
    p_before = np.asarray(red.flat_params)
    red.reduce_and_step({w: (g2[w], 1) for w in g2}, defer=["b"])
    # deferred: residual == g + r_prev, bit of mass neither sent nor lost
    np.testing.assert_allclose(np.asarray(red._residuals["b"]),
                               np.asarray(g2["b"]["w"]) + prev["b"],
                               atol=1e-6)
    # on-time: error-feedback invariant  sent + r_new == g + r_prev
    # (sgd lr=1, sum ns = 1 -> sent_a == p_before - p_after)
    sent_a = p_before - np.asarray(red.flat_params)
    np.testing.assert_allclose(sent_a + np.asarray(red._residuals["a"]),
                               np.asarray(g2["a"]["w"]) + prev["a"],
                               atol=1e-5)


def test_defer_all_messages_raises():
    red = MasterReducer({"w": jnp.zeros(8)}, sgd(lr=0.1),
                        compressor=GradientCompressor("topk", frac=0.5))
    with pytest.raises(ValueError):
        red.reduce_and_step({"a": ({"w": jnp.ones(8)}, 1)}, defer=["a"])


def test_defer_to_residual_accumulates():
    red = MasterReducer({"w": jnp.zeros(8)}, sgd(lr=0.1),
                        compressor=GradientCompressor("topk", frac=0.5))
    red.defer_to_residual("a", {"w": jnp.ones(8)})
    red.defer_to_residual("a", {"w": jnp.ones(8)})
    np.testing.assert_array_equal(np.asarray(red._residuals["a"]),
                                  np.full(8, 2.0, np.float32))
    red.drop_worker("a")
    assert "a" not in red._residuals


# ---------------------------------------------------------------------------
# blocktopk: the block-local rebuild against the scatter-adds it replaces
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kmax", "block_w", "opt"))
def _blocktopk_scatter_reference(flat, opt_state, grads, res, ns, k_arr,
                                 kmax, block_w, opt):
    """One blocktopk master step with both reconstructions as scatter-adds
    over the flat layout: unsent candidates back into each residual, and
    the sent entries summed into the reduced gradient."""
    n = flat.shape[0]
    vals, idx, res_full = fused_block_topk_batched(
        jnp.stack([as_blocks(g, block_w) for g in grads]),
        jnp.stack([as_blocks(r, block_w) for r in res]), k=kmax)
    sent = jnp.where(jnp.arange(kmax)[None, None, :]
                     < k_arr[:, None, None], vals, 0.0)
    new_res = scatter_add_rows(res_full, idx, vals - sent)
    g_bar = jnp.zeros((n,), jnp.float32).at[idx.reshape(-1)].add(
        sent.reshape(-1), mode="drop") / jnp.sum(ns)
    new_flat, new_state = opt.update(flat, g_bar, opt_state)
    return new_flat, new_state, [new_res[i].reshape(-1)[:n]
                                 for i in range(len(grads))]


def _pad_to_capacity(xs, fill):
    """``xs`` and ``fill`` in the vacant rows of the reducer's worker
    capacity (the next power of two)."""
    return xs + [fill] * ((1 << (len(xs) - 1).bit_length()) - len(xs))


@pytest.mark.parametrize("case", ["in_blocks", "above_crossover"])
def test_blocktopk_rebuild_matches_scatter_reference(case, monkeypatch):
    """Three reduce steps (ragged keeps, a deferred worker, a vacant
    capacity row, all-zero and one-entry blocks, n off the 128 x 128
    tiling) match the scatter reference: residuals bit for bit, params
    and AdaGrad state within 1 ulp. Below the crossover the step takes
    the block-local rebuild, above it the scatter-adds."""
    import repro.core.reducer as rd

    block_w = 128
    if case == "in_blocks":
        n, kmax, names = 20_000, 4, ["a", "b", "c"]          # W_cap 4
    else:
        n, kmax = 1_000, block_w
        names = [f"w{i}" for i in range(rd.BLOCK_REBUILD_MAX_PICKS
                                        // kmax + 1)]
    W_cap = 1 << (len(names) - 1).bit_length()
    assert (W_cap * kmax <= rd.BLOCK_REBUILD_MAX_PICKS) == (
        case == "in_blocks")
    rows = -(-n // block_w)
    rng = np.random.RandomState(14)
    grads = {}
    for i, w in enumerate(names):
        g = rng.randn(n).astype(np.float32)
        g[:3 * block_w] = 0.0                    # whole blocks of zeros
        g[5 * block_w:6 * block_w] = 0.0
        g[5 * block_w + 7 * i % block_w] = 1.5   # one entry: (0.0, 0) pads
        g[6 * block_w] = 0.0                     # picks at lane 0 and not
        grads[w] = g
    calls = {"blocks": 0, "scatter": 0}

    def count(kind, fn):
        def wrapped(*a, **kw):
            calls[kind] += 1
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(rd, "add_in_blocks",
                        count("blocks", rd.add_in_blocks))
    monkeypatch.setattr(rd, "scatter_add_rows",
                        count("scatter", rd.scatter_add_rows))
    opt = adagrad(lr=0.05)
    p0 = rng.randn(n).astype(np.float32)
    red = MasterReducer({"w": jnp.asarray(p0)}, opt,
                        GradientCompressor("blocktopk",
                                           frac=kmax / block_w,
                                           block_w=block_w))
    flat, state = jnp.asarray(p0), opt.init(jnp.asarray(p0))
    res = {w: jnp.zeros((n,), jnp.float32) for w in names}
    counts = {w: float(2 + i) for i, w in enumerate(names)}
    # (per-block keep of each worker, deferred workers) per step
    plan = [({w: kmax >> (i % 3) for i, w in enumerate(names)}, set()),
            ({w: kmax for w in names}, {names[1]}),
            ({w: kmax >> ((i + 1) % 3) for i, w in enumerate(names)},
             set())]
    for step, (keep, defer) in enumerate(plan):
        msgs = {w: ({"w": jnp.asarray(grads[w] * (step + 1))}, counts[w])
                for w in names}
        red.reduce_and_step(msgs, keep={w: rows * k for w, k in
                                        keep.items()}, defer=defer)
        k_arr = jnp.asarray(_pad_to_capacity(
            [0 if w in defer else keep[w] for w in names], 0), jnp.int32)
        ns = jnp.asarray(_pad_to_capacity(
            [0.0 if w in defer else counts[w] for w in names], 0.0),
            jnp.float32)
        flat, state, new_res = _blocktopk_scatter_reference(
            flat, state,
            _pad_to_capacity([msgs[w][0]["w"] for w in names],
                             jnp.zeros(n)),
            _pad_to_capacity([res[w] for w in names], jnp.zeros(n)),
            ns, k_arr, kmax=max(keep[w] for w in names if w not in defer),
            block_w=block_w, opt=opt)
        res = dict(zip(names, new_res))
        for w in names:
            np.testing.assert_array_equal(
                np.asarray(red._residuals[w]).view(np.uint32),
                np.asarray(res[w]).view(np.uint32))
        np.testing.assert_array_max_ulp(np.asarray(red.flat_params),
                                        np.asarray(flat), maxulp=1)
        np.testing.assert_array_max_ulp(np.asarray(red.opt_state["accum"]),
                                        np.asarray(state["accum"]),
                                        maxulp=1)
    # one trace (kmax is the same every step): a rebuild per worker for
    # the residual and one for the segment-sum, or the residual's scatter
    assert calls == ({"blocks": 2 * W_cap, "scatter": 0}
                     if case == "in_blocks" else
                     {"blocks": 0, "scatter": 1})

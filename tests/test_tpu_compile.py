"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached (``jax.experimental.topologies``), at the published widths
they run at: the TPU compiler refuses here what interpret mode cannot see
(block shapes off the (8, 128) tiling, too much VMEM). The hybrid serve
programs compile whole, and their memory must fit the chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the suite runs in several.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode import flash_decode
from repro.kernels.topk_compress import (as_blocks, block_topk,
                                         fused_block_topk_batched)

# qwen3-4b decode: 32 query heads over 8 KV heads of 128, bf16, 8 slots of
# 1024 tokens
B, H, K, D, T = 8, 32, 8, 128, 1024
# mlitb-lm-100m's flat parameter buffer, 4 workers
N_FLAT, W = 100_682_496, 4
# granite-4.0-h-micro served as its benchmark cell: 48 slots of 4096
# tokens in 128-token pages, prefill chunks of 512 for up to 4 rows
SLOTS, SEQ, PAGE, CHUNK, ROWS = 48, 4096, 128, 512, 4
# the device memory a program may use on one v5e (of its 16 GiB)
V5E_BYTES = 15.75e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("layout", ["paged", "dense_view"])
def test_flash_decode_compiles(one_chip, layout):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    # paged: 16-token pages, the pool sized to the dense parity; dense
    # view: each slot row cut into 128-token pages (identity page map)
    ps = 16 if layout == "paged" else 128
    P = T // ps
    pool = S((B * P, K, ps, D), jnp.bfloat16)
    text = _compile_text(
        lambda q, k, v, pm, pos, live: flash_decode(q, k, v, pm, pos, live,
                                                    interpret=False),
        S((B, H, D), jnp.bfloat16), pool, pool, S((B, P), jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [1, 4])
def test_fused_block_topk_batched_compiles(one_chip, k):
    rows = jax.eval_shape(lambda: as_blocks(jnp.zeros(N_FLAT), 128)).shape
    stack = jax.ShapeDtypeStruct((W, *rows), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda g, r: fused_block_topk_batched(g, r, k=k, interpret=False),
        stack, stack)
    assert "tpu_custom_call" in text


def test_block_topk_compiles(one_chip):
    x = jax.ShapeDtypeStruct((N_FLAT,), jnp.float32, sharding=one_chip)
    text = _compile_text(lambda v: block_topk(v, interpret=False), x)
    assert "tpu_custom_call" in text


# temp bytes of the blocktopk step below as two scatter-adds (and the sort
# of their indices), compiled for the same described chip
SCATTER_STEP_TEMP_BYTES = 20_103_680


@pytest.mark.parametrize("method,kmax", [("blocktopk", 4), ("topk", 4096)])
def test_reduce_step_scatters_keep_their_scopes(one_chip, method, kmax):
    # the reducer's scope shares read each op's op_name. blocktopk
    # rebuilds its residual and segment-sum block by block: no scatter
    # and no sort, fusions under reduce/residual writing the residual
    # rows, the segment-sum's compares under reduce/segment_sum inside
    # the fusion that writes the parameters, and no more temporary bytes
    # than the scatters took. topk's indices are global: both scatters
    # (the compiler's custom fusions) and the sort of their indices keep
    # the scope that asked for them
    import re

    from repro.core.compression import GradientCompressor
    from repro.core.reducer import MasterReducer
    from repro.optim import get_optimizer

    leaves = {"a": (1024, 1024), "b": (4096,)}
    red = MasterReducer({k: jnp.ones(v) for k, v in leaves.items()},
                        get_optimizer("adagrad", lr=0.05),
                        GradientCompressor(method, frac=4 / 128,
                                           block_w=128))

    def S(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    n = red.flat_n
    tree = {k: S(v) for k, v in leaves.items()}
    compiled = red._build_step_fn(W, kmax).lower(
        S((n,)), jax.tree.map(lambda x: S(x.shape, x.dtype), red.opt_state),
        (tree,) * W, (S((n,)),) * W, S((W,)), S((), jnp.int32),
        S((W,), jnp.int32)).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):].split("\n}")[0].splitlines()
    if method == "topk":
        heavy = [ln for ln in entry if "scatter" in ln and (
            " sort(" in ln or (" fusion(" in ln and "kind=kCustom" in ln))]
        assert len(heavy) == 3, heavy
        for ln in heavy:
            assert re.search(
                r'op_name="[^"]*/reduce/(residual|segment_sum)/', ln), \
                ln[:160]
        return

    assert not re.search(r"\b(scatter|sort)\(", text)
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= SCATTER_STEP_TEMP_BYTES)
    lines = {ln.split(" = ")[0].split()[-1]: ln for ln in entry
             if " = " in ln}
    root = next(ln for ln in entry if ln.lstrip().startswith("ROOT"))
    outs = re.findall(r"%[\w.\-]+", root.split(" tuple(")[1])

    def producer(name):
        """The fusion that writes a ROOT operand (through its tuple)."""
        ln = lines[name]
        if " get-tuple-element(" in ln:
            ln = lines[re.search(r"get-tuple-element\((%[\w.\-]+)",
                                 ln).group(1)]
        assert " fusion(" in ln, ln[:160]
        return ln

    def body(fusion_line):
        comp = re.search(r"calls=(%[\w.\-]+)", fusion_line).group(1)
        start = text.index("\n" + comp + " ")
        return text[start:text.index("\n}", start)]

    for name in outs[-W:]:                      # the residual rows
        assert re.search(r'op_name="[^"]*/reduce/residual/',
                         producer(name)), name
    # the new parameters: the segment-sum rebuilt inside their fusion
    assert re.search(r'op_name="[^"]*/reduce/segment_sum/[^"]*eq"',
                     body(producer(outs[0])))


@pytest.fixture(scope="module")
def hybrid(one_chip):
    """The published hybrid's serve programs, their parameters and cache
    as shapes on the described chip."""
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.train.step import build_serve_programs

    cfg = get_config("granite-4.0-h-micro")

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)
    pages = SLOTS * SEQ // PAGE
    cache = jax.eval_shape(lambda: tf.hybrid_cache(cfg, SLOTS, pages, PAGE))
    return (build_serve_programs(cfg, paged=True, decode_kernel="flash"),
            on_chip(tf.abstract_params(cfg)), on_chip(cache))


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used <= V5E_BYTES, used
    # the cache is donated: updated in place, never held twice
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes - 1e8


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_hybrid_serve_programs_compile_and_fit(one_chip, hybrid,
                                               monkeypatch, program):
    import importlib
    # the kernel compiles natively only where the default backend is a
    # TPU: here it is the CPU, so the test asks for the native kernel
    ops = importlib.import_module("repro.kernels.flash_decode.ops")
    monkeypatch.setattr(ops, "default_interpret", lambda i=None: False)
    progs, params, cache = hybrid

    def S(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    P = SEQ // PAGE
    if program == "decode":
        compiled = jax.jit(progs.decode, donate_argnums=(3,)).lower(
            params, S((SLOTS, 1)), S((SLOTS,)), cache, S((SLOTS,), bool),
            S((SLOTS, P)), S((SLOTS, P))).compile()
        assert compiled.as_text().count("tpu_custom_call") >= 4
    else:
        compiled = jax.jit(progs.prefill_chunk, donate_argnums=(4,)).lower(
            params, S((ROWS, CHUNK)), S((ROWS,)), S((ROWS,)), cache,
            S((ROWS, P)), S((ROWS, P)), S((ROWS,))).compile()
    _fits(compiled)

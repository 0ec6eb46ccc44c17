"""The program's own spans and named programs (``repro.core.obs``).

A real ``jax.profiler`` trace (CPU) of a tiny engine step, train loop and
reducer step holds the ``mlitb.*`` spans with their args, nested as the
docstrings say, and the programs under their stable names; the reducer's
programs carry their names and, inside the compressed ones, the five
scope paths."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ArchConfig
from repro.core import obs
from repro.core.compression import GradientCompressor
from repro.core.reducer import MasterReducer
from repro.launch.train import run_mesh_training
from repro.models import transformer as tf
from repro.optim import get_optimizer
from repro.serving import (PagingConfig, ServeRequest, ServingConfig,
                           ServingEngine)

TINY = ArchConfig(
    name="tiny-obs", arch_type="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv_heads=1, d_ff=64, vocab_size=61, head_dim=16,
    param_dtype="float32", activ_dtype="float32", tie_embeddings=True)
SCOPES = ("reduce/pack", "reduce/compress", "reduce/residual",
          "reduce/segment_sum", "reduce/optimizer")


def _spans(tmp_path, fn, programs=None):
    """Run ``fn`` under the profiler; its ``mlitb.*`` host spans as
    (name, start ns, end ns, {arg: value}), in start order. The names of
    the programs that ran are added to the set ``programs``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.tracing()
        fn()
    finally:
        jax.profiler.stop_trace()
    assert not obs.tracing()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = {k: v for k, v in ev.stats}
                if ev.name.startswith(obs.PREFIX):
                    out.append((ev.name, ev.start_ns, ev.end_ns, stats))
                elif programs is not None and "hlo_module" in stats:
                    programs.add(stats["hlo_module"])
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_a_span_is_a_shared_no_op_without_a_profile():
    assert not obs.tracing()
    a, b = obs.span("serve.step", queued=1), obs.span("train.step")
    assert a is b and not isinstance(a, jax.profiler.TraceAnnotation)
    with a as sp:
        sp.set_metadata(live_rows=2)


def _named(spans, name):
    return [s for s in spans if s[0] == obs.PREFIX + name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_step_spans_carry_their_counters(tmp_path):
    eng = ServingEngine(tf.init_params(jax.random.PRNGKey(0), TINY), TINY,
                        serving=ServingConfig(
                            max_batch=4, max_seq=32, prompt_cap=8,
                            paging=PagingConfig(page_size=8)))
    rng = np.random.RandomState(0)

    def req(rid, plen, new, arrival):
        return ServeRequest(rid, rng.randint(0, 61, plen).astype(np.int32),
                            new, arrival=arrival)

    eng.submit(req(0, 5, 4, 0.0))           # compile outside the trace
    while eng.has_work:
        eng.step(now=0.0)
    eng.submit(req(1, 12, 3, 1.0))          # two chunks of <= 8
    eng.submit(req(2, 3, 3, 1.5))
    done = []

    def run():
        now = 2.0
        while eng.has_work:
            done.extend(eng.step(now=now).completed)
            now += 0.5

    programs = set()
    spans = _spans(tmp_path, run, programs)
    assert {"jit_chunk_paged", "jit_decode_paged"} <= programs
    steps = _named(spans, "serve.step")
    assert len(steps) == eng.engine_steps - 3
    assert steps[0][3]["queued"] == 2 and steps[0][3]["live_rows"] == 0
    admit = _named(spans, "serve.admit")[0]
    assert admit[3]["admitted"] == 2 and admit[3]["shed"] == 0
    assert admit[3]["queue_wait_max_s"] == pytest.approx(1.0)
    pre = _named(spans, "serve.prefill")
    # first step: both prompts in one (2, 8) bucket, 8 + 3 tokens, one
    # prompt done; second step: the last 4 tokens of the long one, in the
    # smallest chunk bucket (8)
    assert [(p[3]["batch"], p[3]["chunk"], p[3]["tokens"],
             p[3]["first_tokens"]) for p in pre] == [(2, 8, 11, 1),
                                                     (1, 8, 4, 1)]
    dec = _named(spans, "serve.decode")
    assert dec and all(d[3]["kv_pages_walked"] == 4 * 4 for d in dec)
    first = dec[0][3]                       # rid 2 alone, at pos 3
    assert (first["rows_live"], first["kv_tokens_live"],
            first["kv_pages_live"]) == (1, 4, 1)
    for d in pre + dec:                     # one read-back per dispatch
        assert sum(_inside(r, d) for r in _named(spans, "serve.readback")) \
            == 1
        assert any(_inside(d, s) for s in steps)
    # the completions carry the clocks of their admission and first token
    by_rid = {c.rid: c for c in done}
    assert by_rid[1].admitted == 2.0 and by_rid[1].first_token == 2.5
    assert by_rid[2].admitted == 2.0 and by_rid[2].first_token == 2.0


def test_train_loop_spans(tmp_path):
    eng = {}

    def run():
        eng["e"], _ = run_mesh_training(
            TINY, get_optimizer("adagrad", lr=0.05), steps=2, batch=4,
            seq=8, n_workers=2)

    programs = set()
    spans = _spans(tmp_path, run, programs)
    assert "jit_train_step" in programs
    build, loop = _named(spans, "train.build"), _named(spans, "train.loop")
    assert len(build) == 1 and len(loop) == 1 and loop[0][3]["steps"] == 2
    steps = _named(spans, "train.step")
    assert len(steps) == 2 and all(s[3]["n_live"] == 2 for s in steps)
    assert all(_inside(s, loop[0]) for s in steps)
    for name in ("train.mask", "train.dispatch", "train.readback"):
        parts = _named(spans, name)
        assert len(parts) == 2
        assert all(any(_inside(p, s) for s in steps) for p in parts)


def _reducer(method="blocktopk"):
    p0 = {"a": jnp.ones((64, 32)), "b": jnp.ones((96,))}
    comp = None if method is None else GradientCompressor(
        method, frac=4 / 128, block_w=128)
    red = MasterReducer(p0, get_optimizer("adagrad", lr=0.05), comp)
    msgs = {f"w{i}": (jax.tree.map(lambda a: a * (i + 1), p0), 4.0)
            for i in range(3)}
    return red, msgs


def test_reducer_step_spans(tmp_path):
    red, msgs = _reducer()
    jax.block_until_ready(red.reduce_and_step(msgs))
    programs = set()
    spans = _spans(tmp_path, lambda: jax.block_until_ready(
        red.reduce_and_step(msgs)), programs)
    assert "jit_reduce_blocktopk_step" in programs
    (step,) = _named(spans, "reduce.step")
    # W = 3 workers in a capacity of 4; blocktopk keeps 4 of each block
    assert (step[3]["W"], step[3]["W_cap"], step[3]["kmax"]) == (4 - 1, 4, 4)
    assert step[3]["wire_bytes"] == red.last_wire_bytes > 0
    for name in ("reduce.pack", "reduce.dispatch"):
        (part,) = _named(spans, name)
        assert _inside(part, step)


@pytest.mark.parametrize("method,defer,name", [
    ("blocktopk", (), "reduce_blocktopk_step"),
    ("topk", (), "reduce_topk_step"),
    (None, (), "reduce_dense_step"),
    (None, ("w2",), "reduce_masked_step")])
def test_reducer_programs_are_named_and_scoped(method, defer, name):
    red, msgs = _reducer(method)
    red.reduce_and_step(msgs, defer=defer)
    (key,) = red._step_fns
    fn = red._step_fns[key]
    zeros = jnp.zeros((red.flat_n,), jnp.float32)
    gtrees = tuple(msgs[w][0] for w in sorted(msgs)) + (red._zero_gtree(),)
    ns = np.asarray([4, 4, 4, 0], np.float32)
    if method is None and not defer:
        args = (red.flat_params, red.opt_state, gtrees, ns)
    elif method is None:
        args = (red.flat_params, red.opt_state, gtrees, (zeros,) * 4, ns)
    else:
        args = (red.flat_params, red.opt_state, gtrees, (zeros,) * 4, ns,
                np.int32(0), jnp.asarray([key[1]] * 3 + [0], jnp.int32))
    text = fn.lower(*args).as_text(debug_info=True)
    assert f"@jit_{name}" in text and "jit_fn" not in text
    for scope in SCOPES:
        assert (f"{name})/{scope}/" in text) == (method is not None), scope

"""The hybrid serve cell (``drivers/serve_hybrid.py``) at small widths on
the CPU (flash decode in interpret mode): the whole run is correct
against the plain reference (``refhybrid``), the float8 control is not,
each fault planted in the program underneath fails the comparison, and
the cell's readers read what the program's spans and scopes carry."""
import io
import json
from types import SimpleNamespace

import jax
import pytest

from chipbench import control, flops_hybrid, harness, spans
from chipbench.tests import tiny

NAME = "serve.granite-4.0-h-micro.rag"
# four layers, attention third, at the widths of tiny.TINY_DENSE; chunks
# of 8, so prompts of 17-80 tokens take several steps while other rows
# decode between their chunks
CONFIG = {
    "num_hidden_layers": 4, "vocab_size": 4096,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_chunk_size": 8,
    "serving": {"max_batch": 4, "max_seq": 96, "page_size": 8,
                "decode_kernel": "flash", "prompt_cap": 8,
                "prompt_bucket_min": 8, "prefix_reuse": False}}
TRAFFIC = {"rate_rps": 4.0, "max_total": 96, "drain_max_s": 30,
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 17, "max": 80},
           "output": {"dist": "lognormal", "median": 12, "sigma": 0.7,
                      "min": 4, "max": 16},
           "check": {"requests": 3, "min_tokens": 20}}
# The limit at these widths, where the cell's (set at the published size)
# does not carry over: the program (bf16 against the float32 reference)
# reads 0.018 here, the float8 control 0.59, a state reset at every chunk
# 0.40 and dead rows' state updated 2.55 (PERF.md has the readings at the
# published size)
SMALL_LIMIT = 0.1


def _run():
    harness.install_compile_listeners()
    return tiny.run(NAME, seconds=2.0, traffic=TRAFFIC, config=CONFIG)


def execute():
    run = _run()
    run.limits = {"logit_gap": {"limit": SMALL_LIMIT}}
    out, err = io.StringIO(), io.StringIO()
    line = harness.execute(run, out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == line
    return line, err.getvalue()


def test_the_hybrid_cell_runs_end_to_end():
    line, err = execute()
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["window_compiles"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_latency_p95_s"}


def test_the_control_fails_where_the_program_passes():
    got = control.readings(_run())
    assert got["program"]["logit_gap"] < SMALL_LIMIT, got
    assert got["control"]["logit_gap"] > SMALL_LIMIT, got


def _h0_reset(monkeypatch):
    """Every prefill chunk starts the Mamba state from zero."""
    from repro.models import ssm
    chunk = ssm.ssm_prefill_chunk

    def fresh(p, x, clen, state, d, cfg):
        return chunk(p, x, clen, dict(state, h=0 * state["h"]), d, cfg)
    monkeypatch.setattr(ssm, "ssm_prefill_chunk", fresh)


def _dead_rows_updated(monkeypatch):
    """Decode writes the state of rows it does not serve."""
    from repro.models import ssm
    monkeypatch.setattr(ssm, "keep_live", lambda live, new, old: new)


@pytest.mark.parametrize("fault", [_h0_reset, _dead_rows_updated],
                         ids=["h0_reset", "dead_rows_updated"])
def test_a_broken_state_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    jax.clear_caches()
    line, err = execute()
    assert line["correct"] is False, err
    jax.clear_caches()


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def _ctx(monkeypatch, reading, config):
    monkeypatch.setattr(spans, "read", lambda ctx: reading)
    return SimpleNamespace(run=SimpleNamespace(config=config),
                           peaks={"hbm_bytes_per_s": 1e9,
                                  "bf16_flops_per_s": 1e12},
                           counters={"window_flops": 2e12, "window_s": 4.0})


def _reading(decode_args, scope_s):
    spans_ = [spans.Span("mlitb.serve.decode", i, i + 1, a)
              for i, a in enumerate(decode_args)]
    return spans.Reading((0, 1e9), spans_, {}, scope_s, {})


def _granite():
    with open(f"{harness.HERE}/configs/granite-4.0-h-micro.json") as f:
        return json.load(f)


def test_the_hybrid_readers_read_their_spans_and_scopes(monkeypatch):
    c = _granite()
    args = [{"state_rows_live": 12, "state_rows_walked": 48},
            {"state_rows_live": 36, "state_rows_walked": 48}]
    scope = {("jit_decode_paged(3)", "jit(decode_paged)/while/body/"
              "serve/ssm/dot_general"): 0.03,
             ("jit_decode_paged(3)", "jit(decode_paged)/while/body/"
              "serve/mlp/dot_general"): 0.05,
             ("jit_chunk_paged(4)", "jit(chunk_paged)/while/body/"
              "serve/ssm/dot_general"): 0.07}
    ctx = _ctx(monkeypatch, _reading(args, scope), c)
    m = flops_hybrid.Hybrid.of(c)
    need = flops_hybrid.ssm_decode_bytes(m, 12) \
        + flops_hybrid.ssm_decode_bytes(m, 36)
    assert harness.load_reader("ssm_decode_roofline").read(ctx) == \
        pytest.approx(100 * need / 1e9 / 0.03)
    assert harness.load_reader("state_live_share.hybrid").read(ctx) == \
        pytest.approx(100 * 48 / 96)
    assert harness.load_reader("mfu.hybrid").read(ctx) == \
        pytest.approx(100 * 2e12 / 4.0 / 1e12)


def test_the_hybrid_readers_are_silent_without_their_spans(monkeypatch):
    # what a dense decoder's program leaves: decode spans without the
    # state counters, and no serve/ssm scope
    args = [{"rows_live": 3, "kv_pages_live": 9}]
    ctx = _ctx(monkeypatch, _reading(args, {}), _granite())
    assert harness.load_reader("ssm_decode_roofline").read(ctx) is None
    assert harness.load_reader("state_live_share.hybrid").read(ctx) is None
    assert harness.load_reader("ssm_decode_roofline").read(
        _ctx(monkeypatch, None, _granite())) is None


def test_the_hybrid_counts_come_from_the_published_sizes():
    m = flops_hybrid.Hybrid.of(_granite())
    assert (m.mamba_layers, m.attn_layers) == (36, 4)
    assert m.params == 3_191_396_096           # the program's n_params
    # one slot's state in one layer: h float32 and the bf16 conv ring
    assert m.state_row_bytes() == 64 * 64 * 128 * 4 + 3 * 4352 * 2
    # the recurrence is a sliver of a Mamba layer's operations
    assert 6 * m.state_elems < 0.1 * 2 * (m.in_proj + m.d_inner * m.d)

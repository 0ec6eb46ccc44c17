"""Each path driver end to end at small widths on the CPU (kernels in
interpret mode): the harness's whole run with its look for a chip
skipped, and the result line's keys as the contract has them."""
import io
import json

import pytest

from chipbench import harness
from chipbench.tests import tiny

SERVE = {"rate_rps": 4.0, "max_total": 64, "drain_max_s": 20,
         "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.8,
                    "min": 2, "max": 40},
         "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                    "min": 2, "max": 20},
         "check": {"requests": 3, "min_tokens": 10}}
SERVING = {"serving": {"max_batch": 4, "max_seq": 64, "page_size": 8,
                       "decode_kernel": "flash", "prompt_cap": 16,
                       "prompt_bucket_min": 8, "prefix_reuse": True}}
CASES = {
    "train.lm100m": dict(traffic={"rows_per_chip": 4, "seq": 32,
                                  "reference_block_rows": 2}),
    "reduce.lm100m.w4": dict(traffic={"sample_blocks": 64}),
    # a wider vocabulary than the other small cells: the widest gap of a
    # served token needs near-ties at the top of the logits to show
    "serve.qwen3-4b.chat": dict(traffic=SERVE, seconds=2.0,
                                config=dict(SERVING, vocab_size=8192)),
}


def execute(name, **kw):
    harness.install_compile_listeners()
    case = dict(CASES[name], **kw)
    run = tiny.run(name, **case)
    out, err = io.StringIO(), io.StringIO()
    line = harness.execute(run, out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == line
    return line, err.getvalue()


@pytest.mark.parametrize("name", sorted(CASES))
def test_driver_runs_a_cell_end_to_end(name):
    line, err = execute(name)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["window_compiles"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    want = {m["name"] for m in tiny.cell(name)[4]}
    assert set(line["metrics"]) == want and "setup_s" in want
    for n, m in line["metrics"].items():
        assert m["value"] > 0, n
    # the compared numbers are the last lines on standard error
    tail = err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and t.endswith(" ok")
               for t in tail)

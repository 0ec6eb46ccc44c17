"""Small versions of the cells, for the CPU tests: the same harness and
drivers, published widths cut to a few dozen (kernels in interpret mode)."""
from __future__ import annotations

import copy
import os
import sys
import time

import jax

from chipbench import harness

if os.path.join(harness.ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))

TINY_DENSE = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512}


def cell(name: str, chips: int = 1):
    """(cell entry, config, traffic, limits) of a real cell, cut small."""
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    c, conf, e2e, per_layer = harness.cell_spec(spec, name)
    config = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
    config.update(TINY_DENSE)
    traffic = harness.load_json(os.path.join(
        harness.HERE, "traffic", c["traffic"] + ".json"))
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            name + ".json"))
    return dict(c, chips=chips), config, traffic, limits, e2e, per_layer


def run(name: str, *, seed: int = 2**31 + 7, seconds: float = 1.0,
        chips: int = 1, traffic=None, config=None, trace=False):
    c, conf, tr, limits, e2e, per_layer = cell(name, chips)
    tr = copy.deepcopy(tr)
    tr.update(traffic or {})
    conf.update(config or {})
    return harness.Run(root=harness.ROOT, cell=c, config=conf, traffic=tr,
                       limits=limits, e2e=e2e, per_layer=per_layer,
                       seed=seed, seconds=seconds, trace=trace,
                       devices=jax.devices()[:chips],
                       t_start=time.perf_counter())

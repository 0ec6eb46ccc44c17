"""The four-chip train cell (``train.lm100m.4chip``) at small widths on
four virtual CPU devices: the run on a ``data=4`` mesh is correct; with
the last chip's rows left out of the all-reduced gradient it is not, nor
with the exchange between chips left out (each chip updates its shard of
the state from its own rows' gradient); and the collective reader reads
the trace's exposed collective time. The mesh runs in a child process:
the device count is fixed when JAX first starts, and the rest of the
suite sees one device."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from chipbench import harness

CHILD = """
import io, json, sys
import jax
from chipbench import harness
from chipbench.tests import tiny
from repro.core.mesh_engine import ElasticMeshSGD

if sys.argv[1] == "last_chip_dropped":
    mask = ElasticMeshSGD.work_mask

    def three_chips(self, seq_len):
        m = mask(self, seq_len)
        return m.at[3 * m.shape[0] // 4:].set(0.0)
    ElasticMeshSGD.work_mask = three_chips
elif sys.argv[1] == "no_exchange":
    # each chip runs the whole step on its own rows: the state comes in
    # whole (the FSDP gather), the gradient is never summed across
    # chips, and each chip keeps its own shard of its own update
    from jax.sharding import PartitionSpec as P
    from repro.distributed import activation_sharding as acts
    from repro.launch import train as launch_train
    build = launch_train.build_train_step

    def no_exchange(cfg, opt, **kw):
        step = build(cfg, opt, **kw)

        def local(state, batch):
            on, acts._STATE["on"] = acts._STATE["on"], False
            try:
                return jax.shard_map(step, in_specs=(P(), P("data")),
                                     out_specs=(P(), P()),
                                     check_vma=False)(state, batch)
            finally:
                acts._STATE["on"] = on
        return local
    launch_train.build_train_step = no_exchange
harness.install_compile_listeners()
run = tiny.run("train.lm100m.4chip", chips=4,
               traffic={"rows_per_chip": 4, "seq": 32,
                        "reference_block_rows": 4})
line = harness.execute(run, out=io.StringIO(), err=io.StringIO())
print("LINE", json.dumps(line))
"""


def _line(case: str) -> dict:
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count"
               "=4", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(harness.ROOT, "src"),
                                           harness.ROOT]))
    r = subprocess.run([sys.executable, "-c", CHILD, case],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=harness.ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    out = [ln for ln in r.stdout.splitlines() if ln.startswith("LINE ")]
    return json.loads(out[-1][5:])


@pytest.mark.parametrize("case,correct", [("whole", True),
                                          ("last_chip_dropped", False),
                                          ("no_exchange", False)])
def test_the_four_chip_cell_on_a_mesh(case, correct):
    line = _line(case)
    assert line["device"]["count"] == 4
    assert line["correct"] is correct, line["compared"]
    assert line["window_compiles"] == 0


def _ctx(collective_s, exposed_s, window_s=2.0):
    return SimpleNamespace(trace=SimpleNamespace(
        collective_s=collective_s, collective_exposed_s=exposed_s,
        window_s=window_s))


def test_the_collective_reader_reads_exposed_time():
    read = harness.load_reader("collective_share.train").read
    assert read(_ctx(0.5, 0.1)) == pytest.approx(5.0)
    assert read(_ctx(0.5, 0.0)) == 0.0        # all hidden behind compute
    assert read(_ctx(0.0, 0.0)) is None       # no collective in the trace

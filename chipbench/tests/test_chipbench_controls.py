"""The control, the plain reference one precision below the
configuration's in the program's place, and each fault planted in the
reference where the driver has them, fail a cell's limits while the
program passes them (at small widths on the CPU, with a limit of their
own where the cell's does not carry over to them; the readings on the
chip at the cells' sizes are in PERF.md)."""
import pytest

from chipbench import control, harness
from chipbench.tests import tiny
from chipbench.tests.test_chipbench_drivers import CASES

# Limits at these small widths where the cell's own limit does not carry
# over: the float8 control's widest logit gap reads 2.7-4.3 at qwen3-4b's
# size on the chip (the cell's limit is 0.6) but only 0.22-0.79 at two to
# sixteen layers of width 64-256 on the CPU, where the program reads
# 0-0.03 (PERF.md).
SMALL_LIMITS = {"serve.qwen3-4b.chat": {"logit_gap": 0.1}}


@pytest.mark.parametrize("name", sorted(CASES))
def test_control_fails_where_the_program_passes(name):
    harness.install_compile_listeners()
    run = tiny.run(name, **CASES[name])
    got = control.readings(run)
    lim = {k: v["limit"] for k, v in run.limits.items()}
    lim.update(SMALL_LIMITS.get(name, {}))
    assert all(v <= lim[k] for k, v in got["program"].items()), got
    assert any(v > lim[k] for k, v in got["control"].items()), got
    for planted in got.get("faults", {}).values():
        assert any(v > lim[k] for k, v in planted.items()), got

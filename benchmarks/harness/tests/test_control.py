"""The control — the plain reference one precision step below the
configuration's, put in the program's place — comes out as not correct
through the cell's own comparison, at the sizes these tests hold, where
the program comes out correct.  On the chip the same comparison, at each
cell's own size, is run by ``python3 -m benchmarks.harness.control``
(its readings are in PERF.md)."""
import pytest

from benchmarks.harness import run
from benchmarks.harness.tests.small import small_cell
from benchmarks.harness.tests.test_rehearsal import SEED

# sizes at which the control's error has room to show: windows as long as
# the cell's own (the error of a lower-precision field grows with them)
CONTROL_SIZES = {
    "l96_long_closed": dict(horizon=600, max_window=600),
    "hp_telemetry_open": dict(max_window=64, max_horizon=64),
    "l96_fit_seg60": dict(),
    "l96_long_closed_4chip": dict(horizon=200),
}


@pytest.mark.parametrize("name", list(CONTROL_SIZES))
def test_control_fails_where_the_program_passes(name):
    cell = small_cell(name, **CONTROL_SIZES[name])
    result, outcome = run.run_cell(cell, SEED, 0.3, False, control=True,
                                   device_kind="TPU v5 lite")
    assert result["correct"], result["checks"]
    assert outcome.info["control"]["correct"] is False, outcome.info["control"]

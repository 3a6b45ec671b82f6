"""With the timed path broken underneath, a run's ``correct`` comes out
false: once for each fault the cell can have.  The chip check is skipped;
the rest of the run is the benchmark's own.  The sharded cell's exchange
between chips is broken on four virtual devices
(``test_rehearsal.test_sharded_cell_on_four_devices``)."""
import pytest

from benchmarks.harness import run
from benchmarks.harness.tests.faults import FAULTS
from benchmarks.harness.tests.small import small_cell
from benchmarks.harness.tests.test_rehearsal import SEED

CASES = [("l96_long_closed", "answer_altered"),
         ("l96_long_closed", "state_unchanged"),
         ("l96_long_closed", "rows_mixed"),
         ("hp_telemetry_open", "answer_altered"),
         ("hp_telemetry_open", "state_unchanged"),
         ("hp_telemetry_open", "rows_mixed"),
         ("l96_long_closed_4chip", "answer_altered"),
         ("l96_long_closed_4chip", "trajectory_frozen"),
         ("l96_long_closed_4chip", "rows_mixed"),
         ("l96_fit_seg60", "update_skipped"),
         ("l96_fit_seg60", "half_batch")]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_makes_run_incorrect(name, fault):
    with FAULTS[fault]():
        result, _ = run.run_cell(small_cell(name), SEED, 0.3, False,
                                 device_kind="TPU v5 lite")
    assert result["correct"] is False, result["checks"]


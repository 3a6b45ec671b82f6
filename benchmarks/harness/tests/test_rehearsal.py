"""Every cell end to end at a small size on the CPU (Pallas interpreted),
and the measuring command's refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import run, spec
from benchmarks.harness.tests.small import small_cell

ROOT = spec.ROOT
SEED = 2 ** 31 + 11                     # past 32 signed bits, as driven


def _check(result, cell):
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    for name, c in result["checks"].items():
        assert c["value"] <= c["limit"], name
    return result


@pytest.mark.parametrize("name", ["l96_long_closed", "hp_telemetry_open",
                                  "l96_fit_seg60", "l96_long_closed_4chip"])
def test_cell_end_to_end(name):
    cell = small_cell(name)
    result, outcome = run.run_cell(cell, SEED, 0.5, False,
                                   device_kind="TPU v5 lite")
    _check(result, cell)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert outcome.info["compiles_in_window"] == 0


def test_traced_run_reports_per_layer_metrics():
    cell = small_cell("l96_long_closed")
    result, _ = run.run_cell(cell, SEED, 0.5, True, device_kind="TPU v5 lite")
    _check(result, cell)
    names = {m["name"] for m in cell.per_layer}
    assert set(result["metrics"]) <= names
    assert "idle_share.serve" in result["metrics"]
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > 0
    assert result["breakdown"]["device_ops"]


def test_same_seed_same_traffic():
    from benchmarks.harness import yardstick
    a = yardstick.poisson_arrivals(yardstick.seeds(SEED)[0], 50, rate_hz=10.0,
                                   population=7, min_horizon=8, max_horizon=64)
    b = yardstick.poisson_arrivals(yardstick.seeds(SEED)[0], 50, rate_hz=10.0,
                                   population=7, min_horizon=8, max_horizon=64)
    c = yardstick.poisson_arrivals(yardstick.seeds(SEED + 1)[0], 50,
                                   rate_hz=10.0, population=7, min_horizon=8,
                                   max_horizon=64)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[1] == c[1]).all()


def test_command_refuses_without_tpu():
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.harness", "--workload",
         "l96_long_closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


SHARDED = f"""
import json
from benchmarks.harness import run
from benchmarks.harness.tests.faults import FAULTS
from benchmarks.harness.tests.small import small_cell
cell = small_cell("l96_long_closed_4chip", devices=4)
sound, _ = run.run_cell(cell, {SEED}, 0.3, True, device_kind="TPU v5 lite")
with FAULTS["exchange_dropped"]():
    broken, _ = run.run_cell(cell, {SEED}, 0.3, False,
                             device_kind="TPU v5 lite")
print(json.dumps({{"sound": sound, "broken": broken}}))
"""


def test_sharded_cell_on_four_devices():
    """The 4-chip cell over four virtual CPU devices: correct and traced,
    and not correct when only the first device's rows come back (the
    exchange between chips left out)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    out = subprocess.run([sys.executable, "-c", SHARDED], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["sound"]["correct"], got["sound"]["checks"]
    assert "idle_share.sharded" in got["sound"]["metrics"]
    assert got["broken"]["correct"] is False, got["broken"]["checks"]

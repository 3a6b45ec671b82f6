"""Finds the knee of an open-loop cell: runs its load at each offered
rate, in one process, and prints the latency and the backlog at each.

    python3 -m benchmarks.harness.sweep --workload hp_telemetry_open \
        --seed 5 --seconds 6 --rates 2000 4000 8000

A rate the system sustains completes every request in the window with a
flat queue; past the knee the backlog at the window's close grows with
the rate and the tail jumps.  The cell's traffic file then takes 0.8 of
the highest sustained rate.  Needs a TPU, like the benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def main(argv=None) -> int:
    from . import run, spec
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    run._prepare_jax()
    for rate in args.rates:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic, rate_hz=rate))
        result, outcome = run.run_cell(c, args.seed, args.seconds, False)
        i = outcome.info
        print(json.dumps({
            "rate_hz": rate, "correct": result["correct"],
            "latency_p50_ms": i["latency_p50_ms"],
            "latency_p95_ms": result["metrics"]["latency_p95_ms"]["value"],
            "latency_p99_ms": i["latency_p99_ms"],
            "backlog_at_close": i["backlog_at_close"],
            "partial_pumps": i["partial_pumps"],
            "pump_ms": outcome.layer["pump_ms"], "pumps": outcome.layer["pumps"],
            "generator_lag_max_ms": i["generator_lag_max_ms"],
            "compiles_in_window": i["compiles_in_window"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

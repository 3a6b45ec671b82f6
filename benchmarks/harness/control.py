"""Reads the numbers ``correct`` compares for many seeds in one process:
the program's (the lower readings of each limit) and the control's, the
plain reference put in the program's place one precision step below the
configuration's (the upper readings), each judged by the cell's own
comparison: the program should come out correct, the control not.  The
benchmark's own runs never run the control.

    python3 -m benchmarks.harness.control --workload l96_long_closed \
        --seconds 2 --seeds 101 102 103

Prints one JSON line per seed.  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    from . import run, spec
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    run._prepare_jax()
    for seed in args.seeds:
        result, outcome = run.run_cell(cell, seed, args.seconds, False,
                                       control=True)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "correct": result["correct"],
            "program": {k: v["value"] for k, v in result["checks"].items()},
            "control_correct": outcome.info["control"]["correct"],
            "control": outcome.control,
            "e2e": {k: v["value"] for k, v in result["metrics"].items()
                    if k != "setup_s"},
            "compiles_in_window": outcome.info["compiles_in_window"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

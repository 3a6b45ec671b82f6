"""Runs one benchmark cell once and prints its result line.

    python3 -m benchmarks.harness --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout.  Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: every number compared with the
reference beside its limit (also the last lines of standard error).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()

from . import spec  # noqa: E402


def _prepare_jax() -> str:
    """The persistent compilation cache at a fixed path inside the
    checkout (or ``JAX_COMPILATION_CACHE_DIR``), for every program however
    short its compile."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def device_info(chips: int) -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": chips}


def per_layer(cell: spec.Cell, layer: dict, device_kind: str) -> dict:
    """Each per-layer metric's reader on what the traced run gathered; a
    reader that finds nothing returns None and the metric is left out."""
    from . import costs
    ctx = dict(layer, peak=costs.peaks(device_kind))
    out = {}
    for m in cell.per_layer:
        value = spec.load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def judge(cell: spec.Cell, values: dict, failed: int) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether all are within
    them with nothing failed."""
    checks = {}
    for name, value in values.items():
        if name not in cell.limits:
            raise KeyError(f"limits/{cell.name}.json has no limit for "
                           f"{name!r}")
        checks[name] = {"value": value, "limit": cell.limits[name]}
    return checks, failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             *, control: bool = False, t_process: float = T_PROCESS,
             device_kind: str | None = None) -> tuple[dict, "Outcome"]:
    """Drive the cell and build its result object (no device check: the
    caller has made it)."""
    from . import cells, trace
    from .yardstick import CompileClock
    compiles = CompileClock()
    load = cells.resolve_load(cell.traffic["load"])
    holder = {}
    clock = cells.Clock(
        on_open=lambda: holder.update(setup=compiles.take()),
        on_close=lambda: holder.update(window=compiles.take()))
    outcome, mem = load(cell.config, cell.traffic, seed, seconds, traced,
                          clock, control=control)
    setup_s = clock.opened - t_process
    checks, correct = judge(cell, outcome.checks, outcome.failed)
    if control:
        # the control, put in the program's place, through the same
        # comparison: a sound comparison finds it not correct
        c_checks, c_correct = judge(cell, outcome.control, 0)
        outcome.info["control"] = {"correct": c_correct, "checks": c_checks}
    import jax
    kind = device_kind or jax.devices()[0].device_kind
    device = dict(device_info(cell.chips), kind=kind, memory_peak_bytes=mem)
    if traced:
        tr = outcome.layer.get("trace")
        metrics = per_layer(cell, outcome.layer, kind)
        device.update(busy_s=trace.busy_s(tr), window_s=trace.window_s(tr))
        result = {"correct": correct, "attempted": outcome.attempted,
                  "failed": outcome.failed, "metrics": metrics,
                  "device": device, "breakdown": trace.breakdown(tr)}
    else:
        values = dict(outcome.e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
        result = {"correct": correct, "attempted": outcome.attempted,
                  "failed": outcome.failed, "metrics": metrics,
                  "device": device}
    outcome.info.update(setup_s=setup_s,
                        compiles_in_window=holder["window"]["compiles"],
                        setup_compiles=holder["setup"])
    result["checks"] = checks
    return result, outcome


def _emit(result: dict, outcome) -> None:
    print(json.dumps({"info": outcome.info}, default=float), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


def main(argv=None, t_process: float = T_PROCESS) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"harness: JAX found no TPU: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"harness: no TPU — JAX found {len(devices)} "
              f"{devices[0].platform} device(s); the benchmark runs only on "
              f"a TPU", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"harness: {args.workload} needs {cell.chips} TPU chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    _prepare_jax()
    result, outcome = run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_process=t_process)
    _emit(result, outcome)
    return 0


if __name__ == "__main__":
    sys.exit(main())

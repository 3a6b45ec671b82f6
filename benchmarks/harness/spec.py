"""Finds a workload's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell's
limits or one per-layer metric sits in a file of its own:

    configs/<config>.json         sizes, precision, weights, plain reference;
                                  ``"twin": "<kind>"`` names its twin kind
    twins/<kind>.py               a twin kind (below); ``mlp`` by default
    traffic/<traffic>.json        the mix: which load and its parameters
    loads/<load>.py               a load ``cells.LOADS`` lacks, as
                                  ``load(config, traffic, seed, seconds,
                                  traced, clock, control=False)``
    limits/<workload>.json        the limit of each number ``correct`` compares
    metrics/<metric>.py           a reader with ``read(ctx) -> float | None``
    kernels/<kernel>.json         ``{"op": regex}`` over a device op's HLO
                                  text, beside the rules of ``kernels.json``

so a cell or a metric is added by adding files and entries, never by
editing one that exists.

A twin kind is everything that depends on the vector field, as module
functions of ``twins/<kind>.py``:

    layer_sizes(config)                   the program twin's sizes, checked
    served_fleet(config, backend)         the program's TwinFleet
    fit_twin(config)                      the program twin fit() trains
    make_weights(config, jax_seed)        seeded weights, made on the device
    initial_states(config, n, jax_seed)   (y0s, thetas) as host arrays
    drive_half_steps(config, thetas, starts, steps)
                                          the control samples the
                                          reference reads, (n, 2*steps+1, Du)
    field(params, u, y, operand_dtype)    the plain f32 reference field,
                                          operands rounded for the control
    flops_per_twin_step(config)
    fused_fwd_cost(config, steps=, rows=), fused_bwd_cost(...)
                                          (operations, HBM bytes) of a call
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

HARNESS = pathlib.Path(__file__).resolve().parent
ROOT = HARNESS.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One resolved workload: its entry, files and the metrics it reports."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    with open(path) as f:
        return json.load(f)


def reports(metric: dict, workload: str) -> bool:
    """A metric without ``workloads`` is reported by every cell."""
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(bench: dict, workload: str) -> Cell:
    """The cell ``workload`` names, with every file it needs loaded."""
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"unknown workload {workload!r}; have "
                       f"{sorted(entries)}")
    w = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(ROOT / configs[w["config"]]["file"])
    traffic = _load_json(HARNESS / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(HARNESS / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"] if reports(m, workload)]
    per_layer = [m for m in bench["per_layer"] if reports(m, workload)]
    for m in per_layer:
        metric_path(m["name"])          # fail now, not after the window
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def module_path(folder: str, name: str) -> pathlib.Path:
    path = HARNESS / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {folder}/{name}.py: {path}")
    return path


def metric_path(name: str) -> pathlib.Path:
    return module_path("metrics", name)


@functools.cache
def load_module(folder: str, name: str):
    """The module ``<folder>/<name>.py``, loaded once."""
    spec = importlib.util.spec_from_file_location(
        f"harness_{folder}_{name.replace('.', '_').replace('-', '_')}",
        module_path(folder, name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    return load_module("metrics", name).read

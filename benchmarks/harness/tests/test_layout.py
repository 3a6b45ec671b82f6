"""Every workload in BENCHMARK.json resolves to its files by name alone:
the proof that a cell or a metric is added by adding files and entries."""
import json
import re

import pytest

from benchmarks.harness import cells, spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
#: what every twin kind provides (spec.py)
TWIN_KIND = ("layer_sizes", "served_fleet", "fit_twin", "make_weights",
             "initial_states", "drive_half_steps", "field",
             "flops_per_twin_step", "fused_fwd_cost", "fused_bwd_cost")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_to_its_files(workload):
    cell = spec.resolve(BENCH, workload)
    assert callable(cells.resolve_load(cell.traffic["load"]))
    kind = cells.twin_kind(cell.config)
    for fn in TWIN_KIND:
        assert callable(getattr(kind, fn)), fn
    assert any(m["name"] != "setup_s" for m in cell.end_to_end)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
        assert any(e["name"] == m["moves"] for e in cell.end_to_end)


def test_names_and_references():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        json.loads((spec.ROOT / c["file"]).read_text())
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert {"device", "kernels"} <= set(layers)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.resolve(BENCH, "no_such_cell")

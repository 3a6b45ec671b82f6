"""Each cell cut to a size the CPU (Pallas interpreted) runs in seconds:
the same loads, files and checks, fewer twins and shorter windows."""
import dataclasses

from benchmarks.harness import spec

SMALL = {
    "l96_long_closed": ({}, dict(twins=16, horizon=8, hot_capacity=16,
                                 max_batch=16, max_window=8, warm_rounds=1,
                                 check_twins=4)),
    "hp_telemetry_open": ({}, dict(rate_hz=200.0, population=64,
                                   hot_capacity=16, max_batch=8,
                                   max_window=16, min_horizon=8,
                                   max_horizon=16)),
    "l96_fit_seg60": (dict(num_points=200, train_points=121),
                      dict(segment=10, chunk_steps=10)),
    "l96_long_closed_4chip": ({}, dict(fleet=16, horizon=8, devices=1,
                                       distinct_batches=100,
                                       warm_batches=1, check_twins=4)),
}


def small_cell(name: str, **traffic) -> spec.Cell:
    cell = spec.resolve(spec.load_benchmark(), name)
    config, small = SMALL[name]
    return dataclasses.replace(
        cell, config=dict(cell.config, **config),
        traffic={**cell.traffic, **small, **traffic})

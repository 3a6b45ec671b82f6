"""The cell added with the neural CDE twin kind, ``ncde_speech_closed``, on
the CPU (Pallas interpreted): end to end at a reduced size against its
reference, the control failing it, the kernel's time chunks above one at
the cell's full window, the new counter readers silent on a program
without the counters, and the served weights reading z in every row."""
import dataclasses

import pytest

from benchmarks.harness import run, spec

SEED = 2 ** 31 + 29                     # past 32 signed bits, as driven

# fewer twins and shorter windows; the widths and the control as served
SMALL = {
    "ncde_speech_closed": dict(twins=16, horizon=16, hot_capacity=16,
                               max_batch=16, max_window=16, warm_rounds=1,
                               check_twins=4),
}
# the full window, over one 64-twin kernel tile
FULL_WINDOW = {
    "ncde_speech_closed": dict(twins=64, hot_capacity=64, max_batch=64,
                               warm_rounds=0, check_twins=4),
}
CELLS = sorted(SMALL)


def _cell(name: str, traffic: dict) -> spec.Cell:
    cell = spec.resolve(spec.load_benchmark(), name)
    return dataclasses.replace(cell, traffic={**cell.traffic, **traffic})


@pytest.mark.parametrize("name", CELLS)
def test_new_cell_correct_and_control_fails(name):
    result, outcome = run.run_cell(_cell(name, SMALL[name]), SEED, 0.3,
                                   False, control=True,
                                   device_kind="TPU v5 lite")
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"twin_steps_per_s", "setup_s"}
    assert outcome.info["compiles_in_window"] == 0
    assert outcome.info["control"]["correct"] is False, outcome.control
    assert set(outcome.info["served_by"]) == {"fused_pallas"}


@pytest.mark.parametrize("name", CELLS)
def test_traced_new_cell_reports_its_counters(name):
    cell = _cell(name, SMALL[name])
    result, _ = run.run_cell(cell, SEED, 0.3, True,
                             device_kind="TPU v5 lite")
    assert result["correct"], result["checks"]
    assert {"time_chunks.serve", "drive_mb.serve", "idle_share.serve",
            "mfu.serve"} <= set(result["metrics"])
    assert result["metrics"]["drive_mb.serve"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_full_window_takes_more_than_one_time_chunk(name):
    """At the cell's own max_window, over a full 64-twin tile under the
    default VMEM budget, the per-twin drive block forces the kernel to
    stream each window in several time chunks (no trace needed: the
    reader takes the program's counters)."""
    cell = _cell(name, FULL_WINDOW[name])
    assert cell.traffic["horizon"] == cell.traffic["max_window"]
    result, outcome = run.run_cell(cell, SEED, 0.0, False,
                                   device_kind="TPU v5 lite")
    assert result["correct"], result["checks"]
    chunks = spec.load_reader("time_chunks.serve")(outcome.layer)
    assert chunks is not None and chunks > 1


@pytest.mark.parametrize("metric", ["time_chunks.serve", "drive_mb.serve"])
def test_counter_readers_silent_without_the_counters(metric):
    read = spec.load_reader(metric)
    assert read({}) is None
    assert read({"counters": {"stream.batches": 4}}) is None
    assert read({"counters": {"stream.time_chunks": 8,
                              "stream.drive_bytes": 8_000_000,
                              "stream.batches": 0}}) is None
    assert read({"counters": {"stream.time_chunks": 8,
                              "stream.drive_bytes": 8_000_000,
                              "stream.batches": 4}}) == {
        "time_chunks.serve": 2.0, "drive_mb.serve": 2.0}[metric]


def test_served_weights_read_z_in_every_row():
    """``bounded_cde``: every one of the D rows of the (D, Du) field has
    z-dependent output weights; over 1600 steps (10 windows) of 64 twins
    the states stay finite and the tanh unsaturated; and swapping two
    data channels' weight columns of the rows that are not damped moves
    a window by more than the cell's ``max_rel_err`` limit, so
    ``correct`` sees those columns."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference

    cell = _cell("ncde_speech_closed", {})
    config, limit = cell.config, cell.limits["max_rel_err"]
    kind = spec.load_module("twins", "ncde")
    D, Du, H = config["state_dim"], config["control_channels"], 160
    n_active = config["weights"]["active"]
    params = kind.make_weights(config, 5)
    w = np.asarray(params[-1]["w"]).reshape(-1, D, Du)
    assert (np.abs(w[:, :, 1:]).sum((0, 2)) > 0).all()
    damped = np.asarray(params[0]["w"])[:, :n_active].sum(1) > 0
    assert damped.sum() == n_active

    z, theta = kind.initial_states(config, 64, 5)
    roll = jax.jit(lambda p, y, u: reference.rk4_rollout(
        kind.field, p, y, u, config["dt"], H))
    with jax.default_matmul_precision("highest"):
        for k in range(10):
            u = jnp.asarray(kind.drive_half_steps(config, theta,
                                                  np.full(64, k * H), H))
            traj = np.asarray(roll(params, jnp.asarray(z), u))
            assert np.isfinite(traj).all()
            z = traj[:, -1]
        g = np.tanh(np.asarray(reference.mlp(params, jnp.asarray(z))))
        assert (np.abs(g) > 0.99).mean() < 0.01

        wf = w.copy()
        wf[:, ~damped, 1], wf[:, ~damped, 2] = w[:, ~damped, 2], w[:, ~damped, 1]
        faulty = params[:-1] + [dict(params[-1],
                                     w=jnp.asarray(wf.reshape(-1, D * Du)))]
        u = jnp.asarray(kind.drive_half_steps(config, theta,
                                              np.full(64, 10 * H), H))
        a = np.asarray(roll(params, jnp.asarray(z), u))
        b = np.asarray(roll(faulty, jnp.asarray(z), u))
    peak = np.abs(a).max((1, 2))
    err = np.abs(a - b).max((1, 2)) / np.maximum(peak, np.median(peak))
    assert err.max() > 2 * limit

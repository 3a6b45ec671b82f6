"""``FleetServer`` over a ``("twins",)`` mesh, closed loop: each request
batch of fresh seeded states is sent when the last batch's trajectories
are back on the host.

Every batch is the same work: one ``FleetServer.serve`` call
(validation, placement on the mesh, the sharded rollout) on ``fleet``
states, and the read of the whole (fleet, horizon + 1, D) output to the
host, which gathers it from every chip.  The states of
``distinct_batches`` batches are drawn from the seed in set-up (the twin
kind's ``initial_states``, ``DRAW_BATCHES`` batches a call) and sent in
turn, so the window holds no work of the client's own.  ``correct``
compares every window of the window's first batch and ``check_twins``
twins drawn from the seed in each later batch with the reference from
the state that was sent.

Traffic keys: ``fleet``, ``horizon``, ``devices`` (the mesh),
``distinct_batches``, ``warm_batches``, ``check_twins``.
"""
from __future__ import annotations

import contextlib
import gc

import numpy as np

from benchmarks.harness import cells, trace, yardstick

DRAW_BATCHES = 64


def load(config, traffic, seed, seconds, traced, clock, control=False):
    import jax
    import jax.numpy as jnp
    from repro.launch.fleet_serving import FleetServer
    from repro.launch.mesh import make_twin_mesh
    kind = cells.twin_kind(config)
    rng, jax_seed = yardstick.seeds(seed)
    params = kind.make_weights(config, jax_seed)
    n, H, dt = traffic["fleet"], traffic["horizon"], config["dt"]
    devices = traffic["devices"]
    fleet = kind.served_fleet(config, cells.served_backend(config))
    cells.check_sizes(fleet.twin, kind, config)
    server = FleetServer(fleet, params, jnp.linspace(0.0, H * dt, H + 1),
                         mesh=make_twin_mesh(devices))
    pool = traffic["distinct_batches"]
    draws = [kind.initial_states(config, DRAW_BATCHES * n,
                                 int(rng.integers(2 ** 32)))
             for _ in range(-(-pool // DRAW_BATCHES))]
    y0_pool = np.concatenate([y for y, _ in draws])
    th_pool = np.concatenate([t for _, t in draws])
    m = len(draws) * DRAW_BATCHES
    y0_pool = y0_pool.reshape(m, n, y0_pool.shape[1])[:pool]
    th_pool = th_pool.reshape(m, n, th_pool.shape[1])[:pool]
    kept = []                       # (y0s, thetas, trajectories) compared

    def batch(i, rows):
        y0s, th = y0_pool[i % pool], th_pool[i % pool]
        with trace.span("serve_batch", traced):
            out = server.serve(y0s, th if th.shape[1] else None)
        with trace.span("batch.wait", traced):
            out.block_until_ready()
        with trace.span("batch.read", traced):
            traj = np.asarray(out)
        kept.append((y0s[rows], th[rows], traj[rows]))

    for i in range(traffic["warm_batches"]):
        batch(i, slice(0, 0))
    kept.clear()
    k = min(n, traffic["check_twins"])
    out = {}
    batches = 0
    with trace.capture(out) if traced else contextlib.nullcontext():
        t0 = clock.open()
        while True:
            batch(batches, np.sort(rng.choice(n, size=k, replace=False))
                  if batches else slice(None))
            batches += 1
            if clock.now() - t0 >= seconds:
                break
        window = clock.close() - t0
    mem = cells.memory_peak(jax.devices()[:devices])
    del server
    gc.collect()
    y0 = np.concatenate([x[0] for x in kept])
    th = np.concatenate([x[1] for x in kept])
    served = np.concatenate([x[2] for x in kept]).astype(np.float32)
    nonfinite = int((~np.isfinite(served)).any(axis=(1, 2)).sum())
    starts = np.zeros(len(y0), np.int64)
    lens = np.full(len(y0), H)
    u = kind.drive_half_steps(config, th, starts, H)
    ref = cells.reference_rollouts(kind.field, params, y0, u, dt, H)
    checks = {"max_rel_err": float(cells.window_rel_err(served, ref,
                                                        lens).max()),
              "rms_rel_err": cells.rms_rel_err(served, ref, lens),
              "nonfinite_windows": nonfinite}
    ctl = {}
    if control:
        low = cells.reference_rollouts(kind.field, params, y0, u, dt, H,
                                       cells.control_operand_dtype(config))
        ctl = {"max_rel_err": float(cells.window_rel_err(low, ref,
                                                         lens).max()),
               "rms_rel_err": cells.rms_rel_err(low, ref, lens)}
    attempted = batches * n
    rate = attempted * H / window
    per_call = -(-n // devices)
    rows = -(-per_call // config["batch_tile"]) * config["batch_tile"]
    layer = {"twin_steps_per_s": rate,
             "flops_per_twin_step": kind.flops_per_twin_step(config),
             "chips": devices, "trace": out.get("trace"),
             "kernels": {"fused_fwd": kind.fused_fwd_cost(config, steps=H,
                                                          rows=rows)}}
    info = {"window_s": window, "batches": batches, "devices": devices,
            "windows_compared": int(len(y0)),
            "batch_ms": 1e3 * window / batches}
    return cells.Outcome(attempted=attempted, failed=nonfinite,
                         e2e={"twin_steps_per_s": rate}, checks=checks,
                         info=info, layer=layer, control=ctl), mem

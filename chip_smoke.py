#!/usr/bin/env python3
"""Smoke run of the twin engine's main path on a TPU, through its normal
entry points, at the paper configurations' own widths.

    python chip_smoke.py              # phases 1-3 on one chip
    python chip_smoke.py --chips 4    # phase 4 only, on a 4-chip host

1. serve the Lorenz96 fleet (``configs/lorenz96_twin.FLEET``: 1024 twins,
   6 -> 64 -> 64 -> 6, H 200) through ``StreamingFleetServer`` on the
   ``fused_pallas`` tier at the platform's default precision;
2. serve the driven HP twin (``configs/hp_twin``: 2 -> 14 -> 14 -> 1,
   dt 1e-3) to 256 twins, each with its own drive parameters, over 500
   steps (the per-twin drive slab of the fused kernel);
3. take a few ``fit()`` steps on the Lorenz96 twin on the fused substrate
   (forward and reverse-time kernels) with its own ``l1+softdtw`` loss
   (both soft-DTW wavefront kernels);
4. (``--chips 4``) serve the Lorenz96 fleet with ``serve_fleet`` over a
   4-chip ``("twins",)`` mesh and over one device: the two must agree
   exactly, and the output must sit on all four chips.

Served trajectories are compared with the plain float32 ``jax.numpy`` RK4
reference (``ops.fused_node_rollout_ref``) at ``highest`` matmul precision,
and the training loss with the digital-adjoint loss, within the documented
``bf16_f32acc`` tolerance.  Every phase also checks that its programs hold
a Pallas kernel (``tpu_custom_call``) and that no request fell back to
another serving tier; the HP phase also checks that its trajectories
fail the same tolerance against the reference with every twin's drive
rolled to its neighbour (a negative control).  Any failure raises;
nothing is caught.

Weights are random, drawn from ``--seed``.  Each phase prints one JSON line
(device, precision, compile seconds, twin-steps/s, errors, tier counters);
those numbers are informative, not a benchmark.  The last line of standard
output is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Max relative error (vs the reference's peak magnitude) of a served
#: trajectory of up to ``TOL_HORIZON`` steps, per precision policy — the
#: tolerance ``docs/kernels.md`` documents and ``tests/test_kernels.py``
#: pins (``PRECISION_REL_TOL``).  Under ``bf16_f32acc`` every matmul
#: input is rounded to bf16, an error that integrates over the horizon,
#: so a horizon of T steps is held to ``REL_TOL * max(1, T/TOL_HORIZON)``.
#: At the HP phase's 500 steps that is 2.5e-2: above the sound kernel's
#: readings and below those of a kernel fed its neighbour's drive (the
#: error model and both sets of readings are in ``docs/kernels.md``).
REL_TOL = {"f32": 1e-5, "bf16_f32acc": 1e-2}
TOL_HORIZON = 200

#: Shooting-segment length of the fit phase: the first stage of
#: ``recipes.train_l96_twin``'s curriculum.
FIT_SEGMENT = 60
FIT_STEPS = 3
HP_FLEET = 256
HP_HORIZON = 500


class CompileClock:
    """Backend compile seconds and persistent-cache hits JAX reports."""

    def __init__(self):
        self.seconds, self.cache_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.seconds, "cache_hits": self.cache_hits}
        self.seconds, self.cache_hits = 0.0, 0
        return out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


def tolerance(precision: str, horizon: int) -> float:
    return REL_TOL[precision] * max(1.0, horizon / TOL_HORIZON)


def max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite output")
    return float(np.abs(got - want).max() / np.abs(want).max())


def reference(params, y0s, u_half, dt) -> np.ndarray:
    """Plain f32 RK4 at highest matmul precision -> (N, T+1, D)."""
    from repro.kernels import ops
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ops.fused_node_rollout_ref, static_argnums=3)(
            params, y0s, u_half, dt)
    return np.asarray(jnp.transpose(ref, (1, 0, 2)))


def kernel_count(compiled_text: str) -> int:
    return compiled_text.count('custom_call_target="tpu_custom_call"')


def serve_streaming(fleet, params, y0s, thetas, horizon: int, dt: float,
                    u_half, precision: str,
                    clock: CompileClock) -> tuple[dict, np.ndarray]:
    """One request of ``horizon`` steps per twin through the streaming
    server (hot slab = the whole population, one batch), checked against
    the reference; then a second round, timed warm.  Returns the phase's
    readings and the served (N, T+1, D) trajectories."""
    from repro.launch.fleet_serving import StreamingFleetServer
    n, D = y0s.shape
    server = StreamingFleetServer(
        fleet, params, dt=dt, hot_capacity=n, max_batch=n,
        max_window=horizon, transient_retries=0)
    for i in range(n):
        server.register_twin(i, y0s[i],
                             theta=None if thetas is None else thetas[i])

    def serve_round():
        t0 = time.perf_counter()
        for i in range(n):
            server.submit(i, horizon)
        done = server.drain()
        wall = time.perf_counter() - t0
        check(len(done) == n, f"{len(done)} of {n} requests completed")
        done.sort(key=lambda c: c.twin_id)
        return np.stack([c.trajectory for c in done]), wall

    traj, wall_cold = serve_round()
    compiled = clock.take()
    err = max_rel_err(traj, reference(params, y0s, u_half, dt))
    tol = tolerance(precision, horizon)
    check(err <= tol, f"served trajectories off the reference by "
          f"{err:.3g} (tolerance {tol:.3g})")
    _, wall_warm = serve_round()

    stats = server.stats().as_dict()
    serving, stream = stats["serving"], stats["stream"]
    tier = server.active_tier
    check(tier == "fused_pallas", f"active tier is {tier}")
    check(serving["served_by"] == {"fused_pallas": stream["batches"]},
          f"batches served by {serving['served_by']}")
    for counter in ("probe_demotions", "retries", "nan_rescues",
                    "transient_retries"):
        check(serving[counter] == 0, f"{counter} = {serving[counter]}")
    # The window program the server ran, compiled as the server compiled
    # it: the fused kernel must be in it (not an XLA fallback).
    H = horizon
    uh = (jax.ShapeDtypeStruct((2 * H + 1, 0), jnp.float32) if thetas is None
          else jax.ShapeDtypeStruct((n, 2 * H + 1, u_half.shape[-1]),
                                    jnp.float32))
    text = server._window_fn(0, H).lower(
        jax.ShapeDtypeStruct((n, D), jnp.float32), uh).compile().as_text()
    check(kernel_count(text) >= 1, "window program holds no tpu_custom_call")
    out = {**compiled, "precision": precision, "max_rel_err": err,
           "tolerance": tol, "cold_wall_s": wall_cold,
           "warm_wall_s": wall_warm,
           "twin_steps_per_s": n * horizon / wall_warm,
           "kernels_in_window_program": kernel_count(text),
           "served_by": serving["served_by"],
           "counters": {k: serving[k] for k in (
               "probe_demotions", "retries", "nan_rescues",
               "transient_retries")},
           "stream": {k: stream[k] for k in (
               "batches", "served", "twin_steps", "padded_steps")}}
    return out, traj


def l96_serve(seed: int, clock):
    from repro.configs.lorenz96_twin import FLEET
    from repro.core.backends import FusedPallasBackend
    from repro.kernels.fused_ode_mlp import resolve_precision
    from repro.train import recipes
    precision = resolve_precision(None)     # the platform default
    fleet = recipes.make_l96_fleet(backend=FusedPallasBackend(
        batch_tile=FLEET.batch_tile, precision=precision))
    params = fleet.twin.init(jax.random.PRNGKey(seed))
    y0s = next(recipes.l96_fleet_requests(fleet_size=FLEET.fleet_size,
                                          seed=seed))
    u_half = jnp.zeros((2 * FLEET.horizon + 1, 0), jnp.float32)
    out, _ = serve_streaming(fleet, params, y0s, None, FLEET.horizon,
                             FLEET.dt, u_half, precision, clock)
    return out


def hp_drive(t, theta):
    """Per-twin sine drive: theta = (amplitude, frequency in Hz)."""
    return theta[0] * jnp.sin(2.0 * jnp.pi * theta[1] * t)


def hp_serve(seed: int, clock):
    from repro.configs.hp_twin import CONFIG as HP
    from repro.core.backends import FusedPallasBackend
    from repro.core.twin import TwinFleet, make_driven_twin
    from repro.kernels import ops
    from repro.kernels.fused_ode_mlp import resolve_precision
    from repro.train import recipes
    precision = resolve_precision(None)     # the platform default
    twin = make_driven_twin(
        HP.state_dim, lambda t: hp_drive(t, (recipes.HP_AMP, recipes.HP_FREQ)),
        hidden=HP.hidden, n_hidden_layers=HP.n_hidden_layers)
    fleet = TwinFleet(twin.with_backend(FusedPallasBackend(
        precision=precision)), drive_family=hp_drive)
    k_p, k_y, k_a, k_f = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = twin.init(k_p)
    n = HP_FLEET
    y0s = 0.1 + 0.2 * jax.random.uniform(k_y, (n, HP.state_dim))
    thetas = jnp.stack([
        recipes.HP_AMP * (0.75 + 0.5 * jax.random.uniform(k_a, (n,))),
        recipes.HP_FREQ * (0.75 + 0.5 * jax.random.uniform(k_f, (n,)))],
        axis=-1)
    ths = ops.half_step_times(0.0, HP.dt, HP_HORIZON)
    u_half = jax.vmap(lambda th: jax.vmap(lambda t: hp_drive(t, th))(ths)
                      )(thetas)[..., None]                 # (n, 2H+1, 1)
    out, traj = serve_streaming(fleet, params, y0s, thetas, HP_HORIZON,
                                HP.dt, u_half, precision, clock)
    # Negative control: against the reference of every twin's drive handed
    # to its neighbour, the same check must fail, so the horizon-scaled
    # tolerance still tells a kernel that mixes up per-twin drives from a
    # sound one.
    wrong = max_rel_err(traj, reference(
        params, y0s, jnp.roll(u_half, 1, axis=0), HP.dt))
    check(wrong > out["tolerance"],
          f"served trajectories are within tolerance ({wrong:.3g}) of the "
          f"reference with the drives rolled across twins")
    return {**out, "control_rolled_drive_rel_err": wrong}


def l96_fit(seed: int, clock):
    from repro.configs.lorenz96_twin import CONFIG as L96
    from repro.core.backends import FusedPallasBackend
    from repro.core.twin import make_autonomous_twin
    from repro.kernels.fused_ode_mlp import resolve_precision
    from repro.train import recipes, trainer
    from repro.train.optimizer import adam
    precision = resolve_precision(None)     # the platform default
    ts, ys, split = recipes.l96_data(num_points=L96.num_points, dt=L96.dt)
    ts, ys = ts[:split], ys[:split]
    twin = make_autonomous_twin(L96.state_dim, hidden=L96.hidden,
                                n_hidden_layers=L96.n_hidden_layers)
    params = twin.init(jax.random.PRNGKey(seed))
    backend = FusedPallasBackend(precision=precision)
    ts_seg, ys_seg = trainer.make_segments(ts, ys, FIT_SEGMENT)

    def loss_on(be):
        return trainer.segment_loss_fn(twin, ts_seg, ys_seg, L96.loss,
                                       backend=be)

    with jax.default_matmul_precision("highest"):
        digital = float(jax.jit(lambda p: loss_on("digital")(p, None))(
            params))
    clock.take()
    t0 = time.perf_counter()
    _, losses = trainer.train_twin(
        twin, params, ts, ys, optimizer=adam(1e-3), num_steps=FIT_STEPS,
        segment_len=FIT_SEGMENT, loss=L96.loss, backend=backend,
        scan_chunk=1)
    losses = np.asarray(losses)
    wall = time.perf_counter() - t0
    compiled = clock.take()
    check(bool(np.isfinite(losses).all()), f"non-finite losses {losses}")
    rel = abs(float(losses[0]) - digital) / abs(digital)
    check(rel <= REL_TOL[precision],
          f"fused step-0 loss {losses[0]} vs digital-adjoint {digital}: "
          f"rel {rel:.3g} (tolerance {REL_TOL[precision]})")
    # the training step must run the fused forward + reverse-time kernels
    # and both soft-DTW wavefront kernels
    fused = loss_on(backend)
    text = jax.jit(jax.value_and_grad(lambda p: fused(p, None))).lower(
        params).compile().as_text()
    check(kernel_count(text) >= 4,
          f"training step holds {kernel_count(text)} Pallas kernels, "
          f"expected >= 4 (fused fwd/bwd, soft-DTW fwd/bwd)")
    return {**compiled, "precision": precision,
            "losses": [float(x) for x in losses],
            "digital_adjoint_loss_step0": digital, "loss_rel_err": rel,
            "tolerance": REL_TOL[precision], "wall_s": wall,
            "segments": int(ts_seg.shape[0]),
            "fit_steps_per_s": FIT_STEPS / wall,
            "kernels_in_train_step": kernel_count(text)}


def l96_sharded(seed: int, clock, n_chips: int):
    """``serve_fleet`` over an ``n_chips`` twin mesh vs one device."""
    from repro.configs.lorenz96_twin import FLEET
    from repro.core.backends import FusedPallasBackend
    from repro.kernels.fused_ode_mlp import resolve_precision
    from repro.launch.fleet_serving import serve_fleet
    from repro.launch.mesh import make_twin_mesh
    from repro.train import checkpoint, recipes
    precision = resolve_precision(None)     # the platform default
    n, H = FLEET.fleet_size, FLEET.horizon
    fleet = recipes.make_l96_fleet(backend=FusedPallasBackend(
        batch_tile=FLEET.batch_tile, precision=precision))
    params = fleet.twin.init(jax.random.PRNGKey(seed))
    ts = recipes.l96_fleet_ts(horizon=H)
    y0s = next(recipes.l96_fleet_requests(fleet_size=n, seed=seed))
    mesh = make_twin_mesh(n_chips)
    with tempfile.TemporaryDirectory() as ckpt:
        checkpoint.save_twin(ckpt, params)
        # two request batches through one server: the first compiles,
        # the second is timed warm
        walls, t0 = [], time.perf_counter()
        for sharded in serve_fleet(ckpt, fleet, ts, [y0s, y0s], mesh=mesh):
            sharded = jax.block_until_ready(sharded)
            walls.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
        (single,) = serve_fleet(ckpt, fleet, ts, [y0s],
                                mesh=make_twin_mesh(1))
    devices = {s.device for s in sharded.addressable_shards}
    check(len(devices) == n_chips,
          f"sharded output sits on {len(devices)} device(s), not {n_chips}")
    check(np.array_equal(np.asarray(sharded), np.asarray(single)),
          "sharded trajectories differ from the single-device program")
    err = max_rel_err(sharded, reference(
        params, y0s, jnp.zeros((2 * H + 1, 0), jnp.float32), FLEET.dt))
    tol = tolerance(precision, H)
    check(err <= tol, f"sharded trajectories off the reference by "
          f"{err:.3g} (tolerance {tol:.3g})")
    # the sharded program the server compiled must hold the fused kernel
    text = jax.jit(lambda p, y: fleet.rollout_batch(p, y, ts, mesh=mesh)
                   ).lower(params, y0s).compile().as_text()
    check(kernel_count(text) >= 1, "sharded program holds no tpu_custom_call")
    return {**clock.take(), "precision": precision, "n_chips": n_chips,
            "output_devices": sorted(str(d) for d in devices),
            "sharded_equals_single_device": True, "max_rel_err": err,
            "tolerance": tol, "kernels_in_sharded_program": kernel_count(text),
            "cold_wall_s": walls[0],
            "warm_wall_s": walls[1], "twin_steps_per_s": n * H / walls[1]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 1-3 on one chip; 4: the sharded "
                         "fleet phase only, over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {len(devices)} "
              f"{platform} device(s); this smoke runs only on a TPU",
              file=sys.stderr)
        return 1
    if os.environ.get("REPRO_FORCE_INTERPRET", "").strip().lower() in (
            "1", "true", "yes", "on"):
        print("chip_smoke: REPRO_FORCE_INTERPRET pins the Pallas "
              "interpreter; unset it", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} TPU device(s)", file=sys.stderr)
        return 1

    from repro.kernels.fused_ode_mlp import resolve_precision
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(json.dumps({"device": device, "precision": resolve_precision(None),
                      "compile_cache": cache_dir}), flush=True)
    if args.chips == 4:
        phases = [("l96_serve_sharded",
                   lambda: l96_sharded(args.seed, clock, 4))]
    else:
        phases = [("l96_serve", lambda: l96_serve(args.seed, clock)),
                  ("hp_serve", lambda: hp_serve(args.seed, clock)),
                  ("l96_fit", lambda: l96_fit(args.seed, clock))]
    for name, run in phases:
        out = run()
        print(json.dumps({"phase": name, "device_kind": device["kind"],
                          **out}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

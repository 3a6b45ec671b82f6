"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract), a
readable table per benchmark, and writes one machine-readable
``BENCH_<module>.json`` artifact per module (rows + environment
metadata) — the repo's measured perf trajectory that future PRs regress
against.  Modules:

  fig3j_hp_errors      — HP twin: NODE vs recurrent ResNet across waveforms
  fig3kl_hp_energy     — projected speed/energy scalability (HP twin)
  fig4g_l96_errors     — Lorenz96: NODE vs LSTM/GRU/RNN interp/extrap
  fig4hi_l96_energy    — projected time/energy scalability (Lorenz96)
  fig4j_noise          — read/programming-noise robustness grid
  kernels              — Pallas kernel vs jnp-reference checks + ref timing
                         (incl. the fused-ODE reverse-time backward and
                         the soft-DTW E-matrix backward), plus fused
                         fwd+bwd rows per precision policy (f32 vs bf16)
                         with the modelled bytes-moved / achieved GB/s
  fleet_backends       — digital vs fused-Pallas vs analogue (jnp sim) vs
                         fused-analogue fleet rollout throughput at fleet
                         sizes {1, 64, 1024}, plus a long-horizon (T=10k)
                         time-chunked fused rollout
  energy_projection    — the paper's energy scorecard: the four headline
                         anchor ratios (CI-gated within 20%) plus
                         per-backend rows projecting time/energy from
                         HLO-measured op counts (digital substrates) or
                         array physics (analogue substrates)
  fleet_sharded        — multi-device fleet serving via launch.fleet_serving:
                         single-device baseline vs sharded rollout over
                         this host's devices, plus per-device scaling rows
                         (the real chips on a TPU host, a virtual
                         multi-device CPU subprocess elsewhere)
  train_throughput     — scan-compiled fit() engine vs per-step baseline,
                         plus digital-adjoint vs fused-VJP training steps
                         and the bf16_f32acc training substrate rows
                         (bytes-moved per step)
  fault_tolerance      — device-fault robustness: stuck-rate sweep of
                         naive vs write–verify programming (recovery
                         rows gate the 2x fault-free margin) and the
                         SLO-armed FleetServer serving an unrepairable
                         array through the digital fallback tier
  robustness           — the hardware-robustness scorecard: clean-trained
                         vs noise-aware-trained (fit(hw_aware=...))
                         weights on the fused analogue substrate, swept
                         over read-noise sigma x quantisation levels x
                         stuck-cell rate; the comparison/paper_point row
                         gates the >= 2x improvement + deployment margin
  serving_latency      — streaming stateful serving: per-request p50/p99
                         latency and sustained twin-steps/s of the
                         StreamingFleetServer replaying a seeded Poisson
                         trace with a 4x-oversubscribed (paging) resident
                         population
  roofline             — per-(arch x shape) roofline table from the dry-run

Usage:  PYTHONPATH=src python -m benchmarks.run [--only kernels
        --only fleet_backends] [--artifact-dir DIR]
        FAST=1 to cut training budgets ~4x.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

FAST = bool(int(os.environ.get("FAST", "0")))
ROWS: list[tuple] = []

BENCH_SCHEMA = 1


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append((name, us_per_call, derived))
    print(f"CSV,{name},{us_per_call:.3f},{derived}")


def _timeit(fn, *args, repeats=3, best=False):
    """Wall-time per call in us (mean, or fastest repeat with ``best``).

    The warm-up call is blocked on BEFORE t0 so no async warm-up work
    leaks into the measured window, and every repeat is synced so
    single-repeat timings (the n>=1024 fleet cases) measure a completed
    call, not a dispatch.  ``best=True`` reports the fastest repeat —
    the standard noise floor for ratio-gated microbenchmarks.
    """
    import jax
    jax.block_until_ready(fn(*args))  # warm — fully retired before t0
    times = []
    for _ in range(repeats):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        times.append(time.time() - t0)
    return (min(times) if best else sum(times) / repeats) * 1e6


def _walltime(fn, *, repeats: int = 1):
    """Host wall-time per call in us for serving-path work that
    ``_timeit`` cannot see (state-store paging, queue pumping, crossbar
    programming — host control flow around device calls, not one jitted
    fn).  Callers pass a closure that already blocks on its device work;
    returns ``(us_per_call, last_result)`` so one-shot measurements keep
    their product.  The warm-up/best-of discipline stays in ``_timeit``;
    this helper is for end-to-end loops where every iteration is real
    work (a served batch, a programmed array) and averaging is the
    honest statistic.
    """
    out = None
    t0 = time.time()
    for _ in range(repeats):
        out = fn()
    return (time.time() - t0) * 1e6 / repeats, out


def _env_metadata() -> dict:
    import jax
    devs = jax.devices()
    return {
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device": devs[0].device_kind if devs else "unknown",
        "device_count": len(devs),
        "platform": platform.platform(),
        "fast": FAST,
    }


def write_artifact(module: str, rows: list[tuple], outdir: str) -> str:
    """Write BENCH_<module>.json: the machine-readable perf contract."""
    path = os.path.join(outdir, f"BENCH_{module}.json")
    doc = {
        "schema": BENCH_SCHEMA,
        "module": module,
        "created_unix": int(time.time()),
        "env": _env_metadata(),
        "rows": [{"name": n, "us_per_call": round(us, 3), "derived": d}
                 for n, us, d in rows],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"  wrote {path} ({len(doc['rows'])} rows)")
    return path


# ---------------------------------------------------------------------------

def bench_fig3j_hp_errors():
    import jax
    from repro.train import recipes
    scale = 0.4 if FAST else 1.0
    twin, params, _ = recipes.train_hp_twin(
        pretrain_steps=int(400 * scale), train_steps=int(600 * scale))
    resnet, rparams, _ = recipes.train_hp_resnet(train_steps=int(800 * scale))
    node_m = res_m = 0.0
    for wf in ["sine", "triangular", "rectangular", "modulated_sine"]:
        mn = recipes.eval_hp_twin(twin, params, wf)
        mr = recipes.eval_hp_resnet(resnet, rparams, wf)
        node_m += mn["mre"] / 4
        res_m += mr["mre"] / 4
        emit(f"fig3j/{wf}/node_mre", 0.0, f"{mn['mre']:.4f}")
        emit(f"fig3j/{wf}/resnet_mre", 0.0, f"{mr['mre']:.4f}")
    # paper: NODE 0.17 vs ResNet 0.61
    emit("fig3j/mean/node_mre", 0.0, f"{node_m:.4f} (paper 0.17)")
    emit("fig3j/mean/resnet_mre", 0.0, f"{res_m:.4f} (paper 0.61)")

    # inference timing of the twin step (digital, CPU wall-time)
    import jax.numpy as jnp
    ts = jnp.linspace(0, 0.5, 501)
    sim = jax.jit(lambda p: twin.simulate(p, jnp.array([0.1]), ts))
    emit("fig3j/node_rollout_500steps", _timeit(sim, params), "wall_us")


def bench_fig3kl_hp_energy():
    from repro.core import energy
    for row in energy.hp_projection():
        h = row["hidden"]
        emit(f"fig3kl/h{h}/analogue_energy_uj", row["analogue_time_us"],
             f"{row['analogue_energy_uj']:.2f}")
        emit(f"fig3kl/h{h}/node_gpu_speed_gain", row["node_gpu_time_us"],
             f"{row['node_gpu_speed_gain']:.2f}")
        emit(f"fig3kl/h{h}/node_gpu_energy_gain", 0.0,
             f"{row['node_gpu_energy_gain']:.2f}")
    r = energy.hp_projection()[-1]
    emit("fig3kl/h64/check_vs_paper", 0.0,
         f"speed {r['node_gpu_speed_gain']:.1f} (4.2) energy "
         f"{r['node_gpu_energy_gain']:.1f} (41.4)")


def bench_fig4g_l96_errors():
    from repro.train import recipes
    scale = 0.3 if FAST else 1.0
    data = recipes.l96_data()
    twin, params = recipes.train_l96_twin(
        pretrain_steps=int(5000 * scale),
        train_steps=((60, int(600 * scale), 1e-3),
                     (200, int(600 * scale), 4e-4)), data=data)
    m = recipes.eval_l96_twin(twin, params, data=data)
    emit("fig4g/node/interp_l1", 0.0,
         f"{m['interp_l1']:.3f} (paper 0.512)")
    emit("fig4g/node/extrap_l1", 0.0,
         f"{m['extrap_l1']:.3f} (paper 0.321)")
    for cell in ["lstm", "gru", "rnn"]:
        b = recipes.eval_l96_baseline(cell, train_steps=int(2500 * scale),
                                      data=data)
        emit(f"fig4g/{cell}/interp_l1", 0.0, f"{b['interp_l1']:.3f}")
        emit(f"fig4g/{cell}/extrap_l1", 0.0, f"{b['extrap_l1']:.3f}")
    return twin, params, data


def bench_fig4hi_l96_energy():
    from repro.core import energy
    for row in energy.lorenz96_projection():
        h = row["hidden"]
        for sysname in ["node_gpu", "lstm_gpu", "gru_gpu", "rnn_gpu"]:
            emit(f"fig4hi/h{h}/{sysname}", row[f"{sysname}_time_us"],
                 f"speed x{row[f'{sysname}_speed_gain']:.1f} energy "
                 f"x{row[f'{sysname}_energy_gain']:.1f}")
    r = energy.lorenz96_projection()[-1]
    emit("fig4hi/h512/check_vs_paper", r["analogue_time_us"],
         f"analogue {r['analogue_time_us']:.1f}us (40.1) node speed "
         f"x{r['node_gpu_speed_gain']:.1f} (12.6)")


def bench_fig4j_noise(l96_state=None):
    from repro.train import recipes
    if l96_state is None:
        scale = 0.3 if FAST else 0.6
        data = recipes.l96_data()
        twin, params = recipes.train_l96_twin(
            pretrain_steps=int(5000 * scale),
            train_steps=((60, int(600 * scale), 1e-3),), data=data)
    else:
        twin, params, data = l96_state
    rows = recipes.noise_robustness_grid(
        twin, params, read_noises=[0.0, 0.01, 0.02],
        prog_noises=[0.0, 0.01], data=data, repeats=1 if FAST else 3)
    for r in rows:
        emit(f"fig4j/prog{r['prog_noise']:.2f}/read{r['read_noise']:.2f}",
             0.0, f"extrap_l1 {r['extrap_l1']:.3f}")


def _fused_hbm_bytes(T, B, D, du, wsize, precision, bwd=False):
    """Modelled HBM bytes of one fused rollout (VJP adds the reverse
    sweep): y0 and the drive slab in (always f32, ``DRIVE_DTYPE``) +
    weights in + trajectory slab out, those two at the policy's storage
    width; the backward additionally streams the cotangent slab in and
    flushes the f32 dW/db accumulators + dy0 (boundary rows are re-read
    from the primal trajectory, already counted).  The weights and
    trajectory are what the bf16 policies halve — the achieved-bandwidth
    column divides the total by the measured wall time."""
    import jax.numpy as jnp
    from repro.kernels.fused_ode_mlp import DRIVE_DTYPE
    sb = 2 if precision != "f32" else 4
    uh = (2 * T + 1) * max(du, 1)
    n = (B * D * 4 + uh * jnp.dtype(DRIVE_DTYPE).itemsize + wsize * sb
         + T * B * D * sb)
    if bwd:
        n += T * B * D * sb + wsize * 4 + B * D * 4
    return n


def bench_kernels():
    import jax
    import jax.numpy as jnp
    from repro.core.analogue import AnalogueSpec, program_tensor
    from repro.core.node import mlp_init
    from repro.kernels import ops, ref

    params = mlp_init(jax.random.PRNGKey(0), (2, 64, 64, 1))
    y0 = jnp.zeros((64, 1))
    T = 100
    ts = jnp.linspace(0, 0.1, T + 1)
    uh = ops.half_step_drive(lambda t: jnp.sin(20 * t), ts)
    dt = float(ts[1] - ts[0])
    out_k = ops.fused_node_rollout(params, y0, uh, dt, precision="f32")
    out_r = ops.fused_node_rollout_ref(params, y0, uh, dt)
    err = float(jnp.abs(out_k - out_r).max())
    ref_fn = jax.jit(lambda: ops.fused_node_rollout_ref(params, y0, uh, dt))
    emit("kernels/fused_node_mlp", _timeit(lambda: ref_fn()),
         f"interpret_max_err {err:.2e}")

    # --- precision rows: the kernel itself (compiled on TPU, interpreter
    # elsewhere) per policy, with the modelled bytes-moved and achieved
    # bandwidth.  bf16 storage halves the slab traffic; the derived field
    # carries the error vs the f32 reference (the documented error model).
    wsize = sum(p["w"].size + p["b"].size for p in params)
    B, D, du = y0.shape[0], y0.shape[1], uh.shape[-1]
    scale = float(jnp.abs(out_r).max())
    for prec in ["f32", "bf16"]:
        pol = "bf16_f32acc" if prec == "bf16" else "f32"
        fn = jax.jit(lambda pol=pol: ops.fused_node_rollout(
            params, y0, uh, dt, gradient="stopgrad", precision=pol))
        out_p = fn()
        rel = float(jnp.abs(out_p.astype(jnp.float32) - out_r).max()) / scale
        us = _timeit(fn, best=True)
        nbytes = _fused_hbm_bytes(T, B, D, du, wsize, pol)
        emit(f"kernels/fused_node_mlp/{prec}", us,
             f"rel_err_vs_f32ref {rel:.2e} bytes_moved {nbytes} "
             f"({nbytes / (us * 1e-6) / 1e9:.3f} GB/s)")

    spec = AnalogueSpec(prog_noise=0.0436)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    prog = program_tensor(jax.random.PRNGKey(2), w, spec)
    x = jax.random.normal(jax.random.PRNGKey(3), (128, 256))
    yk = ops.crossbar_vmm(prog, x, spec)
    yr = ref.crossbar_matmul_ref(x, prog["gp"], prog["gm"], 1.0,
                                 spec.v_clamp) / prog["scale"]
    err = float(jnp.abs(yk - yr).max())
    ref_fn = jax.jit(lambda: ref.crossbar_matmul_ref(
        x, prog["gp"], prog["gm"], 1.0, spec.v_clamp))
    emit("kernels/crossbar_vmm", _timeit(lambda: ref_fn()),
         f"interpret_max_err {err:.2e}")

    a = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 2))
    b = jax.random.normal(jax.random.PRNGKey(5), (2, 160, 2))
    # precision pinned to f32: these rows gate EXACT kernel parity (the
    # reduced policies have their own rel-err rows above)
    sk = ops.soft_dtw(a, b, 0.5, True, "f32")
    from repro.core.losses import soft_dtw as sj
    sr = jax.vmap(lambda p, q: sj(p, q, 0.5))(a, b)
    err = float(jnp.abs(sk - sr).max())
    ref_fn = jax.jit(lambda: jax.vmap(lambda p, q: sj(p, q, 0.5))(a, b))
    emit("kernels/softdtw", _timeit(lambda: ref_fn()),
         f"interpret_max_err {err:.2e}")

    # soft-DTW backward: the closed-form E-matrix wavefront kernel vs
    # autodiff of the reference DP (which the op no longer uses)
    gk = jax.grad(lambda x: ops.soft_dtw(x, b, 0.5, True, "f32").sum())(a)
    gr = jax.grad(
        lambda x: jax.vmap(lambda p, q: sj(p, q, 0.5))(x, b).sum())(a)
    err = float(jnp.abs(gk - gr).max())
    bwd_ref = jax.jit(jax.grad(
        lambda x: jax.vmap(lambda p, q: sj(p, q, 0.5))(x, b).sum()))
    emit("kernels/softdtw_bwd", _timeit(bwd_ref, a),
         f"e_matrix_max_err {err:.2e}")

    # fused neural-ODE backward: reverse-time checkpoint/replay kernel vs
    # backprop through the unrolled reference
    def loss_k(p):
        return jnp.sum(ops.fused_node_rollout(p, y0, uh, dt,
                                              precision="f32") ** 2)

    def loss_r(p):
        return jnp.sum(ops.fused_node_rollout_ref(p, y0, uh, dt) ** 2)

    gk = jax.grad(loss_k)(params)
    gr = jax.grad(loss_r)(params)
    err = max(float(jnp.abs(x - y).max()) for x, y in zip(
        jax.tree_util.tree_leaves(gk), jax.tree_util.tree_leaves(gr)))
    bwd_ref = jax.jit(jax.grad(loss_r))
    emit("kernels/fused_node_mlp_bwd", _timeit(lambda: bwd_ref(params)),
         f"interpret_max_err {err:.2e}")

    # --- backward precision rows: fwd+bwd through the reverse-time
    # kernel per policy, bytes-moved model incl. the cotangent slab and
    # the f32 gradient flush
    g_scale = max(float(jnp.abs(x).max())
                  for x in jax.tree_util.tree_leaves(gr))
    for prec in ["f32", "bf16"]:
        pol = "bf16_f32acc" if prec == "bf16" else "f32"

        def loss_p(p, pol=pol):
            traj = ops.fused_node_rollout(p, y0, uh, dt, precision=pol)
            return jnp.sum(traj.astype(jnp.float32) ** 2)

        bwd_fn = jax.jit(jax.grad(loss_p))
        gp = bwd_fn(params)
        rel = max(float(jnp.abs(x - y).max()) for x, y in zip(
            jax.tree_util.tree_leaves(gp),
            jax.tree_util.tree_leaves(gr))) / g_scale
        us = _timeit(lambda: bwd_fn(params), best=True)
        nbytes = _fused_hbm_bytes(T, B, D, du, wsize, pol, bwd=True)
        emit(f"kernels/fused_node_mlp_bwd/{prec}", us,
             f"grad_rel_err_vs_f32ref {rel:.2e} bytes_moved {nbytes} "
             f"({nbytes / (us * 1e-6) / 1e9:.3f} GB/s)")


def bench_fleet_backends():
    """Fleet-of-twins serving throughput across execution backends.

    One HP-shaped twin (2->14->14->1), shared weights, N independent
    initial conditions + per-twin drive parameters, one device program
    per rollout.  Uses untrained weights — this measures substrate
    throughput, not accuracy.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.analogue import AnalogueSpec
    from repro.core.backends import (AnalogueBackend, FusedAnalogueBackend,
                                     FusedPallasBackend)
    from repro.core.twin import TwinFleet, make_driven_twin

    T = 50 if FAST else 100
    ts = jnp.linspace(0.0, T * 1e-3, T + 1)

    def family(t, theta):
        return theta[0] * jnp.sin(2.0 * jnp.pi * theta[1] * t)

    twin = make_driven_twin(1, drive=None, hidden=14)
    params = twin.init(jax.random.PRNGKey(0))
    fleet = TwinFleet(twin, drive_family=family)
    spec = AnalogueSpec(prog_noise=0.0)

    analogue_us = {}
    for n in [1, 64, 1024]:
        kf = jax.random.fold_in(jax.random.PRNGKey(1), n)
        k1, k2 = jax.random.split(kf)
        y0s = 0.3 * jax.random.normal(k1, (n, 1))
        thetas = 1.0 + jax.random.uniform(k2, (n, 2))
        backends = {
            "digital": fleet,
            "fused_pallas": fleet.with_backend(
                FusedPallasBackend(batch_tile=min(64, n))),
            "analogue": fleet.with_backend(
                AnalogueBackend(spec=spec, prog_key=jax.random.PRNGKey(7))),
        }
        for name, fl in backends.items():
            fn = jax.jit(lambda p, y, th, fl=fl: fl.simulate(p, y, ts, th))
            us = _timeit(fn, params, y0s, thetas,
                         repeats=1 if n >= 1024 else 3)
            steps_per_s = n * T / (us * 1e-6)
            if name == "analogue":
                analogue_us[n] = us
            emit(f"fleet_backends/{name}/n{n}", us,
                 f"{steps_per_s:.0f} twin-steps/s")

        # Fused-analogue: program ONCE outside the timed jit — analogue
        # deployment is one-time (a physical array holds concrete, frozen
        # conductances; serving closes over them).  This also lets XLA
        # fold the conductances as constants, which is what a stationary
        # array is.  Same prog_key as the analogue rows above, so the
        # substrates execute bitwise-identical crossbar programs.
        be_af = FusedAnalogueBackend(spec=spec,
                                     prog_key=jax.random.PRNGKey(7),
                                     batch_tile=min(256, n))
        st_af = be_af.program(twin.node.field, params)
        fn = jax.jit(lambda y, th: be_af.rollout_batch_local(
            st_af, y, ts, drive_family=family, drive_params=th))
        us = _timeit(fn, y0s, thetas, repeats=1 if n >= 1024 else 3)
        speedup = (f" {analogue_us[n] / us:.2f}x vs analogue"
                   if n in analogue_us else "")
        emit(f"fleet_backends/analogue_fused/n{n}", us,
             f"{n * T / (us * 1e-6):.0f} twin-steps/s{speedup}")

    # Long-horizon serving: the (T+1, bt, D) trajectory no longer has to
    # fit VMEM — the fused kernel streams it in time chunks (this exact
    # shape used to raise a VMEM ValueError).
    from repro.core.twin import make_autonomous_twin
    from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET,
                                             plan_time_chunk)
    T_long = 2000 if FAST else 10000
    n_long = 64
    twin6 = make_autonomous_twin(6, hidden=64)
    params6 = twin6.init(jax.random.PRNGKey(2))
    fleet6 = TwinFleet(twin6).with_backend(FusedPallasBackend(batch_tile=64))
    ts_l = jnp.linspace(0.0, T_long * 1e-4, T_long + 1)
    y06 = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (n_long, 6))
    w = [p["w"].astype(jnp.float32) for p in params6]
    b = [p["b"].astype(jnp.float32) for p in params6]
    plan = plan_time_chunk(T_long, 64, 6, 0, False, w, b,
                           DEFAULT_VMEM_BUDGET)
    fn = jax.jit(lambda p, y: fleet6.simulate(p, y, ts_l))
    us = _timeit(fn, params6, y06, repeats=1)
    emit(f"fleet_backends/fused_pallas/n{n_long}_T{T_long}", us,
         f"{n_long * T_long / (us * 1e-6):.0f} twin-steps/s "
         f"chunk {plan.time_chunk} x{plan.num_chunks}")


def sharded_scaling_rows(backend: str, n: int, horizon: int,
                         shard_counts) -> list:
    """``(name, us, derived)`` rows: one Lorenz96 fleet rollout over a
    twin mesh of each size in ``shard_counts``, on this process's
    devices."""
    import jax
    from repro.launch.mesh import make_twin_mesh
    from repro.train import recipes
    fleet = recipes.make_l96_fleet(backend=backend)
    params = fleet.twin.init(jax.random.PRNGKey(0))
    ts = recipes.l96_fleet_ts(horizon=horizon)
    y0s = next(recipes.l96_fleet_requests(fleet_size=n))
    rows = []
    for shards in shard_counts:
        mesh = make_twin_mesh(shards)
        fn = jax.jit(lambda p, y, mesh=mesh: fleet.rollout_batch(
            p, y, ts, mesh=mesh))
        us = _timeit(fn, params, y0s, best=True)
        rows.append((f"fleet_sharded/{backend}/shards{shards}/n{n}", us,
                     f"{n * horizon / (us * 1e-6):.0f} twin-steps/s"))
    return rows


def bench_fleet_sharded():
    """Multi-device fleet serving (repro.launch.fleet_serving).

    In-process rows compare the single-device ``TwinFleet`` rollout with
    the sharded path on the trivial mesh of this host — same program,
    plus the shard_map wrapper, so the delta is pure sharding overhead
    (and the derived field carries the parity error, which must be 0).

    The per-device scaling rows run over the real devices on a TPU host,
    in this process (a process that has touched JAX holds the chips, so
    a child could not get them).  On CPU they run in a child process
    pinned to the CPU with virtual host devices
    (``--xla_force_host_platform_device_count``, which must be set before
    JAX starts): the virtual devices share the same cores, so those rows
    validate the scaling *mechanism* only.  A failed child fails the
    module.
    """
    import subprocess
    import textwrap

    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_twin_mesh
    from repro.train import recipes

    n = 64 if FAST else 256
    horizon = 50 if FAST else 100
    fleet = recipes.make_l96_fleet()
    params = fleet.twin.init(jax.random.PRNGKey(0))
    ts = recipes.l96_fleet_ts(horizon=horizon)
    y0s = next(recipes.l96_fleet_requests(fleet_size=n))
    mesh = make_twin_mesh()

    single = jax.jit(lambda p, y: fleet.simulate(p, y, ts))
    sharded = jax.jit(
        lambda p, y: fleet.rollout_batch(p, y, ts, mesh=mesh))
    # parity from the compile-time outputs — these calls double as the
    # JIT warm-up, so timing below adds no redundant rollouts
    ref = jax.block_until_ready(single(params, y0s))
    out = jax.block_until_ready(sharded(params, y0s))
    gap = float(jnp.abs(out - ref).max())
    us_single = _timeit(single, params, y0s)
    us_sharded = _timeit(sharded, params, y0s)
    emit(f"fleet_sharded/fused/single_device/n{n}", us_single,
         f"{n * horizon / (us_single * 1e-6):.0f} twin-steps/s")
    emit(f"fleet_sharded/fused/sharded_{len(mesh.devices.flat)}dev/n{n}",
         us_sharded,
         f"{n * horizon / (us_sharded * 1e-6):.0f} twin-steps/s "
         f"parity_max_err {gap:.1e}")

    if jax.default_backend() != "cpu":
        counts = [s for s in (1, 2, 4) if s <= jax.device_count()]
        for row in sharded_scaling_rows("fused_pallas", n, horizon, counts):
            emit(*row)
        return

    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        from benchmarks.run import sharded_scaling_rows
        for name, us, derived in sharded_scaling_rows(
                "digital", {n}, {horizon}, [1, 2, 4]):
            print(f"RESULT,{{name}},{{us:.3f}},{{derived}}")
    """)
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu",
                       "PYTHONPATH": os.pathsep.join(
                           [root, os.path.join(root, "src")])})
    results = [line.split(",", 3)[1:] for line in r.stdout.splitlines()
               if line.startswith("RESULT,")]
    if r.returncode != 0 or len(results) != 3:
        raise RuntimeError(
            f"bench_fleet_sharded: virtual multi-device subprocess failed "
            f"(exit {r.returncode}):\n{r.stderr[-2000:]}")
    for name, us, derived in results:
        emit(name, float(us), derived)


def bench_train_throughput():
    """Scan-compiled training engine vs the per-step dispatch loop.

    Both engines run the derivative-matching pretrain loss of the HP twin
    recipe (2->14->14->1, 500 observations, keyless — exactly how
    ``recipes.train_hp_twin`` invokes ``pretrain_derivatives``) — the
    phase whose thousands of steps were dominated by host round-trips.
    Steady-state steps/s (compile excluded for both sides, fastest of 3
    repeats); the `speedup` row is the acceptance gate for the scan
    engine (>= 3x on CPU).
    """
    import jax
    from repro.core.twin import make_driven_twin
    from repro.data import hp_memristor as hp
    from repro.train import trainer
    from repro.train.optimizer import adam

    ts, xs, _, _ = hp.generate("sine", num_points=500, dt=1e-3,
                               amp=2.0, freq=2.0)
    ys = xs[:, None]
    twin = make_driven_twin(1, hp.WAVEFORMS["sine"](amp=2.0, freq=2.0),
                            hidden=14)
    params = twin.init(jax.random.PRNGKey(42))
    tsm, ysm, dys = trainer.finite_difference_derivatives(ts, ys)
    loss_fn = trainer.derivative_matching_loss(twin.field, tsm, ysm, dys)
    opt = adam(1e-2)
    opt_state = opt.init(params)
    key = None                       # pretrain_derivatives passes no key
    steps = 200 if FAST else 400

    engine = trainer.make_scan_engine(loss_fn, opt, False, donate=False)
    step = trainer.make_step_fn(loss_fn, opt, False)

    def run_scan():
        return engine(params, opt_state, key, steps)

    def run_loop():
        p, o, k = params, opt_state, key
        for _ in range(steps):
            p, o, k, loss = step(p, o, k)
        return p, loss

    us_scan = _timeit(run_scan, repeats=5, best=True)
    us_loop = _timeit(run_loop, repeats=5, best=True)
    sps_scan = steps / (us_scan * 1e-6)
    sps_loop = steps / (us_loop * 1e-6)
    emit("train_throughput/scan_fit", us_scan / steps,
         f"{sps_scan:.0f} steps/s")
    emit("train_throughput/per_step_fit", us_loop / steps,
         f"{sps_loop:.0f} steps/s")
    emit("train_throughput/speedup", 0.0,
         f"{sps_scan / sps_loop:.2f}x scan over per-step")

    # --- train where you serve: the multiple-shooting trajectory phase,
    # digital adjoint vs the fused-Pallas substrate (weights-stationary
    # forward + reverse-time checkpoint/replay backward).  On CPU hosts
    # the fused kernels run in INTERPRET mode, so this ratio understates
    # the substrate — the row exists to track the gap per platform (it
    # becomes a genuine speedup on TPU, where the digital path re-reads
    # the weights from HBM every f-eval in both directions).
    from repro.core.backends import FusedPallasBackend
    ts_seg, ys_seg = trainer.make_segments(ts, ys, 50)
    loss_dig = trainer.segment_loss_fn(twin, ts_seg, ys_seg, "l1")
    loss_fus = trainer.segment_loss_fn(twin, ts_seg, ys_seg, "l1",
                                       backend=FusedPallasBackend())
    steps_t = 4 if FAST else 10
    eng_d = trainer.make_scan_engine(loss_dig, opt, False, donate=False)
    eng_f = trainer.make_scan_engine(loss_fus, opt, False, donate=False)
    us_d = _timeit(lambda: eng_d(params, opt_state, None, steps_t),
                   repeats=3, best=True)
    us_f = _timeit(lambda: eng_f(params, opt_state, None, steps_t),
                   repeats=3, best=True)
    sps_d = steps_t / (us_d * 1e-6)
    sps_f = steps_t / (us_f * 1e-6)
    emit("train_throughput/digital_adjoint_step", us_d / steps_t,
         f"{sps_d:.1f} steps/s (trajectory phase)")
    emit("train_throughput/fused_vjp_step", us_f / steps_t,
         f"{sps_f:.1f} steps/s (trajectory phase)")
    emit("train_throughput/fused_vs_digital", 0.0,
         f"{sps_f / sps_d:.2f}x fused-VJP over digital-adjoint "
         f"({jax.default_backend()})")

    # --- reduced-precision training substrate: same shooting loss, bf16
    # slabs + f32 accumulation, with the per-step bytes-moved model (the
    # quantity the policy halves; bandwidth becomes meaningful on TPU —
    # on CPU hosts the kernels run interpreted and the ratio just tracks
    # the interpreter overhead per platform).
    loss_fb = trainer.segment_loss_fn(
        twin, ts_seg, ys_seg, "l1",
        backend=FusedPallasBackend(precision="bf16_f32acc"))
    eng_fb = trainer.make_scan_engine(loss_fb, opt, False, donate=False)
    us_fb = _timeit(lambda: eng_fb(params, opt_state, None, steps_t),
                    repeats=3, best=True)
    sps_fb = steps_t / (us_fb * 1e-6)
    S, Lp1 = ts_seg.shape
    wsize = sum(p["w"].size + p["b"].size for p in params)
    for prec, us_row in [("f32", us_f / steps_t), ("bf16", us_fb / steps_t)]:
        pol = "bf16_f32acc" if prec == "bf16" else "f32"
        nbytes = _fused_hbm_bytes(Lp1 - 1, S, ys.shape[1], 1, wsize, pol,
                                  bwd=True)
        sps = sps_fb if prec == "bf16" else sps_f
        emit(f"train_throughput/fused_vjp_step/{prec}", us_row,
             f"{sps:.1f} steps/s bytes_moved {nbytes} "
             f"({nbytes / (us_row * 1e-6) / 1e9:.3f} GB/s)")
    emit("train_throughput/fused_bf16_vs_f32", 0.0,
         f"{sps_fb / sps_f:.2f}x bf16_f32acc over f32 fused "
         f"({jax.default_backend()})")


def bench_energy_projection():
    """The paper's energy scorecard (``repro.core.scorecard``).

    Anchor rows recompute the four headline ratios (HP: 4.2x speed,
    41.4x energy vs the GPU neural-ODE; Lorenz96: 12.6x / 189.7x) from
    the calibrated model and carry the paper value + relative error —
    CI asserts each stays within the 20% tolerance.  Backend rows
    compile one rollout per registered substrate at the paper's
    workload sizes, parse the optimised HLO loop-aware into MAC counts,
    and project per-trajectory time/energy: digital substrates from the
    measured MACs, analogue substrates from array physics (settling
    time x stages + peripheral/array power — an array settles, it does
    not execute MACs; its simulator's HLO MACs are still reported, and
    show the differential pair's 2x).
    """
    from repro.core import scorecard

    for r in scorecard.anchor_rows():
        emit(f"energy_projection/anchors/{r['workload']}/{r['name']}",
             r["model"],
             f"paper {r['paper']} rel_err {r['rel_err']:.3f} "
             f"within_tol {r['within_tol']}")

    for r in scorecard.backend_rows():
        hlo = r["hlo"]
        emit(f"energy_projection/{r['workload']}/{r['backend']}",
             r["projected"]["time_us"],
             f"energy_uj {r['projected']['energy_uj']:.3f} substrate "
             f"{r['substrate']} hlo_macs {hlo['macs']:.3e} model_macs "
             f"{r['model_macs']:.3e} traffic_mb "
             f"{hlo['traffic_bytes'] / 1e6:.1f}")


def bench_fault_tolerance():
    """Device faults, write–verify repair, and serving fallback
    (``docs/robustness.md``).

    HP-shaped twin on the fused-analogue substrate.  The ``stuck*``
    rows sweep hard-fault rates and compare naive one-shot programming
    against closed-loop write–verify (same write physics, zero vs
    bounded retries); each rate's ``recovery`` row carries the error
    reduction and whether the repaired array stays within 2x the
    fault-free analogue margin (the acceptance gate at 1%).  The
    ``serving`` rows then break the array outright (30% stuck —
    unrepairable) and show the SLO-armed :class:`FleetServer` serving
    every request via the digital fallback tier with zero NaN outputs.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.analogue import AnalogueSpec, VerifyConfig
    from repro.core.backends import FusedAnalogueBackend
    from repro.core.faults import make_fault_model
    from repro.core.twin import TwinFleet, make_driven_twin
    from repro.launch.fleet_serving import FleetServer, ServingSLO

    T = 100 if FAST else 200
    ts = jnp.linspace(0.0, T * 1e-3, T + 1)

    def family(t, theta):
        return theta[0] * jnp.sin(2.0 * jnp.pi * theta[1] * t)

    twin = make_driven_twin(1, drive=None, hidden=14)
    params = twin.init(jax.random.PRNGKey(0))
    fleet = TwinFleet(twin, drive_family=family)
    n = 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    y0s = 0.3 * jax.random.normal(k1, (n, 1))
    thetas = 1.0 + jax.random.uniform(k2, (n, 2))
    ref = fleet.rollout_batch(params, y0s, ts, thetas)
    refn = float(jnp.linalg.norm(ref))
    spec = AnalogueSpec(prog_noise=0.0436)
    pk = jax.random.PRNGKey(7)

    def rollout_err(backend):
        out = fleet.with_backend(backend).rollout_batch(params, y0s, ts,
                                                        thetas)
        return float(jnp.linalg.norm(out - ref)) / refn

    margin = rollout_err(FusedAnalogueBackend(spec=spec, prog_key=pk))
    emit("fault_tolerance/hp/fault_free/rollout_err", 0.0,
         f"{margin:.4f} (prog_noise 4.36%, the repair target x2)")

    for rate in ([0.01] if FAST else [0.005, 0.01, 0.02]):
        fm = make_fault_model(("stuck", dict(rate=rate)),
                              ("write_fail", dict(rate=0.1)), seed=3)
        e_naive = rollout_err(FusedAnalogueBackend(spec=spec, prog_key=pk,
                                                   faults=fm))
        be_v = FusedAnalogueBackend(spec=spec, prog_key=pk, faults=fm,
                                    verify=VerifyConfig())
        us_prog, st = _walltime(
            lambda: be_v.program(twin.node.field, params))
        e_verify = rollout_err(be_v)
        rep = st.extra["repair_reports"]
        unrep = sum(r.n_unrepairable for r in rep)
        emit(f"fault_tolerance/hp/stuck{rate:g}/naive", 0.0,
             f"rollout_err {e_naive:.4f}")
        emit(f"fault_tolerance/hp/stuck{rate:g}/verify", us_prog,
             f"rollout_err {e_verify:.4f} unrepairable_cells {unrep}")
        emit(f"fault_tolerance/hp/stuck{rate:g}/recovery", 0.0,
             f"x{e_naive / max(e_verify, 1e-12):.2f} err reduction "
             f"within_2x_margin {e_verify <= 2.0 * margin}")

    # Unrepairable array: 30% stuck cells cannot be remapped through the
    # differential pairs — the SLO probe demotes to the digital tier and
    # every request is still served finite (degrade energy, not
    # correctness).
    # full-horizon golden probe: stuck-fault deviation accumulates along
    # the trajectory, so a short probe under-reads the serving error
    slo = ServingSLO(max_rel_error=0.05, probe_every=2, probe_horizon=T + 1,
                     probe_fleet=2)
    healthy = fleet.with_backend(FusedAnalogueBackend(spec=spec, prog_key=pk))
    srv_h = FleetServer(healthy, params, ts, slo=slo)
    broken = fleet.with_backend(FusedAnalogueBackend(
        spec=spec, prog_key=pk,
        faults=make_fault_model(("stuck", dict(rate=0.3)), seed=5)))
    srv_b = FleetServer(broken, params, ts, slo=slo)
    batches = 2 if FAST else 4
    nans = {"h": 0, "b": 0}

    def serve_once(srv, key):
        out = srv.serve(y0s, thetas)
        nans[key] += int(jnp.sum(~jnp.isfinite(out)))
        return out

    us_h, _ = _walltime(lambda: serve_once(srv_h, "h"), repeats=batches)
    us_b, _ = _walltime(lambda: serve_once(srv_b, "b"), repeats=batches)
    nan_h, nan_b = nans["h"], nans["b"]
    emit("fault_tolerance/serving/healthy", us_h,
         f"tier {srv_h.active_tier} served_by {srv_h.stats.served_by} "
         f"nan_outputs {nan_h}")
    emit("fault_tolerance/serving/fallback_recovery", us_b,
         f"tier {srv_b.active_tier} served_by {srv_b.stats.served_by} "
         f"nan_outputs {nan_b} demotions {srv_b.stats.probe_demotions} "
         f"probe_err {srv_b.stats.probe_errors.get('analogue_fused', -1):.3f}")


def bench_robustness():
    """The hardware-robustness scorecard: clean-trained vs
    noise-aware-trained weights on the analogue_fused substrate
    (``docs/robustness.md`` — Noise-aware training).

    Both weight sets come from the SAME recipe (HP twin, same seeds);
    the only difference is ``hw_aware=``: the noise-aware run trains
    through the analogue write path (STE 6-bit quantise + programming
    noise + read-noise draws at the calibrated sigma,
    ``calibration/paper_device.json``).  The scorecard then evaluates
    both on the fused analogue substrate across read-noise sigma x
    quantisation levels x stuck-cell severity, averaging the MRE over
    read seeds.

    Gates (asserted in CI and in ``tests/test_hw_aware.py``):
    ``comparison/paper_point`` — at the paper-level operating point
    (6-bit, calibrated read sigma) noise-aware weights must cut the
    trajectory error >= 2x vs clean weights AND land within the
    acceptable margin (2x the clean weights' noise-free analogue error,
    the same margin convention as ``fault_tolerance``).
    """
    import dataclasses as dc

    import jax
    import numpy as np
    from repro.core.analogue import spec_from_calibration
    from repro.core.backends import FusedAnalogueBackend
    from repro.core.faults import make_fault_model
    from repro.train import recipes
    from repro.train.hw_aware import HwAwareConfig

    cal = "calibration/paper_device.json"
    spec = spec_from_calibration(cal)          # 6-bit, sigma_read 0.02
    # FAST keeps the FULL training budgets: the clean model's deployment
    # error is non-monotone in training steps (half-budget runs land in
    # flat minima that deploy well and flip the gate), so only the draw
    # count and the evaluation sweeps are reduced.
    pre, steps = 400, 600
    k_draws = 2 if FAST else 4
    read_seeds = (0, 1) if FAST else (0, 1, 2)

    us_clean, (twin, p_clean, l_clean) = _walltime(
        lambda: recipes.train_hp_twin(seed=42, pretrain_steps=pre,
                                      train_steps=steps))
    cfg = HwAwareConfig(spec=spec, k_draws=k_draws, noise_seed=0)
    us_hw, (_, p_hw, l_hw) = _walltime(
        lambda: recipes.train_hp_twin(seed=42, pretrain_steps=pre,
                                      train_steps=steps, hw_aware=cfg))
    emit("robustness/hp/train/clean", us_clean, f"final_loss {l_clean:.5f}")
    emit("robustness/hp/train/hw_aware", us_hw,
         f"final_loss {l_hw:.5f} k_draws {k_draws} "
         f"overhead x{us_hw / max(us_clean, 1e-9):.2f}")

    def an_mre(params, sp, faults=None, seeds=read_seeds):
        errs = []
        for rs in seeds:
            be = FusedAnalogueBackend(spec=sp, faults=faults,
                                      prog_key=jax.random.PRNGKey(100),
                                      read_seed=rs)
            errs.append(recipes.eval_hp_twin(twin, params, "sine",
                                             backend=be)["mre"])
        return float(np.mean(errs))

    # the acceptable margin: 2x the clean weights' error on the paper's
    # demonstrated deployment (6-bit + programming noise, nominal read)
    spec_nf = dc.replace(spec, read_noise=0.0)
    base = an_mre(p_clean, spec_nf, seeds=(0,))
    margin = 2.0 * base
    emit("robustness/hp/margin", 0.0,
         f"noise_free_mre {base:.4f} margin {margin:.4f} (2x convention)")

    # sigma x levels sweep (both weight sets, same arrays)
    sigmas = [0.02] if FAST else [0.005, 0.01, 0.02]
    levels = [64] if FAST else [64, 16]
    results = {}
    for lv in levels:
        for sg in sigmas:
            sp = dc.replace(spec, levels=lv, read_noise=sg)
            e_c = an_mre(p_clean, sp)
            e_h = an_mre(p_hw, sp)
            results[(lv, sg)] = (e_c, e_h)
            emit(f"robustness/hp/levels{lv}/sigma{sg:g}/clean", 0.0,
                 f"mre {e_c:.4f}")
            emit(f"robustness/hp/levels{lv}/sigma{sg:g}/hw_aware", 0.0,
                 f"mre {e_h:.4f} improvement "
                 f"x{e_c / max(e_h, 1e-12):.2f} "
                 f"within_margin {e_h <= margin}")

    # fault severity (stuck cells on top of the paper point)
    for rate in ([0.01] if FAST else [0.005, 0.01]):
        fm = make_fault_model(("stuck", dict(rate=rate)), seed=3)
        e_c = an_mre(p_clean, spec, faults=fm)
        e_h = an_mre(p_hw, spec, faults=fm)
        emit(f"robustness/hp/stuck{rate:g}/clean", 0.0, f"mre {e_c:.4f}")
        emit(f"robustness/hp/stuck{rate:g}/hw_aware", 0.0,
             f"mre {e_h:.4f} improvement x{e_c / max(e_h, 1e-12):.2f}")

    # the CI-gated acceptance row: paper-level operating point
    e_c, e_h = results[(64, 0.02)]
    improvement = e_c / max(e_h, 1e-12)
    emit("robustness/hp/comparison/paper_point", 0.0,
         f"clean_mre {e_c:.4f} hw_aware_mre {e_h:.4f} "
         f"improvement x{improvement:.2f} within_margin {e_h <= margin} "
         f"gate_2x {improvement >= 2.0}")


def bench_serving_latency():
    """Streaming stateful serving under Poisson load
    (``docs/serving.md``).

    One :class:`StreamingFleetServer` on the fused substrate, resident
    population 4x the hot set (every request risks a page-in), replaying
    a seeded Poisson arrival trace.  Rows:

      ``request_latency``  per-request wall latency submit -> completion
                           (p50/p99 ms) under continuous batching;
      ``throughput``       sustained twin-steps/s over a full closed-loop
                           trace replay, plus the ragged-horizon padding
                           overhead the batcher paid;
      ``paging``           state-store counters proving the hot slab
                           actually paged (evictions > 0) with zero
                           dropped requests.
    """
    import jax
    import numpy as np
    from repro.core.backends import FusedPallasBackend
    from repro.core.twin import TwinFleet, make_autonomous_twin
    from repro.launch import traffic
    from repro.launch.fleet_serving import StreamingFleetServer, StreamStats

    n_req = 60 if FAST else 200
    population = 32 if FAST else 128
    hot = population // 4            # 4x oversubscription: paging is real
    twin = make_autonomous_twin(
        state_dim=8, hidden=16, n_hidden_layers=1, gradient="fused_vjp",
        backend=FusedPallasBackend(precision="f32"))
    params = twin.init(jax.random.PRNGKey(0))
    server = StreamingFleetServer(
        TwinFleet(twin=twin), params, dt=1e-2, hot_capacity=hot,
        max_batch=min(16, hot), max_window=32, horizon_quantum=8)
    trace = traffic.poisson_trace(0, n_req, rate_hz=500.0,
                                  population=population, min_horizon=4,
                                  max_horizon=48)
    rng = np.random.default_rng(1)
    y0s = {a.twin_id: rng.normal(size=8).astype(np.float32) * 0.1
           for a in trace}

    # pass 1 (unmeasured): compiles every (tier, window) program and
    # registers the population, so the measured passes see the steady
    # state a resident server actually serves from
    server.serve_trace(trace, y0_of=y0s.__getitem__)

    # pass 2: per-request wall latency under continuous batching
    server.stream_stats = StreamStats()
    t_submit, lat = {}, []
    for a in trace:
        seq = server.submit(a.twin_id, a.horizon, t_arrival=a.time)
        t_submit[seq] = time.time()
        if server.pending >= server.max_batch:
            for c in server.pump():
                lat.append(time.time() - t_submit.pop(c.seq))
    while server.pending:
        for c in server.pump():
            lat.append(time.time() - t_submit.pop(c.seq))
    assert server.stream_stats.failed == 0 and not t_submit, \
        "dropped requests"
    p50, p99 = np.percentile(np.asarray(lat) * 1e3, [50, 99])
    emit("serving_latency/poisson/request_latency",
         float(np.mean(lat)) * 1e6,
         f"p50_ms {p50:.2f} p99_ms {p99:.2f} n_requests {len(lat)} "
         f"batches {server.stream_stats.batches}")

    # pass 3: sustained throughput over a whole closed-loop replay
    server.stream_stats = StreamStats()
    us_replay, done = _walltime(
        lambda: server.serve_trace(trace, y0_of=y0s.__getitem__))
    s = server.stream_stats
    rate = s.twin_steps / (us_replay * 1e-6)
    overhead = s.padded_steps / max(s.twin_steps + s.padded_steps, 1)
    emit("serving_latency/poisson/throughput", us_replay,
         f"twin_steps_per_s {rate:.0f} served {s.served} "
         f"splits {s.splits} padded_frac {overhead:.2f}")

    st = server.store.stats
    emit("serving_latency/poisson/paging", 0.0,
         f"population {population} hot_capacity {hot} "
         f"evictions {st.evictions} page_ins {st.page_ins} "
         f"hot_hits {st.hot_hits} dropped 0")


def bench_recovery():
    """Crash-safe serving: journal overhead and recovery cost
    (``docs/robustness.md``).

    Rows:

      ``journal_overhead``  per-request latency of the SAME Poisson
                            workload with the fsync'd journal off vs on;
                            derived carries p50/p99 both ways and the
                            p99 ratio (the CI bench-smoke gate:
                            ratio <= 1.2);
      ``replay/interval_K`` crash mid-trace with snapshots every K
                            pumps, then time ``recover()`` (snapshot
                            load + journal replay) — the
                            replay-time-vs-snapshot-cadence trade;
      ``parity``            the zero-loss row: after every crash above,
                            recovered state is bitwise-equal (f32) to
                            the crash-free run and no completion is
                            lost or invented.
    """
    import shutil
    import tempfile

    import jax
    import numpy as np
    from repro.core.backends import FusedPallasBackend
    from repro.core.twin import TwinFleet, make_autonomous_twin
    from repro.launch import chaos, traffic
    from repro.launch.fleet_serving import StreamingFleetServer

    n_req = 40 if FAST else 120
    population = 16 if FAST else 48
    hot = population // 2
    twin = make_autonomous_twin(
        state_dim=8, hidden=16, n_hidden_layers=1, gradient="fused_vjp",
        backend=FusedPallasBackend(precision="f32"))
    params = twin.init(jax.random.PRNGKey(0))
    fleet = TwinFleet(twin=twin)
    kw = dict(dt=1e-2, hot_capacity=hot, max_batch=min(8, hot),
              max_window=16, horizon_quantum=8)
    trace = traffic.poisson_trace(0, n_req, rate_hz=500.0,
                                  population=population, min_horizon=4,
                                  max_horizon=24)
    rng = np.random.default_rng(1)
    y0s = {a.twin_id: rng.normal(size=8).astype(np.float32) * 0.1
           for a in trace}
    y0_of = y0s.__getitem__

    def lat_pass(server):
        """Per-request submit->completion wall latency (ms array)."""
        t_submit, lat = {}, []
        for a in trace:
            if a.twin_id not in server.store:
                server.register_twin(a.twin_id, y0_of(a.twin_id))
            seq = server.submit(a.twin_id, a.horizon, t_arrival=a.time)
            t_submit[seq] = time.time()
            if server.pending >= server.max_batch:
                for c in server.pump(now=a.time):
                    lat.append(time.time() - t_submit.pop(c.seq))
        for c in server.drain(now=trace[-1].time):
            lat.append(time.time() - t_submit.pop(c.seq))
        assert not t_submit, "dropped requests"
        return np.asarray(lat) * 1e3

    # compile pass (unmeasured), then journal-off vs journal-on
    StreamingFleetServer(fleet, params, **kw).serve_trace(trace,
                                                          y0_of=y0_of)
    lat_off = lat_pass(StreamingFleetServer(fleet, params, **kw))
    tmp = tempfile.mkdtemp(prefix="bench_recovery_")
    try:
        lat_on = lat_pass(StreamingFleetServer(
            fleet, params, durability_dir=os.path.join(tmp, "lat"),
            snapshot_every=16, **kw))
        p50_off, p99_off = np.percentile(lat_off, [50, 99])
        p50_on, p99_on = np.percentile(lat_on, [50, 99])
        ratio = p99_on / max(p99_off, 1e-9)
        emit("recovery/journal_overhead", float(np.mean(lat_on)) * 1e3,
             f"p50_off_ms {p50_off:.3f} p99_off_ms {p99_off:.3f} "
             f"p50_on_ms {p50_on:.3f} p99_on_ms {p99_on:.3f} "
             f"p99_ratio {ratio:.3f}")

        # crash-free reference for the parity row
        ref = StreamingFleetServer(fleet, params, **kw)
        ref_done = ref.serve_trace(trace, y0_of=y0_of)
        ref_ids, _, _, _ = ref.store.export_state()

        lost = phantom = diverged = 0
        for interval in (4, 16, 64):
            d = os.path.join(tmp, f"replay_{interval}")
            live = StreamingFleetServer(fleet, params, durability_dir=d,
                                        snapshot_every=interval, **kw)
            delivered = []
            try:
                with chaos.crash_at("pump:post_commit", hit=n_req // 8):
                    live.serve_trace(trace, y0_of=y0_of, sink=delivered)
            except chaos.SimulatedCrash:
                pass
            jbytes = live._journal.nbytes
            t0 = time.time()
            rec, redelivered = StreamingFleetServer.recover(d, fleet,
                                                            params)
            recover_ms = (time.time() - t0) * 1e3
            resumed = rec.serve_trace(trace, y0_of=y0_of,
                                      start=rec.stream_stats.enqueued)
            got = {c.seq for c in delivered} | \
                  {c.seq for c in redelivered} | {c.seq for c in resumed}
            ref_seqs = {c.seq for c in ref_done}
            lost += len(ref_seqs - got)
            phantom += len(got - ref_seqs)
            for tid in ref_ids:
                y_ref, s_ref = ref.store.peek(tid)
                y_rec, s_rec = rec.store.peek(tid)
                if s_ref != s_rec or not np.array_equal(y_ref, y_rec):
                    diverged += 1
            emit(f"recovery/replay/interval_{interval}",
                 recover_ms * 1e3,
                 f"recover_ms {recover_ms:.1f} journal_bytes {jbytes} "
                 f"replayed {len(redelivered)} "
                 f"resumed {len(resumed)}")
        emit("recovery/parity", 0.0,
             f"lost {lost} phantom {phantom} diverged_twins {diverged} "
             f"bitwise {'true' if not (lost or phantom or diverged) else 'FALSE'}")
        assert not (lost or phantom or diverged), \
            "recovery parity violated (see recovery/parity row)"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_roofline():
    import glob
    import json
    files = sorted(glob.glob("runs/dryrun/*.json"))
    if not files:
        print("  (no dry-run artifacts found; run repro.launch.dryrun)")
        return
    for f in files:
        d = json.load(open(f))
        name = f"roofline/{d['arch']}/{d['shape']}/{d['mesh']}"
        t_step = max(d["t_compute_s"], d["t_memory_s"], d["t_collective_s"])
        emit(name, t_step * 1e6,
             f"{d['bottleneck']}-bound frac {d['roofline_fraction']:.4f}")


BENCHES = {
    "fig3j_hp_errors": bench_fig3j_hp_errors,
    "fig3kl_hp_energy": bench_fig3kl_hp_energy,
    "fig4g_l96_errors": None,   # chained with fig4j below
    "fig4hi_l96_energy": bench_fig4hi_l96_energy,
    "fig4j_noise": None,
    "kernels": bench_kernels,
    "fleet_backends": bench_fleet_backends,
    "energy_projection": bench_energy_projection,
    "fleet_sharded": bench_fleet_sharded,
    "train_throughput": bench_train_throughput,
    "fault_tolerance": bench_fault_tolerance,
    "robustness": bench_robustness,
    "serving_latency": bench_serving_latency,
    "recovery": bench_recovery,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", action="append", default=None,
                    help="module to run (repeatable); default: all")
    ap.add_argument("--artifact-dir", default=".",
                    help="where BENCH_<module>.json artifacts are written")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    names = args.only if args.only else list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown module(s) {unknown}; have {sorted(BENCHES)}")
    l96_state = None
    for name in names:
        print(f"\n=== {name} ===")
        start = len(ROWS)
        if name == "fig4g_l96_errors":
            l96_state = bench_fig4g_l96_errors()
        elif name == "fig4j_noise":
            bench_fig4j_noise(l96_state)
        else:
            BENCHES[name]()
        if len(ROWS) > start:
            write_artifact(name, ROWS[start:], args.artifact_dir)
    print(f"\nname,us_per_call,derived  ({len(ROWS)} rows, "
          f"{time.time()-t0:.0f}s total)")
    for r in ROWS:
        print(f"{r[0]},{r[1]:.3f},{r[2]}")


if __name__ == "__main__":
    main()

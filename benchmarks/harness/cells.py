"""The general loads a traffic file names (``"load"``), one per kind of
load, and the comparison each makes with the plain reference.

    stream_closed   StreamingFleetServer, every twin sends its next window
                    as soon as its last one completes
    stream_open     StreamingFleetServer, Poisson arrivals over a registered
                    population, latency from each request's due time
    fit_chunks      fit()'s scan engine, chunks back to back

A load not named here is the ``load`` function of ``loads/<load>.py``,
with the same signature (``resolve_load``).  Whatever depends on the
vector field comes from the configuration's twin kind (``twin_kind``).

A load builds the system from the configuration and the seed, warms
every shape its traffic uses (set-up), runs the window, reads the device
memory peak, frees the program's state, and only then runs the reference.
It returns an ``Outcome``; ``run.py`` turns that into the result line.

The program is reached only through its public entry points:
``StreamingFleetServer``, ``FusedPallasBackend``, ``TwinFleet``,
``make_driven_twin``, ``make_autonomous_twin``,
``trainer.make_scan_engine``, ``trainer.segment_loss_fn``,
``optimizer.adam`` and ``recipes.make_l96_fleet``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from . import reference, spec, trace, yardstick

CHECK_BLOCK = 4096          # reference rows per compiled call


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    e2e: dict                     # end-to-end metric -> value
    checks: dict                  # compared number -> value
    info: dict                    # printed on earlier lines, not compared
    layer: dict                   # what the per-layer readers read
    control: dict = dataclasses.field(default_factory=dict)


class Clock:
    """The loads' clock.  ``open`` marks the end of set-up and the start
    of the window, ``close`` its end; ``on_open``/``on_close`` let the
    caller read its compile clock there."""

    def __init__(self, on_open=None, on_close=None):
        self.now = time.perf_counter
        self.on_open, self.on_close = on_open, on_close
        self.opened = self.closed = None

    def open(self) -> float:
        self.opened = self.now()
        if self.on_open:
            self.on_open()
        return self.opened

    def close(self) -> float:
        self.closed = self.now()
        if self.on_close:
            self.on_close()
        return self.closed


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def control_operand_dtype(config: dict):
    """The control's operand type: one precision step below the
    configuration's (bf16 matmul operands -> fp8 e4m3, f32 -> bf16)."""
    import jax.numpy as jnp
    return {"bf16_f32acc": jnp.float8_e4m3fn, "bf16": jnp.float8_e4m3fn,
            "f32": jnp.bfloat16}[config["precision"]]


def window_rel_err(served: np.ndarray, ref: np.ndarray,
                   lengths: np.ndarray) -> np.ndarray:
    """Per window: max |served - ref| over its first ``length + 1`` rows,
    over the larger of max |ref| there and the median window's max |ref|
    (a window whose state passes near zero would otherwise set the
    number by its own small scale).  (N,) for (N, T+1, D) inputs."""
    rows = np.arange(served.shape[1])[None, :, None] <= lengths[:, None, None]
    diff = np.where(rows, np.abs(served - ref), 0.0).max(axis=(1, 2))
    scale = np.where(rows, np.abs(ref), 0.0).max(axis=(1, 2))
    return diff / np.maximum(scale, max(float(np.median(scale)), 1e-30))


def rms_rel_err(served: np.ndarray, ref: np.ndarray,
                lengths: np.ndarray) -> float:
    """Over every compared window's first ``length + 1`` rows together:
    the root of the summed squared error over the root of the summed
    squared reference."""
    rows = np.arange(served.shape[1])[None, :, None] <= lengths[:, None, None]
    diff = np.where(rows, served - ref, 0.0).astype(np.float64)
    scale = np.where(rows, ref, 0.0).astype(np.float64)
    return float(np.sqrt((diff ** 2).sum() / max((scale ** 2).sum(), 1e-300)))


def reference_rollouts(field, params, y0s: np.ndarray, u_half: np.ndarray,
                       dt: float, steps: int, operand_dtype=None):
    """The reference of the twin kind's ``field`` over many windows,
    CHECK_BLOCK rows per call (the last block padded), at ``highest``
    matmul precision."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda y, u: reference.rk4_rollout(
        field, params, y, u, dt, steps, operand_dtype))
    out = []
    for lo in range(0, y0s.shape[0], CHECK_BLOCK):
        y, u = y0s[lo:lo + CHECK_BLOCK], u_half[lo:lo + CHECK_BLOCK]
        n = y.shape[0]
        if n < CHECK_BLOCK and y0s.shape[0] > CHECK_BLOCK:
            pad = CHECK_BLOCK - n
            y = np.concatenate([y, np.repeat(y[-1:], pad, 0)])
            u = np.concatenate([u, np.repeat(u[-1:], pad, 0)])
        out.append(np.asarray(fn(jnp.asarray(y), jnp.asarray(u)))[:n])
    return np.concatenate(out)


def twin_kind(config: dict):
    """The module of the configuration's twin kind, ``twins/<kind>.py``
    (``mlp`` where the configuration names none)."""
    return spec.load_module("twins", config.get("twin", "mlp"))


def resolve_load(name: str):
    """The load a traffic file names: one of ``LOADS``, or else the
    ``load`` function of ``loads/<name>.py``."""
    return LOADS[name] if name in LOADS else spec.load_module(
        "loads", name).load


def served_backend(config: dict):
    from repro.core.backends import FusedPallasBackend
    return FusedPallasBackend(batch_tile=config["batch_tile"],
                              precision=config["precision"])


def check_sizes(twin, kind, config: dict) -> None:
    sizes = tuple(twin.field.sizes)
    if sizes != tuple(kind.layer_sizes(config)):
        raise RuntimeError(f"the program's twin has layer sizes {sizes}, the "
                           f"configuration {kind.layer_sizes(config)}")


# ---------------------------------------------------------------------------
# Streaming server: shared build and checks
# ---------------------------------------------------------------------------

def _build_stream(config: dict, traffic: dict, params):
    from repro.launch.fleet_serving import StreamingFleetServer
    kind = twin_kind(config)
    fleet = kind.served_fleet(config, served_backend(config))
    check_sizes(fleet.twin, kind, config)
    server = StreamingFleetServer(
        fleet, params, dt=config["dt"], hot_capacity=traffic["hot_capacity"],
        max_batch=traffic["max_batch"], max_window=traffic["max_window"],
        horizon_quantum=traffic["horizon_quantum"], transient_retries=0)
    return server


class StreamLedger:
    """What the harness keeps of every completed request: enough to check
    that each twin's windows chain exactly (a window starts from the last
    row of the twin's previous one, at the step the harness counted),
    and the full trajectories of a sample for the reference."""

    def __init__(self, y0s: np.ndarray, storage_dtype):
        import jax.numpy as jnp
        # a twin's first window starts from its registered state rounded
        # to the kernel's storage dtype, once
        self.last = np.array(jnp.asarray(y0s).astype(storage_dtype)
                             .astype(jnp.float32))
        self.steps = np.zeros(y0s.shape[0], np.int64)
        self.breaks = 0
        self.step_errors = 0
        self.completed = 0
        self.kept = []            # (twin, start_step, horizon, trajectory)

    def record(self, done, keep=lambda c: False) -> None:
        if not done:
            return
        ids = np.fromiter((c.twin_id for c in done), np.int64, len(done))
        first = np.stack([c.trajectory[0] for c in done])
        last = np.stack([c.trajectory[-1] for c in done])
        lens = np.fromiter((c.trajectory.shape[0] - 1 for c in done),
                           np.int64, len(done))
        starts = np.fromiter((c.start_step for c in done), np.int64,
                             len(done))
        if len(np.unique(ids)) == len(ids):
            self.breaks += int((first != self.last[ids]).any(axis=1).sum())
            self.step_errors += int((starts != self.steps[ids]).sum())
            self.last[ids] = last
            self.steps[ids] += lens
        else:                     # one twin twice in one pump: in order
            for c, i, f, l, s, h in zip(done, ids, first, last, starts, lens):
                self.breaks += int((f != self.last[i]).any())
                self.step_errors += int(s != self.steps[i])
                self.last[i], self.steps[i] = l, self.steps[i] + h
        self.completed += len(done)
        for c, s in zip(done, starts):
            if keep(c):
                self.kept.append((c.twin_id, int(s), c.trajectory.shape[0] - 1,
                                  c.trajectory))


def _stream_checks(ledger: StreamLedger, params, config: dict,
                   thetas: np.ndarray, lost: int, control: bool):
    """The comparison of a streaming cell: the kept windows against the
    reference from each window's own first row, exact chaining, exact
    step counts, nothing lost."""
    if not ledger.kept:
        raise RuntimeError("no completed window was kept for the check")
    ids = np.array([k[0] for k in ledger.kept])
    starts = np.array([k[1] for k in ledger.kept])
    lens = np.array([k[2] for k in ledger.kept])
    T = int(lens.max())
    D = config["state_dim"]
    served = np.zeros((len(ids), T + 1, D), np.float32)
    for i, k in enumerate(ledger.kept):
        served[i, :k[2] + 1] = k[3]
    y0 = served[:, 0]
    kind = twin_kind(config)
    u = kind.drive_half_steps(config, thetas[ids], starts, T)
    ref = reference_rollouts(kind.field, params, y0, u, config["dt"], T)
    checks = {"max_rel_err": float(window_rel_err(served, ref, lens).max()),
              "rms_rel_err": rms_rel_err(served, ref, lens),
              "chain_breaks": ledger.breaks,
              "step_errors": ledger.step_errors,
              "lost": lost}
    info = {"windows_compared": int(len(ids)),
            "longest_compared": T}
    ctl = {}
    if control:
        low = reference_rollouts(kind.field, params, y0, u, config["dt"], T,
                                 control_operand_dtype(config))
        ctl = {"max_rel_err": float(window_rel_err(low, ref, lens).max()),
               "rms_rel_err": rms_rel_err(low, ref, lens)}
    return checks, info, ctl


def _storage_dtype(precision: str):
    import jax.numpy as jnp
    return jnp.float32 if precision == "f32" else jnp.bfloat16


def counter_deltas(s0: dict, s1: dict, groups=("stream", "store")) -> dict:
    """``"<group>.<field>"`` -> the window's change of every numeric field
    of the server's ``stats().as_dict()`` groups, read with ``.get`` so
    that a field an older program lacks is simply absent."""
    out = {}
    for grp in groups:
        a, b = s0.get(grp) or {}, s1.get(grp) or {}
        for k, v in b.items():
            if (isinstance(v, (int, float)) and not isinstance(v, bool)
                    and isinstance(a.get(k), (int, float))):
                out[f"{grp}.{k}"] = v - a[k]
    return out


def _stream_layer(config: dict, traffic: dict, stats0, stats1, pump_s,
                  window: float, chips: int = 1) -> dict:
    s0, s1 = stats0.as_dict(), stats1.as_dict()
    d = lambda grp, k: s1[grp][k] - s0[grp][k]
    twin_steps, padded = d("stream", "twin_steps"), d("stream", "padded_steps")
    page_ins, hits = d("store", "page_ins"), d("store", "hot_hits")
    return {"twin_steps": twin_steps,
            "twin_steps_per_s": twin_steps / window,
            "flops_per_twin_step": twin_kind(config).flops_per_twin_step(
                config),
            "padded_frac": (100.0 * padded / (twin_steps + padded)
                            if twin_steps + padded else None),
            "page_in_share": (100.0 * page_ins / (page_ins + hits)
                              if page_ins + hits else None),
            "pump_ms": 1e3 * float(np.mean(pump_s)) if pump_s else None,
            "pumps": len(pump_s), "chips": chips,
            "counters": counter_deltas(s0, s1)}


# ---------------------------------------------------------------------------
# stream_closed
# ---------------------------------------------------------------------------

def stream_closed(config, traffic, seed, seconds, traced, clock, control=False):
    import jax
    rng, jax_seed = yardstick.seeds(seed)
    kind = twin_kind(config)
    params = kind.make_weights(config, jax_seed)
    n, H = traffic["twins"], traffic["horizon"]
    y0s, thetas = kind.initial_states(config, n, jax_seed)
    server = _build_stream(config, traffic, params)
    with trace.span("register", traced):
        for i in range(n):
            server.register_twin(i, y0s[i], theta=thetas[i]
                                 if thetas.shape[1] else None)
    ledger = StreamLedger(y0s, _storage_dtype(config["precision"]))
    ids = list(range(n))

    phase = {"submit_s": 0.0, "pump_s": 0.0, "record_s": 0.0}

    def round_(keep):
        t_submit = clock.now()
        with trace.span("submit", traced):
            for i in ids:
                server.submit(i, H)
        done, pumps = [], []
        t1 = clock.now()
        while server.pending:
            t = clock.now()
            with trace.span("pump", traced):
                done.extend(server.pump())
            pumps.append(clock.now() - t)
        t2 = clock.now()
        ledger.record(done, keep)
        phase["submit_s"] += t1 - t_submit
        phase["pump_s"] += t2 - t1
        phase["record_s"] += clock.now() - t2
        return len(done), pumps

    for _ in range(traffic["warm_rounds"]):
        round_(lambda c: False)
    # the window: the twins drawn from the seed in every round, and every
    # twin in the window's first round
    sample = set(rng.choice(n, size=min(n, traffic["check_twins"]),
                            replace=False).tolist())
    phase.update(submit_s=0.0, pump_s=0.0, record_s=0.0)
    keep_all = lambda c: True
    keep_sample = lambda c: c.twin_id in sample
    out, rounds, attempted, pump_s = {}, 0, 0, []
    stats0 = server.stats()
    before = ledger.completed
    ctx = trace.capture(out) if traced else _nullcontext()
    with ctx:
        t0 = clock.open()
        while True:
            attempted += n
            _, p = round_(keep_all if rounds == 0 else keep_sample)
            pump_s += p
            rounds += 1
            if clock.now() - t0 >= seconds:
                break
        window = clock.close() - t0
    stats1 = server.stats()
    layer = _stream_layer(config, traffic, stats0, stats1, pump_s, window)
    rows = -(-traffic["max_batch"] // config["batch_tile"]) * config["batch_tile"]
    layer["kernels"] = {"fused_fwd": kind.fused_fwd_cost(config, steps=H,
                                                         rows=rows)}
    layer["trace"] = out.get("trace")
    mem = memory_peak(jax.devices()[:1])
    served_by = stats1.serving.served_by
    del server
    gc.collect()
    completed = ledger.completed - before
    checks, info, ctl = _stream_checks(ledger, params, config, thetas,
                                       attempted - completed, control)
    info.update(rounds=rounds, window_s=window, served_by=served_by,
                **{k: v / rounds for k, v in phase.items()})
    return Outcome(attempted=attempted, failed=attempted - completed,
                   e2e={"twin_steps_per_s": layer["twin_steps_per_s"]},
                   checks=checks, info=info, layer=layer, control=ctl), mem


class _nullcontext:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


# ---------------------------------------------------------------------------
# stream_open
# ---------------------------------------------------------------------------

def _page_in_range(traffic: dict) -> range:
    """Page-in counts a full batch of uniformly drawn twins meets: each of
    its rows is hot with probability hot_capacity / population."""
    n = traffic["max_batch"]
    p = min(1.0, traffic["hot_capacity"] / traffic["population"])
    hits = n * p + 8.0 * math.sqrt(n * p * (1.0 - p))
    return range(max(0, n - int(math.ceil(hits))), n + 1)


def stream_open(config, traffic, seed, seconds, traced, clock, control=False):
    """Poisson arrivals, latency from each request's due time.

    The client pumps when ``max_batch`` distinct twins are waiting, or when
    the oldest request has waited ``max_wait_s``: the server compiles its
    per-batch host ops for every batch size, so a client that pumped
    whatever was queued would meet a new size, and a compile, at every
    turn of the queue.  Full batches keep one window shape, (max_batch,
    max_window), whose programs set-up compiles for every page-in count
    the traffic meets.  The source keeps sending past the window's close,
    uncounted, so its last requests still go in full batches."""
    import jax
    rng, jax_seed = yardstick.seeds(seed)
    kind = twin_kind(config)
    params = kind.make_weights(config, jax_seed)
    P, B = traffic["population"], traffic["max_batch"]
    H = traffic["max_window"]
    y0s, thetas = kind.initial_states(config, P, jax_seed)
    server = _build_stream(config, traffic, params)
    driven = thetas.shape[1] > 0
    with trace.span("register", traced):
        for i in range(P):
            server.register_twin(i, y0s[i], theta=thetas[i] if driven
                                 else None)
    ledger = StreamLedger(y0s, _storage_dtype(config["precision"]))
    # set-up: one full batch of the longest window for every page-in
    # count, from all-cold down to the most hot rows the traffic meets
    cursor = 0
    for k in sorted(_page_in_range(traffic), reverse=True):
        hot = server.store.hot_ids[::-1][:B - k]
        taken = set(hot)
        cold = []
        while len(cold) < k:
            if cursor % P not in taken and cursor % P not in server.store.hot_ids:
                cold.append(cursor % P)
                taken.add(cursor % P)
            cursor += 1
        for i in hot + cold:
            server.submit(i, H)
        while server.pending:
            ledger.record(server.pump())
    # the arrivals, from the seed: the window's, then as many again
    n_req = int(traffic["rate_hz"] * seconds * 2.0) + 4 * B
    due, tw, hz = yardstick.poisson_arrivals(
        rng, n_req, rate_hz=traffic["rate_hz"], population=P,
        min_horizon=traffic["min_horizon"], max_horizon=traffic["max_horizon"])
    n_due = int(np.searchsorted(due, seconds))
    due_at, waiting = {}, {}          # pending seq -> due; twin -> count
    lat, lag, pump_s = [], [], []
    partial = 0
    out = {}
    i = 0
    stats0 = server.stats()
    before = ledger.completed

    def serve(now):
        t = clock.now()
        with trace.span("pump", traced):
            done = server.pump(now)
        t_done = clock.now()
        ledger.record(done, lambda c: True)
        for c in done:
            d = due_at.pop(c.seq)
            if d < seconds:
                lat.append(t_done - t0 - d)
            waiting[c.twin_id] -= 1
            if not waiting[c.twin_id]:
                del waiting[c.twin_id]
        return t_done - t

    def submit_due(now):
        nonlocal i
        with trace.span("submit", traced):
            while i < n_req and due[i] <= now:
                seq = server.submit(int(tw[i]), int(hz[i]),
                                    t_arrival=float(due[i]))
                due_at[seq] = due[i]
                waiting[int(tw[i])] = waiting.get(int(tw[i]), 0) + 1
                if i < n_due:
                    lag.append(now - due[i])
                i += 1

    closed = None                     # (stats, backlog) at the close
    ctx = trace.capture(out) if traced else _nullcontext()
    with ctx:
        t0 = clock.open()
        while True:
            now = clock.now() - t0
            if closed is None and now >= seconds:
                clock.close()
                closed = (server.stats(), len(due_at))
            if closed and (now > seconds + traffic["drain_s"] or (
                    i >= n_due and all(d >= seconds
                                       for d in due_at.values()))):
                break
            if i < n_req and due[i] <= now:
                submit_due(now)
            if len(waiting) >= B or (
                    due_at and now - next(iter(due_at.values()))
                    >= traffic["max_wait_s"]):
                partial += len(waiting) < B
                dur = serve(now)
                if closed is None:
                    pump_s.append(dur)
            elif i < n_req:
                time.sleep(max(0.0, min(due[i] - (clock.now() - t0), 5e-4)))
            else:
                raise RuntimeError("the arrival schedule ran out before the "
                                   "window's requests completed")
    stats1, backlog = closed
    window = clock.closed - clock.opened
    layer = _stream_layer(config, traffic, stats0, stats1, pump_s, window)
    layer["trace"] = out.get("trace")
    mem = memory_peak(jax.devices()[:1])
    served_by = stats1.serving.served_by
    del server
    gc.collect()
    completed = len(lat)
    checks, info, ctl = _stream_checks(ledger, params, config, thetas,
                                       n_due - completed, control)
    lat_ms = 1e3 * np.asarray(lat)
    q = lambda a, p: float(np.percentile(a, p)) if len(a) else float("nan")
    info.update(window_s=window, requests=completed, rate_hz=traffic["rate_hz"],
                latency_p50_ms=q(lat_ms, 50), latency_p99_ms=q(lat_ms, 99),
                generator_lag_p50_ms=q(1e3 * np.asarray(lag), 50),
                generator_lag_max_ms=float(1e3 * max(lag)) if lag else 0.0,
                pumps=len(pump_s), partial_pumps=partial,
                backlog_at_close=backlog, served_by=served_by,
                warm_page_ins=[_page_in_range(traffic).start, B])
    return Outcome(attempted=n_due, failed=n_due - completed,
                   e2e={"latency_p95_ms": q(lat_ms, 95)}, checks=checks,
                   info=info, layer=layer, control=ctl), mem


# ---------------------------------------------------------------------------
# fit_chunks
# ---------------------------------------------------------------------------

def _leaf_err(prog, ref, scale: np.ndarray, keep: np.ndarray) -> float:
    """Worst kept leaf: ||prog leaf - ref leaf|| over the larger of that
    leaf's ``scale`` and the median kept leaf's."""
    err = np.array([np.linalg.norm(np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64))
                    for a, b in zip(prog, ref)])
    med = float(np.median(scale[keep]))
    return float((err / np.maximum(scale, med))[keep].max())


def fit_checks(losses, params0, params_n, mu_n, ref):
    """The fit cell's numbers after its first chunk of n steps, against
    ``reference.fit_reference`` over the same n steps (``ref`` = its
    losses, parameters, first and second moments): the worst step's
    relative loss error; by worst leaf, the parameters' error over the
    reference's change from the start; by worst leaf, the error of Adam's
    first moment, the gradients as the optimizer holds them."""
    import jax
    leaves = jax.tree_util.tree_leaves
    r_losses = np.asarray(ref[0], np.float64)
    loss_err = float(np.max(np.abs(np.asarray(losses[:len(r_losses)],
                                              np.float64) - r_losses)
                            / np.abs(r_losses)))
    r_mu = [np.asarray(x, np.float64) for x in leaves(ref[2])]
    mu_norm = np.array([np.linalg.norm(x) for x in r_mu])
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone under Adam; they are left out by this rule
    keep = mu_norm >= 1e-3 * np.median(mu_norm)
    change = np.array([np.linalg.norm(np.asarray(x, np.float64)
                                      - np.asarray(y, np.float64))
                       for x, y in zip(leaves(ref[1]), leaves(params0))])
    return {"loss_err": loss_err,
            "update_err": _leaf_err(leaves(params_n), leaves(ref[1]),
                                    change, keep),
            "moment_err": _leaf_err(leaves(mu_n), r_mu, mu_norm, keep)}, \
        int((~keep).sum())


def fit_chunks(config, traffic, seed, seconds, traced, clock, control=False):
    import jax
    import jax.numpy as jnp
    from repro.train import trainer
    from repro.train.optimizer import adam
    _, jax_seed = yardstick.seeds(seed)
    L = traffic["segment"]
    ts, ys = yardstick.lorenz96_data(config["num_points"], config["dt"],
                                     config["forcing"])
    ts, ys = ts[:config["train_points"]], ys[:config["train_points"]]
    S = (ys.shape[0] - 1) // L
    idx = np.arange(S)[:, None] * L + np.arange(L + 1)[None, :]
    ts_seg, ys_seg = ts[idx], ys[idx]
    kind = twin_kind(config)
    twin = kind.fit_twin(config)
    check_sizes(twin, kind, config)
    backend = served_backend(config)
    loss_fn = trainer.segment_loss_fn(
        twin, ts_seg, ys_seg, traffic["loss"], gamma=traffic["gamma"],
        noise_std=traffic["noise_std"], backend=backend)
    opt = adam(traffic["lr"])
    engine = trainer.make_scan_engine(loss_fn, opt, has_key=True, donate=True)
    init = dict(config, weights=config["fit_init"])
    params0 = kind.make_weights(init, jax_seed)
    key0 = jax.random.fold_in(jax.random.PRNGKey(jax_seed), 2)
    n = traffic["chunk_steps"]
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
    # set-up: the first chunk, through the window's own call, feed and
    # compiled program; the losses, parameters and Adam state it hands on
    # are what ``correct`` compares, and the window carries on from them
    with trace.span("engine_chunk", traced):
        p, o, k, first = engine(copy(params0), opt.init(params0), key0, n)
    first = np.asarray(first)
    params_n = jax.tree_util.tree_map(np.asarray, p)
    mu_n = jax.tree_util.tree_map(np.asarray, o.mu)
    carry = (p, o, k)
    out, chunks, nonfinite = {}, 0, int((~np.isfinite(first)).sum())
    ctx = trace.capture(out) if traced else _nullcontext()
    with ctx:
        t0 = clock.open()
        pending = None
        while True:
            with trace.span("engine_chunk", traced):
                p, o, k, losses = engine(*carry, n)
            carry = (p, o, k)
            if pending is not None:
                nonfinite += int((~np.isfinite(np.asarray(pending))).sum())
            pending = losses
            chunks += 1
            if clock.now() - t0 >= seconds:
                break
        nonfinite += int((~np.isfinite(np.asarray(pending))).sum())
        window = clock.close() - t0
    mem = memory_peak(jax.devices()[:1])
    del carry, p, o, k, engine, loss_fn
    gc.collect()
    ref_fn = jax.jit(
        lambda prm, y, key, odt: reference.fit_reference(
            kind.field, prm, y, key, steps=n, dt=config["dt"], lr=traffic["lr"],
            noise_std=traffic["noise_std"], gamma=traffic["gamma"],
            operand_dtype=odt), static_argnums=(3,))

    def ref(odt=None):
        with jax.default_matmul_precision("highest"):
            return ref_fn(params0, ys_seg, key0, odt)

    r = ref()
    checks, skipped = fit_checks(first, params0, params_n, mu_n, r)
    checks["nonfinite_losses"] = nonfinite
    ctl = {}
    if control:
        low = ref(control_operand_dtype(config))
        ctl, _ = fit_checks(np.asarray(low[0]), params0, low[1], low[2], r)
        ctl["nonfinite_losses"] = int((~np.isfinite(np.asarray(low[0]))).sum())
    steps = chunks * n
    fwd = kind.fused_fwd_cost(config, steps=L, rows=S)
    bwd = kind.fused_bwd_cost(config, steps=L, rows=S)
    layer = {"fit_steps_per_s": steps / window,
             "flops_per_step": fwd[0] + bwd[0],
             "kernels": {"fused_fwd": fwd, "fused_bwd": bwd},
             "trace": out.get("trace"), "chips": 1}
    info = {"window_s": window, "chunks": chunks, "steps": steps,
            "segments": S, "steps_compared": n,
            "first_losses": first[:3].tolist(),
            "reference_losses": np.asarray(r[0])[:3].tolist(),
            "leaves_left_out": skipped}
    return Outcome(attempted=steps, failed=0,
                   e2e={"fit_steps_per_s": steps / window}, checks=checks,
                   info=info, layer=layer, control=ctl), mem


LOADS = {"stream_closed": stream_closed, "stream_open": stream_open,
         "fit_chunks": fit_chunks}

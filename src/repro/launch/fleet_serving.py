"""Sharded fleet serving: many digital twins, many devices, one program.

The paper's Industry-4.0 pitch is serving *fleets* of twins — one trained
neural ODE, thousands of physical assets, each with its own sensed state
and stimulus (Hartmann 2023, arXiv:2311.14691; Fuller et al. 2019,
arXiv:1911.01276).  A fleet rollout is embarrassingly parallel across
assets, so the multi-device mapping is the weights-stationary layout one
level up:

  * the trained weights are **replicated** onto every device (each
    device is "a crossbar chip" holding the full twin);
  * the fleet axis (``y0s``, per-twin ``drive_params``) is **sharded**
    over a 1-D ``("twins",)`` mesh with ``shard_map``;
  * each device runs its slice through the backend's single-device
    fleet implementation (``rollout_batch_local`` — vmap for
    digital/analogue, the fused-Pallas grid for TPU), with zero
    cross-device traffic during the solve;
  * uneven fleet sizes are padded up to a multiple of the shard count
    and the padded trajectories are dropped before results are returned
    (``pad_fleet_inputs`` also hands back the real-row mask for callers
    that keep padded outputs).

On a 1-device host the mesh is trivial and the sharded path runs the
identical program (same numerics — pinned by
``tests/test_fleet_serving.py``); on a pod it scales linearly in devices.

Layers (bottom-up):

  ``shard_rollout_batch``  backend-level shard_map wrapper (called by
                           ``Backend.rollout_batch(mesh=...)``)
  ``FleetServer``          programmed server: weights replicated once,
                           request batches in, trajectories out
  ``serve_fleet``          end-to-end pipeline: checkpoint -> server ->
                           streamed request batches -> gathered results

CLI smoke (Lorenz96 fleet, trivial mesh on CPU):

  PYTHONPATH=src python -m repro.launch.fleet_serving --fleet 256 \
      --horizon 100 --batches 2
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import os
import tempfile
import time
from typing import Any, Callable, Iterable, Iterator, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.backends import (AnalogueBackend, DigitalBackend,
                                 FusedAnalogueBackend, FusedPallasBackend,
                                 _with_drive, resolve_backend)
from repro.launch import chaos
from repro.launch import journal as journal_lib
from repro.launch.mesh import TWIN_AXIS, make_twin_mesh, twin_shard_count
from repro.launch.sharding import (fleet_input_shardings,
                                   fleet_param_shardings)
from repro.launch.state_store import StoreStats, TwinStateStore
from repro.train import checkpoint as ckpt_lib

Pytree = Any
Request = Union[jax.Array, tuple]


# ---------------------------------------------------------------------------
# Front-door input validation
# ---------------------------------------------------------------------------

def validate_fleet_request(caller: str, y0s=None, ts=None,
                           drive_params=None) -> None:
    """Reject malformed serving inputs with errors naming the offending
    argument — a NaN initial condition or a backwards time grid would
    otherwise propagate silently through the whole rollout and surface
    as garbage trajectories.

    Value checks only run on concrete arrays: traced inputs (the jitted
    serving path) skip them, so this is free inside jit — callers
    validate at the host-side front door (``FleetServer.serve``) where
    values exist.
    """
    for name, x in (("y0s", y0s), ("drive_params", drive_params)):
        if x is None:
            continue
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise ValueError(
                f"{caller}: {name} has non-floating dtype {x.dtype}")
        if (not isinstance(x, jax.core.Tracer)
                and not bool(jnp.isfinite(x).all())):
            bad = int(jnp.sum(~jnp.isfinite(x)))
            raise ValueError(
                f"{caller}: {name} contains {bad} non-finite "
                f"(NaN/Inf) value(s) — rejecting the request instead of "
                f"rolling garbage through the fleet")
    if ts is not None and not isinstance(jnp.asarray(ts), jax.core.Tracer):
        tsn = np.asarray(ts)
        if tsn.ndim != 1 or tsn.size < 2:
            raise ValueError(
                f"{caller}: ts must be a 1-D time grid with >= 2 points, "
                f"got shape {tsn.shape}")
        if not bool(np.isfinite(tsn).all()):
            raise ValueError(f"{caller}: ts contains non-finite values")
        if not bool((np.diff(tsn) > 0).all()):
            raise ValueError(
                f"{caller}: ts must be strictly increasing (non-monotone "
                f"time grids silently break the fixed-step integrators)")


# ---------------------------------------------------------------------------
# Uneven-N padding
# ---------------------------------------------------------------------------

def padded_size(n: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` >= n."""
    return -(-n // n_shards) * n_shards


def pad_fleet_inputs(y0s: jax.Array,
                     drive_params: Optional[jax.Array],
                     n_shards: int):
    """Pad the fleet axis up to a multiple of the shard count.

    Padding rows replicate the LAST real asset (in-distribution values —
    a padded lane can never overflow into inf/NaN that a zero-filled
    state might, and its trajectory is discarded anyway).  Returns
    ``(y0s_padded, drive_params_padded, mask)`` where ``mask`` is a
    length-``padded_size`` bool vector marking the real rows; slicing the
    result back to ``mask.sum()`` rows undoes the padding exactly.
    """
    n = y0s.shape[0]
    if drive_params is not None and drive_params.shape[0] != n:
        raise ValueError(
            f"drive_params batch {drive_params.shape[0]} != y0s batch {n}")
    np_ = padded_size(n, n_shards)
    mask = np.arange(np_) < n

    def pad(x):
        if x is None or np_ == n:
            return x
        tail = jnp.repeat(x[-1:], np_ - n, axis=0)
        return jnp.concatenate([x, tail], axis=0)

    return pad(y0s), pad(drive_params), mask


# ---------------------------------------------------------------------------
# shard_map wrapper (the Backend.rollout_batch(mesh=...) implementation)
# ---------------------------------------------------------------------------

def shard_rollout_batch(backend, state, y0s: jax.Array, ts: jax.Array, *,
                        mesh, drive_family: Optional[Callable] = None,
                        drive_params: Optional[jax.Array] = None,
                        **solver_kw) -> jax.Array:
    """Shard a fleet rollout over the twin axis of ``mesh``.

    ``backend``/``state``: a programmed execution substrate (see
    :mod:`repro.core.backends`) — the state's weights are closed over,
    i.e. replicated to every device.  ``y0s`` (N, D) and optional
    ``drive_params`` (N, ...) are split along dim 0; each device calls
    ``backend.rollout_batch_local`` on its slice, so the per-device
    program is exactly the single-device one.  N that does not divide the
    shard count is padded (see :func:`pad_fleet_inputs`) and the padded
    trajectories are dropped before returning (N, T+1, D).

    ``solver_kw`` forwards verbatim to every device's
    ``rollout_batch_local`` — including the fused backend's
    ``precision=`` override, so a sharded fleet can serve the bf16
    substrate (half the replicated-weight bytes and per-device slab
    traffic) with one keyword.
    """
    validate_fleet_request("shard_rollout_batch", y0s=y0s, ts=ts,
                           drive_params=drive_params)
    n_shards = twin_shard_count(mesh)
    n = y0s.shape[0]
    y0s_p, dp_p, _ = pad_fleet_inputs(y0s, drive_params, n_shards)

    def per_device(y_loc, dp_loc):
        return backend.rollout_batch_local(
            state, y_loc, ts, drive_family=drive_family,
            drive_params=dp_loc, **solver_kw)

    if dp_p is None:
        sharded = jax.shard_map(lambda y: per_device(y, None), mesh=mesh,
                                in_specs=P(TWIN_AXIS),
                                out_specs=P(TWIN_AXIS), check_vma=False)
        out = sharded(y0s_p)
    else:
        sharded = jax.shard_map(per_device, mesh=mesh,
                                in_specs=(P(TWIN_AXIS), P(TWIN_AXIS)),
                                out_specs=P(TWIN_AXIS), check_vma=False)
        out = sharded(y0s_p, dp_p)
    return out[:n]


# ---------------------------------------------------------------------------
# Serving SLO + graceful degradation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServingSLO:
    """Correctness contract for analogue serving.

    ``max_rel_error``: worst tolerated relative deviation of a health
    probe from the digital reference (relative to the reference's peak
    magnitude).  ``probe_every``: run a golden-trajectory probe every
    this many requests (1 = every request).  ``probe_horizon`` /
    ``probe_fleet``: probe cost knobs — first ``probe_fleet`` rows of
    the request over the first ``probe_horizon`` grid points.
    ``max_retries``: extra tiers a single request may fall through when
    its output comes back non-finite.  ``timeout_s``: wall-clock budget
    per attempt (None = unbounded); overruns are counted, not killed —
    a slow answer is still an answer.
    """
    max_rel_error: float = 0.05
    probe_every: int = 8
    probe_horizon: int = 11
    probe_fleet: int = 2
    max_retries: int = 2
    timeout_s: Optional[float] = None

    def __post_init__(self):
        if self.max_rel_error <= 0:
            raise ValueError(f"ServingSLO.max_rel_error must be > 0, "
                             f"got {self.max_rel_error}")
        for f in ("probe_every", "probe_horizon", "probe_fleet"):
            if getattr(self, f) < 1:
                raise ValueError(f"ServingSLO.{f} must be >= 1, "
                                 f"got {getattr(self, f)}")
        if self.max_retries < 0:
            raise ValueError(f"ServingSLO.max_retries must be >= 0, "
                             f"got {self.max_retries}")
        if self.timeout_s is not None and self.timeout_s <= 0:
            raise ValueError(f"ServingSLO.timeout_s must be > 0 or None, "
                             f"got {self.timeout_s}")


@dataclasses.dataclass
class ServingStats:
    """Counters the degradation machinery maintains (one per server)."""
    requests: int = 0
    probes: int = 0
    probe_demotions: int = 0
    probe_recoveries: int = 0
    nan_rescues: int = 0
    retries: int = 0
    transient_retries: int = 0
    timeouts: int = 0
    served_by: dict = dataclasses.field(default_factory=dict)
    probe_errors: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def fallback_chain(fleet) -> list:
    """Ordered degradation tiers ``[(name, fleet_variant), ...]`` for a
    serving fleet: primary substrate -> noise-free fused analogue (same
    programmed array and faults, stochastic read noise off) -> digital
    golden reference.  Each step strips one failure mode; the last tier
    cannot be degraded by array health at all, so a served fleet trades
    energy/throughput for correctness, never the reverse.
    """
    primary = resolve_backend(fleet.backend)
    tiers = [(primary.name, fleet)]
    if isinstance(primary, (AnalogueBackend, FusedAnalogueBackend)):
        spec = primary.spec
        if spec.read_noise > 0.0 or isinstance(primary, AnalogueBackend):
            clean_spec = dataclasses.replace(spec, read_noise=0.0)
            if isinstance(primary, FusedAnalogueBackend):
                clean = dataclasses.replace(primary, spec=clean_spec)
            else:
                # jnp-simulator primary: the quiet tier is the fused
                # substrate with the same programming physics.
                clean = FusedAnalogueBackend(
                    spec=clean_spec, prog_key=primary.prog_key,
                    storage=primary.storage, faults=primary.faults,
                    verify=primary.verify, n_reads=primary.n_reads)
            tiers.append((f"{clean.name}_clean", fleet.with_backend(clean)))
    if not isinstance(primary, DigitalBackend):
        tiers.append(("digital", fleet.with_backend(DigitalBackend())))
    return tiers


# ---------------------------------------------------------------------------
# Programmed fleet server
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FleetServer:
    """A twin fleet programmed for serving: weights placed once,
    request batches streamed through a cached compiled program.

    Construction replicates ``params`` onto every device of the twin
    mesh (the "program the crossbars" step at datacentre scale) and
    freezes the time grid; each :meth:`serve` call pads + shards the
    request batch, runs the jitted sharded rollout (compiled once per
    padded batch shape) and returns the unpadded trajectories.

    Passing an :class:`ServingSLO` arms graceful degradation for
    analogue substrates (``docs/robustness.md``): the server builds the
    :func:`fallback_chain` of tiers, health-probes the chain every
    ``probe_every`` requests (a short golden rollout on the request's
    own leading rows, checked against the digital reference) and serves
    each request from the healthiest tier that meets the SLO — probing
    always restarts from the primary tier, so a recovered array is
    promoted back automatically.  Any request whose trajectories come
    back non-finite is retried down the chain; the digital tier cannot
    be degraded by array health, so a served fleet loses energy
    efficiency under faults, never correctness.  ``stats`` counts what
    happened.
    """
    fleet: Any                        # repro.core.twin.TwinFleet
    params: Pytree
    ts: Any                           # concrete uniform time grid
    mesh: Any = None                  # None -> all visible devices
    slo: Optional[ServingSLO] = None  # None -> no degradation machinery

    def __post_init__(self):
        if self.mesh is None:
            self.mesh = make_twin_mesh()
        self.ts = jnp.asarray(np.asarray(self.ts))   # concrete for Pallas
        validate_fleet_request("FleetServer", ts=self.ts)
        self.params = jax.device_put(
            self.params, fleet_param_shardings(self.mesh, self.params))
        ts, mesh = self.ts, self.mesh
        self.stats = ServingStats()
        if self.slo is None:
            self._tiers = [(getattr(resolve_backend(self.fleet.backend),
                                    "name", "primary"), self.fleet)]
        else:
            self._tiers = fallback_chain(self.fleet)
        self._active = 0

        def compiled(f):
            return jax.jit(lambda p, y0s, thetas: f.rollout_batch(
                p, y0s, ts, thetas, mesh=mesh))

        self._rollouts = [compiled(f) for _, f in self._tiers]
        self._rollout = self._rollouts[0]     # primary tier, legacy name
        self._golden = (None if self.slo is None else
                        self.fleet.with_backend(DigitalBackend()))

    @property
    def n_shards(self) -> int:
        return twin_shard_count(self.mesh)

    @property
    def active_tier(self) -> str:
        """Name of the tier requests are currently served from."""
        return self._tiers[self._active][0]

    # -- health probing ----------------------------------------------------
    def _probe(self, y0s: jax.Array, thetas: Optional[jax.Array]) -> None:
        """Golden-trajectory health check: roll the request's first
        ``probe_fleet`` rows over ``ts[:probe_horizon]`` on each tier
        (eagerly, no mesh — the probe is tiny) and activate the first
        tier whose worst deviation from the digital reference meets the
        SLO.  Scanning from the top every time is what makes recovery
        automatic; the final (digital) tier is the reference itself and
        needs no probe."""
        s = self.slo
        self.stats.probes += 1
        h = min(s.probe_horizon, int(self.ts.shape[0]))
        ts_p = self.ts[:h]
        yp = y0s[: s.probe_fleet]
        tp = None if thetas is None else thetas[: s.probe_fleet]
        ref = np.asarray(self._golden.rollout_batch(self.params, yp, ts_p,
                                                    tp))
        scale = float(np.max(np.abs(ref))) + 1e-9
        prev, chosen = self._active, len(self._tiers) - 1
        for i, (name, tier) in enumerate(self._tiers[:-1]):
            out = np.asarray(tier.rollout_batch(self.params, yp, ts_p, tp))
            err = float(np.max(np.abs(out - ref))) / scale
            self.stats.probe_errors[name] = err
            if np.isfinite(err) and err <= s.max_rel_error:
                chosen = i
                break
        if chosen > prev:
            self.stats.probe_demotions += 1
        elif chosen < prev:
            self.stats.probe_recoveries += 1
        self._active = chosen

    # -- serving -----------------------------------------------------------
    def serve(self, y0s: jax.Array,
              drive_params: Optional[jax.Array] = None) -> jax.Array:
        """Roll out one request batch -> (N, T+1, D) trajectories.

        With an armed SLO the batch is served from the healthiest tier
        (see class docstring) and retried down the chain if its output
        is non-finite; raises ``RuntimeError`` only if even the digital
        tier returns non-finite values."""
        y0s = jnp.asarray(y0s)
        if drive_params is not None:
            drive_params = jnp.asarray(drive_params)
        validate_fleet_request("FleetServer.serve", y0s=y0s,
                               drive_params=drive_params)
        n = y0s.shape[0]
        y0s_p, dp_p, _ = pad_fleet_inputs(y0s, drive_params, self.n_shards)
        place = fleet_input_shardings(self.mesh, {"y": y0s_p})["y"]
        y0s_p = jax.device_put(y0s_p, place)
        if dp_p is not None:
            dp_p = jax.device_put(
                dp_p, fleet_input_shardings(self.mesh, {"d": dp_p})["d"])

        s = self.slo
        if s is None:
            self.stats.requests += 1
            out = self._rollout(self.params, y0s_p, dp_p)[:n]
            self.stats.served_by["primary"] = (
                self.stats.served_by.get("primary", 0) + 1)
            return out

        if len(self._tiers) > 1 and self.stats.requests % s.probe_every == 0:
            self._probe(y0s, drive_params)
        self.stats.requests += 1

        first = self._active
        last = min(first + s.max_retries, len(self._tiers) - 1)
        for i in range(first, last + 1):
            name = self._tiers[i][0]
            if i > first:
                self.stats.retries += 1
            t0 = time.perf_counter()
            out = jax.block_until_ready(
                self._rollouts[i](self.params, y0s_p, dp_p))[:n]
            if (s.timeout_s is not None
                    and time.perf_counter() - t0 > s.timeout_s):
                self.stats.timeouts += 1
            if bool(jnp.isfinite(out).all()):
                if i > first:
                    self.stats.nan_rescues += 1
                self.stats.served_by[name] = (
                    self.stats.served_by.get(name, 0) + 1)
                return out
        raise RuntimeError(
            "FleetServer: every fallback tier (including digital) "
            "returned non-finite trajectories — the request itself is "
            "pathological, not the substrate")


def serve_fleet(ckpt_dir: str, fleet, ts, requests: Iterable[Request], *,
                step: Optional[int] = None, mesh=None,
                params_template: Optional[Pytree] = None,
                init_key: Optional[jax.Array] = None
                ) -> Iterator[jax.Array]:
    """End-to-end serving pipeline over a stream of request batches.

    checkpoint load (:func:`repro.train.checkpoint.load_twin`) ->
    weights replicated onto the twin mesh once (:class:`FleetServer`) ->
    each request batch padded, sharded, rolled out -> trajectories
    yielded in order.

    ``requests`` yields either ``y0s`` arrays (autonomous fleets) or
    ``(y0s, drive_params)`` tuples (driven fleets).  ``params_template``
    gives the weight pytree structure for the restore; by default it is
    built with ``fleet.twin.init`` (``init_key`` seeds it — structure
    and shapes are all that matter, the values are overwritten).
    """
    if params_template is None:
        key = init_key if init_key is not None else jax.random.PRNGKey(0)
        params_template = fleet.twin.init(key)
    params = ckpt_lib.load_twin(ckpt_dir, params_template, step=step)
    server = FleetServer(fleet, params, ts, mesh=mesh)
    for req in requests:
        y0s, thetas = req if isinstance(req, tuple) else (req, None)
        yield server.serve(y0s, thetas)


# ---------------------------------------------------------------------------
# Streaming stateful serving: continuous batching over a resident population
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamRequest:
    """One queued streaming request: advance ``twin_id`` by ``horizon``
    RK4 steps from its carried state.  ``seq`` is the server-assigned
    arrival index (global FIFO order); ``remaining`` counts the steps
    still unserved (requests longer than the server's window are split
    across batches through the chunk-carry mechanism).  ``deadline`` is
    the latest virtual time the request may still be *started* —
    assembly drops stale requests (counted ``expired``); a request that
    has begun being served always runs to completion."""
    seq: int
    twin_id: Any
    horizon: int
    remaining: int
    t_arrival: float = 0.0
    deadline: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class Completed:
    """A finished request: ``trajectory`` is the (horizon+1, D) host
    array with row 0 the state the request started from; ``tier`` names
    the substrate that served the final window."""
    seq: int
    twin_id: Any
    trajectory: np.ndarray
    start_step: int
    tier: str
    t_arrival: float
    t_done: float


@dataclasses.dataclass
class StreamStats:
    """Continuous-batching counters; conservation invariant (checked by
    ``tests/traffic.py``): every submitted request lands in exactly one
    terminal bucket — ``enqueued == served + failed + shed + expired +
    quarantined + pending``."""
    enqueued: int = 0
    served: int = 0
    failed: int = 0
    shed: int = 0            # load-shedding victims (bounded queue)
    expired: int = 0         # deadline passed before assembly
    quarantined: int = 0     # poison requests parked with a diagnostic
    batches: int = 0
    twin_steps: int = 0      # real (unpadded) RK4 steps served
    padded_steps: int = 0    # ragged-horizon + batch padding overhead
    splits: int = 0          # requests split across serving windows

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Quarantined:
    """A poison request, parked instead of served: even the digital tier
    produced non-finite output for its batch.  ``reason`` records what
    every tier said — the diagnostic an operator starts from.  The
    twin's carried state is untouched."""
    seq: int
    twin_id: Any
    horizon: int
    remaining: int
    t_arrival: float
    reason: str


@dataclasses.dataclass
class ServerStats:
    """The one structured observability snapshot
    (:meth:`StreamingFleetServer.stats`): continuous-batching counters,
    degradation-machinery counters, and the state store's paging
    counters under a single ``as_dict`` schema — what the benches and
    the traffic invariant checkers consume."""
    stream: StreamStats
    serving: ServingStats
    store: StoreStats

    def as_dict(self) -> dict:
        return {"stream": self.stream.as_dict(),
                "serving": self.serving.as_dict(),
                "store": self.store.as_dict()}


class StreamingFleetServer:
    """Continuous batching for a resident twin population.

    Where :class:`FleetServer` rolls fixed request batches from t0, this
    server keeps per-twin ODE state alive BETWEEN requests: a stream of
    sensor windows (``submit``) feeds a queue; each ``pump`` assembles
    the longest admissible batch (one in-flight request per twin — a
    twin's next window consumes its previous one's end state), fetches
    the carried states from the :class:`TwinStateStore` (host-paged, LRU
    — the population may exceed the hot slab), coalesces the ragged
    horizons into ONE fused-kernel launch padded to the batch's widest
    window, then scatters the end states back and advances each twin's
    global step counter.

    Determinism contract (``docs/serving.md``): every time value any
    twin ever sees is the canonical float64 grid ``t0 + dt*k`` rounded
    to f32 once, keyed by the twin's own global step ``k`` — so the
    trajectory a twin accumulates over any sequence of windows is
    bit-identical (f32 substrates) to one uninterrupted rollout, no
    matter how the scheduler batched, split, or paged it.  Requests
    longer than ``max_window`` steps are split across pumps through the
    same chunk-carry path.

    Compiled-shape discipline: batches are padded to ``max_batch`` rows
    and window lengths quantised up to ``horizon_quantum`` multiples
    (capped at ``max_window``), so each serving tier compiles one
    program per window length instead of one per batch composition.

    Passing an :class:`ServingSLO` arms the same degradation machinery
    as :class:`FleetServer`: the :func:`fallback_chain` tiers are
    programmed once at construction, a golden window probe re-picks the
    healthiest tier every ``probe_every`` batches, and a batch whose
    trajectories come back non-finite is retried down the chain; a
    request that even the digital tier cannot serve is quarantined with
    a per-tier diagnostic (its carried state is left untouched) instead
    of killing the stream or looping the fallback chain.

    Admission control: ``max_queue`` bounds the request queue; an
    arrival past the bound is load-shed per ``shed_policy`` —
    ``"reject_new"`` (the arrival itself is refused, ``submit`` returns
    ``None``) or ``"drop_oldest"`` (the submitting twin's oldest
    still-unstarted request is dropped to make room).  Per-request
    ``deadline``s are checked at assembly time; transient tier
    exceptions are retried ``transient_retries`` times with exponential
    backoff before falling down the chain.

    Durability: pass ``durability_dir`` to arm the write-ahead journal +
    periodic snapshots (:mod:`repro.launch.journal`) — every externally
    visible event is fsync'd before it is acknowledged, and
    :meth:`recover` rebuilds a bitwise-identical (f32) server from disk
    after a crash at ANY point.  Twin ids must be JSON-serialisable
    scalars when durability is armed.
    """

    def __init__(self, fleet, params, *, dt: float, t0: float = 0.0,
                 hot_capacity: int = 64, max_batch: int = 32,
                 max_window: int = 64, horizon_quantum: int = 8,
                 slo: Optional[ServingSLO] = None,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject_new",
                 transient_retries: int = 2,
                 backoff_base_s: float = 0.01,
                 durability_dir: Optional[str] = None,
                 snapshot_every: int = 16, snapshot_keep: int = 3,
                 fsync: bool = True):
        if dt <= 0:
            raise ValueError(f"StreamingFleetServer: dt must be > 0, "
                             f"got {dt}")
        if not 1 <= max_batch <= hot_capacity:
            raise ValueError(
                f"StreamingFleetServer: need 1 <= max_batch <= "
                f"hot_capacity, got max_batch={max_batch}, "
                f"hot_capacity={hot_capacity}")
        if max_window < 1 or horizon_quantum < 1:
            raise ValueError(
                "StreamingFleetServer: max_window and horizon_quantum "
                "must be >= 1")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"StreamingFleetServer: max_queue must be "
                             f">= 1 or None, got {max_queue}")
        if shed_policy not in ("reject_new", "drop_oldest"):
            raise ValueError(
                f"StreamingFleetServer: shed_policy must be 'reject_new'"
                f" or 'drop_oldest', got {shed_policy!r}")
        if transient_retries < 0 or backoff_base_s < 0:
            raise ValueError(
                "StreamingFleetServer: transient_retries and "
                "backoff_base_s must be >= 0")
        if snapshot_every < 0 or snapshot_keep < 1:
            raise ValueError(
                "StreamingFleetServer: need snapshot_every >= 0 "
                "(0 = manual snapshots only) and snapshot_keep >= 1")
        self.fleet = fleet
        self.params = params
        self.dt = float(dt)
        self.t0 = float(t0)
        self.max_batch = int(max_batch)
        self.max_window = int(max_window)
        self.horizon_quantum = int(horizon_quantum)
        self.slo = slo
        self.max_queue = None if max_queue is None else int(max_queue)
        self.shed_policy = shed_policy
        self.transient_retries = int(transient_retries)
        self.backoff_base_s = float(backoff_base_s)
        self.snapshot_every = int(snapshot_every)
        self.snapshot_keep = int(snapshot_keep)
        self.store = TwinStateStore(fleet.twin.state_dim, hot_capacity)
        self.stream_stats = StreamStats()
        self.serving_stats = ServingStats()
        self.quarantine: dict = {}             # seq -> Quarantined
        self._audit = os.environ.get("REPRO_STORE_AUDIT", "") == "1"
        self._journal: Optional[journal_lib.Journal] = None
        self._serve_dir: Optional[str] = None
        self._pumps_since_snapshot = 0
        self._tiers = (fallback_chain(fleet) if slo is not None else
                       [(getattr(resolve_backend(fleet.backend), "name",
                                 "primary"), fleet)])
        self._active = 0
        # Program every tier ONCE (the "write the crossbars" step); the
        # jitted window programs are built lazily per (tier, H) shape.
        self._backends, self._states = [], []
        for _, tier_fleet in self._tiers:
            backend = resolve_backend(tier_fleet.backend)
            node = tier_fleet.twin.node
            self._backends.append(backend)
            self._states.append(backend.program(node.field, params))
        self._window_fns: dict = {}            # (tier_idx, H) -> jit fn
        self._queue: list = []                 # FIFO of StreamRequest
        self._partial: dict = {}               # seq -> list of row blocks
        self._seq = 0
        if durability_dir is not None:
            self._attach_durability(durability_dir, fsync=fsync,
                                    resume=False)

    # -- population / ingest -------------------------------------------------
    @property
    def active_tier(self) -> str:
        return self._tiers[self._active][0]

    @property
    def pending(self) -> int:
        return len(self._queue)

    def stats(self) -> ServerStats:
        """One structured observability snapshot: stream + serving +
        store counters (copies — mutating the snapshot cannot corrupt
        the live counters)."""
        return ServerStats(stream=copy.deepcopy(self.stream_stats),
                           serving=copy.deepcopy(self.serving_stats),
                           store=copy.deepcopy(self.store.stats))

    def register_twin(self, twin_id, y0, *, theta=None) -> None:
        """Admit a twin with its initial condition (and per-twin drive
        parameters for driven fleets) — host-side, no device traffic.
        Rejects non-finite / mis-shaped ``y0`` and ``theta`` with errors
        naming the argument (the store checks ``y0``)."""
        if (theta is None) != (self.fleet.drive_family is None):
            raise ValueError(
                "register_twin: theta must be given exactly when the "
                "fleet has a drive_family")
        if theta is not None:
            th = np.asarray(theta)
            if not np.issubdtype(th.dtype, np.floating):
                raise ValueError(
                    f"register_twin: theta has non-floating dtype "
                    f"{th.dtype}")
            if not np.isfinite(th).all():
                raise ValueError(
                    f"register_twin: theta for twin {twin_id!r} contains "
                    f"non-finite (NaN/Inf) values")
        self.store.register(twin_id, y0, theta=theta)
        if self._journal is not None:
            rec = {"t": "register", "id": twin_id,
                   "y0": journal_lib.json_floats(
                       self.store.peek(twin_id)[0])}
            if theta is not None:
                th32 = np.asarray(theta, np.float32)
                rec["theta"] = journal_lib.json_floats(th32)
                rec["tshape"] = list(th32.shape)
            self._journal.append(rec)

    def submit(self, twin_id, horizon: int, t_arrival: float = 0.0, *,
               deadline: Optional[float] = None) -> Optional[int]:
        """Enqueue a request to advance ``twin_id`` by ``horizon`` RK4
        steps; returns its ``seq``, or ``None`` if the bounded queue
        shed it (``shed_policy="reject_new"``).  Per-twin FIFO order is
        guaranteed; cross-twin order is whatever batching finds
        profitable.  ``deadline`` (virtual time, same clock as
        ``t_arrival``/``pump(now)``) is the latest the request may still
        be started.  Malformed arguments raise ``ValueError`` naming the
        offender at the front door — nothing invalid reaches a batch."""
        if twin_id not in self.store:
            raise KeyError(f"submit: twin {twin_id!r} is not registered")
        if isinstance(horizon, bool) or not isinstance(
                horizon, (int, np.integer)):
            raise ValueError(
                f"submit: horizon must be an integer step count, got "
                f"{type(horizon).__name__} {horizon!r}")
        horizon = int(horizon)
        if horizon < 1:
            raise ValueError(f"submit: horizon must be >= 1, got {horizon}")
        t_arrival = float(t_arrival)
        if not np.isfinite(t_arrival):
            raise ValueError(
                f"submit: t_arrival must be finite, got {t_arrival}")
        if deadline is not None:
            deadline = float(deadline)
            if not np.isfinite(deadline):
                raise ValueError(
                    f"submit: deadline must be finite (omit it for "
                    f"no deadline), got {deadline}")
            if deadline < t_arrival:
                raise ValueError(
                    f"submit: deadline {deadline} precedes t_arrival "
                    f"{t_arrival} — the request is dead on arrival")
        seq = self._seq
        self._seq += 1
        self.stream_stats.enqueued += 1
        jrec = {"t": "submit", "seq": seq, "id": twin_id, "h": horizon,
                "ta": t_arrival, "dl": deadline}
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            victim = None
            if self.shed_policy == "drop_oldest":
                # oldest still-unstarted request of THIS twin — a
                # half-served continuation is never shed (its work is
                # already paid for and its state already advanced).
                victim = next(
                    (r for r in self._queue if r.twin_id == twin_id
                     and r.remaining == r.horizon), None)
            if victim is None:
                # reject_new policy, or drop_oldest with nothing of this
                # twin's to drop: the newcomer itself is shed.
                self.stream_stats.shed += 1
                if self._journal is not None:
                    self._journal.append({**jrec, "shed": True})
                return None
            self._queue.remove(victim)
            self.stream_stats.shed += 1
            if self._journal is not None:
                self._journal.append({"t": "shed", "seq": victim.seq},
                                     sync=False)
        req = StreamRequest(seq=seq, twin_id=twin_id, horizon=horizon,
                            remaining=horizon, t_arrival=t_arrival,
                            deadline=deadline)
        self._queue.append(req)
        if self._journal is not None:
            self._journal.append(jrec)
        return req.seq

    # -- batch assembly ------------------------------------------------------
    def _assemble(self):
        """Pop the next batch: scan the queue in FIFO order, taking the
        FIRST pending request of each twin (later requests for the same
        twin must wait — their start state does not exist yet) up to
        ``max_batch``.  Returns the requests and the padded window
        length H."""
        picked, skipped, seen = [], [], set()
        for req in self._queue:
            if req.twin_id in seen or len(picked) == self.max_batch:
                skipped.append(req)
            else:
                seen.add(req.twin_id)
                picked.append(req)
        self._queue = skipped
        if not picked:
            return [], 0
        h_max = min(self.max_window,
                    max(r.remaining for r in picked))
        q = self.horizon_quantum
        H = min(self.max_window, -(-h_max // q) * q)
        return picked, H

    # -- window programs -----------------------------------------------------
    def _window_fn(self, tier_idx: int, H: int):
        """The jitted fixed-shape window solve of one tier: carried
        states (B, D) + canonical time/drive windows in, (B, H+1, D)
        trajectories out.  Fused tiers take the pre-sampled per-twin
        half-step drive slabs; digital/analogue tiers take the per-twin
        time grids (odeint consumes time VALUES, so traced per-row
        grids keep bitwise parity with the canonical windows)."""
        key = (tier_idx, H)
        fn = self._window_fns.get(key)
        if fn is not None:
            return fn
        backend = self._backends[tier_idx]
        state = self._states[tier_idx]
        _, tier_fleet = self._tiers[tier_idx]
        node = tier_fleet.twin.node
        drive_family = tier_fleet.drive_family
        if isinstance(backend, FusedPallasBackend):
            from repro.kernels.fused_ode_mlp import pad_fleet_to_tile

            def run(ys, uh):
                y0s, uh_p, bt, B = pad_fleet_to_tile(ys, uh,
                                                     backend.batch_tile)
                traj = backend._solve(state, y0s, uh_p, self.dt, bt,
                                      "stopgrad", None)
                return jnp.transpose(traj[:, :B], (1, 0, 2))
        else:
            kw = node._solver_kw()
            if drive_family is None:
                def run(ys, tss):
                    return jax.vmap(lambda y, ts: backend.rollout(
                        state, y, ts, **kw))(ys, tss)
            else:
                def run(ys, tss, thetas):
                    def single(y, ts, th):
                        st = _with_drive(state,
                                         lambda t: drive_family(t, th))
                        return backend.rollout(st, y, ts, **kw)
                    return jax.vmap(single)(ys, tss, thetas)
        fn = jax.jit(run)
        self._window_fns[key] = fn
        return fn

    def _run_tier(self, tier_idx: int, ys, starts: np.ndarray, thetas,
                  H: int):
        """Serve one assembled window on one tier.  The canonical
        time/drive windows are built HOST-side (concrete float64 grid —
        the determinism contract) and only the solve is jitted."""
        from repro.kernels import ops
        backend = self._backends[tier_idx]
        state = self._states[tier_idx]
        drive_family = self._tiers[tier_idx][1].drive_family
        fn = self._window_fn(tier_idx, H)
        if isinstance(backend, FusedPallasBackend):
            uh = backend._u_half_window(state, self.t0, self.dt, H,
                                        starts, drive_family, thetas)
            if uh.ndim == 2 and uh.shape[-1] > 0:
                uh = jnp.broadcast_to(uh, (ys.shape[0],) + uh.shape)
            return fn(ys, uh)
        tss = ops.window_times(self.t0, self.dt, H, starts)
        if drive_family is None:
            return fn(ys, tss)
        return fn(ys, tss, thetas)

    def _probe(self, ys, starts, thetas, H: int) -> None:
        """Golden-window health check (the streaming analogue of
        ``FleetServer._probe``): roll the batch's first ``probe_fleet``
        rows over a short window on every non-digital tier, compare to
        the digital reference, activate the healthiest tier that meets
        the SLO."""
        s = self.slo
        self.serving_stats.probes += 1
        nf = min(s.probe_fleet, int(ys.shape[0]))
        h = min(s.probe_horizon - 1, H)
        yp, sp = ys[:nf], starts[:nf]
        tp = None if thetas is None else thetas[:nf]
        ref_backend = self._backends[-1]      # digital tier, by chain
        ref_state = self._states[-1]
        drive_family = self._tiers[-1][1].drive_family
        ref = np.asarray(ref_backend.rollout_batch_resumed(
            ref_state, yp, dt=self.dt, num_steps=h, t0=self.t0,
            start_steps=sp, drive_family=drive_family, drive_params=tp))
        scale = float(np.max(np.abs(ref))) + 1e-9
        prev, chosen = self._active, len(self._tiers) - 1
        for i, (name, tier_fleet) in enumerate(self._tiers[:-1]):
            out = np.asarray(self._backends[i].rollout_batch_resumed(
                self._states[i], yp, dt=self.dt, num_steps=h, t0=self.t0,
                start_steps=sp,
                drive_family=tier_fleet.drive_family, drive_params=tp))
            err = float(np.max(np.abs(out - ref))) / scale
            self.serving_stats.probe_errors[name] = err
            if np.isfinite(err) and err <= s.max_rel_error:
                chosen = i
                break
        if chosen > prev:
            self.serving_stats.probe_demotions += 1
        elif chosen < prev:
            self.serving_stats.probe_recoveries += 1
        self._active = chosen

    # -- the serving loop ----------------------------------------------------
    def _fetch_padded(self, ids):
        """Fetch carried state for a batch and pad it to the fixed
        compiled width (replicating the last row keeps padding
        in-distribution; results are sliced back).  Returns
        ``(ys, starts, thetas, n)`` with ``n`` the real row count."""
        ys, starts, thetas = self.store.fetch(ids)
        n = len(ids)
        pad = self.max_batch - n
        if pad:
            ys = jnp.concatenate(
                [ys, jnp.broadcast_to(ys[-1:], (pad,) + ys.shape[1:])])
            starts = np.concatenate([starts, np.repeat(starts[-1:], pad)])
            if thetas is not None:
                thetas = jnp.concatenate(
                    [thetas,
                     jnp.broadcast_to(thetas[-1:],
                                      (pad,) + thetas.shape[1:])])
        return ys, starts, thetas, n

    def _expire(self, now: float) -> None:
        """Deadline check at assembly time: drop queued requests whose
        deadline has passed before they were ever started.  A split
        continuation (``remaining < horizon``) is exempt — its state has
        already advanced, so dropping it would tear the twin's
        trajectory; it runs to completion."""
        stale = [r for r in self._queue
                 if r.deadline is not None and r.remaining == r.horizon
                 and now > r.deadline]
        if not stale:
            return
        dead = {r.seq for r in stale}
        self._queue = [r for r in self._queue if r.seq not in dead]
        self.stream_stats.expired += len(stale)
        if self._journal is not None:
            self._journal.append({"t": "expire", "seqs": sorted(dead)},
                                 sync=False)

    def _attempt_tier(self, tier_idx: int, ys, starts, thetas, H: int):
        """One tier's solve with retry-with-exponential-backoff for
        transient failures (device hiccups, preemptions — anything that
        raises an ``Exception``).  Injected ``SimulatedCrash``es are
        ``BaseException`` and pass straight through: a crash is not a
        retryable fault.  Raises the last exception when retries are
        exhausted."""
        s = self.slo
        delay = self.backoff_base_s
        last_exc: Optional[Exception] = None
        for attempt in range(self.transient_retries + 1):
            if attempt:
                time.sleep(delay)
                delay *= 2.0
                self.serving_stats.transient_retries += 1
            try:
                chaos.fault_point("pump:run_tier")
                t_start = time.perf_counter()
                out = jax.block_until_ready(
                    self._run_tier(tier_idx, ys, starts, thetas, H))
                if (s is not None and s.timeout_s is not None
                        and time.perf_counter() - t_start > s.timeout_s):
                    self.serving_stats.timeouts += 1
                return out
            except Exception as e:
                last_exc = e
        raise last_exc

    def _solve_batch(self, ys, starts, thetas, H: int, n: int):
        """Run the fallback chain over one assembled window.  Returns
        ``(traj, tier_idx, diags)`` — ``traj is None`` means even the
        final (digital) tier produced non-finite output, with ``diags``
        naming what each tier said.  A tier whose attempts all raise
        transiently falls through to the next tier; the FINAL tier
        exhausting its retries re-raises (that is infrastructure
        failure, not a poison request)."""
        s = self.slo
        first = self._active
        last = (len(self._tiers) - 1 if s is None
                else min(first + s.max_retries, len(self._tiers) - 1))
        diags = []
        for i in range(first, last + 1):
            name = self._tiers[i][0]
            if i > first:
                self.serving_stats.retries += 1
            try:
                out = self._attempt_tier(i, ys, starts, thetas, H)
            except Exception as e:
                if i == last:
                    raise
                diags.append(f"{name}: raised {type(e).__name__}: {e}")
                continue
            if bool(jnp.isfinite(out[:n]).all()):
                if i > first:
                    self.serving_stats.nan_rescues += 1
                return out, i, diags
            diags.append(f"{name}: non-finite output")
        return None, None, diags

    def _commit_batch(self, picked, ids, traj, starts, n: int, H: int,
                      tier_idx: int, now: float) -> list:
        """Apply one solved window: scatter end states into the store,
        advance step counters, stitch/stream partial trajectories, and
        re-queue split continuations.  Shared verbatim between the live
        pump and journal replay — which is what makes replay reproduce
        the crash-free state transition exactly."""
        tier_name = self._tiers[tier_idx][0]
        traj_h = np.asarray(traj[:n], np.float32)
        served = [min(r.remaining, H) for r in picked]
        end_states = traj[jnp.arange(n), jnp.asarray(served)]
        self.store.commit(ids, end_states,
                          starts[:n] + np.asarray(served))
        chaos.kill_point("pump:post_commit")
        self.stream_stats.twin_steps += int(sum(served))
        self.stream_stats.padded_steps += int(
            self.max_batch * H - sum(served))
        self.serving_stats.requests += 1
        self.serving_stats.served_by[tier_name] = (
            self.serving_stats.served_by.get(tier_name, 0) + 1)
        done = []
        for i, req in enumerate(picked):
            h = served[i]
            rows = traj_h[i, : h + 1]
            blocks = self._partial.setdefault(req.seq, [])
            blocks.append(rows if not blocks else rows[1:])
            if h < req.remaining:
                # Long request: re-queue the remainder at the FRONT so
                # it stays ahead of the twin's later requests.
                self.stream_stats.splits += 1
                self._queue.insert(0, dataclasses.replace(
                    req, remaining=req.remaining - h))
                continue
            full = np.concatenate(self._partial.pop(req.seq), axis=0)
            done.append(Completed(
                seq=req.seq, twin_id=req.twin_id, trajectory=full,
                start_step=int(starts[i]) - (req.horizon - h),
                tier=tier_name, t_arrival=req.t_arrival, t_done=now))
            self.stream_stats.served += 1
        return done

    def pump(self, now: float = 0.0) -> list:
        """Assemble and serve ONE batch; returns the list of
        :class:`Completed` requests it finished (possibly empty — a
        window that only partially serves long requests completes
        nothing).  Call repeatedly (``drain``) to empty the queue."""
        done = self._pump(now)
        if self._audit:
            self.store.check_invariants()
        if self._journal is not None and self.snapshot_every:
            self._pumps_since_snapshot += 1
            if self._pumps_since_snapshot >= self.snapshot_every:
                self.snapshot()
        return done

    def _pump(self, now: float) -> list:
        self._expire(now)
        picked, H = self._assemble()
        if not picked:
            if self._journal is not None:
                self._journal.sync()    # flush any expire records
            return []
        ids = [r.twin_id for r in picked]
        ys, starts, thetas, n = self._fetch_padded(ids)
        s = self.slo
        if (s is not None and len(self._tiers) > 1
                and self.stream_stats.batches % s.probe_every == 0):
            self._probe(ys[:n], starts[:n], None if thetas is None
                        else thetas[:n], H)
        self.stream_stats.batches += 1
        traj, tier_idx, diags = self._solve_batch(ys, starts, thetas, H, n)
        chaos.kill_point("pump:pre_commit")
        if traj is None:
            # Even the digital tier returned non-finite values: the
            # requests themselves are poison.  Park them with the
            # per-tier diagnostic; carried states stay untouched.
            reason = "; ".join(diags) or "non-finite on every tier"
            for req in picked:
                self.stream_stats.quarantined += 1
                self._partial.pop(req.seq, None)
                self.quarantine[req.seq] = Quarantined(
                    seq=req.seq, twin_id=req.twin_id, horizon=req.horizon,
                    remaining=req.remaining, t_arrival=req.t_arrival,
                    reason=reason)
            if self._journal is not None:
                self._journal.append(
                    {"t": "quarantine", "seqs": [r.seq for r in picked],
                     "reason": reason}, sync=False)
                self._journal.sync()
            return []
        done = self._commit_batch(picked, ids, traj, starts, n, H,
                                  tier_idx, now)
        if self._journal is not None:
            self._journal.append(
                {"t": "commit", "seqs": [r.seq for r in picked],
                 "tier": tier_idx, "H": H,
                 "served": [min(r.remaining, H) for r in picked],
                 "now": now}, sync=False)
            for c in done:
                self._journal.append({"t": "complete", "seq": c.seq},
                                     sync=False)
            self._journal.sync()
        return done

    # -- durability: journal, snapshots, crash recovery ----------------------
    def _config(self) -> dict:
        """Constructor arguments the journal header pins, so
        :meth:`recover` rebuilds a server with identical batching/
        shedding behaviour — replay determinism needs the same
        scheduler, not just the same records."""
        return {"dt": self.dt, "t0": self.t0,
                "hot_capacity": self.store.hot_capacity,
                "max_batch": self.max_batch,
                "max_window": self.max_window,
                "horizon_quantum": self.horizon_quantum,
                "max_queue": self.max_queue,
                "shed_policy": self.shed_policy,
                "transient_retries": self.transient_retries,
                "backoff_base_s": self.backoff_base_s,
                "snapshot_every": self.snapshot_every,
                "snapshot_keep": self.snapshot_keep}

    def _attach_durability(self, serve_dir: str, *, fsync: bool,
                           resume: bool) -> None:
        os.makedirs(serve_dir, exist_ok=True)
        jrnl = journal_lib.Journal(journal_lib.journal_path(serve_dir),
                                   fsync=fsync)
        if jrnl.lsn and not resume:
            jrnl.close()
            raise ValueError(
                f"StreamingFleetServer: {serve_dir!r} already holds a "
                f"journal with {jrnl.lsn} record(s) — use "
                f"StreamingFleetServer.recover() to resume it (a fresh "
                f"server writing over live state would fork history)")
        self._serve_dir = serve_dir
        self._journal = jrnl
        if jrnl.lsn == 0:
            jrnl.append({"t": "config",
                         "schema": journal_lib.JOURNAL_SCHEMA,
                         "cfg": self._config()})

    def snapshot(self) -> str:
        """Atomically publish a full-state snapshot covering every
        journal record so far: the store (hot slab flushed to host),
        the queue, in-flight partial trajectories, quarantine, and all
        counters.  Returns the snapshot path.  Called automatically
        every ``snapshot_every`` pumps; callable any time."""
        if self._journal is None:
            raise RuntimeError(
                "snapshot: durability is not armed — construct with "
                "durability_dir=")
        self._journal.sync()
        lsn = self._journal.lsn
        ids, ys, steps, thetas = self.store.export_state()
        arrays = {"store_ys": ys, "store_steps": steps}
        if thetas is not None:
            arrays["store_thetas"] = thetas
        for seq, blocks in self._partial.items():
            for i, b in enumerate(blocks):
                arrays[f"partial/{seq}/{i}"] = np.asarray(b, np.float32)
        extra = {
            "ids": list(ids),
            "seq": self._seq,
            "active": self._active,
            "queue": [[r.seq, r.twin_id, r.horizon, r.remaining,
                       r.t_arrival, r.deadline] for r in self._queue],
            "partial": {str(s): len(b) for s, b in self._partial.items()},
            "quarantine": [dataclasses.asdict(q)
                           for q in self.quarantine.values()],
            "stream_stats": self.stream_stats.as_dict(),
            "serving_stats": self.serving_stats.as_dict(),
            "store_stats": self.store.stats.as_dict(),
        }
        path = journal_lib.write_snapshot(self._serve_dir, lsn, arrays,
                                          extra, keep=self.snapshot_keep)
        self._pumps_since_snapshot = 0
        return path

    def _restore_snapshot(self, arrays: dict, extra: dict) -> None:
        ys, steps = arrays["store_ys"], arrays["store_steps"]
        thetas = arrays.get("store_thetas")
        for i, tid in enumerate(extra["ids"]):
            self.store.register(
                tid, ys[i], theta=None if thetas is None else thetas[i],
                step=int(steps[i]))
        self._seq = int(extra["seq"])
        self._active = int(extra["active"])
        self._queue = [
            StreamRequest(seq=q[0], twin_id=q[1], horizon=q[2],
                          remaining=q[3], t_arrival=q[4], deadline=q[5])
            for q in extra["queue"]]
        self._partial = {
            int(s): [arrays[f"partial/{s}/{i}"] for i in range(nb)]
            for s, nb in extra["partial"].items()}
        self.quarantine = {q["seq"]: Quarantined(**q)
                           for q in extra["quarantine"]}
        self.stream_stats = StreamStats(**extra["stream_stats"])
        self.serving_stats = ServingStats(**extra["serving_stats"])
        self.store.stats = StoreStats(**extra["store_stats"])

    def _drop_seqs(self, seqs) -> list:
        want = set(seqs)
        dropped = [r for r in self._queue if r.seq in want]
        if len(dropped) != len(want):
            have = {r.seq for r in dropped}
            raise ValueError(
                f"recover: journal references request seq(s) "
                f"{sorted(want - have)} that are not pending — the "
                f"journal is inconsistent beyond its torn tail")
        self._queue = [r for r in self._queue if r.seq not in want]
        return dropped

    def _replay(self, rec: dict) -> list:
        """Apply one journal record during recovery.  Decision records
        (register/submit/shed/expire/quarantine) are applied directly;
        ``commit`` records are re-EXECUTED through the recorded tier —
        the determinism contract makes the recompute bitwise-identical
        to the pre-crash execution.  Returns completions the replayed
        record (re)produces."""
        t = rec["t"]
        if t == "register":
            theta = None
            if "theta" in rec:
                theta = journal_lib.from_json_floats(rec["theta"],
                                                     rec["tshape"])
            self.store.register(
                rec["id"],
                journal_lib.from_json_floats(rec["y0"],
                                             (self.store.state_dim,)),
                theta=theta)
            return []
        if t == "submit":
            self.stream_stats.enqueued += 1
            self._seq = max(self._seq, rec["seq"] + 1)
            if rec.get("shed"):
                self.stream_stats.shed += 1
                return []
            self._queue.append(StreamRequest(
                seq=rec["seq"], twin_id=rec["id"], horizon=rec["h"],
                remaining=rec["h"], t_arrival=rec["ta"],
                deadline=rec["dl"]))
            return []
        if t == "shed":
            self._drop_seqs([rec["seq"]])
            self.stream_stats.shed += 1
            return []
        if t == "expire":
            self._drop_seqs(rec["seqs"])
            self.stream_stats.expired += len(rec["seqs"])
            return []
        if t == "quarantine":
            for req in self._drop_seqs(rec["seqs"]):
                self.stream_stats.quarantined += 1
                self._partial.pop(req.seq, None)
                self.quarantine[req.seq] = Quarantined(
                    seq=req.seq, twin_id=req.twin_id,
                    horizon=req.horizon, remaining=req.remaining,
                    t_arrival=req.t_arrival, reason=rec["reason"])
            return []
        if t == "commit":
            return self._replay_commit(rec)
        if t == "complete":
            return []                   # verified by recover()'s caller
        raise ValueError(f"recover: unknown journal record type {t!r}")

    def _replay_commit(self, rec: dict) -> list:
        by_seq = {r.seq: r for r in self._queue}
        missing = [s for s in rec["seqs"] if s not in by_seq]
        if missing:
            raise ValueError(
                f"recover: commit record references seq(s) {missing} "
                f"that are not pending — the journal is inconsistent")
        picked = [by_seq[s] for s in rec["seqs"]]
        taken = set(rec["seqs"])
        self._queue = [r for r in self._queue if r.seq not in taken]
        ids = [r.twin_id for r in picked]
        ys, starts, thetas, n = self._fetch_padded(ids)
        H, tier_idx = int(rec["H"]), int(rec["tier"])
        served = [min(r.remaining, H) for r in picked]
        if served != [int(x) for x in rec["served"]]:
            raise ValueError(
                "recover: replayed window disagrees with the journalled "
                "served step counts — scheduler state diverged")
        self.stream_stats.batches += 1
        traj = jax.block_until_ready(
            self._run_tier(tier_idx, ys, starts, thetas, H))
        if not bool(jnp.isfinite(traj[:n]).all()):
            raise ValueError(
                "recover: a journalled commit re-executed to non-finite "
                "output — the substrate changed since the crash")
        return self._commit_batch(picked, ids, traj, starts, n, H,
                                  tier_idx, float(rec.get("now", 0.0)))

    @classmethod
    def recover(cls, serve_dir: str, fleet, params, *,
                slo: Optional[ServingSLO] = None, fsync: bool = True):
        """Rebuild a crashed server from its serving directory.

        Loads the newest loadable snapshot (damaged ones are skipped for
        older siblings — the atomic publish protocol guarantees any
        published snapshot is internally consistent), replays the
        journal suffix deterministically through the recorded tiers, and
        reopens the journal (torn tail truncated) so serving continues
        appending where the crash left off.

        Returns ``(server, redelivered)``: ``redelivered`` holds the
        :class:`Completed` results regenerated by replayed commits —
        results whose original delivery may or may not have reached the
        caller before the crash (at-least-once delivery; state advance
        is exactly-once).  The server's store, queue, partials and
        counters are bitwise-equal (f32) to a crash-free run's.
        """
        records, _, _ = journal_lib.read_journal(
            journal_lib.journal_path(serve_dir))
        if not records or records[0].get("t") != "config":
            raise ValueError(
                f"recover: {serve_dir!r} has no usable journal (missing "
                f"or torn config header) — nothing to recover")
        if records[0].get("schema") != journal_lib.JOURNAL_SCHEMA:
            raise ValueError(
                f"recover: journal schema {records[0].get('schema')!r} "
                f"!= supported {journal_lib.JOURNAL_SCHEMA}")
        server = cls(fleet, params, slo=slo, **records[0]["cfg"])
        snap = journal_lib.load_latest_snapshot(serve_dir)
        start = 1                       # past the config header
        if snap is not None:
            lsn, arrays, extra = snap
            server._restore_snapshot(arrays, extra)
            start = lsn
        redelivered, completed_seqs = [], set()
        for rec in records[start:]:
            out = server._replay(rec)
            completed_seqs.update(c.seq for c in out)
            redelivered.extend(out)
            if rec["t"] == "complete" and rec["seq"] not in completed_seqs:
                raise ValueError(
                    f"recover: journal records completion of seq "
                    f"{rec['seq']} that replay never produced — the "
                    f"journal is inconsistent beyond its torn tail")
        server._attach_durability(serve_dir, fsync=fsync, resume=True)
        return server, redelivered

    def drain(self, now: float = 0.0) -> list:
        """Pump until the queue is empty; returns all completions.
        Safe with quarantined requests pending (they are already out of
        the queue) and immediately after :meth:`recover` (replay leaves
        the queue exactly as the crash-free schedule would have)."""
        done = []
        while self._queue:
            done.extend(self.pump(now))
        return done

    def serve_trace(self, trace, *, y0_of, theta_of=None,
                    auto_register: bool = True, start: int = 0,
                    sink: Optional[list] = None) -> list:
        """Replay a recorded arrival trace (see
        :mod:`repro.launch.traffic`) through the streaming loop.

        Arrivals are ingested in timestamp order; a batch is pumped
        whenever the queue can fill one, and the tail is drained at the
        end.  ``y0_of(twin_id)`` (and ``theta_of(twin_id)`` for driven
        fleets) lazily registers first-contact twins.  Returns the
        completions in service order — the deterministic-schedule
        replay the stress tests assert invariants over.

        ``start`` skips the first ``start`` arrivals — the crash-
        recovery resume idiom: a recovered server already holds every
        arrival its journal acknowledged, so the caller re-feeds the
        trace from ``server.stream_stats.enqueued`` onward (an arrival
        whose submit never reached the journal is simply re-submitted —
        the client-retry contract).

        ``sink``: optional list that completions are ALSO appended to as
        they are delivered.  A consumer that may die mid-trace (the
        chaos harness, any real streaming client) passes one so the
        completions delivered before the death are not lost to the
        raised exception — completions already committed to a snapshot
        are deliberately NOT redelivered by recovery.
        """
        done = [] if sink is None else sink
        for arrival in trace[start:]:
            if auto_register and arrival.twin_id not in self.store:
                theta = None if theta_of is None else theta_of(
                    arrival.twin_id)
                self.register_twin(arrival.twin_id, y0_of(arrival.twin_id),
                                   theta=theta)
            self.submit(arrival.twin_id, arrival.horizon,
                        t_arrival=arrival.time,
                        deadline=getattr(arrival, "deadline", None))
            if self.pending >= self.max_batch:
                done.extend(self.pump(now=arrival.time))
        t_end = trace[-1].time if trace else 0.0
        done.extend(self.drain(now=t_end))
        return done


# ---------------------------------------------------------------------------
# CLI smoke: the Lorenz96 fleet workload on whatever devices exist
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Serve a Lorenz96 twin fleet over the local twin mesh")
    ap.add_argument("--fleet", type=int, default=256,
                    help="assets per request batch")
    ap.add_argument("--horizon", type=int, default=100,
                    help="RK4 steps per rollout")
    ap.add_argument("--batches", type=int, default=2,
                    help="request batches to stream")
    ap.add_argument("--backend", default="fused_pallas",
                    choices=["digital", "fused_pallas"])
    ap.add_argument("--precision", default=None,
                    choices=["f32", "bf16", "bf16_f32acc"],
                    help="fused-substrate mixed-precision policy "
                         "(default: auto — bf16_f32acc on TPU, f32 "
                         "elsewhere)")
    ap.add_argument("--ckpt-dir", default="",
                    help="trained-twin checkpoint (default: untrained "
                         "weights saved to a temp dir — substrate smoke)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.train import recipes
    enable_compile_cache()
    backend = args.backend
    if args.precision is not None:
        if backend != "fused_pallas":
            ap.error("--precision is a fused-substrate policy; it does "
                     "not apply to --backend digital")
        from repro.core.backends import FusedPallasBackend
        backend = FusedPallasBackend(precision=args.precision)
    fleet = recipes.make_l96_fleet(backend=backend)
    ts = recipes.l96_fleet_ts(horizon=args.horizon)
    mesh = make_twin_mesh()
    print(f"mesh: {twin_shard_count(mesh)} device(s) on axis '{TWIN_AXIS}'; "
          f"backend {args.backend} precision "
          f"{'n/a' if args.backend == 'digital' else args.precision or 'auto'}")

    ckpt_dir = args.ckpt_dir
    if not ckpt_dir:
        ckpt_dir = tempfile.mkdtemp(prefix="l96_fleet_ckpt_")
        params = fleet.twin.init(jax.random.PRNGKey(0))
        ckpt_lib.save_twin(ckpt_dir, params)
        print(f"no --ckpt-dir: saved untrained twin to {ckpt_dir}")

    reqs = list(recipes.l96_fleet_requests(fleet_size=args.fleet,
                                           num_batches=args.batches))
    t0 = time.perf_counter()
    outs = []
    for i, traj in enumerate(serve_fleet(ckpt_dir, fleet, ts, reqs,
                                         mesh=mesh)):
        traj = jax.block_until_ready(traj)
        outs.append(traj)
        dt_s = time.perf_counter() - t0
        rate = (i + 1) * args.fleet * args.horizon / dt_s
        print(f"  batch {i}: {tuple(traj.shape)} trajectories "
              f"({rate:,.0f} twin-steps/s cumulative)")
    assert all(bool(jnp.isfinite(o).all()) for o in outs)
    print(f"served {args.batches} x {args.fleet} twins x {args.horizon} "
          f"steps in {time.perf_counter() - t0:.2f}s")
    return outs


if __name__ == "__main__":
    main()

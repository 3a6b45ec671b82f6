"""What the streaming server tells an operator about itself: the program's
profiler spans inside each pump, the counters they are read with, and
the names of the Pallas kernels in a trace.

The spans are ``jax.profiler.TraceAnnotation`` blocks, so they land in
the profiler's own trace on the device planes' clock.  The benchmark
harness's reducer (``benchmarks.harness.trace``) keeps the host spans
whose names are in its ``SPANS``; the tests below add the program's
names to it for the duration of a capture.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import trace
from repro.core.backends import FusedPallasBackend
from repro.core.twin import TwinFleet, make_driven_twin
from repro.launch import journal as journal_lib
from repro.launch.fleet_serving import StreamingFleetServer

DT = 0.01
#: each program span and the span it opens inside
PARENT = {"pump.assemble": "pump", "pump.fetch": "pump",
          "store.page": "pump.fetch", "pump.solve": "pump",
          "solve.drive": "pump.solve", "pump.check": "pump",
          "pump.commit": "pump", "commit.copy_out": "pump.commit",
          "commit.stitch": "pump.commit"}


def _drive(t, theta):
    return theta[0] * jnp.sin(theta[1] * t)


def _server(population=12, hot=4, batch=4, **kw):
    """A driven fused-kernel server with ``population`` registered twins
    over a ``hot``-row slab."""
    twin = make_driven_twin(2, lambda t: jnp.sin(t), hidden=8,
                            n_hidden_layers=1)
    fleet = TwinFleet(twin.with_backend(FusedPallasBackend(precision="f32")),
                      drive_family=_drive)
    params = twin.init(jax.random.PRNGKey(0))
    server = StreamingFleetServer(
        fleet, params, dt=DT, hot_capacity=hot, max_batch=batch,
        max_window=8, horizon_quantum=4, transient_retries=0, **kw)
    rng = np.random.default_rng(5)
    for i in range(population):
        server.register_twin(i, rng.normal(size=2).astype(np.float32) * 0.1,
                             theta=np.float32([1.0 + 0.1 * i, 2.0]))
    return server


def _pump_batch(server, ids, now=0.0, horizon=8):
    for i in ids:
        server.submit(i, horizon, t_arrival=now)
    return server.pump(now)


@pytest.fixture(scope="module")
def traced():
    """Three pumps of 4 twins each over a 4-row slab, each batch new to
    the slab (so every pump evicts 4 rows), inside the harness's capture
    and its ``pump`` span.  Returns the reduced trace and the evictions
    of each pump."""
    server = _server()
    _pump_batch(server, range(0, 4))           # compiles, fills the slab
    out, evictions = {}, []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "SPANS", trace.SPANS + tuple(PARENT))
        with trace.capture(out):
            for lo in (4, 8, 0):
                before = server.store.stats.evictions
                with trace.span("pump", True):
                    _pump_batch(server, range(lo, lo + 4))
                evictions.append(server.store.stats.evictions - before)
    return out["trace"], evictions


def _per_pump(tr):
    """{pump index: {span name: [start, end]}} and the pumps' [start,
    end]; fails on a span that opens twice in one pump."""
    pumps = sorted((s, s + d) for n, s, d in tr["spans"] if n == "pump")
    found = {i: {} for i in range(len(pumps))}
    for name, s, d in tr["spans"]:
        if name not in PARENT:
            continue
        (i,) = [i for i, (a, b) in enumerate(pumps) if a <= s <= b]
        assert name not in found[i], f"{name} opened twice in pump {i}"
        found[i][name] = [s, s + d]
    return found, pumps


def test_each_pump_opens_each_span_once(traced):
    tr, evictions = traced
    found, pumps = _per_pump(tr)
    assert len(pumps) == 3
    for spans in found.values():
        assert set(spans) == set(PARENT)
    # a pump that evicts several rows still opens one store.page
    assert evictions == [4, 4, 4]


def test_spans_nest_inside_their_parents(traced):
    tr, _ = traced
    found, pumps = _per_pump(tr)
    for i, spans in found.items():
        spans = dict(spans, pump=list(pumps[i]))
        for name, parent in PARENT.items():
            (s, e), (ps, pe) = spans[name], spans[parent]
            assert ps <= s and e <= pe, f"{name} outside {parent}"
    # siblings under pump follow the pump's order and do not overlap
    order = ["pump.assemble", "pump.fetch", "pump.solve", "pump.check",
             "pump.commit"]
    for spans in found.values():
        ends = [spans[n] for n in order]
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))


def test_breakdown_names_the_innermost_program_span():
    """An idle gap goes to the innermost span that covers its midpoint,
    whichever level of the program's spans that is."""
    spans = [["pump", 0, 100], ["pump.fetch", 10, 30], ["store.page", 15, 15],
             ["pump.solve", 40, 30], ["solve.drive", 42, 8],
             ["pump.commit", 70, 30], ["commit.copy_out", 72, 8]]
    busy = [[0, 12], [14, 20], [24, 32], [38, 43], [47, 60], [68, 74],
            [78, 85], [95, 100]]
    tr = {"window": [0, 100],
          "devices": {"/device:TPU:0": [["op", s, e - s, "m"]
                                        for s, e in busy]},
          "spans": spans}
    gaps = dict(trace.breakdown(tr)["idle_gaps"])
    want = {"pump.fetch": 8, "store.page": 4, "solve.drive": 4,
            "pump.solve": 8, "commit.copy_out": 4, "pump.commit": 10}
    assert set(gaps) == set(want)
    for name, ns in want.items():
        assert gaps[name] == pytest.approx(ns * 1e-9)


def test_host_syncs_three_per_pump():
    """With one tier and no retries a pump reads from the device three
    times: the solve's wait, the finiteness check and the trajectory
    copy.  Eviction reads are counted by the store: one a pump that
    evicts, whatever the number of rows."""
    server = _server()
    for k, lo in enumerate((0, 4, 8, 0)):
        before = server.stats()
        _pump_batch(server, range(lo, lo + 4))
        after = server.stats()
        assert after.stream.host_syncs - before.stream.host_syncs == 3
        assert (after.store.evictions - before.store.evictions
                == (0 if k == 0 else 4))
        assert (after.store.evict_reads - before.store.evict_reads
                == (0 if k == 0 else 1))


def test_queue_wait_counts_first_assembly_only():
    """A request's wait is counted when it is first assembled; the
    continuations of a split request are not counted again."""
    server = _server(population=2, hot=2, batch=2)
    server.submit(0, 4, t_arrival=0.5)
    server.submit(1, 20, t_arrival=1.0)          # split over three pumps
    server.pump(2.0)                             # starts both
    server.pump(3.0)                             # twin 1's continuation
    server.submit(0, 4, t_arrival=3.5)
    server.pump(5.0)                             # continuation + new start
    s = server.stats().stream
    assert s.splits == 2 and server.pending == 0
    assert s.started == 3
    assert s.queue_wait_s == pytest.approx((2.0 - 0.5) + (2.0 - 1.0)
                                           + (5.0 - 3.5))


def test_counters_survive_snapshot_and_recovery(tmp_path):
    """The counters ride through a snapshot, and journal replay counts
    the pumps after it as the live server did."""
    d = str(tmp_path)
    live = _server(durability_dir=d, snapshot_every=0, fsync=False)
    for k, lo in enumerate((0, 4, 8, 0, 4)):
        _pump_batch(live, range(lo, lo + 4), now=float(k), horizon=12)
        if k == 2:
            live.snapshot()                  # the rest is replayed
    live.drain(now=6.0)
    fleet, params = live.fleet, live.params
    rec, _ = StreamingFleetServer.recover(d, fleet, params, fsync=False)
    want = live.stats().stream.as_dict()
    assert want["host_syncs"] > 0 and want["started"] == 20
    assert want["queue_wait_s"] > 0
    assert rec.stats().stream.as_dict() == want


def test_snapshot_without_new_counters_restores(tmp_path):
    """A snapshot written before the server counted host syncs and queue
    waits restores, with those counters at zero."""
    d = str(tmp_path)
    live = _server(durability_dir=d, snapshot_every=0, fsync=False)
    _pump_batch(live, range(0, 4))
    live.snapshot()
    _, arrays, extra = journal_lib.load_latest_snapshot(d)
    for key in ("host_syncs", "started", "queue_wait_s"):
        extra["stream_stats"].pop(key)
    fresh = _server(population=0)
    fresh._restore_snapshot(arrays, extra)
    s = fresh.stats().stream
    assert (s.host_syncs, s.started, s.queue_wait_s) == (0, 0, 0.0)
    assert s.batches == 1 and s.served == 4
    assert len(fresh.store) == 12


# ---------------------------------------------------------------------------
# Kernel names
# ---------------------------------------------------------------------------

def _pallas_names(jaxpr) -> list:
    """The ``name`` of every ``pallas_call`` in a jaxpr, sub-jaxprs
    included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names += _pallas_names(inner)
    return names


def _kernel_call(name):
    """A function and its abstract arguments that run ``name``."""
    from repro.kernels import ops
    from repro.kernels.crossbar_vmm import crossbar_matmul
    from repro.kernels.fused_analogue import fused_analogue_rollout
    from repro.kernels.fused_ode_mlp import fused_node_rollout
    from repro.kernels.fused_ode_mlp_bwd import fused_node_rollout_vjp
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    w, b = [f32(2, 8), f32(8, 2)], [f32(8), f32(2)]
    if name == "fused_fwd":
        return (lambda y, u, w, b: fused_node_rollout(y, u, w, b, DT),
                (f32(8, 2), f32(17, 0), w, b))
    if name == "fused_bwd":
        def loss(y, u, w, b):
            return jnp.sum(fused_node_rollout_vjp(
                y, u, w, b, DT, 8, None, None, 1 << 22, "f32") ** 2)
        return jax.grad(loss, argnums=(0, 2)), (f32(8, 2), f32(17, 0), w, b)
    if name in ("softdtw_fwd", "softdtw_bwd"):
        fn = lambda x, y: jnp.sum(ops.soft_dtw(x, y, 0.1))
        return (fn if name == "softdtw_fwd" else jax.grad(fn),
                (f32(2, 8, 2), f32(2, 8, 2)))
    if name == "fused_analogue":
        g = [f32(3, 8), f32(9, 2)]
        return (lambda gp, gm, sc, y, u: fused_analogue_rollout(
            gp, gm, sc, y, u, DT, g_min=1e-6),
            (g, g, f32(2), f32(8, 2), f32(8, 17, 0)))
    if name == "crossbar_vmm":
        return (lambda x, gp, gm: crossbar_matmul(x, gp, gm, inv_scale=1.0),
                (f32(8, 3), f32(3, 8), f32(3, 8)))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["fused_fwd", "fused_bwd", "softdtw_fwd",
                                  "softdtw_bwd", "fused_analogue",
                                  "crossbar_vmm"])
def test_pallas_kernel_is_named(name):
    """Each main-path kernel carries its name into the traced program,
    where a profiler trace can find it."""
    fn, args = _kernel_call(name)
    assert name in _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)

"""Streaming stateful serving: resume-parity properties, the state
store's paging invariants, and the continuous-batching server under
seeded traffic.

The load-bearing claim (docs/serving.md): serving a twin's trajectory in
pieces through :class:`TwinStateStore` — split anywhere, batched with
anything, paged to host and back — produces the SAME trajectory as one
uninterrupted rollout.  Bit-identical for f32 (and pure-bf16) substrates,
within one storage rounding for bf16_f32acc.  The hypothesis suite
samples random split points when hypothesis is installed; a seeded
parametrised subset always runs.
"""
import dataclasses
import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import traffic
from repro.core.analogue import AnalogueSpec
from repro.core.backends import (DigitalBackend, FusedAnalogueBackend,
                                 FusedPallasBackend, resolve_backend)
from repro.core.twin import TwinFleet, make_autonomous_twin, make_driven_twin
from repro.launch.fleet_serving import ServingSLO, StreamingFleetServer
from repro.launch.state_store import TwinStateStore

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

DT = 0.01
DIM = 3

BACKENDS = {
    "digital": lambda: DigitalBackend(),
    "fused_f32": lambda: FusedPallasBackend(precision="f32"),
    "fused_bf16": lambda: FusedPallasBackend(precision="bf16"),
    "fused_bf16_f32acc": lambda: FusedPallasBackend(
        precision="bf16_f32acc"),
    "analogue_fused": lambda: FusedAnalogueBackend(
        spec=AnalogueSpec(read_noise=0.02),
        prog_key=jax.random.PRNGKey(7)),
}
#: split-and-resume must be bit-identical on these (f32 arithmetic, or a
#: single rounded dtype end to end); bf16_f32acc is exact only at chunk
#: boundaries, so it gets a one-storage-rounding tolerance instead.
BITWISE = ("digital", "fused_f32", "fused_bf16", "analogue_fused")


@functools.lru_cache(maxsize=None)
def _setup(backend_key: str):
    """Programmed execution state + a small carried fleet, shared across
    parametrised cases and hypothesis examples (weights are programmed
    once, like a physical array)."""
    backend = BACKENDS[backend_key]()
    twin = make_autonomous_twin(state_dim=DIM, hidden=8, n_hidden_layers=1,
                                backend=backend)
    params = twin.init(jax.random.PRNGKey(0))
    state = backend.program(twin.node.field, params)
    ys = jnp.asarray(
        np.random.default_rng(3).normal(size=(3, DIM)) * 0.1, jnp.float32)
    return backend, state, ys


def _split_and_resume(backend_key: str, k: int, T: int):
    """Roll [0, k] then resume [k, T] THROUGH the state store; return
    (head, tail, full) trajectories."""
    backend, state, ys = _setup(backend_key)
    n = ys.shape[0]
    full = backend.rollout_batch_resumed(state, ys, dt=DT, num_steps=T)
    head = backend.rollout_batch_resumed(state, ys, dt=DT, num_steps=k)
    store = TwinStateStore(DIM, n)
    ids = list(range(n))
    for i in ids:
        store.register(i, np.asarray(ys[i]))
    store.fetch(ids)
    store.commit(ids, head[:, k], np.full(n, k))
    mid, steps, _ = store.fetch(ids)
    assert list(steps) == [k] * n
    tail = backend.rollout_batch_resumed(state, mid, dt=DT,
                                         num_steps=T - k, start_steps=steps)
    return np.asarray(head), np.asarray(tail), np.asarray(full)


@pytest.mark.parametrize("backend_key", list(BACKENDS))
@pytest.mark.parametrize("k,T", [(1, 12), (5, 12), (11, 12), (8, 24)])
def test_resume_parity_seeded(backend_key, k, T):
    head, tail, full = _split_and_resume(backend_key, k, T)
    if backend_key in BITWISE:
        np.testing.assert_array_equal(head, full[:, : k + 1])
        np.testing.assert_array_equal(tail, full[:, k:])
    else:
        # bf16_f32acc: the carry is exact at time-chunk boundaries and
        # within ONE bf16 storage rounding elsewhere; the deviation can
        # grow with the remaining horizon, so bound it loosely.
        np.testing.assert_allclose(tail, full[:, k:], rtol=0.03, atol=0.03)
        np.testing.assert_array_equal(tail[:, 0], full[:, k])


def test_resume_matches_plain_rollout_digital():
    """The stronger cross-API property (digital only): a resumed rollout
    equals the ordinary ``rollout_batch`` over the canonical window grid
    bitwise — resume is not a parallel implementation, it IS the same
    arithmetic."""
    from repro.kernels.ops import window_times
    backend, state, ys = _setup("digital")
    T = 16
    ts = window_times(0.0, DT, T)
    plain = jax.vmap(lambda y: backend.rollout(state, y, ts))(ys)
    resumed = backend.rollout_batch_resumed(state, ys, dt=DT, num_steps=T)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(resumed))


def test_resume_rejects_traced_and_negative_starts():
    backend, state, ys = _setup("digital")
    with pytest.raises(ValueError, match="concrete host"):
        jax.jit(lambda s: backend.rollout_batch_resumed(
            state, ys, dt=DT, num_steps=2, start_steps=s))(jnp.arange(3))
    with pytest.raises(ValueError, match="non-negative"):
        backend.rollout_batch_resumed(state, ys, dt=DT, num_steps=2,
                                      start_steps=np.array([0, -1, 0]))


def test_resume_mixed_phases_fused():
    """Twins at DIFFERENT global steps batch into one fused launch; each
    row must equal that twin's own homogeneous resume."""
    backend, state, ys = _setup("fused_f32")
    starts = np.array([0, 5, 11])
    mixed = backend.rollout_batch_resumed(state, ys, dt=DT, num_steps=6,
                                          start_steps=starts)
    for i, s in enumerate(starts):
        solo = backend.rollout_batch_resumed(
            state, ys[i: i + 1], dt=DT, num_steps=6,
            start_steps=np.array([s]))
        np.testing.assert_array_equal(np.asarray(mixed[i]),
                                      np.asarray(solo[0]))


if HAVE_HYPOTHESIS:
    @given(data=st.data(), T=st.integers(2, 40))
    @settings(max_examples=15, deadline=None)
    def test_resume_parity_random_split_digital(data, T):
        k = data.draw(st.integers(1, T - 1))
        head, tail, full = _split_and_resume("digital", k, T)
        np.testing.assert_array_equal(head, full[:, : k + 1])
        np.testing.assert_array_equal(tail, full[:, k:])

    @given(data=st.data(), T=st.integers(2, 40))
    @settings(max_examples=8, deadline=None)
    def test_resume_parity_random_split_fused(data, T):
        k = data.draw(st.integers(1, T - 1))
        head, tail, full = _split_and_resume("fused_f32", k, T)
        np.testing.assert_array_equal(head, full[:, : k + 1])
        np.testing.assert_array_equal(tail, full[:, k:])

    @given(data=st.data(), T=st.integers(2, 24))
    @settings(max_examples=5, deadline=None)
    def test_resume_parity_random_split_analogue(data, T):
        k = data.draw(st.integers(1, T - 1))
        head, tail, full = _split_and_resume("analogue_fused", k, T)
        np.testing.assert_array_equal(head, full[:, : k + 1])
        np.testing.assert_array_equal(tail, full[:, k:])


# ---------------------------------------------------------------------------
# TwinStateStore: paging mechanics
# ---------------------------------------------------------------------------

def test_store_lru_eviction_pages_not_drops():
    store = TwinStateStore(2, hot_capacity=2)
    for i in range(4):
        store.register(i, np.float32([i, i]))
    store.fetch([0, 1])                   # hot: 0, 1
    store.fetch([2])                      # evicts 0 (LRU)
    assert 0 not in store.hot_ids and 2 in store.hot_ids
    assert store.stats.evictions == 1
    y, step = store.peek(0)               # paged, not lost
    np.testing.assert_array_equal(y, np.float32([0, 0]))
    store.fetch([0])                      # pages 0 back in
    store.check_invariants()
    assert store.stats.page_ins == 4      # 0,1,2 cold-first + 0 again


def test_store_fetch_touches_lru_order():
    store = TwinStateStore(2, hot_capacity=2)
    for i in range(3):
        store.register(i, np.float32([i, i]))
    store.fetch([0, 1])
    store.fetch([0])                      # 0 becomes MRU -> 1 is LRU
    store.fetch([2])                      # must evict 1, not 0
    assert set(store.hot_ids) == {0, 2}
    store.check_invariants()


def test_store_commit_round_trips_state():
    store = TwinStateStore(3, hot_capacity=2)
    store.register("a", np.zeros(3, np.float32))
    store.fetch(["a"])
    store.commit(["a"], np.float32([[1, 2, 3]]), np.array([5]))
    y, step = store.peek("a")
    np.testing.assert_array_equal(y, np.float32([1, 2, 3]))
    assert step == 5
    # survives an eviction round-trip bitwise
    store.register("b", np.zeros(3, np.float32))
    store.register("c", np.zeros(3, np.float32))
    store.fetch(["b", "c"])
    y2, step2 = store.peek("a")
    np.testing.assert_array_equal(y2, y)
    assert step2 == 5


def _per_row_reference(store, ids):
    """The slot each twin of ``ids`` gets, in LRU order, and the
    ``(victim, slot)`` evictions, as a per-twin promotion loop assigns
    them (free slots first, then least-recently-used unpinned twins)."""
    slot_of, free, evicted = OrderedDict(store._slot_of), list(store._free), []
    for t in ids:
        if t in slot_of:
            slot_of.move_to_end(t)
            continue
        if free:
            slot = free.pop()
        else:
            victim = next(v for v in slot_of if v not in ids)
            slot = slot_of.pop(victim)
            evicted.append((victim, slot))
        slot_of[t] = slot
    return list(slot_of.items()), evicted


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("ids,k", [
    ([1, 4, 5, 6, 7, 8], 3),     # a hot hit, two free slots, 3 evictions
    ([6, 7, 8, 9], 4),           # evictions only
    ([3, 1], 0),                 # hot hits only: no read
])
def test_store_fetch_pages_out_in_one_read(ids, k):
    """One fetch that evicts k rows reads the device once: every paged
    row is the slab row from before the fetch, bit for bit, and the LRU
    order and slots are what a per-twin loop gives."""
    store = TwinStateStore(2, hot_capacity=6)
    for i in range(10):
        store.register(i, np.float32([i, -i]))
    store.fetch([0, 1, 2, 3])                 # 2 slots stay free
    store.commit([0, 1, 2, 3], np.float32([[.1, .2], [.3, .4], [.5, .6],
                                           [.7, .8]]) * 3, np.arange(4))
    if k == 4:
        store.fetch([4, 5])                   # fill the free slots
    slab = np.asarray(store._hot).copy()
    want_slots, want_evicted = _per_row_reference(store, ids)
    assert len(want_evicted) == k
    cold_in = {t: store.peek(t)[0].copy() for t in ids
               if t not in store.hot_ids}
    before = dataclasses.replace(store.stats)
    store.fetch(ids)
    assert store.stats.evictions - before.evictions == k
    assert store.stats.evict_reads - before.evict_reads == (1 if k else 0)
    assert list(store._slot_of.items()) == want_slots
    for victim, slot in want_evicted:
        np.testing.assert_array_equal(_bits(store._cold[victim]),
                                      _bits(slab[slot]))
    for t, y in cold_in.items():              # page-ins landed in place
        np.testing.assert_array_equal(_bits(store.peek(t)[0]), _bits(y))
    store.check_invariants()


def test_store_evictions_in_one_bucket_compile_once():
    """Fetches evicting 5 and then 6 rows, with every other shape the
    same (8 ids, 2 hot hits, 6 page-ins), share the padded gather: the
    second adds no backend compile."""
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    store = TwinStateStore(2, hot_capacity=10)
    for i in range(30):
        store.register(i, np.float32([i, i]))
    store.fetch(range(9))                     # 1 slot stays free
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        store.fetch([0, 1, *range(9, 15)])    # 1 free slot + 5 evictions
        first = len(compiles)
        store.fetch([0, 1, *range(15, 21)])   # 6 evictions
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert store.stats.evictions == 11 and store.stats.evict_reads == 2
    assert first > 0 and len(compiles) == first


def test_store_rejects_bad_usage():
    store = TwinStateStore(2, hot_capacity=2)
    store.register(0, np.zeros(2, np.float32))
    with pytest.raises(ValueError, match="already registered"):
        store.register(0, np.zeros(2, np.float32))
    with pytest.raises(ValueError, match="shape"):
        store.register(1, np.zeros(3, np.float32))
    with pytest.raises(ValueError, match="non-finite"):
        store.register(2, np.float32([np.nan, 0.0]))
    with pytest.raises(KeyError, match="unregistered"):
        store.fetch([99])
    with pytest.raises(ValueError, match="duplicate"):
        store.register(3, np.zeros(2, np.float32)) or store.fetch([0, 0])
    store.register(4, np.zeros(2, np.float32))
    with pytest.raises(ValueError, match="exceeds hot_capacity"):
        store.fetch([0, 3, 4])
    with pytest.raises(KeyError, match="not hot"):
        store.commit([4], np.zeros((1, 2), np.float32), np.array([1]))
    with pytest.raises(ValueError, match="mixed drive"):
        store.register("t", np.zeros(2, np.float32),
                       theta=np.float32([1.0]))
        store.fetch([0, "t"])


# ---------------------------------------------------------------------------
# StreamingFleetServer: continuous batching under seeded traffic
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fused_fleet():
    twin = make_autonomous_twin(state_dim=DIM, hidden=8, n_hidden_layers=1,
                                gradient="fused_vjp",
                                backend=FusedPallasBackend(precision="f32"))
    params = twin.init(jax.random.PRNGKey(1))
    return TwinFleet(twin=twin), params


def _serve(trace, **kw):
    fleet, params = _fused_fleet()
    cfg = dict(dt=DT, hot_capacity=8, max_batch=4, max_window=8,
               horizon_quantum=4)
    cfg.update(kw)
    server = StreamingFleetServer(fleet, params, **cfg)
    rng = np.random.default_rng(11)
    y0s = {}

    def y0_of(tid):
        if tid not in y0s:
            y0s[tid] = rng.normal(size=DIM).astype(np.float32) * 0.1
        return y0s[tid]

    done = server.serve_trace(trace, y0_of=y0_of)
    return server, done


@pytest.mark.parametrize("trace_name",
                         sorted(set(traffic.TRACES) - {"deadline"}))
def test_streaming_invariants_under_traffic(trace_name):
    """Every healthy traffic shape — memoryless, bursty, all-cold paging
    storm, single-twin serialisation, ragged horizons — must drop
    nothing, preserve per-twin order, and conserve both requests and
    state.  (The deadline trace is exercised by its own test: it is
    *designed* to expire requests, so no-drop does not apply.)"""
    gen = traffic.TRACES[trace_name]
    trace = gen(seed=5, n_requests=24, max_horizon=12)
    server, done = _serve(trace)
    traffic.check_all(server, trace, done)


def test_streaming_deadline_trace_expires_exactly_once():
    """The deadline trace's stale requests are dropped at assembly time,
    each counted ``expired`` exactly once; everything else is served and
    the conservation sum still closes after a further drain (no
    double-count on re-pump)."""
    trace = traffic.deadline_trace(seed=5, n_requests=30, population=8,
                                   max_horizon=10, tight_fraction=0.4)
    server, done = _serve(trace)
    s = server.stats().stream
    assert s.expired > 0, "the deadline trace never expired anything"
    traffic.check_conservation(server, done)
    traffic.check_arrival_order(done)
    traffic.check_state_safety(server, trace, done)
    expired_before = s.expired
    extra = server.drain(now=trace[-1].time + 1.0)   # nothing left
    assert extra == []
    assert server.stats().stream.expired == expired_before, \
        "an expired request was counted again on a later pump"
    traffic.check_conservation(server, done)


def test_streaming_paging_exercised_population_4x_hot():
    """The acceptance bar: resident population >= 4x the hot set, served
    to completion with paging actually happening and nothing dropped."""
    trace = traffic.poisson_trace(seed=9, n_requests=40, population=32,
                                  min_horizon=2, max_horizon=10)
    assert traffic.population_of(trace) >= 4 * 8 // 2  # >=16 distinct twins
    server, done = _serve(trace, hot_capacity=4, max_batch=4)
    assert traffic.population_of(trace) >= 4 * server.store.hot_capacity
    traffic.check_all(server, trace, done)
    assert server.store.stats.evictions > 0, "paging was not exercised"


def test_streaming_matches_uninterrupted_rollout():
    """Continuous batching is invisible in the numbers: each twin's
    stitched completions equal ONE uninterrupted resumed rollout of the
    same total horizon, bitwise (f32)."""
    trace = traffic.poisson_trace(seed=2, n_requests=20, population=6,
                                  min_horizon=2, max_horizon=12)
    server, done = _serve(trace)
    traffic.check_all(server, trace, done)
    fleet, params = _fused_fleet()
    backend = resolve_backend(fleet.backend)
    state = backend.program(fleet.twin.node.field, params)
    by_twin = {}
    for c in sorted(done, key=lambda c: c.seq):
        by_twin.setdefault(c.twin_id, []).append(c.trajectory)
    for tid, parts in by_twin.items():
        stitched = np.concatenate(
            [parts[0]] + [p[1:] for p in parts[1:]], axis=0)
        total = stitched.shape[0] - 1
        full = backend.rollout_batch_resumed(
            state, stitched[None, 0], dt=DT, num_steps=total)
        np.testing.assert_array_equal(stitched, np.asarray(full[0]))


def test_streaming_deterministic_replay():
    """Same trace + same seeds -> byte-identical completions (the whole
    schedule is a pure function of the trace)."""
    trace = traffic.bursty_trace(seed=4, n_requests=16, population=8,
                                 max_horizon=10)
    _, done_a = _serve(trace)
    _, done_b = _serve(trace)
    assert [c.seq for c in done_a] == [c.seq for c in done_b]
    for a, b in zip(done_a, done_b):
        assert a.twin_id == b.twin_id and a.tier == b.tier
        np.testing.assert_array_equal(a.trajectory, b.trajectory)


def test_streaming_splits_long_requests():
    """A horizon longer than max_window is served across several batches
    through the chunk-carry path — one completion, full trajectory, and
    the split counter shows it happened."""
    trace = [traffic.Arrival(0.0, 0, 21)]
    server, done = _serve(trace, max_window=8)
    traffic.check_all(server, trace, done)
    assert len(done) == 1 and done[0].trajectory.shape == (22, DIM)
    assert server.stream_stats.splits >= 2


def test_streaming_front_door_validation():
    fleet, params = _fused_fleet()
    server = StreamingFleetServer(fleet, params, dt=DT, hot_capacity=4,
                                  max_batch=2, max_window=8)
    with pytest.raises(KeyError, match="not registered"):
        server.submit("ghost", 4)
    server.register_twin(0, np.zeros(DIM, np.float32))
    with pytest.raises(ValueError, match="horizon"):
        server.submit(0, 0)
    with pytest.raises(ValueError, match="theta"):
        server.register_twin(1, np.zeros(DIM, np.float32),
                             theta=np.float32([1.0]))
    with pytest.raises(ValueError, match="max_batch"):
        StreamingFleetServer(fleet, params, dt=DT, hot_capacity=2,
                             max_batch=4)
    with pytest.raises(ValueError, match="dt"):
        StreamingFleetServer(fleet, params, dt=0.0)


def test_streaming_driven_fleet_with_slo_fallback_chain():
    """Driven analogue fleet under an armed SLO: the fallback chain is
    built, probes run, and every request is served by SOME tier with the
    conservation invariants intact."""
    drive_family = lambda t, th: th[0] * jnp.sin(th[1] * t)
    twin = make_driven_twin(state_dim=2, hidden=8, n_hidden_layers=1,
                            drive=lambda t: jnp.sin(t),
                            gradient="fused_vjp")
    params = twin.init(jax.random.PRNGKey(2))
    backend = FusedAnalogueBackend(spec=AnalogueSpec(read_noise=0.05),
                                   prog_key=jax.random.PRNGKey(3))
    fleet = TwinFleet(twin=twin.with_backend(backend),
                      drive_family=drive_family)
    server = StreamingFleetServer(
        fleet, params, dt=DT, hot_capacity=8, max_batch=4, max_window=8,
        horizon_quantum=4, slo=ServingSLO(max_rel_error=0.5))
    assert [n for n, _ in server._tiers] == \
        ["analogue_fused", "analogue_fused_clean", "digital"]
    trace = traffic.bursty_trace(seed=6, n_requests=12, population=6,
                                 max_horizon=8)
    rng = np.random.default_rng(13)
    done = server.serve_trace(
        trace,
        y0_of=lambda i: rng.normal(size=2).astype(np.float32) * 0.1,
        theta_of=lambda i: np.float32([0.5, 2.0 + 0.1 * i]))
    traffic.check_all(server, trace, done)
    assert server.serving_stats.probes > 0
    assert sum(server.serving_stats.served_by.values()) == \
        server.stream_stats.batches


def test_streaming_pathological_request_quarantined_with_diagnostic():
    """A server whose only tier produces non-finite trajectories (here: a
    corrupted weight program) must *quarantine* the request — not drop it
    silently, not raise, not retry forever — record a diagnostic naming
    the tier that rejected it, and leave carried state untouched for the
    next (possibly re-programmed) attempt."""
    fleet, params = _fused_fleet()
    bad_params = jax.tree_util.tree_map(
        lambda x: x * np.float32(np.nan), params)
    server = StreamingFleetServer(fleet, bad_params, dt=DT, hot_capacity=4,
                                  max_batch=2, max_window=8,
                                  horizon_quantum=4)
    y0 = np.float32([0.1, 0.2, 0.3])
    server.register_twin("t", y0)
    seq = server.submit("t", 4)
    done = server.drain()
    assert done == [] and server.stream_stats.quarantined == 1
    assert seq in server.quarantine
    q = server.quarantine[seq]
    assert q.twin_id == "t" and q.horizon == 4
    assert "non-finite" in q.reason and "fused" in q.reason
    traffic.check_conservation(server, done)
    y, step = server.store.peek("t")
    np.testing.assert_array_equal(y, y0)   # state untouched by poison
    assert step == 0
    server.store.check_invariants()
    # quarantine is terminal: further pumps never resurrect the seq
    assert server.drain() == []
    assert server.stream_stats.quarantined == 1


def test_streaming_drain_with_quarantined_pending_mix():
    """drain() with a mixed queue — healthy requests AND a poison twin —
    serves the healthy ones, quarantines the poison one, and terminates
    (the quarantined seq must not wedge the drain loop)."""
    fleet, params = _fused_fleet()
    server = StreamingFleetServer(fleet, params, dt=DT, hot_capacity=4,
                                  max_batch=2, max_window=8,
                                  horizon_quantum=4)
    rng = np.random.default_rng(21)
    for tid in range(4):
        server.register_twin(tid, rng.normal(size=DIM).astype(np.float32)
                             * 0.1)
    # A non-finite *initial state* cannot enter via register_twin (it
    # validates), so poison the request with a finite-but-extreme state:
    # the first matvec overflows f32 and the window goes NaN.  Four
    # healthy twins ahead of it mean the poison assembles into a batch
    # of its own (quarantine parks whole batches).
    server.register_twin("hot", np.float32([3e38, 3e38, 3e38]))
    seqs = [server.submit(tid, 4) for tid in range(4)]
    bad = server.submit("hot", 8)
    done = server.drain()
    s = server.stats().stream
    assert sorted(c.seq for c in done) == seqs
    assert s.quarantined == 1 and bad in server.quarantine
    assert server.pending == 0
    traffic.check_conservation(server, done)
    traffic.check_state_safety(
        server,
        [traffic.Arrival(0.0, tid, 4) for tid in range(4)]
        + [traffic.Arrival(0.0, "hot", 8)],
        done)


def test_streaming_backpressure_reject_new():
    """With a bounded queue and the reject_new policy, submits past the
    bound return None, count shed, and conservation still closes."""
    fleet, params = _fused_fleet()
    server = StreamingFleetServer(fleet, params, dt=DT, hot_capacity=4,
                                  max_batch=2, max_window=8,
                                  horizon_quantum=4, max_queue=2,
                                  shed_policy="reject_new")
    rng = np.random.default_rng(3)
    for tid in range(4):
        server.register_twin(tid, rng.normal(size=DIM).astype(np.float32)
                             * 0.1)
    accepted = [server.submit(tid, 4) for tid in range(2)]
    assert all(s is not None for s in accepted)
    assert server.submit(2, 4) is None and server.submit(3, 4) is None
    s = server.stats().stream
    assert s.enqueued == 4 and s.shed == 2 and server.pending == 2
    done = server.drain()
    assert sorted(c.seq for c in done) == accepted
    traffic.check_conservation(server, done)


def test_streaming_backpressure_drop_oldest_same_twin():
    """drop_oldest sheds the oldest *unstarted request of the same twin*
    to make room (fresher data supersedes stale), and falls back to
    rejecting the newcomer when no same-twin victim exists."""
    fleet, params = _fused_fleet()
    server = StreamingFleetServer(fleet, params, dt=DT, hot_capacity=4,
                                  max_batch=2, max_window=8,
                                  horizon_quantum=4, max_queue=2,
                                  shed_policy="drop_oldest")
    rng = np.random.default_rng(4)
    for tid in ("a", "b"):
        server.register_twin(tid, rng.normal(size=DIM).astype(np.float32)
                             * 0.1)
    s0 = server.submit("a", 4)
    s1 = server.submit("b", 4)
    s2 = server.submit("a", 8)          # sheds s0 (same twin, oldest)
    assert s2 is not None
    assert [r.seq for r in server._queue] == [s1, s2]
    s3 = server.submit("b", 4)          # sheds s1
    assert s3 is not None
    # queue now [s2 (a), s3 (b)]; a twin with no queued request must NOT
    # steal another twin's slot — the newcomer is rejected instead
    server.register_twin("c", np.zeros(DIM, np.float32))
    assert server.submit("c", 4) is None
    done = server.drain()
    assert sorted(c.seq for c in done) == sorted([s2, s3])
    st = server.stats().stream
    assert st.enqueued == 5 and st.shed == 3 and st.served == 2
    traffic.check_conservation(server, done)


def test_streaming_submit_validation_names_argument():
    """Front-door validation on submit: each bad argument is rejected
    with a ValueError naming it, before any counter moves."""
    fleet, params = _fused_fleet()
    server = StreamingFleetServer(fleet, params, dt=DT, hot_capacity=4,
                                  max_batch=2, max_window=8)
    server.register_twin(0, np.zeros(DIM, np.float32))
    with pytest.raises(ValueError, match="horizon"):
        server.submit(0, True)          # bool is not a step count
    with pytest.raises(ValueError, match="horizon"):
        server.submit(0, 2.5)
    with pytest.raises(ValueError, match="t_arrival"):
        server.submit(0, 4, t_arrival=float("nan"))
    with pytest.raises(ValueError, match="deadline"):
        server.submit(0, 4, t_arrival=1.0, deadline=0.5)
    with pytest.raises(ValueError, match="deadline"):
        server.submit(0, 4, deadline=float("inf"))
    assert server.stats().stream.enqueued == 0 and server.pending == 0


def test_streaming_transient_fault_retried_with_backoff():
    """An injected transient tier fault (chaos.flaky) is absorbed by the
    retry path — the request is still served on the SAME tier, the retry
    counter moves, and no fallback/quarantine is triggered."""
    from repro.launch import chaos
    fleet, params = _fused_fleet()
    server = StreamingFleetServer(fleet, params, dt=DT, hot_capacity=4,
                                  max_batch=2, max_window=8,
                                  horizon_quantum=4, transient_retries=2,
                                  backoff_base_s=0.0)
    server.register_twin(0, np.float32([0.1, 0.2, 0.3]))
    server.submit(0, 4)
    with chaos.flaky("pump:run_tier", times=2):
        done = server.drain()
    assert len(done) == 1
    assert server.serving_stats.transient_retries == 2
    assert server.stream_stats.quarantined == 0
    assert server.stream_stats.failed == 0


def test_streaming_transient_exhaustion_falls_to_next_tier():
    """More consecutive faults than the retry budget exhausts the tier;
    with a fallback chain armed the next tier serves the batch (infra
    failure is NOT poison — nothing is quarantined)."""
    from repro.launch import chaos
    drive_family = lambda t, th: th[0] * jnp.sin(th[1] * t)
    twin = make_driven_twin(state_dim=2, hidden=8, n_hidden_layers=1,
                            drive=lambda t: jnp.sin(t),
                            gradient="fused_vjp")
    params = twin.init(jax.random.PRNGKey(2))
    backend = FusedAnalogueBackend(spec=AnalogueSpec(read_noise=0.05),
                                   prog_key=jax.random.PRNGKey(3))
    fleet = TwinFleet(twin=twin.with_backend(backend),
                      drive_family=drive_family)
    server = StreamingFleetServer(
        fleet, params, dt=DT, hot_capacity=4, max_batch=2, max_window=8,
        horizon_quantum=4, slo=ServingSLO(max_rel_error=0.5),
        transient_retries=1, backoff_base_s=0.0)
    server.register_twin(0, np.float32([0.1, 0.2]),
                         theta=np.float32([0.5, 2.0]))
    server.submit(0, 4)
    # 2 faults > 1 retry: first tier exhausts, but flaky heals before the
    # *second* tier attempts, so the fallback serves it
    with chaos.flaky("pump:run_tier", times=2):
        done = server.drain()
    assert len(done) == 1
    assert done[0].tier != server._tiers[0][0]
    assert server.stream_stats.quarantined == 0
    traffic.check_conservation(server, done)


def test_streaming_stats_unified_snapshot():
    """server.stats() returns one consistent snapshot of all three stat
    families, detached from live state (mutating the server afterwards
    does not change the snapshot)."""
    trace = traffic.poisson_trace(seed=3, n_requests=8, population=4,
                                  max_horizon=8)
    server, done = _serve(trace)
    snap = server.stats()
    assert snap.stream.served == len(done)
    assert snap.store.page_ins == server.store.stats.page_ins
    assert snap.serving.served_by == server.serving_stats.served_by
    d = snap.as_dict()
    assert set(d) == {"stream", "serving", "store"}
    assert d["stream"]["served"] == len(done)
    before = snap.stream.enqueued
    server.submit(done[0].twin_id, 4)
    assert snap.stream.enqueued == before    # snapshot is a deep copy
    server.drain()


def test_streaming_store_audit_env_flag(monkeypatch):
    """REPRO_STORE_AUDIT=1 runs the store's structural audit after every
    pump — smoke that the flag wires through and a healthy run passes."""
    monkeypatch.setenv("REPRO_STORE_AUDIT", "1")
    trace = traffic.poisson_trace(seed=6, n_requests=10, population=4,
                                  max_horizon=8)
    server, done = _serve(trace)
    assert server._audit is True
    traffic.check_all(server, trace, done)


def test_streaming_theta_survives_paging():
    """Per-twin drive parameters are host metadata: they survive
    eviction round-trips and come back with fetch in batch order."""
    store = TwinStateStore(2, hot_capacity=1)
    store.register("a", np.zeros(2, np.float32), theta=np.float32([1, 2]),
                   step=3)
    store.register("b", np.zeros(2, np.float32), theta=np.float32([3, 4]))
    _, steps, thetas = store.fetch(["a"])
    assert list(steps) == [3]
    np.testing.assert_array_equal(np.asarray(thetas),
                                  np.float32([[1, 2]]))
    store.fetch(["b"])                        # evicts "a"
    np.testing.assert_array_equal(store.theta("a"), np.float32([1, 2]))
    _, _, thetas = store.fetch(["a"])         # pages back with theta
    np.testing.assert_array_equal(np.asarray(thetas),
                                  np.float32([[1, 2]]))


def test_streaming_digital_backend_serves_too():
    """The streaming loop is substrate-agnostic: a digital-backend fleet
    goes through the vmap window path and meets the same invariants."""
    twin = make_autonomous_twin(state_dim=DIM, hidden=8, n_hidden_layers=1,
                                backend=DigitalBackend())
    params = twin.init(jax.random.PRNGKey(1))
    fleet = TwinFleet(twin=twin)
    server = StreamingFleetServer(fleet, params, dt=DT, hot_capacity=4,
                                  max_batch=2, max_window=8,
                                  horizon_quantum=4)
    trace = traffic.poisson_trace(seed=8, n_requests=10, population=5,
                                  min_horizon=2, max_horizon=8)
    rng = np.random.default_rng(17)
    done = server.serve_trace(
        trace, y0_of=lambda i: rng.normal(size=DIM).astype(np.float32) * 0.1)
    traffic.check_all(server, trace, done)

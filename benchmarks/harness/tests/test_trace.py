"""The trace reduction: by hand on a synthetic trace, on a trace recorded
on the chip (``tests/data``), and end to end on one recorded here."""
import json
import pathlib

import pytest

from benchmarks.harness import readers, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def synthetic():
    return {"window": [0, 100],
            "devices": {"/device:TPU:0": [["a", 10, 20, "m"],
                                          ["b", 25, 10, "m"],
                                          ["c", 50, 10, "m"],
                                          ["d", 95, 20, "m"]]},
            "spans": [["pump", 5, 50], ["submit", 30, 25]]}


def test_busy_union_and_idle_share():
    tr = synthetic()
    ev = tr["devices"]["/device:TPU:0"]
    # [10, 35) and [50, 60) and [95, 100) after clipping to the window
    assert trace.busy_intervals(ev, tr["window"]) == [[10, 35], [50, 60],
                                                      [95, 100]]
    assert trace.busy_s(tr) == pytest.approx(40e-9)
    assert trace.idle_share(tr) == pytest.approx(60.0)
    assert readers.idle_share({"trace": tr}) == pytest.approx(60.0)
    assert readers.idle_share({"trace": None}) is None


def test_breakdown_labels_gaps_by_host_span():
    b = trace.breakdown(synthetic())
    assert b["device_ops"][0] == ["a", pytest.approx(20e-9)]
    gaps = dict(b["idle_gaps"])
    # [0,10) by its midpoint in "pump", [35,50) in "submit" (the
    # innermost span), [60,95) outside every span
    assert gaps["pump"] == pytest.approx(10e-9)
    assert gaps["submit"] == pytest.approx(15e-9)
    assert gaps["outside_spans"] == pytest.approx(35e-9)


def test_two_chips_average():
    tr = synthetic()
    tr["devices"]["/device:TPU:1"] = [["a", 0, 100, "m"]]
    assert trace.busy_s(tr) == pytest.approx(70e-9)


def test_absent_kernel_reads_none():
    ctx = {"trace": synthetic(), "kernels": {"fused_fwd": (1e6, 1e3)},
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}
    assert readers.roofline(ctx, "fused_fwd") is None


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    tr = json.loads(path.read_text())
    want = tr.pop("expect")
    assert trace.idle_share(tr) == pytest.approx(want["idle_share"], rel=1e-9)
    b = trace.breakdown(tr)
    assert [n for n, _ in b["device_ops"]] == want["top_ops"]
    for kernel, count in want["kernel_events"].items():
        assert len(trace.op_events(tr, readers.kernel_matcher(kernel))) == count


def test_capture_and_reduce_a_live_trace():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    out = {}
    with trace.capture(out):
        for _ in range(3):
            with trace.span("pump", True):
                f(x).block_until_ready()
    tr = out["trace"]
    assert tr["window"][1] > tr["window"][0]
    assert [s[0] for s in tr["spans"]] == ["pump"] * 3
    assert any(tr["devices"].values())
    assert 0.0 < trace.idle_share(tr) < 100.0


def test_span_ms_and_counters():
    tr = synthetic()
    tr["spans"].append(["pump", 120, 40])          # starts past the window
    ctx = {"trace": tr, "counters": {"stream.batches": 4}}
    assert readers.span_ms(ctx, "pump") == pytest.approx(50e-6)
    assert readers.span_ms(ctx, "submit") == pytest.approx(25e-6)
    assert readers.span_ms(ctx, "pump.solve") is None
    assert readers.span_ms({"trace": None}, "pump") is None
    assert readers.counter(ctx, "stream.batches") == 4
    assert readers.counter(ctx, "store.evict_reads") is None
    assert readers.counter({}, "stream.batches") is None


def test_counter_deltas_read_every_numeric_field():
    from benchmarks.harness import cells
    s0 = {"stream": {"batches": 1, "queue_wait_s": 0.5, "flag": False},
          "serving": {"requests": 1},
          "store": {"evict_reads": 2, "served_by": {}}}
    s1 = {"stream": {"batches": 4, "queue_wait_s": 2.0, "flag": True,
                     "new": 3},
          "serving": {"requests": 9},
          "store": {"evict_reads": 5, "served_by": {"a": 1}}}
    assert cells.counter_deltas(s0, s1) == {
        "stream.batches": 3, "stream.queue_wait_s": 1.5,
        "store.evict_reads": 3}


def test_program_spans_kept_from_a_live_capture():
    """The reducer keeps the spans the program opens inside its own
    calls, by name, with no list of them in the harness."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.backends import FusedPallasBackend
    from repro.core.twin import TwinFleet, make_driven_twin
    from repro.launch.fleet_serving import StreamingFleetServer
    twin = make_driven_twin(2, lambda t: jnp.sin(t), hidden=8,
                            n_hidden_layers=1)
    fleet = TwinFleet(twin.with_backend(FusedPallasBackend(precision="f32")),
                      drive_family=lambda t, th: th[0] * jnp.sin(t))
    server = StreamingFleetServer(
        fleet, twin.init(jax.random.PRNGKey(0)), dt=0.01, hot_capacity=4,
        max_batch=4, max_window=8, horizon_quantum=4, transient_retries=0)
    for i in range(8):
        server.register_twin(i, np.full(2, 0.1, np.float32),
                             theta=np.float32([1.0]))

    def pump(ids):
        for i in ids:
            server.submit(i, 8)
        server.pump()

    pump(range(4))                                  # compiles
    out = {}
    with trace.capture(out):
        with trace.span("pump", True):
            pump(range(4, 8))                       # evicts: store.page
    names = {s[0] for s in out["trace"]["spans"]}
    assert {"pump", "pump.fetch", "store.page", "pump.solve", "solve.drive",
            "pump.commit"} <= names
    assert not names & {"ParseArguments", "PjitFunction(<lambda>)"}
    assert "store.page" not in trace.SPANS
    assert readers.span_ms({"trace": out["trace"]}, "store.page") > 0

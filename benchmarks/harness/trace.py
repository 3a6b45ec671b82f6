"""Profiler trace of a traced run, and its reduction to numbers.

``capture`` wraps the measured window in ``jax.profiler`` and returns the
trace in a plain form (``reduce_xspace``):

    {"window": [start_ns, end_ns],              # the harness's "window" span
     "devices": {plane: [[op, start_ns, dur_ns, hlo_module], ...]},
     "spans":   [[name, start_ns, dur_ns], ...]} # every host annotation

``spans`` holds every ``jax.profiler.TraceAnnotation`` the window opened,
the harness's and the program's alike.  The profiler writes the
runtime's own host events beside them with nothing to tell the two
apart, so ``capture`` records the name of each annotation opened while
it runs, and the reduction keeps the host events of those names (and of
``SPANS``).

Device ops are the events of each ``/device:TPU:<i>`` plane's "XLA Ops"
line.  A host without such planes (the CPU rehearsal) contributes the
host-side XLA op events instead, so the reduction still runs there; those
numbers are never reported under a device metric, because the benchmark
refuses to run without a TPU.

Everything after ``reduce_xspace`` works on that plain form, so the
reduction is checked on a small recorded trace (``tests/data``).
"""
from __future__ import annotations

import contextlib
import functools
import glob
import json
import os
import pathlib
import re
import shutil
import tempfile

#: The host spans the harness records around the public calls it makes.
SPANS = ("window", "register", "submit", "pump", "engine_chunk",
         "serve_batch")
OPS_LINE = "XLA Ops"
KERNELS = pathlib.Path(__file__).resolve().parent / "kernels.json"
#: One more kernel rule per file, ``<kernel>.json``.
KERNEL_DIR = KERNELS.with_name("kernels")
#: Ops that contain other ops of the same line (a scan's loop): they count
#: toward busy time through the union, not as ops of their own.
CONTAINERS = ("while", "conditional", "call")
#: 1 keeps the harness's annotations and drops the runtime's own host
#: events, which would otherwise dominate the trace of a serving loop.
HOST_TRACER_LEVEL = 1


@contextlib.contextmanager
def capture(out: dict):
    """Trace the enclosed block; on exit ``out["trace"]`` holds the plain
    form.  The profile is written under ``TMPDIR`` and removed after it
    has been read."""
    import jax
    tmp = tempfile.mkdtemp(prefix="harness_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = HOST_TRACER_LEVEL
    real, names = jax.profiler.TraceAnnotation, set()

    def recorded(name, **kwargs):
        names.add(name)
        return real(name, **kwargs)

    jax.profiler.start_trace(tmp, profiler_options=opts)
    jax.profiler.TraceAnnotation = recorded
    try:
        with real("window"):
            yield
    finally:
        jax.profiler.TraceAnnotation = real
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            out["trace"] = reduce_xspace(
                jax.profiler.ProfileData.from_file(paths[0]), names)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def span(name: str, traced: bool):
    """A host span the trace can attribute idle device time to."""
    if not traced:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def reduce_xspace(pd, annotations=()) -> dict:
    """The plain form of a profile: device ops, the window, and the host
    events named in ``SPANS`` or ``annotations``."""
    keep = set(SPANS) | set(annotations)
    devices, host_ops, spans, window = {}, [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([e.name, e.start_ns, e.duration_ns,
                                _stat(e, "hlo_module")] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        if e.name == "window":
                            window = [e.start_ns, e.start_ns + e.duration_ns]
                        else:
                            spans.append([e.name, e.start_ns, e.duration_ns])
                    elif "hlo_op" in dict(e.stats):
                        host_ops.append([e.name, e.start_ns, e.duration_ns,
                                         _stat(e, "hlo_module")])
    if not devices and host_ops:
        devices = {"/host:CPU": host_ops}
    if window is None:
        layout = {p.name: [line.name for line in p.lines] for p in pd.planes}
        raise RuntimeError(f"trace holds no 'window' span; planes and lines: "
                           f"{layout}")
    return {"window": window, "devices": devices, "spans": spans}


@functools.cache
def kernel_rules() -> dict:
    """kernel -> compiled regex over the device op's HLO text, from
    ``kernels.json`` and each ``kernels/<kernel>.json``: on a TPU an op
    event is named by its HLO instruction, and a Pallas kernel is a
    ``custom-call`` told apart by its operand and result shapes."""
    with open(KERNELS) as f:
        rules = json.load(f)
    for path in sorted(KERNEL_DIR.glob("*.json")):
        if path.stem in rules:
            raise ValueError(f"kernel {path.stem!r} has two rules: "
                             f"{KERNELS.name} and {path}")
        with open(path) as f:
            rules[path.stem] = json.load(f)
    return {k: re.compile(r["op"]) for k, r in rules.items()}


_HLO = re.compile(r"^%\S+ = (.*?) ([a-z][a-z0-9-]*)\(")


def label(op: str) -> str:
    """A short, stable name for a device op: the kernel it is, or its HLO
    opcode and result shape (layouts dropped), so that the unrolled
    copies of one op in a scan add up under one name."""
    for name, rule in kernel_rules().items():
        if rule.search(op):
            return name
    m = _HLO.match(op)
    if not m:
        return op[:120]
    return f"{m.group(2)} {re.sub(r'{[^}]*}', '', m.group(1))}"[:120]


def _stat(event, key: str) -> str:
    return str(dict(event.stats).get(key, ""))


def _clip(events, window):
    lo, hi = window
    for name, start, dur, module in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e, module


def busy_intervals(events, window) -> list:
    """The union of the events' [start, end) inside the window, merged."""
    merged = []
    for _, s, e, _ in sorted(_clip(events, window), key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) * 1e-9


def busy_s(trace: dict) -> float:
    """Seconds in which some op ran, averaged over the traced chips."""
    devs = trace["devices"].values()
    if not devs:
        return 0.0
    total = sum(sum(e - s for s, e in busy_intervals(ev, trace["window"]))
                for ev in devs)
    return total * 1e-9 / len(devs)


def idle_share(trace: dict):
    """1 - busy / window, in %, mean over chips; None without device ops."""
    if not any(trace["devices"].values()):
        return None
    return 100.0 * (1.0 - busy_s(trace) / window_s(trace))


def op_events(trace: dict, match) -> list:
    """[(op, dur_ns)] of every device op in the window that ``match(op,
    hlo_module)`` accepts, on every chip."""
    return [(n, e - s) for ev in trace["devices"].values()
            for n, s, e, m in _clip(ev, trace["window"]) if match(n, m)]


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device ops that took most time (by ``label``, seconds per chip),
    and idle device time by the host span that covered it."""
    per_op = {}
    n_dev = max(1, len(trace["devices"]))
    for ev in trace["devices"].values():
        for n, s, e, _ in _clip(ev, trace["window"]):
            name = label(n)
            if name.split(" ")[0] not in CONTAINERS:
                per_op[name] = per_op.get(name, 0.0) + (e - s) * 1e-9 / n_dev
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = {}
    spans = sorted(trace["spans"], key=lambda x: x[1])
    for ev in trace["devices"].values():
        busy = busy_intervals(ev, trace["window"])
        lo, hi = trace["window"]
        edges = [lo] + [x for b in busy for x in b] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                where = _covering_span(spans, (s + e) / 2)
                idle[where] = idle.get(where, 0.0) + (e - s) * 1e-9 / n_dev
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def _covering_span(spans, t) -> str:
    """The innermost (latest-starting) harness span containing t."""
    best = "outside_spans"
    for name, s, d in spans:
        if s > t:
            break
        if s + d >= t:
            best = name
    return best

"""A new twin kind, load, kernel rule and per-layer metric enter the
harness as new files and entries alone.

The harness is copied into a temporary checkout; a toy twin kind (a
linear field dy/dt = y·W + b, served by the program's digital backend),
a load of its own, a kernel rule, two metric readers and a cell are
added there as files, with entries in its ``BENCHMARK.json``.  The copy
resolves and rehearses the cell against its reference, untraced and
traced, in a process of its own, and every file the harness had is
still byte for byte what it was."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

from benchmarks.harness import spec

SEED = 2 ** 31 + 23

TWIN = '''"""Toy twin kind: dy/dt = y W + b, the program's one-layer field."""
import numpy as np

from benchmarks.harness import reference


def layer_sizes(config):
    return (config["state_dim"], config["state_dim"])


def served_fleet(config, backend):
    from repro.core.twin import TwinFleet, make_autonomous_twin
    return TwinFleet(make_autonomous_twin(config["state_dim"],
                                          n_hidden_layers=0))


def fit_twin(config):
    return served_fleet(config, None).twin


def make_weights(config, jax_seed):
    import jax
    import jax.numpy as jnp
    d = config["state_dim"]

    def make(key):
        a = jax.random.normal(key, (d, d)) / d
        return [{"w": a - a.T - 0.5 * jnp.eye(d), "b": jnp.zeros((d,))}]
    return jax.jit(make)(jax.random.PRNGKey(jax_seed))


def initial_states(config, n, jax_seed):
    rng = np.random.default_rng(jax_seed)
    y = rng.normal(size=(n, config["state_dim"])).astype(np.float32)
    return y, np.zeros((n, 0), np.float32)


def drive_half_steps(config, thetas, starts, steps):
    return np.zeros((len(starts), 2 * steps + 1, 0), np.float32)


def field(params, u, y, operand_dtype=None):
    import jax
    import jax.numpy as jnp
    w = reference.operand_round(params[0]["w"], operand_dtype)
    return jnp.dot(reference.operand_round(y, operand_dtype), w,
                   precision=jax.lax.Precision.HIGHEST) + params[0]["b"]


def flops_per_twin_step(config):
    return 8 * config["state_dim"] ** 2


def fused_fwd_cost(config, *, steps, rows):
    d = config["state_dim"]
    return float(rows * steps * flops_per_twin_step(config)), float(
        4 * rows * d * (steps + 1) + 4 * d * (d + 1))


def fused_bwd_cost(config, *, steps, rows):
    ops, nbytes = fused_fwd_cost(config, steps=steps, rows=rows)
    return 2 * ops, 2 * nbytes
'''

LOAD = '''"""Toy load: one batch rolled out again and again, closed loop."""
import contextlib

import numpy as np

from benchmarks.harness import cells, trace, yardstick


def load(config, traffic, seed, seconds, traced, clock, control=False):
    import jax
    kind = cells.twin_kind(config)
    _, jax_seed = yardstick.seeds(seed)
    params = kind.make_weights(config, jax_seed)
    n, H, dt = traffic["fleet"], traffic["horizon"], config["dt"]
    y0, th = kind.initial_states(config, n, jax_seed)
    fleet = kind.served_fleet(config, None)
    cells.check_sizes(fleet.twin, kind, config)
    ts = np.arange(H + 1, dtype=np.float32) * dt
    roll = jax.jit(lambda p, y: fleet.rollout_batch(p, y, ts))
    np.asarray(roll(params, y0))
    out, batches = {}, 0
    with trace.capture(out) if traced else contextlib.nullcontext():
        t0 = clock.open()
        while True:
            with trace.span("toy.batch", traced):
                traj = np.asarray(roll(params, y0))
            batches += 1
            if clock.now() - t0 >= seconds:
                break
        window = clock.close() - t0
    u = kind.drive_half_steps(config, th, np.zeros(n, np.int64), H)
    ref = cells.reference_rollouts(kind.field, params, y0, u, dt, H)
    err = cells.window_rel_err(traj, ref, np.full(n, H)).max()
    rate = batches * n * H / window
    layer = {"twin_steps_per_s": rate, "trace": out.get("trace")}
    return cells.Outcome(attempted=batches * n, failed=0,
                         e2e={"twin_steps_per_s": rate},
                         checks={"max_rel_err": float(err)}, info={},
                         layer=layer), 0
'''

METRICS = {
    "toy_dot_ops.toy": '''from benchmarks.harness import readers, trace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    events = trace.op_events(tr, readers.kernel_matcher("toy_dot"))
    return len(events) or None
''',
    "toy_batch_ms.toy": '''from benchmarks.harness.readers import span_ms


def read(ctx):
    return span_ms(ctx, "toy.batch")
''',
}

FILES = {
    "twins/toy_linear.py": TWIN,
    "loads/toy_batches.py": LOAD,
    "kernels/toy_dot.json": json.dumps({"op": "^dot"}),
    "configs/toy_twin.json": json.dumps({
        "twin": "toy_linear", "state_dim": 3, "dt": 0.01,
        "precision": "f32"}),
    "traffic/toy_batches.json": json.dumps({
        "load": "toy_batches", "why": "a toy", "fleet": 8, "horizon": 16}),
    "limits/toy_cell.json": json.dumps({"max_rel_err": 1e-4}),
    **{f"metrics/{k}.py": v for k, v in METRICS.items()},
}

RUN = f'''
import json
from benchmarks.harness import run, spec, trace
assert "toy_dot" in trace.kernel_rules()
cell = spec.resolve(spec.load_benchmark(), "toy_cell")
plain, _ = run.run_cell(cell, {SEED}, 0.3, False, device_kind="TPU v5 lite")
traced, _ = run.run_cell(cell, {SEED}, 0.3, True, device_kind="TPU v5 lite")
print(json.dumps({{"plain": plain, "traced": traced, "at": run.__file__}}))
'''


def _entries(bench: dict) -> dict:
    bench["configs"].append({
        "name": "toy_twin", "source": "https://example.org/toy",
        "file": "benchmarks/harness/configs/toy_twin.json", "reduced": [],
        "why": "a toy"})
    bench["workloads"].append({
        "name": "toy_cell", "config": "toy_twin", "traffic": "toy_batches",
        "chips": 1, "why": "a toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "twin_steps_per_s":
            m["workloads"].append("toy_cell")
    for name in METRICS:
        bench["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "toy",
            "moves": "twin_steps_per_s", "workloads": ["toy_cell"]})
    return bench


def _snapshot(root: pathlib.Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_alone_make_a_cell(tmp_path):
    harness = tmp_path / "benchmarks" / "harness"
    shutil.copytree(spec.HARNESS, harness,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _snapshot(harness)
    for rel, text in FILES.items():
        path = harness / rel
        path.parent.mkdir(exist_ok=True)
        assert not path.exists(), rel
        path.write_text(text)
    (tmp_path / "BENCHMARK.json").write_text(
        json.dumps(_entries(spec.load_benchmark())))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{tmp_path}{os.pathsep}{spec.ROOT / 'src'}")
    out = subprocess.run([sys.executable, "-c", RUN], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert pathlib.Path(got["at"]).parent == harness
    plain, traced = got["plain"], got["traced"]
    assert plain["correct"] and traced["correct"], plain["checks"]
    assert set(plain["metrics"]) == {"twin_steps_per_s", "setup_s"}
    assert set(traced["metrics"]) == set(METRICS)
    assert all(m["value"] > 0 for m in traced["metrics"].values())
    after = _snapshot(harness)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {pathlib.Path(k) for k in FILES}

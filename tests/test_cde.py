"""The neural CDE field (Kidger et al., arXiv:2005.08926) on every tier,
against the plain reference ``kernels.ref.cde_rollout_ref``, on seeded
random weights with Pallas interpreted.

Tolerances are relative to the reference's largest |z| over the rollout:

- ``f32``: 1e-5.  The kernel and the reference both compute in f32 and
  differ only in the order of their sums (measured ~2e-7 over 16 steps).
- ``bf16_f32acc``: the kernel rounds every matmul operand to bf16
  (2^-9 relative) through 5 layers, 4 evaluations a step and 16 steps
  (measured 0.003 small, 0.0044 at the published widths).  The limit is
  set between that and the same reference with its matmul operands at
  fp8 e4m3 (measured 0.0094 and 0.027), and the test checks that the
  fp8 rollout fails it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import FusedPallasBackend
from repro.core.twin import TwinFleet, make_cde_twin
from repro.kernels import ops, ref
from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET, CDEField,
                                         rollout_plan)
from repro.launch.fleet_serving import StreamingFleetServer

# (D, H, Du, hidden layers, twins, steps): small, and the Speech Commands
# widths 90 -> 40 x 4 -> 1890 read (90, 21)
SMALL = (6, 5, 3, 2, 8, 16)
PUBLISHED = (90, 40, 21, 4, 8, 16)
TOL = {("small", "f32"): 1e-5, ("published", "f32"): 1e-5,
       ("small", "bf16_f32acc"): 0.006, ("published", "bf16_f32acc"): 0.012}
DT = 0.5


def _setup(D, H, Du, L, B, T, seed=0):
    sizes = (D,) + (H,) * L + (D * Du,)
    n = len(sizes) - 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 2 * n + 2)
    ws = [jax.random.normal(k, (i, o)) / np.sqrt(i)
          for k, i, o in zip(ks, sizes[:-1], sizes[1:])]
    bs = [0.3 * jax.random.normal(k, (o,)) for k, o in zip(ks[n:], sizes[1:])]
    z0 = jax.random.normal(ks[-2], (B, D))
    uh = 0.3 * jax.random.normal(ks[-1], (B, 2 * T + 1, Du))
    return ws, bs, z0, uh


def _fp8(x):
    """x at fp8 e4m3, scaled per tensor to the format's largest value."""
    scale = jnp.maximum(jnp.abs(x).max(), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _fp8_rollout(z0, uh, ws, bs, dt):
    """The reference's rollout with every matmul operand at fp8."""
    def f(u, z):
        x = z
        for i, (w, b) in enumerate(zip(ws, bs)):
            x = jnp.dot(_fp8(x), _fp8(w), precision="highest") + b
            if i < len(ws) - 1:
                x = jax.nn.relu(x)
        g = jnp.tanh(x).reshape(z.shape[0], z.shape[1], u.shape[-1])
        return jnp.einsum("bic,bc->bi", g, u, precision="highest")

    u = jnp.transpose(uh, (1, 0, 2))

    def step(z, t):
        k1 = f(u[2 * t], z)
        k2 = f(u[2 * t + 1], z + dt / 2 * k1)
        k3 = f(u[2 * t + 1], z + dt / 2 * k2)
        k4 = f(u[2 * t + 2], z + dt * k3)
        z = z + dt / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
        return z, z

    _, zs = jax.lax.scan(step, z0, jnp.arange((u.shape[0] - 1) // 2))
    return jnp.concatenate([z0[None], zs])


def _rel(a, r):
    return float(jnp.abs(jnp.asarray(a, jnp.float32) - r).max()
                 / jnp.abs(r).max())


@pytest.mark.parametrize("precision", ["f32", "bf16_f32acc"])
@pytest.mark.parametrize("size", ["small", "published"])
def test_fused_cde_matches_reference(size, precision):
    D, H, Du, L, B, T = SMALL if size == "small" else PUBLISHED
    ws, bs, z0, uh = _setup(D, H, Du, L, B, T)
    r = ref.cde_rollout_ref(z0, uh, ws, bs, DT)
    out = ops.fused_node_rollout(
        [{"w": w, "b": b} for w, b in zip(ws, bs)], z0, uh, DT,
        batch_tile=B, gradient="stopgrad", precision=precision,
        field=CDEField(L + 1, D, Du))
    assert out.shape == (T + 1, B, D)
    tol = TOL[(size, precision)]
    assert _rel(out, r) <= tol
    if precision != "f32":
        # the limit is tight enough that a precision step lower fails it
        assert _rel(_fp8_rollout(z0, uh, ws, bs, DT), r) > tol


def _published_layers():
    sizes = (90, 40, 40, 40, 40, 1890)
    return ([jnp.zeros((i, o)) for i, o in zip(sizes[:-1], sizes[1:])],
            [jnp.zeros((o,)) for o in sizes[1:]])


def test_plan_splits_a_clip_at_the_published_widths():
    """A 160-step window of 64 twins does not fit one VMEM chunk: the
    (2C+1, 64, 21) f32 control block pads to 128 lanes (32 KiB a
    half-step row) beside the (64, 2688) f32 tiles of the field."""
    ws, bs = _published_layers()
    plan = rollout_plan(64, 90, (64, 321, 21), ws, bs, batch_tile=64,
                        precision="bf16_f32acc", field=CDEField(5, 90, 21))
    assert plan.num_chunks >= 2
    assert plan.vmem_bytes <= DEFAULT_VMEM_BUDGET


def _kernel_grid(jaxpr):
    """The grid of the first pallas_call in ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["grid_mapping"].grid
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            inner = getattr(inner, "jaxpr", inner)
            if inner is not None and hasattr(inner, "eqns"):
                grid = _kernel_grid(inner)
                if grid is not None:
                    return grid
    return None


@pytest.mark.parametrize("batch", [8, 64])
def test_time_chunks_counter_is_the_kernels_plan(batch):
    """The serving counter's chunk count is the grid the forward kernel
    runs, for a batch under one tile (the tile shrinks to it, so its
    chunk grows) as for a full tile."""
    ws, bs = _published_layers()
    backend = FusedPallasBackend(batch_tile=64, precision="bf16_f32acc")
    twin = make_cde_twin(90, 21, hidden=40, n_hidden_layers=4)
    state = backend.program(twin.node.field,
                            [{"w": w, "b": b} for w, b in zip(ws, bs)])
    ys, uh = jnp.zeros((batch, 90)), jnp.zeros((batch, 321, 21))
    jaxpr = jax.make_jaxpr(lambda y, u: backend._solve(
        state, y, u, 1.0, min(64, batch), "stopgrad"))(ys, uh)
    assert _kernel_grid(jaxpr.jaxpr)[1] == backend.time_chunks(state, ys, uh)
    assert backend.time_chunks(state, ys, uh) == (1 if batch == 8 else 4)


def _control(t, theta):
    """dX/dt of X = (t, sin(2π f_c t + φ_c)), θ = (f, φ)."""
    f, phi = jnp.split(theta, 2)
    w = 2.0 * jnp.pi * f
    return jnp.concatenate([jnp.ones((1,)), w * jnp.cos(w * t + phi)])


def _fleet(backend):
    D, H, Du, L, _, _ = SMALL
    twin = make_cde_twin(D, Du, hidden=H, n_hidden_layers=L)
    params = twin.init(jax.random.PRNGKey(3))
    params = [{"w": 0.5 * p["w"], "b": p["b"] + 0.1} for p in params]
    return TwinFleet(twin.with_backend(backend), drive_family=_control), params


def _twins(n, Du):
    rng = np.random.default_rng(7)
    theta = np.concatenate([rng.uniform(0.02, 0.1, (n, Du - 1)),
                            rng.uniform(0, 2 * np.pi, (n, Du - 1))], 1)
    return (rng.normal(size=(n, SMALL[0])).astype(np.float32),
            theta.astype(np.float32))


def _reference(params, y0, theta, starts, steps):
    ths = ops.half_step_times(0.0, DT, steps, starts)
    uh = jax.vmap(lambda ts, th: jax.vmap(lambda t: _control(t, th))(ts))(
        ths, jnp.asarray(theta))
    r = ref.cde_rollout_ref(jnp.asarray(y0), uh, [p["w"] for p in params],
                            [p["b"] for p in params], DT)
    return np.transpose(np.asarray(r), (1, 0, 2))


def test_streaming_cde_windows_chain_bitwise():
    """Served through StreamingFleetServer on the fused tier at f32, a
    twin's windows chain bit for bit across pumps: 3 windows of 8 steps
    split over 4-step pumps equal one uninterrupted 24-step rollout."""
    n, Du = 8, SMALL[2]
    y0, theta = _twins(n, Du)

    def serve(max_window, horizons):
        fleet, params = _fleet(FusedPallasBackend(batch_tile=8,
                                                  precision="f32"))
        server = StreamingFleetServer(fleet, params, dt=DT, hot_capacity=n,
                                      max_batch=n, max_window=max_window,
                                      horizon_quantum=4)
        for i in range(n):
            server.register_twin(i, y0[i], theta=theta[i])
        rows = {i: [] for i in range(n)}
        for h in horizons:
            for i in range(n):
                server.submit(i, h)
            for c in server.drain():
                prev = rows[c.twin_id]
                assert c.start_step == sum(len(w) - 1 for w in prev)
                if prev:
                    assert np.array_equal(c.trajectory[0], prev[-1][-1])
                prev.append(c.trajectory)
        whole = np.stack([np.concatenate([rows[i][0]] + [
            w[1:] for w in rows[i][1:]]) for i in range(n)])
        return params, whole, server.stats().stream

    params, split, stats = serve(4, [8, 8, 8])
    _, whole, _ = serve(24, [24])
    assert split.shape == (n, 25, SMALL[0])
    assert np.array_equal(split, whole)
    assert stats.batches == 6 and stats.time_chunks >= stats.batches
    assert stats.drive_bytes == stats.batches * n * 9 * Du * 4
    r = _reference(params, y0, theta, np.zeros(n, np.int64), 24)
    assert _rel(split, r) <= TOL[("small", "f32")]


def test_digital_tier_matches_reference():
    """The server's fallback tier runs the field through ``core.ode``."""
    n, Du = 8, SMALL[2]
    y0, theta = _twins(n, Du)
    fleet, params = _fleet("digital")
    starts = np.arange(n) * 5
    out = fleet.rollout_batch_resumed(params, y0, dt=DT, num_steps=16,
                                      start_steps=starts,
                                      drive_params=jnp.asarray(theta))
    r = _reference(params, y0, theta, starts, 16)
    # f32 both, different evaluation order (odeint vs scan of the reference)
    assert _rel(out, r) <= 1e-5


def test_fused_backward_refuses_cde():
    D, H, Du, L, B, T = SMALL
    ws, bs, z0, uh = _setup(D, H, Du, L, B, 4)
    params = [{"w": w, "b": b} for w, b in zip(ws, bs)]
    field = CDEField(L + 1, D, Du)

    def loss(p):
        return ops.fused_node_rollout(p, z0, uh, DT, batch_tile=B,
                                      precision="f32", field=field).sum()

    # the forward serves under the differentiable mode too ...
    assert np.isfinite(float(loss(params)))
    # ... and differentiating it says why it cannot
    with pytest.raises(NotImplementedError, match="no backward for CDEField"):
        jax.grad(loss)(params)

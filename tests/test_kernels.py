"""Per-kernel shape/dtype sweeps asserting allclose vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.analogue import AnalogueSpec, program_mlp
from repro.core.losses import dtw as dtw_jnp, soft_dtw as soft_dtw_jnp
from repro.core.node import mlp_init
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# fused ODE MLP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes,drive_dim,state_dim", [
    ((2, 14, 14, 1), 1, 1),      # paper's HP-twin arrays (2x14, 14x14, 14x1)
    ((6, 64, 64, 6), 0, 6),      # paper's Lorenz96 twin
    ((3, 8, 2), 1, 2),           # 2-layer variant
    ((4, 32, 32, 32, 4), 0, 4),  # 4-layer variant
])
@pytest.mark.parametrize("batch,T", [(8, 16), (16, 50)])
def test_fused_node_matches_ref(sizes, drive_dim, state_dim, batch, T):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, sizes[1]))
    params = mlp_init(k1, sizes)
    y0 = 0.3 * jax.random.normal(k2, (batch, state_dim))
    ts = jnp.linspace(0.0, 0.5, T + 1)
    dt = float(ts[1] - ts[0])
    if drive_dim:
        uh = ops.half_step_drive(lambda t: jnp.sin(4 * t), ts)
    else:
        uh = jnp.zeros((2 * T + 1, 0))
    out_k = ops.fused_node_rollout(params, y0, uh, dt, batch_tile=8,
                                   precision="f32")
    out_r = ops.fused_node_rollout_ref(params, y0, uh, dt)
    np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-5)
    assert out_k.shape == (T + 1, batch, state_dim)


@pytest.mark.parametrize("T,chunk", [
    (5, 8),     # single partial chunk (chunk > T)
    (8, 4),     # exactly two chunks
    (5, 4),     # two chunks, T not divisible by the chunk
    (20, 4),    # many chunks
    (21, 4),    # many chunks + partial tail
])
def test_fused_node_time_chunks_match_ref(T, chunk):
    """The time-chunked grid must carry the state across chunk boundaries
    exactly — parity vs the jnp reference straddling one/two/many chunks,
    including T not divisible by the chunk size."""
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 31 * T + chunk))
    params = mlp_init(k1, (2, 14, 14, 1))
    y0 = 0.3 * jax.random.normal(k2, (8, 1))
    ts = jnp.linspace(0.0, 0.5, T + 1)
    uh = ops.half_step_drive(lambda t: jnp.sin(4 * t), ts)
    out_k = ops.fused_node_rollout(params, y0, uh, float(ts[1] - ts[0]),
                                   batch_tile=4, time_chunk=chunk,
                                   precision="f32")
    out_r = ops.fused_node_rollout_ref(params, y0, uh, float(ts[1] - ts[0]))
    assert out_k.shape == (T + 1, 8, 1)
    np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-5)


def test_fused_node_time_chunks_per_tile_drive():
    """Per-twin drives must be sliced per (tile, chunk) cell correctly."""
    params = mlp_init(KEY, (2, 14, 14, 1))
    T, B = 11, 8
    ts = jnp.linspace(0.0, 0.5, T + 1)
    amps = 0.5 + jnp.arange(B, dtype=jnp.float32) / B
    uh = jnp.stack([ops.half_step_drive(lambda t, a=a: a * jnp.sin(4 * t), ts)
                    for a in amps])                       # (B, 2T+1, 1)
    y0 = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 5), (B, 1))
    out_k = ops.fused_node_rollout(params, y0, uh, float(ts[1] - ts[0]),
                                   batch_tile=4, time_chunk=3,
                                   precision="f32")
    out_r = ops.fused_node_rollout_ref(params, y0, uh, float(ts[1] - ts[0]))
    np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-5)


def test_fused_node_long_horizon_no_vmem_error():
    """The old hard VMEM guard is gone: the exact shape that used to raise
    'needs ~X MiB VMEM' now auto-chunks over time and matches the
    reference at T=10,000 (acceptance: max abs err <= 1e-4)."""
    from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET,
                                             plan_time_chunk)
    params = mlp_init(KEY, (6, 64, 64, 6))
    w = [p["w"].astype(jnp.float32) for p in params]
    b = [p["b"].astype(jnp.float32) for p in params]
    T = 10000
    plan = plan_time_chunk(T, 64, 6, 0, False, w, b, DEFAULT_VMEM_BUDGET)
    assert plan.num_chunks > 1                # genuinely exceeds one chunk
    assert plan.vmem_bytes <= DEFAULT_VMEM_BUDGET
    y0 = 0.1 * jax.random.normal(jax.random.fold_in(KEY, 9), (64, 6))
    uh = jnp.zeros((2 * T + 1, 0))
    out_k = ops.fused_node_rollout(params, y0, uh, 1e-4,
                                   precision="f32")   # no ValueError
    out_r = ops.fused_node_rollout_ref(params, y0, uh, 1e-4)
    assert out_k.shape == (T + 1, 64, 6)
    assert float(jnp.abs(out_k - out_r).max()) <= 1e-4


# ---------------------------------------------------------------------------
# mixed precision (the bf16 streaming policies)
# ---------------------------------------------------------------------------

# documented per-policy tolerances for the HP-shaped rollout (see
# docs/kernels.md "Precision policy"): bf16 storage rounds each stored
# row to ~2^-8 relative, and the chunk-boundary carry re-rounds once per
# chunk; f32 accumulation keeps the in-chunk integration exact.
PRECISION_REL_TOL = {"f32": 1e-5, "bf16_f32acc": 1e-2, "bf16": 4e-2}


@pytest.mark.parametrize("precision", ["f32", "bf16_f32acc", "bf16"])
def test_fused_node_precision_parity(precision):
    """Reduced-precision rollouts track the f32 reference within the
    documented per-policy tolerance (HP-twin config, chunk-straddling)."""
    params = mlp_init(KEY, (2, 14, 14, 1))
    y0 = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 77), (8, 1))
    T = 50
    ts = jnp.linspace(0.0, 0.5, T + 1)
    uh = ops.half_step_drive(lambda t: jnp.sin(4 * t), ts)
    dt = float(ts[1] - ts[0])
    out_k = ops.fused_node_rollout(params, y0, uh, dt, batch_tile=4,
                                   time_chunk=7, precision=precision)
    out_r = ops.fused_node_rollout_ref(params, y0, uh, dt)
    if precision == "f32":
        assert out_k.dtype == jnp.float32
    else:
        assert out_k.dtype == jnp.bfloat16   # half the HBM bytes
    rel = float(jnp.abs(out_k.astype(jnp.float32) - out_r).max()
                / jnp.abs(out_r).max())
    assert rel <= PRECISION_REL_TOL[precision]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("precision", ["f32", "bf16_f32acc"])
def test_fused_node_matmul_precision(precision, grad):
    """Under ``f32`` every in-kernel matmul asks for full-precision
    products: Mosaic's default f32 matmul is one bf16 MXU pass, which the
    interpreter does not show (2.3e-3 off the f32 reference on a v5e at
    HP 256 x 500).  The bf16 policies keep the default."""
    import re
    params = mlp_init(KEY, (2, 14, 14, 1))
    y0 = jnp.zeros((8, 1))
    uh = jnp.zeros((2 * 10 + 1, 1))

    def run(p):
        out = ops.fused_node_rollout(p, y0, uh, 1e-3, precision=precision)
        return jnp.sum(out.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(run) if grad else run)(params))
    dots = re.findall(r"dot_general\[(.*?)\n\s*\]", text, re.DOTALL)
    assert dots
    highest = ["Precision.HIGHEST" in d for d in dots]
    assert all(highest) if precision == "f32" else not any(highest)


def test_plan_time_chunk_bf16_doubles_chunk():
    """The ISSUE acceptance: dtype-aware planning must give bf16 >= 1.8x
    the f32 time chunk at the default VMEM budget (and the plan must
    actually fit it)."""
    from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET,
                                             plan_time_chunk)
    params = mlp_init(KEY, (6, 64, 64, 6))
    w = [p["w"].astype(jnp.float32) for p in params]
    b = [p["b"].astype(jnp.float32) for p in params]
    T = 10 ** 9                       # never clamp C at the horizon
    p32 = plan_time_chunk(T, 64, 6, 0, False, w, b, DEFAULT_VMEM_BUDGET)
    for bf in ["bf16", "bf16_f32acc"]:
        pbf = plan_time_chunk(T, 64, 6, 0, False, w, b,
                              DEFAULT_VMEM_BUDGET, precision=bf)
        assert pbf.time_chunk >= 1.8 * p32.time_chunk
        assert pbf.vmem_bytes <= DEFAULT_VMEM_BUDGET
    # the weights-must-fit threshold moves too: a budget that rejects
    # f32 weights can still fit the bf16-stored ones (double-buffered
    # and tiled, the f32 weights alone take ~0.9 MiB, bf16 ~0.45 MiB)
    big = mlp_init(jax.random.fold_in(KEY, 1), (64, 256, 256, 64))
    wb = [p["w"].astype(jnp.float32) for p in big]
    bb = [p["b"].astype(jnp.float32) for p in big]
    budget = 700 * 1024
    with pytest.raises(ValueError, match="VMEM"):
        plan_time_chunk(100, 8, 64, 0, False, wb, bb, budget)
    plan = plan_time_chunk(100, 8, 64, 0, False, wb, bb, budget,
                           precision="bf16_f32acc")
    assert plan.time_chunk >= 1


def test_fused_node_rejects_non_float_inputs():
    """Clear ValueError naming the offending input instead of an opaque
    Mosaic lowering failure (ISSUE satellite)."""
    params = mlp_init(KEY, (2, 8, 1))
    y0 = jnp.zeros((4, 1))
    uh = jnp.zeros((11, 1))
    with pytest.raises(ValueError, match="y0"):
        ops.fused_node_rollout(params, y0.astype(jnp.int32), uh, 1e-2)
    with pytest.raises(ValueError, match="u_half"):
        ops.fused_node_rollout(params, y0, uh.astype(jnp.int32), 1e-2)
    bad = [dict(p) for p in params]
    bad[1]["w"] = bad[1]["w"].astype(jnp.int8)
    with pytest.raises(ValueError, match=r"params\[1\]\['w'\]"):
        ops.fused_node_rollout(bad, y0, uh, 1e-2)


def test_force_interpret_env_override(monkeypatch):
    """REPRO_FORCE_INTERPRET pins the lowering mode of every kernel
    module (they all resolve ``interpret=None`` through
    ``platform.default_interpret``) without monkeypatching jax."""
    from repro.kernels.platform import default_interpret
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert default_interpret() is True
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    assert default_interpret() is False
    # common boolean-env spellings work; garbage names the variable
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "true")
    assert default_interpret() is True
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "off")
    assert default_interpret() is False
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "maybe")
    with pytest.raises(ValueError, match="REPRO_FORCE_INTERPRET"):
        default_interpret()
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "")
    assert default_interpret() is (jax.default_backend() != "tpu")


@pytest.mark.parametrize("precision", ["bf16_f32acc", "bf16"])
def test_softdtw_bf16_cost_matrix(precision):
    """The wavefront kernels accept a bf16 cost slab; f32 R/E carries
    keep the DP well-conditioned (forward AND E-matrix backward)."""
    x = jax.random.normal(KEY, (2, 60, 2))
    y = jax.random.normal(jax.random.fold_in(KEY, 7), (2, 80, 2))
    sk = ops.soft_dtw(x, y, 0.5, True, precision)
    sr = jax.vmap(lambda a, b: soft_dtw_jnp(a, b, 0.5))(x, y)
    assert sk.dtype == jnp.float32            # answer stays full precision
    np.testing.assert_allclose(sk, sr, rtol=2e-3, atol=1e-3)
    gk = jax.grad(lambda a: ops.soft_dtw(a, y, 0.5, True, precision).sum())(x)
    gr = jax.grad(
        lambda a: jax.vmap(lambda p, q: soft_dtw_jnp(p, q, 0.5))(a, y).sum())(x)
    np.testing.assert_allclose(gk, gr, rtol=3e-2, atol=2e-2)


def test_fused_node_vmem_guard_only_when_weights_dont_fit():
    """ValueError survives only for the genuinely impossible cases: the
    weights plus a single RK4 step exceed the budget, or an explicit
    time_chunk is oversized for it."""
    params = mlp_init(KEY, (6, 64, 64, 6))
    y0 = jnp.zeros((64, 6))
    uh = jnp.zeros((2 * 100 + 1, 0))
    with pytest.raises(ValueError, match="VMEM"):
        ops.fused_node_rollout(params, y0, uh, 1e-3,
                               vmem_budget_bytes=16 * 1024)
    with pytest.raises(ValueError, match="time_chunk"):
        ops.fused_node_rollout(params, y0, uh, 1e-3, time_chunk=100,
                               vmem_budget_bytes=128 * 1024)


def test_fused_node_matches_odeint():
    """The kernel must agree with the framework's own RK4 odeint."""
    from repro.core.node import MLPVectorField
    from repro.core.ode import odeint

    field = MLPVectorField(sizes=(2, 14, 14, 1),
                           drive=lambda t: jnp.sin(4 * t))
    params = field.init(KEY)
    T = 32
    ts = jnp.linspace(0.0, 0.25, T + 1)
    y0 = jnp.array([[0.2]])
    ys = odeint(field, y0[0], ts, params, method="rk4")
    uh = ops.half_step_drive(lambda t: jnp.sin(4 * t), ts)
    out = ops.fused_node_rollout(params, y0, uh, float(ts[1] - ts[0]),
                                 batch_tile=1, precision="f32")
    np.testing.assert_allclose(out[:, 0, :], ys, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# crossbar VMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (1, 3, 15), (8, 65, 14), (37, 129, 100), (130, 256, 257), (256, 512, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_crossbar_shapes(m, k, n, dtype):
    spec = AnalogueSpec(prog_noise=0.02)
    kx, kw = jax.random.split(jax.random.fold_in(KEY, m * k + n))
    x = jax.random.normal(kx, (m, k), dtype)
    from repro.core.analogue import program_tensor
    w = jax.random.normal(kw, (k, n))
    prog = program_tensor(kw, w, spec)
    yk = ops.crossbar_vmm(prog, x, spec)
    yr = ref.crossbar_matmul_ref(x, prog["gp"], prog["gm"], 1.0,
                                 spec.v_clamp) / prog["scale"]
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(16, 64, 32), (33, 200, 129)])
def test_crossbar_quantized(m, k, n):
    spec = AnalogueSpec()
    kx, kw = jax.random.split(jax.random.fold_in(KEY, m + k + n))
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n))
    gpq, gmq, scale = ops.quantize_to_levels(w, spec)
    assert gpq.dtype == jnp.uint8
    yq = ops.crossbar_vmm_quantized(x, gpq, gmq, spec, scale)
    g_step = (spec.g_max - spec.g_min) / (spec.levels - 1)
    yr = ref.crossbar_matmul_q_ref(x, gpq, gmq, g_step, 1.0,
                                   spec.v_clamp) / scale
    np.testing.assert_allclose(np.asarray(yq), np.asarray(yr), rtol=1e-5,
                               atol=1e-5)
    # quantisation itself must stay within half a level of the ideal weight
    ideal = x @ w
    lvl_err = jnp.abs(yq - ideal).max() / (jnp.abs(w).max() * k)
    assert float(lvl_err) < 1.0 / spec.levels


def test_crossbar_quantized_matches_digital_coarsely():
    """6-bit differential storage should approximate the digital matmul."""
    spec = AnalogueSpec()
    w = jax.random.normal(KEY, (64, 64)) * 0.1
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (16, 64))
    gpq, gmq, scale = ops.quantize_to_levels(w, spec)
    y = ops.crossbar_vmm_quantized(x, gpq, gmq, spec, scale)
    rel = jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w)
    assert float(rel) < 0.05


# ---------------------------------------------------------------------------
# soft-DTW wavefront
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,d", [
    (1, 1, 1), (5, 5, 1), (50, 70, 2), (128, 128, 3), (300, 200, 1),
    (257, 513, 2),
])
def test_softdtw_shapes(n, m, d):
    kx, ky = jax.random.split(jax.random.fold_in(KEY, n * m))
    x = jax.random.normal(kx, (2, n, d))
    y = jax.random.normal(ky, (2, m, d))
    sk = ops.soft_dtw(x, y, 0.7, True, "f32")
    sr = jax.vmap(lambda a, b: soft_dtw_jnp(a, b, 0.7))(x, y)
    np.testing.assert_allclose(sk, sr, rtol=1e-4, atol=1e-4)
    hk = ops.dtw_distance(x, y)
    hr = jax.vmap(dtw_jnp)(x, y)
    np.testing.assert_allclose(hk, hr, rtol=1e-5, atol=1e-5)


def test_softdtw_grad_matches_ref():
    x = jax.random.normal(KEY, (2, 40, 2))
    y = jax.random.normal(jax.random.fold_in(KEY, 7), (2, 60, 2))
    gk = jax.grad(lambda a: ops.soft_dtw(a, y, 0.5, True, "f32").sum())(x)
    gr = jax.grad(
        lambda a: jax.vmap(lambda p, q: soft_dtw_jnp(p, q, 0.5))(a, y).sum())(x)
    np.testing.assert_allclose(gk, gr, rtol=1e-4, atol=1e-5)


def test_dtw_identity_is_zero():
    x = jax.random.normal(KEY, (1, 64, 2))
    assert float(ops.dtw_distance(x, x)[0]) == pytest.approx(0.0, abs=1e-6)


def test_dtw_shift_invariance_property():
    """DTW of a time-warped copy must be far below an unrelated series."""
    t = jnp.linspace(0, 6.28, 100)
    a = jnp.sin(t)[None, :, None]
    warped = jnp.sin(t ** 1.08 / t[-1] ** 0.08)[None, :, None]
    noise = jax.random.normal(KEY, (1, 100, 1))
    d_w = float(ops.dtw_distance(a, warped)[0])
    d_n = float(ops.dtw_distance(a, noise)[0])
    assert d_w < 0.2 * d_n


# ---------------------------------------------------------------------------
# crossbar VMM: kernel-level clamp, read noise, masked padding
# ---------------------------------------------------------------------------

def _toy_pair(k, n, seed=0, quantize=True):
    """A programmed (gp, gm, scale) triple plus the raw weights."""
    from repro.core.analogue import program_tensor
    spec = AnalogueSpec(prog_noise=0.0, quantize=quantize)
    kx, kw = jax.random.split(jax.random.fold_in(KEY, seed + k * n))
    x = jax.random.normal(kx, (11, k))
    w = jax.random.normal(kw, (k, n))
    prog = program_tensor(kw, w, spec)
    return spec, x, w, prog


@pytest.mark.parametrize("m,k,n", [(1, 3, 15), (37, 129, 100), (13, 200, 7)])
@pytest.mark.parametrize("quantized", [False, True])
def test_crossbar_float_vs_quantized_parity_odd_dims(m, k, n, quantized):
    """Float and uint8 storage agree with the jnp reference on odd
    (non-tile-multiple) M/K/N — the accumulator-neutral padding at work."""
    spec = AnalogueSpec(prog_noise=0.0)
    kx, kw = jax.random.split(jax.random.fold_in(KEY, 7 * m + k + n))
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n))
    if quantized:
        gpq, gmq, scale = ops.quantize_to_levels(w, spec)
        got = ops.crossbar_vmm_quantized(x, gpq, gmq, spec, scale)
        g_step = (spec.g_max - spec.g_min) / (spec.levels - 1)
        want = ref.crossbar_matmul_q_ref(x, gpq, gmq, g_step, 1.0,
                                         spec.v_clamp) / scale
    else:
        from repro.core.analogue import program_tensor
        prog = program_tensor(kw, w, spec)
        got = ops.crossbar_vmm(prog, x, spec)
        want = ref.crossbar_matmul_ref(x, prog["gp"], prog["gm"], 1.0,
                                       spec.v_clamp) / prog["scale"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("clamp", [None, 0.5])
def test_crossbar_kernel_clamp(clamp):
    """The in-kernel clamp epilogue (applied after the true inv_scale)
    must match clip(x @ (gp - gm) * inv_scale)."""
    from repro.kernels.crossbar_vmm import crossbar_matmul
    _, x, w, prog = _toy_pair(130, 150, seed=1)
    inv_scale = 1.0 / float(prog["scale"])
    got = crossbar_matmul(x, prog["gp"], prog["gm"], inv_scale=inv_scale,
                          clamp=clamp)
    want = (x @ (prog["gp"] - prog["gm"])) * inv_scale
    if clamp is not None:
        want = jnp.clip(want, -clamp, clamp)
        assert float(jnp.abs(got).max()) <= clamp + 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_crossbar_read_noise_deterministic(quantized):
    """Same noise_seed => bitwise-identical read; different seed =>
    different read; noise magnitude tracks read_noise."""
    spec, x, w, prog = _toy_pair(130, 150, seed=2)
    kw = dict(read_noise=0.02)
    if quantized:
        gpq, gmq, scale = ops.quantize_to_levels(w, spec)
        run = lambda s: ops.crossbar_vmm_quantized(x, gpq, gmq, spec, scale,
                                                   noise_seed=s, **kw)
        clean = ops.crossbar_vmm_quantized(x, gpq, gmq, spec, scale)
    else:
        run = lambda s: ops.crossbar_vmm(prog, x, spec, noise_seed=s, **kw)
        clean = ops.crossbar_vmm(prog, x, spec)
    a, b, c = run(5), run(5), run(6)
    assert jnp.array_equal(a, b)
    assert not jnp.array_equal(a, c)
    rel = float(jnp.linalg.norm(a - clean) / jnp.linalg.norm(clean))
    assert 0.0 < rel < 0.5


def test_crossbar_noisy_quantized_pad_rows_are_neutral():
    """Masked-padding discipline: in noisy quantised mode the pads
    reconstruct to ~g_min and their noise would NOT cancel — the kernel
    must mask them out.  Parity vs a jnp oracle that perturbs the
    reconstructed conductances with the same counter-derived stream
    catches any pad leakage (K=130, N=150 are not tile multiples)."""
    from repro.kernels.noise import counter_normal
    spec, x, w, _ = _toy_pair(130, 150, seed=3)
    gpq, gmq, scale = ops.quantize_to_levels(w, spec)
    got = ops.crossbar_vmm_quantized(x, gpq, gmq, spec, scale,
                                     read_noise=0.02, noise_seed=9)
    # jnp oracle with the kernel's exact stream: tiles are 128-wide, so
    # (k, n) < (130, 150) spans k-tiles {0,1} x n-tiles {0,1}; rebuild
    # each tile's noise block and crop
    g_step = (spec.g_max - spec.g_min) / (spec.levels - 1)
    gp = spec.g_min + gpq.astype(jnp.float32) * g_step
    gm = spec.g_min + gmq.astype(jnp.float32) * g_step

    def stream(pair_off):
        rows = []
        for kt in range(2):
            row = []
            for nt in range(2):
                salt = kt * (2 * 65536) + nt * 2 + pair_off
                row.append(counter_normal(9, salt, (128, 128)))
            rows.append(jnp.concatenate(row, axis=1))
        return jnp.concatenate(rows, axis=0)[:130, :150]

    gp_n = gp * (1.0 + 0.02 * stream(0))
    gm_n = gm * (1.0 + 0.02 * stream(1))
    want = (x @ gp_n - x @ gm_n) / scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_crossbar_noisy_quantized_requires_g_min():
    from repro.kernels.crossbar_vmm import crossbar_matmul
    spec, x, w, _ = _toy_pair(64, 32, seed=4)
    gpq, gmq, _ = ops.quantize_to_levels(w, spec)
    with pytest.raises(ValueError, match="g_min"):
        crossbar_matmul(x, gpq, gmq, inv_scale=1.0, g_step=1e-6,
                        read_noise=0.01)


def test_counter_normal_stats_and_determinism():
    from repro.kernels.noise import counter_normal
    z1 = counter_normal(3, 7, (256, 256))
    z2 = counter_normal(3, 7, (256, 256))
    z3 = counter_normal(3, 8, (256, 256))
    assert jnp.array_equal(z1, z2)
    assert not jnp.array_equal(z1, z3)
    assert abs(float(z1.mean())) < 0.02
    assert abs(float(z1.std()) - 1.0) < 0.02
    assert bool(jnp.isfinite(z1).all())


def test_crossbar_vmm_validates_inputs():
    spec, x, w, prog = _toy_pair(64, 32, seed=5)
    with pytest.raises(ValueError, match="x"):
        ops.crossbar_vmm(prog, x[0], spec)           # 1-D input
    with pytest.raises(ValueError, match="non-floating"):
        ops.crossbar_vmm(prog, x.astype(jnp.int32), spec)

"""Backend parity: the same weights must produce the same trajectory on
every execution substrate (paper's portability claim, Fig. 3-4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.analogue import AnalogueSpec
from repro.core.backends import (AnalogueBackend, DigitalBackend,
                                 FusedPallasBackend, resolve_backend)
from repro.core.ode import odeint
from repro.core.twin import TwinFleet, make_autonomous_twin, make_driven_twin

KEY = jax.random.PRNGKey(0)
DRIVE = lambda t: jnp.sin(4.0 * t)

NOISE_FREE = AnalogueSpec(prog_noise=0.0, read_noise=0.0, quantize=False)


@pytest.fixture(scope="module")
def hp_setup():
    """Paper's HP-twin shape (2->14->14->1), driven."""
    twin = make_driven_twin(1, DRIVE)
    params = twin.init(KEY)
    ts = jnp.linspace(0.0, 0.25, 51)
    y0 = jnp.array([0.2])
    return twin, params, y0, ts


@pytest.fixture(scope="module")
def l96_setup():
    """Paper's Lorenz96-twin shape (6->64->64->6), autonomous."""
    twin = make_autonomous_twin(6)
    params = twin.init(jax.random.fold_in(KEY, 1))
    ts = jnp.linspace(0.0, 0.125, 51)
    y0 = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 2), (6,))
    return twin, params, y0, ts


# ---------------------------------------------------------------------------
# (a) digital backend == odeint, exactly
# ---------------------------------------------------------------------------

def test_digital_backend_equals_odeint(hp_setup):
    twin, params, y0, ts = hp_setup
    got = twin.with_backend(DigitalBackend()).simulate(params, y0, ts)
    want = odeint(twin.field, y0, ts, params, method="rk4")
    assert jnp.array_equal(got, want)


def test_default_backend_is_digital(hp_setup):
    twin, params, y0, ts = hp_setup
    default = twin.simulate(params, y0, ts)
    explicit = twin.with_backend("digital").simulate(params, y0, ts)
    assert jnp.array_equal(default, explicit)


def test_resolve_backend_names():
    assert isinstance(resolve_backend("digital"), DigitalBackend)
    assert isinstance(resolve_backend("analogue"), AnalogueBackend)
    assert isinstance(resolve_backend("fused_pallas"), FusedPallasBackend)
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("quantum")


# ---------------------------------------------------------------------------
# (b) fused Pallas == digital within 1e-4
# ---------------------------------------------------------------------------

def test_fused_matches_digital_hp_driven(hp_setup):
    twin, params, y0, ts = hp_setup
    dig = twin.simulate(params, y0, ts)
    fus = twin.with_backend(
        FusedPallasBackend(batch_tile=1, precision="f32")).simulate(
            params, y0, ts)
    np.testing.assert_allclose(fus, dig, atol=1e-4, rtol=1e-4)


def test_fused_matches_digital_l96_autonomous(l96_setup):
    twin, params, y0, ts = l96_setup
    dig = twin.simulate(params, y0, ts)
    fus = twin.with_backend(
        FusedPallasBackend(batch_tile=1, precision="f32")).simulate(
            params, y0, ts)
    np.testing.assert_allclose(fus, dig, atol=1e-4, rtol=1e-4)


def test_fused_honours_steps_per_interval(hp_setup):
    twin, params, y0, ts = hp_setup
    twin_s = make_driven_twin(1, DRIVE, steps_per_interval=4)
    dig = twin_s.simulate(params, y0, ts)
    fus = twin_s.with_backend(
        FusedPallasBackend(precision="f32")).simulate(params, y0, ts)
    assert fus.shape == dig.shape
    np.testing.assert_allclose(fus, dig, atol=1e-4, rtol=1e-4)


def test_fused_rejects_non_uniform_grid(hp_setup):
    twin, params, y0, _ = hp_setup
    bad_ts = jnp.array([0.0, 0.1, 0.15, 0.4])
    with pytest.raises(ValueError, match="uniform"):
        twin.with_backend(FusedPallasBackend()).simulate(params, y0, bad_ts)


def test_fused_rejects_non_rk4(hp_setup):
    twin, params, y0, ts = hp_setup
    import dataclasses
    node = dataclasses.replace(twin.node, method="euler",
                               backend=FusedPallasBackend())
    with pytest.raises(ValueError, match="RK4"):
        node.trajectory(params, y0, ts)


def test_interpret_autodetect_off_tpu():
    from repro.kernels.platform import default_interpret
    if jax.default_backend() == "tpu":
        assert default_interpret() is False
    else:
        # CPU/GPU hosts must fall back to the Pallas interpreter
        assert default_interpret() is True


# ---------------------------------------------------------------------------
# (b') mixed precision: bf16 substrate == f32 digital within the
#      documented per-policy tolerance (docs/kernels.md)
# ---------------------------------------------------------------------------

# ISSUE acceptance: <= 1e-2 rel on the HP-twin config for bf16_f32acc;
# pure-bf16 carries compound one rounding per step, so its gate is wider
PRECISION_REL_TOL = {"f32": 1e-4, "bf16_f32acc": 1e-2, "bf16": 4e-2}


@pytest.mark.parametrize("precision", ["f32", "bf16_f32acc", "bf16"])
def test_fused_precision_matches_digital_hp(hp_setup, precision):
    twin, params, y0, ts = hp_setup
    dig = twin.simulate(params, y0, ts)
    fus = twin.with_backend(
        FusedPallasBackend(batch_tile=1, precision=precision)).simulate(
            params, y0, ts)
    scale = float(jnp.abs(dig).max())
    rel = float(jnp.abs(fus.astype(jnp.float32) - dig).max()) / scale
    assert rel <= PRECISION_REL_TOL[precision]


@pytest.mark.parametrize("precision,tol", [
    # pure bf16 re-rounds the carried state EVERY step, so on the wider
    # chaotic L96 twin the per-step eps (~4e-3) compounds with the flow's
    # Lipschitz growth; f32 accumulation keeps the drift ~30x smaller
    ("bf16_f32acc", 1e-2),
    ("bf16", 2e-1),
])
def test_fused_precision_matches_digital_l96(l96_setup, precision, tol):
    twin, params, y0, ts = l96_setup
    dig = twin.simulate(params, y0, ts)
    fus = twin.with_backend(
        FusedPallasBackend(batch_tile=1, precision=precision)).simulate(
            params, y0, ts)
    scale = float(jnp.abs(dig).max())
    rel = float(jnp.abs(fus.astype(jnp.float32) - dig).max()) / scale
    assert rel <= tol


def test_fused_precision_storage_dtype(hp_setup):
    """The bf16 policies actually store the trajectory at half width —
    the byte win is real, not cosmetic — while the STAGED weights stay
    f32 masters (so a per-call precision override never sees
    pre-rounded operands)."""
    twin, params, y0, ts = hp_setup
    be = FusedPallasBackend(batch_tile=1, precision="bf16_f32acc")
    fus = twin.with_backend(be).simulate(params, y0, ts)
    assert fus.dtype == jnp.bfloat16
    state = be.program(twin.field, params)
    assert all(w.dtype == jnp.float32 for w in state.extra["weights"])
    # f32 policy stays f32; an f32 per-call override on the bf16 backend
    # must match the f32 backend exactly (no double rounding)
    be32 = FusedPallasBackend(batch_tile=1, precision="f32")
    f32_traj = twin.with_backend(be32).simulate(params, y0, ts)
    assert f32_traj.dtype == jnp.float32
    over = be.rollout(be.program(twin.field, params), y0, ts,
                      precision="f32")
    np.testing.assert_array_equal(np.asarray(over), np.asarray(f32_traj))


def test_fused_precision_fleet_and_per_call_override(hp_setup):
    """precision threads through TwinFleet batching AND the per-call
    rollout_batch override used by sharded serving's solver_kw."""
    twin, params, _, ts = hp_setup

    def family(t, theta):
        return theta[0] * jnp.sin(theta[1] * t)

    y0s = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 60), (5, 1))
    thetas = 1.0 + jax.random.uniform(jax.random.fold_in(KEY, 61), (5, 2))
    fleet = TwinFleet(twin, drive_family=family)
    dig = fleet.simulate(params, y0s, ts, thetas)
    bf = fleet.with_backend(
        FusedPallasBackend(batch_tile=4, precision="bf16_f32acc")).simulate(
            params, y0s, ts, thetas)
    assert bf.dtype == jnp.bfloat16
    rel = float(jnp.abs(bf.astype(jnp.float32) - dig).max()
                / jnp.abs(dig).max())
    assert rel <= PRECISION_REL_TOL["bf16_f32acc"]
    # per-call override beats the backend attribute: an f32 backend asked
    # for bf16_f32acc must produce the identical bf16 trajectory
    be32 = FusedPallasBackend(batch_tile=4)
    state = be32.program(twin.field, params)
    over = be32.rollout_batch(state, y0s, ts, drive_family=family,
                              drive_params=thetas,
                              precision="bf16_f32acc")
    np.testing.assert_array_equal(np.asarray(over, np.float32),
                                  np.asarray(bf, np.float32))


def test_fused_precision_sharded_serving_matches_local(hp_setup):
    """The bf16 policy survives shard_map: sharded == single-device on
    the trivial mesh, still at storage dtype."""
    from repro.launch.mesh import make_twin_mesh
    twin, params, _, ts = hp_setup
    y0s = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 62), (6, 1))
    fleet = TwinFleet(twin).with_backend(
        FusedPallasBackend(batch_tile=2, precision="bf16_f32acc"))
    local = fleet.rollout_batch(params, y0s, ts)
    sharded = fleet.rollout_batch(params, y0s, ts, mesh=make_twin_mesh())
    assert sharded.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(sharded, np.float32),
                                  np.asarray(local, np.float32))


# ---------------------------------------------------------------------------
# (c) noise-free analogue == digital within quantisation-free tolerance
# ---------------------------------------------------------------------------

def test_analogue_noise_free_matches_digital(hp_setup):
    twin, params, y0, ts = hp_setup
    dig = twin.simulate(params, y0, ts)
    ana = twin.with_backend(
        AnalogueBackend(spec=NOISE_FREE, prog_key=KEY)).simulate(
            params, y0, ts)
    np.testing.assert_allclose(ana, dig, atol=5e-4, rtol=1e-4)


def test_analogue_backend_supports_dopri5(hp_setup):
    """Adaptive dopri5 twins must still deploy to the analogue substrate
    (regression: the default rollout used to reject 'dopri5')."""
    twin, params, y0, ts = hp_setup
    twin5 = make_driven_twin(1, DRIVE, method="dopri5")
    dig = twin5.simulate(params, y0, ts)
    ana = twin5.with_backend(
        AnalogueBackend(spec=NOISE_FREE, prog_key=KEY)).simulate(
            params, y0, ts)
    np.testing.assert_allclose(ana, dig, atol=5e-4, rtol=1e-4)


def test_analogue_needs_params_or_progs(hp_setup):
    twin, params, y0, ts = hp_setup
    at = twin.with_backend(AnalogueBackend(spec=NOISE_FREE))
    with pytest.raises(ValueError, match="program the crossbars"):
        at.simulate(None, y0, ts)


def test_deploy_analogue_shim_still_works(hp_setup):
    """Legacy path: deprecation warning, pre-programmed crossbars, and
    the old ``simulate(None, ...)`` call pattern."""
    twin, params, y0, ts = hp_setup
    with pytest.warns(DeprecationWarning):
        at = twin.deploy_analogue(KEY, params, NOISE_FREE)
    old = at.simulate(None, y0, ts)
    new = twin.with_backend(
        AnalogueBackend(spec=NOISE_FREE, prog_key=KEY)).simulate(
            params, y0, ts)
    np.testing.assert_allclose(old, new, atol=1e-6)


# ---------------------------------------------------------------------------
# (d) batched fleet == stacked single-trajectory solves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", [
    None,
    FusedPallasBackend(batch_tile=2, precision="f32"),
    AnalogueBackend(spec=NOISE_FREE, prog_key=KEY),
])
def test_simulate_batch_equals_stacked_singles(hp_setup, backend):
    twin, params, y0, ts = hp_setup
    if backend is not None:
        twin = twin.with_backend(backend)
    y0s = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 3), (4, 1))
    batched = twin.simulate_batch(params, y0s, ts)
    stacked = jnp.stack([twin.simulate(params, y, ts) for y in y0s])
    assert batched.shape == stacked.shape == (4, ts.shape[0], 1)
    np.testing.assert_allclose(batched, stacked, atol=1e-5, rtol=1e-5)


def test_fleet_per_twin_drives_match_across_backends(hp_setup):
    """Per-twin drive parameters: the fused grid-tiled path must agree
    with the digital vmap path."""
    twin, params, _, ts = hp_setup

    def family(t, theta):
        return theta[0] * jnp.sin(theta[1] * t)

    y0s = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 4), (4, 1))
    thetas = jnp.array([[1.0, 4.0], [0.5, 8.0], [2.0, 2.0], [1.5, 6.0]])
    fleet = TwinFleet(twin, drive_family=family)
    dig = fleet.simulate(params, y0s, ts, thetas)
    fus = fleet.with_backend(
        FusedPallasBackend(batch_tile=2, precision="f32")).simulate(
            params, y0s, ts, thetas)
    ana = fleet.with_backend(
        AnalogueBackend(spec=NOISE_FREE, prog_key=KEY)).simulate(
            params, y0s, ts, thetas)
    np.testing.assert_allclose(fus, dig, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(ana, dig, atol=5e-4, rtol=1e-4)


def test_fleet_drive_params_contract(hp_setup):
    twin, params, _, ts = hp_setup
    y0s = jnp.zeros((2, 1))
    fleet = TwinFleet(twin, drive_family=lambda t, th: th * jnp.sin(t))
    with pytest.raises(ValueError, match="together"):
        fleet.simulate(params, y0s, ts)


def test_fleet_autonomous_batch(l96_setup):
    twin, params, _, ts = l96_setup
    y0s = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 5), (8, 6))
    dig = TwinFleet(twin).simulate(params, y0s, ts)
    fus = TwinFleet(twin).with_backend(
        FusedPallasBackend(batch_tile=4, precision="f32")).simulate(
            params, y0s, ts)
    np.testing.assert_allclose(fus, dig, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("n", [5, 7, 13])
def test_fused_fleet_prime_sizes_pad_to_tile(hp_setup, n):
    """Prime fleet sizes must PAD up to the batch tile (one extra tile)
    instead of degenerating to bt=1 grid cells — and the padded rows must
    not leak into the result (parity vs the digital vmap path)."""
    twin, params, _, ts = hp_setup
    y0s = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 40 + n), (n, 1))
    dig = twin.simulate_batch(params, y0s, ts)
    fus = twin.with_backend(
        FusedPallasBackend(batch_tile=4, precision="f32")).simulate_batch(
            params, y0s, ts)
    assert fus.shape == dig.shape == (n, ts.shape[0], 1)
    np.testing.assert_allclose(fus, dig, atol=1e-4, rtol=1e-4)


def test_fused_fleet_prime_sizes_pad_per_twin_drives(hp_setup):
    """The padding path must also replicate per-twin drive slabs."""
    twin, params, _, ts = hp_setup

    def family(t, theta):
        return theta[0] * jnp.sin(theta[1] * t)

    n = 5
    y0s = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 50), (n, 1))
    thetas = 1.0 + jax.random.uniform(jax.random.fold_in(KEY, 51), (n, 2))
    fleet = TwinFleet(twin, drive_family=family)
    dig = fleet.simulate(params, y0s, ts, thetas)
    fus = fleet.with_backend(
        FusedPallasBackend(batch_tile=4, precision="f32")).simulate(
            params, y0s, ts, thetas)
    np.testing.assert_allclose(fus, dig, atol=1e-4, rtol=1e-4)


def test_fused_time_chunk_threads_through_backend(hp_setup):
    """An explicit time_chunk forcing many chunks must not change the
    trajectory the backend serves."""
    twin, params, y0, ts = hp_setup
    one = twin.with_backend(
        FusedPallasBackend(batch_tile=1, precision="f32")).simulate(
            params, y0, ts)
    many = twin.with_backend(
        FusedPallasBackend(batch_tile=1, time_chunk=7,
                           precision="f32")).simulate(
            params, y0, ts)
    np.testing.assert_allclose(many, one, atol=1e-6, rtol=1e-6)


def test_fused_fleet_long_horizon_rollout(l96_setup):
    """T=10,000-step fleet serving through the fused backend — the shape
    that used to die on the VMEM guard now streams in time chunks and
    matches the jnp reference kernel within 1e-4."""
    twin, params, _, _ = l96_setup
    from repro.kernels import ops
    T = 10000
    ts = jnp.linspace(0.0, T * 1e-4, T + 1)
    y0s = 0.1 * jax.random.normal(jax.random.fold_in(KEY, 6), (64, 6))
    fleet = TwinFleet(twin).with_backend(
        FusedPallasBackend(batch_tile=64, precision="f32"))
    got = fleet.simulate(params, y0s, ts)
    assert got.shape == (64, T + 1, 6)
    uh = jnp.zeros((2 * T + 1, 0))
    want = jnp.transpose(
        ops.fused_node_rollout_ref(params, y0s, uh, float(ts[1] - ts[0])),
        (1, 0, 2))
    assert float(jnp.abs(got - want).max()) <= 1e-4


# ---------------------------------------------------------------------------
# training still differentiates through the digital backend
# ---------------------------------------------------------------------------

def test_digital_backend_adjoint_gradients(hp_setup):
    twin, params, y0, ts = hp_setup

    def loss(p):
        ys = twin.simulate(p, y0, ts[:9])
        return jnp.mean(ys ** 2)

    grads = jax.grad(loss)(params)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(jnp.all(jnp.isfinite(g)) for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


# ---------------------------------------------------------------------------
# (g) fused-analogue backend: crossbar semantics on the fused kernel
# ---------------------------------------------------------------------------

QUANT_CLEAN = AnalogueSpec(prog_noise=0.0)   # quantised, no noise


def test_resolve_analogue_fused():
    from repro.core.backends import BACKENDS, FusedAnalogueBackend
    assert "analogue_fused" in BACKENDS
    assert isinstance(resolve_backend("analogue_fused"),
                      FusedAnalogueBackend)


def _analogue_pair(twin, params, spec, **fused_kw):
    """(jnp-sim state+backend, fused state+backend) with the SAME
    programming key — bitwise-identical crossbar programs."""
    from repro.core.backends import FusedAnalogueBackend
    sim = AnalogueBackend(spec=spec, prog_key=KEY)
    fused = FusedAnalogueBackend(spec=spec, prog_key=KEY, **fused_kw)
    return (sim, sim.program(twin.node.field, params),
            fused, fused.program(twin.node.field, params))


def test_analogue_fused_matches_sim_hp(hp_setup):
    """Noise-free fused rollout == jnp crossbar simulator (<=1e-5 rel)."""
    twin, params, y0, ts = hp_setup
    sim, st_s, fused, st_f = _analogue_pair(twin, params, QUANT_CLEAN)
    want = sim.rollout(st_s, y0, ts)
    got = fused.rollout(st_f, y0, ts)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert rel <= 1e-5


def test_analogue_fused_matches_sim_l96(l96_setup):
    twin, params, y0, ts = l96_setup
    sim, st_s, fused, st_f = _analogue_pair(twin, params, QUANT_CLEAN)
    want = sim.rollout(st_s, y0, ts)
    got = fused.rollout(st_f, y0, ts)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert rel <= 1e-5


def test_analogue_fused_uint8_matches_float(hp_setup):
    """Noise-free conductances sit exactly ON the 6-bit level grid, so
    the uint8 level-index deployment represents the float program
    exactly; the rollouts agree to float32 rounding (the dequant
    computes (i - j) * step where float mode subtracts the absolute
    conductances — one ulp apart)."""
    twin, params, y0, ts = hp_setup
    _, _, f_float, st_float = _analogue_pair(twin, params, QUANT_CLEAN)
    _, _, f_u8, st_u8 = _analogue_pair(twin, params, QUANT_CLEAN,
                                       storage="uint8")
    assert st_u8.extra["gps"][0].dtype == jnp.uint8
    a = f_float.rollout(st_float, y0, ts)
    b = f_u8.rollout(st_u8, y0, ts)
    rel = float(jnp.abs(a - b).max() / jnp.abs(a).max())
    assert rel <= 1e-6


def test_analogue_fused_fleet_per_twin_drives(hp_setup):
    """Fleet tiling + per-twin drives on the fused-analogue grid must
    match the jnp simulator's vmap path."""
    from repro.core.backends import FusedAnalogueBackend
    twin, params, _, ts = hp_setup

    def family(t, theta):
        return theta[0] * jnp.sin(theta[1] * t)

    y0s = 0.3 * jax.random.normal(jax.random.fold_in(KEY, 11), (4, 1))
    thetas = jnp.array([[1.0, 4.0], [0.5, 8.0], [2.0, 2.0], [1.5, 6.0]])
    fleet = TwinFleet(twin, drive_family=family)
    sim = fleet.with_backend(
        AnalogueBackend(spec=QUANT_CLEAN, prog_key=KEY)).simulate(
            params, y0s, ts, thetas)
    fused = fleet.with_backend(
        FusedAnalogueBackend(spec=QUANT_CLEAN, prog_key=KEY,
                             batch_tile=2)).simulate(
            params, y0s, ts, thetas)
    np.testing.assert_allclose(fused, sim, atol=1e-5, rtol=1e-5)


def test_analogue_fused_read_noise_deterministic(hp_setup):
    """Counter-derived read noise: same seed => bitwise-identical
    rollout; different seed => different trajectory; noise visibly
    perturbs vs the clean solve."""
    import dataclasses
    from repro.core.backends import FusedAnalogueBackend
    twin, params, y0, ts = hp_setup
    spec = AnalogueSpec(prog_noise=0.0, read_noise=0.01)
    be = FusedAnalogueBackend(spec=spec, prog_key=KEY, read_seed=42)
    st = be.program(twin.node.field, params)
    o1 = be.rollout(st, y0, ts)
    o2 = be.rollout(st, y0, ts)
    assert jnp.array_equal(o1, o2)
    be2 = dataclasses.replace(be, read_seed=43)
    o3 = be2.rollout(be2.program(twin.node.field, params), y0, ts)
    assert not jnp.array_equal(o1, o3)
    clean = FusedAnalogueBackend(spec=QUANT_CLEAN, prog_key=KEY)
    o_clean = clean.rollout(clean.program(twin.node.field, params), y0, ts)
    assert float(jnp.abs(o1 - o_clean).max()) > 0.0


def test_analogue_fused_is_detached(hp_setup):
    """The analogue substrate is inference-only: gradients through the
    fused rollout are zero, never an error."""
    from repro.core.backends import FusedAnalogueBackend
    twin, params, y0, ts = hp_setup
    be = FusedAnalogueBackend(spec=QUANT_CLEAN, prog_key=KEY)
    st = be.program(twin.node.field, params)

    g = jax.grad(lambda y: jnp.sum(be.rollout(st, y, ts) ** 2))(y0)
    assert float(jnp.abs(g).max()) == 0.0


@pytest.mark.parametrize("bad,match", [
    (jnp.array([[1, 2], [3, 4]]), "non-floating"),
    (jnp.array([[jnp.nan, 1.0], [0.0, 2.0]]), "NaN"),
])
def test_analogue_programming_validation(bad, match):
    """Programming rejects unprogrammable weights with an error naming
    the offending input."""
    from repro.core.analogue import program_tensor
    with pytest.raises(ValueError, match=match):
        program_tensor(KEY, bad, QUANT_CLEAN, name="w_bad")
    try:
        program_tensor(KEY, bad, QUANT_CLEAN, name="w_bad")
    except ValueError as e:
        assert "w_bad" in str(e)


def test_analogue_fused_storage_validation(hp_setup):
    from repro.core.backends import FusedAnalogueBackend
    twin, params, _, _ = hp_setup
    be = FusedAnalogueBackend(spec=QUANT_CLEAN, storage="int4")
    with pytest.raises(ValueError, match="storage"):
        be.program(twin.node.field, params)

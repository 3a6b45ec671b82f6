"""The main-path Pallas kernels compile for a TPU v5e at the paper
configurations' widths.

Interpret mode (what every other kernel test runs on CPU) accepts block
shapes, dtypes and VMEM footprints that the chip's compiler refuses.
These tests compile each kernel for a described, unattached ``v5e:2x2``
topology — the TPU compiler runs here, nothing executes — and check that
the compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU runtime,
and describing it at import would make pytest-xdist workers collect
different tests.  Where it cannot be described, the tests skip.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.crossbar_vmm import crossbar_matmul
from repro.kernels.fused_analogue import fused_analogue_rollout
from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET, CDEField,
                                         fused_node_rollout)
from repro.kernels.fused_ode_mlp_bwd import fused_node_rollout_vjp

PRECISIONS = ["f32", "bf16_f32acc"]
L96 = dict(sizes=(6, 64, 64, 6), dt=0.0025, T=200)       # FLEET widths
HP = dict(sizes=(2, 14, 14, 1), dt=1e-3, T=500)          # HP twin widths
# the neural CDE at its Speech Commands widths, one 160-step clip
NCDE = dict(sizes=(90, 40, 40, 40, 40, 1890), dt=1.0, T=160, Du=21)


@pytest.fixture(scope="module")
def one_chip():
    """A single described v5e chip, with the persistent compilation
    cache off (its entries for an unattached device cannot be read
    back, so every such compile would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mlp(sharding, sizes):
    pairs = list(zip(sizes[:-1], sizes[1:]))
    return ([_spec(sharding, p) for p in pairs],
            [_spec(sharding, (p[1],)) for p in pairs])


def _compiles_to_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


# the forward also serves the pure "bf16" policy (its backward does not
# compile for a v5e yet)
@pytest.mark.parametrize("precision", PRECISIONS + ["bf16"])
@pytest.mark.parametrize("case", ["l96_fleet", "hp_shared", "hp_per_twin"])
def test_fused_forward_compiles(one_chip, case, precision):
    cfg, B, drive = {
        "l96_fleet": (L96, 1024, (2 * L96["T"] + 1, 0)),
        "hp_shared": (HP, 64, (2 * HP["T"] + 1, 1)),
        "hp_per_twin": (HP, 256, (256, 2 * HP["T"] + 1, 1)),
    }[case]
    w, b = _mlp(one_chip, cfg["sizes"])
    D = cfg["sizes"][-1]
    _compiles_to_kernel(
        lambda y0, u, w, b: fused_node_rollout(
            y0, u, w, b, cfg["dt"], interpret=False, precision=precision),
        _spec(one_chip, (B, D)), _spec(one_chip, drive), w, b)


@pytest.mark.parametrize("precision", PRECISIONS)
def test_fused_cde_forward_compiles(one_chip, precision):
    """The CDE field in the forward kernel over a 1024-twin fleet with a
    per-twin 21-channel control: the (bt, 2688) tanh tile, its lane-block
    contraction and the chunked control slab."""
    w, b = _mlp(one_chip, NCDE["sizes"])
    field = CDEField(len(w), NCDE["sizes"][0], NCDE["Du"])
    _compiles_to_kernel(
        lambda y0, u, w, b: fused_node_rollout(
            y0, u, w, b, NCDE["dt"], interpret=False, precision=precision,
            field=field),
        _spec(one_chip, (1024, NCDE["sizes"][0])),
        _spec(one_chip, (1024, 2 * NCDE["T"] + 1, NCDE["Du"])), w, b)


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("case", ["l96", "hp_shared"])
def test_fused_backward_compiles(one_chip, case, precision):
    cfg, B, drive = {
        "l96": (L96, 256, (2 * L96["T"] + 1, 0)),
        "hp_shared": (HP, 64, (2 * HP["T"] + 1, 1)),
    }[case]
    w, b = _mlp(one_chip, cfg["sizes"])

    def loss(y0, u, w, b):
        traj = fused_node_rollout_vjp(y0, u, w, b, cfg["dt"], 64, None,
                                      False, DEFAULT_VMEM_BUDGET, precision)
        return jnp.sum(traj.astype(jnp.float32) ** 2)

    _compiles_to_kernel(jax.grad(loss, argnums=(0, 2, 3)),
                        _spec(one_chip, (B, cfg["sizes"][-1])),
                        _spec(one_chip, drive), w, b)


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_softdtw_compiles(one_chip, grad, precision):
    """The L96 loss's soft-DTW at the fit() segment shapes."""
    def sdtw(x, y):
        return jnp.sum(ops.soft_dtw(x, y, 0.1, False, precision))

    x = _spec(one_chip, (8, 200, 6))
    _compiles_to_kernel(jax.grad(sdtw) if grad else sdtw, x, x)


@pytest.mark.parametrize("storage,read_noise", [
    ("float", 0.0), ("float", 0.02), ("uint8", 0.0)])
def test_fused_analogue_compiles_hp(one_chip, storage, read_noise):
    dtype = jnp.uint8 if storage == "uint8" else jnp.float32
    pairs = list(zip(HP["sizes"][:-1], HP["sizes"][1:]))
    gs = [_spec(one_chip, (k + 1, n), dtype) for k, n in pairs]
    g_step = 0.01 if storage == "uint8" else None
    _compiles_to_kernel(
        lambda gp, gm, sc, y0, u: fused_analogue_rollout(
            gp, gm, sc, y0, u, HP["dt"], g_step=g_step, g_min=1e-6,
            read_noise=read_noise, interpret=False),
        gs, gs, _spec(one_chip, (len(pairs),)), _spec(one_chip, (256, 1)),
        _spec(one_chip, (256, 2 * HP["T"] + 1, 1)))


@pytest.mark.parametrize("storage", ["float", "uint8"])
def test_crossbar_vmm_compiles_hp(one_chip, storage):
    dtype = jnp.uint8 if storage == "uint8" else jnp.float32
    g = _spec(one_chip, (15, 14), dtype)
    g_step = 0.01 if storage == "uint8" else None
    _compiles_to_kernel(
        lambda x, gp, gm: crossbar_matmul(
            x, gp, gm, inv_scale=1.0, g_step=g_step, read_noise=0.02,
            g_min=1e-6, interpret=False),
        _spec(one_chip, (256, 15)), g, g)

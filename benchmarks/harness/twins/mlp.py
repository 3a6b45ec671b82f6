"""The ReLU-MLP twin, dy/dt = MLP([u(t), y]): the kind of every
configuration that names no ``"twin"``.

Autonomous (``drive_dim`` 0) it is the program's Lorenz96 fleet twin;
driven, the HP twin with a per-twin sine drive a·sin(2πft), a and f
drawn from the configuration's ``drive`` spread.  ``spec.py`` lists what
a twin kind provides.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmarks.harness import costs, reference, weights, yardstick

field = reference.mlp_field
make_weights = weights.make_weights
layer_sizes = weights.layer_sizes


def served_fleet(config: dict, backend):
    """The program's ``TwinFleet`` on ``backend``."""
    if config.get("drive_dim", 0):
        from repro.core.twin import TwinFleet, make_driven_twin
        drive = config["drive"]
        twin = make_driven_twin(
            config["state_dim"],
            lambda t: yardstick.sine_drive(t, (drive["amp"], drive["freq"])),
            hidden=config["hidden"],
            n_hidden_layers=config["n_hidden_layers"])
        return TwinFleet(twin.with_backend(backend),
                         drive_family=yardstick.sine_drive)
    from repro.train import recipes
    return recipes.make_l96_fleet(backend=backend)


def fit_twin(config: dict):
    from repro.core.twin import make_autonomous_twin
    return make_autonomous_twin(config["state_dim"], hidden=config["hidden"],
                                n_hidden_layers=config["n_hidden_layers"])


@functools.cache
def _draw(n: int, d: int, spread, y0_range, drive):
    """One compiled draw per shape, reused by every call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        ky, ka, kf = jax.random.split(key, 3)
        if spread is not None:
            y = spread * jax.random.normal(ky, (n, d))
        else:
            lo, hi = y0_range
            y = lo + (hi - lo) * jax.random.uniform(ky, (n, d))
        if drive is None:
            return y, jnp.zeros((n, 0))
        a0, f0, lo, hi = drive
        amp = a0 * (lo + (hi - lo) * jax.random.uniform(ka, (n,)))
        freq = f0 * (lo + (hi - lo) * jax.random.uniform(kf, (n,)))
        return y, jnp.stack([amp, freq], axis=-1)

    return draw


def initial_states(config: dict, n: int, jax_seed: int):
    """Seeded initial states (n, D) and drive parameters (n, 2) or (n, 0)
    of n twins, as host arrays."""
    import jax
    spread = config.get("y0_spread")
    y0_range = None if spread is not None else tuple(config["y0_range"])
    drive = None
    if config.get("drive_dim", 0):
        d = config["drive"]
        drive = (d["amp"], d["freq"], *d["spread"])
    draw = _draw(n, config["state_dim"], spread, y0_range, drive)
    y, th = draw(jax.random.fold_in(jax.random.PRNGKey(jax_seed), 1))
    return np.asarray(y, np.float32), np.asarray(th, np.float32)


def drive_half_steps(config: dict, thetas: np.ndarray, starts: np.ndarray,
                     steps: int) -> np.ndarray:
    """Each twin's drive on the canonical half-step grid of its window,
    (n, 2*steps+1, drive_dim)."""
    if not config.get("drive_dim", 0):
        return np.zeros((len(starts), 2 * steps + 1, 0), np.float32)
    import jax
    import jax.numpy as jnp
    t = reference.half_step_times(config["dt"], starts, steps)
    u = jax.jit(jax.vmap(jax.vmap(yardstick.sine_drive, (0, None))))(
        jnp.asarray(t), jnp.asarray(thetas, jnp.float32))
    return np.asarray(u, np.float32)[..., None]


def flops_per_twin_step(config: dict) -> int:
    return costs.rk4_step_flops(layer_sizes(config))


def fused_fwd_cost(config: dict, *, steps: int, rows: int):
    return costs.fused_fwd_cost(layer_sizes(config), steps=steps, rows=rows,
                                precision=config["precision"],
                                per_twin_drive=bool(config.get("drive_dim")))


def fused_bwd_cost(config: dict, *, steps: int, rows: int):
    return costs.fused_bwd_cost(layer_sizes(config), steps=steps, rows=rows,
                                precision=config["precision"],
                                per_twin_drive=bool(config.get("drive_dim")))

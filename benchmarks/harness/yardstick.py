"""The benchmark's own copies of what it measures with.

Later changes to the program cannot move these: the seed derivation, the
Poisson arrival generator (copied from ``repro.launch.traffic``), the HP
twin's sine drive family (copied from ``chip_smoke.hp_drive``), the
compile clock (copied from ``chip_smoke.CompileClock``) and the Lorenz96
ground truth the fit cell trains on (copied from ``repro.data.lorenz96``
and ``repro.train.recipes.l96_data``).
"""
from __future__ import annotations

import numpy as np


def seeds(seed: int) -> tuple[np.random.Generator, int]:
    """A NumPy generator and a 32-bit JAX seed, both drawn from ``seed``.

    ``jax.random.PRNGKey`` keeps only 32 bits of a larger integer, so any
    whole number is first hashed through a ``SeedSequence``."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    ss = np.random.SeedSequence(seed)
    jax_seed = int(ss.spawn(1)[0].generate_state(1, np.uint32)[0])
    return np.random.default_rng(ss), jax_seed


def poisson_arrivals(rng: np.random.Generator, n_requests: int, *,
                     rate_hz: float, population: int, min_horizon: int,
                     max_horizon: int):
    """Memoryless arrivals: exponential gaps at ``rate_hz``, twin ids
    uniform over ``population``, horizons uniform in
    ``[min_horizon, max_horizon]``.  Returns (due_s, twin_ids, horizons)."""
    gaps = rng.exponential(1.0 / rate_hz, size=n_requests)
    due = np.cumsum(gaps)
    twins = rng.integers(0, population, size=n_requests)
    horizons = rng.integers(min_horizon, max_horizon + 1, size=n_requests)
    return due, twins, horizons


def sine_drive(t, theta):
    """The HP twin's per-twin drive: theta = (amplitude, frequency in Hz)."""
    import jax.numpy as jnp
    return theta[0] * jnp.sin(2.0 * jnp.pi * theta[1] * t)


class CompileClock:
    """Backend compiles, their seconds, and persistent-cache hits that JAX
    reports while the clock is installed."""

    def __init__(self):
        import jax
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {"compiles": self.compiles, "compile_s": self.seconds,
               "cache_hits": self.cache_hits}
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        return out


def lorenz96_data(num_points: int, dt: float, forcing: float,
                  substeps: int = 8):
    """The paper's Lorenz96 trajectory (6 variables from its initial
    condition, RK4 with ``substeps`` per sample), standardised per
    variable.  Returns (ts, ys) as float32 device arrays."""
    import jax
    import jax.numpy as jnp
    y0 = jnp.array([-1.2061, 0.0617, 1.1632, -1.5008, -1.5944, -0.0187])

    def field(x):
        return (jnp.roll(x, -1) - jnp.roll(x, 2)) * jnp.roll(x, 1) - x + forcing

    h = dt / substeps

    def rk4(x, _):
        k1 = field(x)
        k2 = field(x + h / 2 * k1)
        k3 = field(x + h / 2 * k2)
        k4 = field(x + h * k3)
        return x + h / 6 * (k1 + 2 * k2 + 2 * k3 + k4), None

    def sample(x, _):
        x, _ = jax.lax.scan(rk4, x, None, length=substeps)
        return x, x

    @jax.jit
    def run():
        _, ys = jax.lax.scan(sample, y0, None, length=num_points - 1)
        ys = jnp.concatenate([y0[None], ys])
        return (ys - ys.mean(0)) / (ys.std(0) + 1e-8)

    ts = jnp.arange(num_points, dtype=jnp.float32) * dt
    return ts, run()

"""Neural-ODE modules: the paper's core contribution as composable JAX.

Pieces:

* ``mlp_init`` / ``mlp_apply`` — the small ReLU MLP the paper deploys on
  the memristor crossbars (HP twin: 2->14->14->1; Lorenz96: 3-layer, 64
  hidden).  ``mlp_apply`` takes a pluggable ``linear_fn`` so the same
  network can execute digitally (jnp dot), through the analogue-crossbar
  simulator (:mod:`repro.core.analogue`) or through the fused Pallas
  kernel (:mod:`repro.kernels`).
* ``CDEVectorField`` — the neural CDE field dz/dt = f_θ(z)·dX/dt
  (Kidger et al. 2020), served as a twin with a per-twin control path.
* ``NeuralODE`` — ties a vector field to an integrator + gradient mode
  (adjoint vs backprop-through-solver); handles driven systems (external
  input u(t), HP twin) and autonomous systems (Lorenz96 twin).
* ``ContinuousDepthBlock`` — lifts the idea to any residual stack: a
  weight-tied block integrated in pseudo-depth, the paper's Eq. (8)/(9)
  equivalence as a framework feature usable inside the LM models.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.backends import Backend, resolve_backend
from repro.core.ode import odeint
from repro.kernels.fused_ode_mlp import CDEField, MLPField

Pytree = Any


# ---------------------------------------------------------------------------
# MLP vector field
# ---------------------------------------------------------------------------

def mlp_init(key: jax.Array, sizes: Sequence[int],
             dtype=jnp.float32) -> list[dict]:
    """He-init MLP parameters: list of {'w': (in,out), 'b': (out,)}."""
    params = []
    keys = jax.random.split(key, len(sizes) - 1)
    for k, din, dout in zip(keys, sizes[:-1], sizes[1:]):
        w = jax.random.normal(k, (din, dout), dtype) * jnp.sqrt(2.0 / din)
        params.append({"w": w, "b": jnp.zeros((dout,), dtype)})
    return params


def dense_linear(w: jax.Array, b: jax.Array, x: jax.Array) -> jax.Array:
    return x @ w + b


def mlp_apply(params: list[dict], x: jax.Array,
              activation: Callable = jax.nn.relu,
              linear_fn: Callable = dense_linear) -> jax.Array:
    """ReLU MLP, no activation on the output layer (paper, Methods)."""
    for i, layer in enumerate(params):
        x = linear_fn(layer["w"], layer["b"], x)
        if i < len(params) - 1:
            x = activation(x)
    return x


@dataclasses.dataclass(frozen=True)
class MLPVectorField:
    """dy/dt = MLP([u(t), y]) (driven) or MLP(y) (autonomous).

    ``drive``: optional continuous input signal u(t) -> array; mirrors the
    analogue waveform generator feeding x1 in the paper's HP-twin loop.
    """
    sizes: tuple
    drive: Optional[Callable[[jax.Array], jax.Array]] = None
    activation: Callable = jax.nn.relu
    linear_fn: Callable = dense_linear

    def init(self, key: jax.Array) -> Pytree:
        return mlp_init(key, self.sizes)

    def __call__(self, t: jax.Array, y: jax.Array, params: Pytree) -> jax.Array:
        if self.drive is not None:
            u = jnp.atleast_1d(jnp.asarray(self.drive(t), dtype=y.dtype))
            inp = jnp.concatenate([u, y], axis=-1)
        else:
            inp = y
        return mlp_apply(params, inp, self.activation, self.linear_fn)

    def kernel_field(self) -> MLPField:
        """The fused kernel's form of this field."""
        return MLPField(len(self.sizes) - 1)


@dataclasses.dataclass(frozen=True)
class CDEVectorField:
    """Neural CDE field dz/dt = f_θ(z) · dX/dt (Kidger, Morrill, Foster,
    Lyons, arXiv:2005.08926, the ``FinalTanh`` field): f_θ a ReLU MLP of
    ``sizes`` = (D, H, ..., H, D·Du) with tanh on its output, read as a
    (D, Du) matrix and contracted with the control's derivative.

    ``drive(t) -> (Du,)`` is dX/dt, the twin's control derivative (the
    name lets the backends re-bind it per twin, as they do a driven
    MLP's input); it is required.
    """
    sizes: tuple
    control_channels: int
    drive: Optional[Callable[[jax.Array], jax.Array]] = None

    def __post_init__(self):
        D, Du = self.sizes[0], self.control_channels
        if self.sizes[-1] != D * Du:
            raise ValueError(
                f"CDEVectorField: the output layer must be D·Du = {D * Du} "
                f"wide for state {D} and {Du} control channels, got "
                f"{self.sizes[-1]}")

    def init(self, key: jax.Array) -> Pytree:
        return mlp_init(key, self.sizes)

    def __call__(self, t: jax.Array, z: jax.Array, params: Pytree) -> jax.Array:
        if self.drive is None:
            raise ValueError("CDEVectorField needs its control derivative "
                             "(drive=dX/dt)")
        dxdt = jnp.asarray(self.drive(t), dtype=z.dtype)
        g = jnp.tanh(mlp_apply(params, z)).reshape(
            z.shape[:-1] + (self.sizes[0], self.control_channels))
        return jnp.einsum("...ic,c->...i", g, dxdt)

    def kernel_field(self) -> CDEField:
        """The fused kernel's form of this field."""
        return CDEField(len(self.sizes) - 1, self.sizes[0],
                        self.control_channels)


# ---------------------------------------------------------------------------
# NeuralODE module
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NeuralODE:
    """The memristive neural-ODE solver's software twin.

    gradient: 'adjoint' (O(1) memory; paper's training method) or
    'direct' (backprop through the unrolled solver).

    ``backend`` selects the execution substrate (None -> digital): the
    field is programmed onto it once per solve and the backend owns the
    integration (see :mod:`repro.core.backends`).
    """
    field: Callable  # f(t, y, params) -> dy/dt
    method: str = "rk4"
    steps_per_interval: int = 1
    gradient: str = "adjoint"
    backend: Optional[Backend] = None

    def init(self, key: jax.Array) -> Pytree:
        init = getattr(self.field, "init", None)
        if init is None:
            raise ValueError("vector field has no .init; pass params explicitly")
        return init(key)

    def _solver_kw(self) -> dict:
        return dict(method=self.method,
                    steps_per_interval=self.steps_per_interval,
                    gradient=self.gradient)

    def trajectory(self, params: Pytree, y0: jax.Array,
                   ts: jax.Array) -> jax.Array:
        """Solve the IVP, returning y at every ts (leading axis len(ts))."""
        backend = resolve_backend(self.backend)
        state = backend.program(self.field, params)
        return backend.rollout(state, y0, ts, **self._solver_kw())

    def trajectory_batch(self, params: Pytree, y0s: jax.Array,
                         ts: jax.Array, *, drive_family=None,
                         drive_params=None, mesh=None) -> jax.Array:
        """Fleet solve: N initial conditions (and optionally per-twin
        drive parameters) in one device program, (N, len(ts), D).

        ``mesh``: optional twin mesh — shards the fleet dimension across
        devices (see :meth:`repro.core.backends.BaseBackend.rollout_batch`).
        """
        backend = resolve_backend(self.backend)
        state = backend.program(self.field, params)
        return backend.rollout_batch(state, y0s, ts,
                                     drive_family=drive_family,
                                     drive_params=drive_params,
                                     mesh=mesh,
                                     **self._solver_kw())

    def __call__(self, params, y0, ts):
        return self.trajectory(params, y0, ts)


# ---------------------------------------------------------------------------
# Continuous-depth residual block (paper Eq. 8 <-> Eq. 9 as a feature)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ContinuousDepthBlock:
    """Weight-tied residual block integrated in pseudo-depth.

    A discrete stack ``h <- h + block(h)`` repeated K times is the Euler
    discretisation of ``dh/ds = block(h)`` on s in [0, K].  This module
    integrates that ODE with RK4 instead, giving the infinite-depth
    approximation of the paper with a single block's parameters.

    ``block_fn(params, h) -> residual`` must be s-independent (weight tied).
    """
    block_fn: Callable[[Pytree, jax.Array], jax.Array]
    depth: float = 1.0          # pseudo-time horizon (== #discrete layers)
    num_steps: int = 4          # RK4 steps across the horizon
    method: str = "rk4"

    def __call__(self, params: Pytree, h: jax.Array) -> jax.Array:
        def f(t, y, p):
            del t
            return self.block_fn(p, y)

        ts = jnp.linspace(0.0, self.depth, self.num_steps + 1, dtype=h.dtype)
        ys = odeint(f, h, ts, params, method=self.method)
        return jax.tree_util.tree_map(lambda x: x[-1], ys)

"""The neural CDE twin, dz/dt = f_θ(z) · dX/dt (Kidger, Morrill, Foster,
Lyons, arXiv:2005.08926): f_θ a ReLU MLP D -> H -> ... -> H -> D·Du with
tanh on its output, read as a (D, Du) matrix and contracted with the
twin's own control derivative.  ``spec.py`` lists what a twin kind
provides.

The control.  Each twin's Du - 1 data channels are
x_c(t) = a_c sin(2π f_c t + φ_c), with a_c the configuration's ``amp`` and
f_c, φ_c drawn per twin; channel 0 is time, X_0 = t.  θ = (a, f, φ), so
dX/dt is analytic: (1, a_c 2π f_c cos(2π f_c t + φ_c)).

The served weights (``bounded_cde``) keep the tanh unsaturated and every
twin's state in range over the ~10^4 steps a twin advances in a run,
and give every row of the (D, Du) field z-dependent entries:

- ``active`` coordinates of z, drawn from the seed, pass through the
  first ``active`` hidden units as relu(z_i + s) = z_i + s (the
  ``shift`` s), and on through the identity of every hidden layer.  The
  output layer gives each the time-channel entry tanh(-κ z_i)
  (``damping`` κ), which holds it to a bounded orbit, and random
  z-dependent entries on the data channels (``out_gain``).
- The other hidden units are random ReLU features of the other
  (passive) coordinates, and of each other in the deeper layers.  A
  passive row reads these alone, at ``passive_gain``, beside tanh of a
  seeded bias, on the data channels; its time-channel entry is 0.  A
  field of width 40 can damp no more than 40 coordinates, and a passive
  row that read the lagging active ones would drift with the lag; read
  from the passive coordinates alone, its drive stays near a function of
  X(t), and the passive coordinates wander slowly (max |z| ~30-50 over
  12,000 steps).
- Every data-channel row is centred at z = 0 on the active units.

A plain default initialisation drifts: the time channel's constant
derivative integrates a nonzero row without limit, and the field's
other rows rectify the oscillating channels into a random walk that
saturates the tanh within a few thousand steps.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmarks.harness import reference


def layer_sizes(config: dict) -> tuple:
    D, Du = config["state_dim"], config["control_channels"]
    return ((D,) + (config["hidden_hidden"],) * config["num_hidden_layers"]
            + (D * Du,))


def control_derivative(t, theta):
    """dX/dt at time t of the twin with θ = (a, f, φ), each Du - 1 long."""
    import jax.numpy as jnp
    a, f, phi = jnp.split(theta, 3)
    w = 2.0 * jnp.pi * f
    return jnp.concatenate([jnp.ones((1,), theta.dtype),
                            a * w * jnp.cos(w * t + phi)])


def served_fleet(config: dict, backend):
    """The program's ``TwinFleet`` on ``backend``, each twin driven by its
    own control derivative."""
    from repro.core.twin import TwinFleet, make_cde_twin
    twin = make_cde_twin(config["state_dim"], config["control_channels"],
                         hidden=config["hidden_hidden"],
                         n_hidden_layers=config["num_hidden_layers"])
    return TwinFleet(twin.with_backend(backend),
                     drive_family=control_derivative)


def fit_twin(config: dict):
    raise NotImplementedError(
        "the neural CDE has no fused backward: fit() does not train it on "
        "the fused substrate")


def make_weights(config: dict, jax_seed: int):
    import jax
    w = dict(config["weights"])
    if w.pop("scheme") != "bounded_cde":
        raise ValueError(f"unknown weights scheme {config['weights']!r}")
    return jax.jit(functools.partial(
        bounded_cde, state_dim=config["state_dim"],
        channels=config["control_channels"],
        hidden=config["hidden_hidden"],
        layers=config["num_hidden_layers"], **w))(
            jax.random.PRNGKey(jax_seed))


def bounded_cde(key, *, state_dim, channels, hidden, layers, active, shift,
                damping, out_gain, passive_gain, bias_std):
    """The ``bounded_cde`` weights (module docstring), as f32 masters in
    the source's layout: output column i·Du + c is entry (i, c)."""
    import jax
    import jax.numpy as jnp
    D, Du, H, NA, s = state_dim, channels, hidden, active, shift
    NR = H - NA
    ks = jax.random.split(key, 2 * layers + 4)
    perm = jax.random.permutation(ks[0], D)
    act, pas = perm[:NA], perm[NA:]
    lanes = jnp.arange(NA)
    # the active coordinates pass through as z_i + s; the random units
    # read the passive ones
    w = jnp.zeros((D, H)).at[act, lanes].set(1.0)
    w = w.at[pas, NA:].set(jax.random.normal(ks[1], (D - NA, NR))
                           / np.sqrt(D - NA))
    b = jnp.zeros(H).at[:NA].set(s).at[NA:].set(
        0.5 * jax.random.normal(ks[2], (NR,)))
    params = [{"w": w, "b": b}]
    for i in range(layers - 1):
        k, kb = ks[3 + 2 * i], ks[4 + 2 * i]
        w = jnp.zeros((H, H)).at[lanes, lanes].set(1.0)
        w = w.at[NA:, NA:].set(jax.random.normal(k, (NR, NR))
                               * np.sqrt(2.0 / H))
        b = jnp.zeros(H).at[NA:].set(0.1 * jax.random.normal(kb, (NR,)))
        params.append({"w": w, "b": b})
    k_out, k_bias = ks[-2], ks[-1]
    out = jax.random.normal(k_out, (H, D, Du - 1)) / np.sqrt(H)
    # the active units (each z_i + s) read at 1/s, so each term is of
    # order one; the passive rows read the random units alone
    out = out.at[:NA].multiply(1.0 / s)
    out = out.at[:, act].multiply(out_gain)
    out = out.at[:, pas].multiply(passive_gain).at[:NA, pas].set(0.0)
    w = jnp.zeros((H, D, Du)).at[lanes, act, 0].set(-damping)
    w = w.at[:, :, 1:].set(out)
    b = jnp.zeros((D, Du)).at[act, 0].set(damping * s)
    b = b.at[:, 1:].set(bias_std * jax.random.normal(k_bias, (D, Du - 1)))
    b = b.at[:, 1:].add(-out[:NA].sum(0))   # the active units' part centred
    params.append({"w": w.reshape(H, D * Du), "b": b.reshape(D * Du)})
    return params


@functools.cache
def _draw(n: int, D: int, Du: int, amp: float, f_lo: float, f_hi: float):
    """One compiled draw per shape, reused by every call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        kf, kp, kz = jax.random.split(key, 3)
        f = jax.random.uniform(kf, (n, Du - 1), minval=f_lo, maxval=f_hi)
        phi = jax.random.uniform(kp, (n, Du - 1), maxval=2.0 * jnp.pi)
        theta = jnp.concatenate([jnp.full((n, Du - 1), amp), f, phi], 1)
        # z0 = ζ(X(0)): a seeded Linear Du -> D of the control at t = 0
        x0 = jnp.concatenate([jnp.zeros((n, 1)), amp * jnp.sin(phi)], 1)
        zeta = jax.random.normal(kz, (Du, D)) / np.sqrt(Du)
        return x0 @ zeta, theta

    return draw


def initial_states(config: dict, n: int, jax_seed: int):
    """Seeded initial states (n, D) and control parameters (n, 3(Du-1))
    of n twins, as host arrays."""
    import jax
    c = config["control"]
    draw = _draw(n, config["state_dim"], config["control_channels"],
                 float(c["amp"]), *map(float, c["freq"]))
    with jax.default_matmul_precision("highest"):
        z, th = draw(jax.random.fold_in(jax.random.PRNGKey(jax_seed), 1))
    return np.asarray(z, np.float32), np.asarray(th, np.float32)


def drive_half_steps(config: dict, thetas: np.ndarray, starts: np.ndarray,
                     steps: int) -> np.ndarray:
    """Each twin's control derivative on the canonical half-step grid of
    its window, (n, 2*steps+1, Du)."""
    import jax
    import jax.numpy as jnp
    t = reference.half_step_times(config["dt"], starts, steps)
    u = jax.jit(jax.vmap(jax.vmap(control_derivative, (0, None))))(
        jnp.asarray(t), jnp.asarray(thetas, jnp.float32))
    return np.asarray(u, np.float32)


def field(params, u, z, operand_dtype=None):
    """dz/dt = tanh(MLP(z)) read as (D, Du), times dX/dt; the MLP's
    matmul operands rounded for the control, tanh and the contraction at
    f32."""
    import jax
    import jax.numpy as jnp
    g = jnp.tanh(reference.mlp(params, z, operand_dtype))
    g = g.reshape(z.shape[0], z.shape[1], u.shape[-1])
    return jnp.einsum("bic,bc->bi", g, u,
                      precision=jax.lax.Precision.HIGHEST)


def flops_per_twin_step(config: dict) -> int:
    """Four field evaluations: the MLP's multiply-adds and the D·Du of
    the contraction, 2 operations each (tanh not counted)."""
    sizes = layer_sizes(config)
    macs = sum(i * o for i, o in zip(sizes[:-1], sizes[1:])) + sizes[-1]
    return 4 * 2 * macs


def fused_fwd_cost(config: dict, *, steps: int, rows: int):
    """(operations, HBM bytes) of one forward call over ``rows`` twins and
    ``steps`` steps: y0 and the f32 per-twin control slab in, the weights
    and the trajectory slab at the storage width."""
    sizes = layer_sizes(config)
    D, Du = config["state_dim"], config["control_channels"]
    sb = 4 if config["precision"] == "f32" else 2
    weights = sum(i * o + o for i, o in zip(sizes[:-1], sizes[1:]))
    nbytes = (rows * D * 4 + (2 * steps + 1) * rows * Du * 4
              + weights * sb + steps * rows * D * sb)
    return float(rows * steps * flops_per_twin_step(config)), float(nbytes)


def fused_bwd_cost(config: dict, *, steps: int, rows: int):
    raise NotImplementedError("the neural CDE has no fused backward")

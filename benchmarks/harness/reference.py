"""The plain reference every cell's ``correct`` is decided against.

Straightforward float32 ``jax.numpy``: RK4 of a vector field, the ReLU
MLP field dy/dt = MLP([u(t), y]), soft-DTW by its anti-diagonal
recursion, L1, and Adam.  Matmuls run at ``highest`` precision (a TPU
otherwise rounds f32 operands to bf16).  Nothing here imports the
program.  A field is ``field(params, u, y, operand_dtype) -> dy/dt``;
each twin kind (``twins/<kind>.py``) names its own.

``operand_dtype`` puts the same reference one precision step below the
configuration's, for the control: every matmul operand (activations and
weights) and every stored trajectory row is rounded to that float type,
as a policy of that width would store them; products accumulate at f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BIG = 1e10


def operand_round(x, operand_dtype):
    """x rounded to the mantissa of the float type ``operand_dtype`` and
    back; an 8-bit type is scaled per tensor so that its largest magnitude
    maps to the format's largest finite value, as such arithmetic is used.
    The rounding is ``lax.reduce_precision``, which the compiler keeps: a
    cast to the narrow type and back inside one fusion may be dropped as
    excess precision.  The cotangent is rounded the same way, so gradients
    too are taken in the lower precision."""
    if operand_dtype is None:
        return x
    info = jnp.finfo(operand_dtype)
    e, m = int(info.nexp), int(info.nmant)
    top = (2.0 - 2.0 ** -m) * 2.0 ** (2 ** (e - 1) - 1)   # IEEE-style max

    @jax.custom_vjp
    def rnd(v):
        scale = (jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / top
                 if info.bits <= 8 else 1.0)
        q = jax.lax.reduce_precision(v / scale, exponent_bits=e,
                                     mantissa_bits=m)
        return q * scale

    rnd.defvjp(lambda v: (rnd(v), None), lambda _, g: (rnd(g),))
    return rnd(x)


def mlp(params, x, operand_dtype=None):
    for i, layer in enumerate(params):
        x = jnp.dot(operand_round(x, operand_dtype),
                    operand_round(layer["w"], operand_dtype),
                    precision=jax.lax.Precision.HIGHEST) + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def mlp_field(params, u, y, operand_dtype=None):
    """dy/dt = MLP([u, y]), or MLP(y) where the drive has no channels."""
    inp = jnp.concatenate([u, y], axis=-1) if u.shape[-1] else y
    return mlp(params, inp, operand_dtype)


def rk4_rollout(field, params, y0, u_half, dt: float, steps: int,
                operand_dtype=None):
    """(B, D) states and (B, 2*steps+1, Du) half-step drives (Du may be 0)
    -> (B, steps+1, D) trajectories with row 0 = y0."""
    def f(u, y):
        return field(params, u, y, operand_dtype)

    def step(y, t):
        u0 = u_half[:, 2 * t]
        um = u_half[:, 2 * t + 1]
        u1 = u_half[:, 2 * t + 2]
        k1 = f(u0, y)
        k2 = f(um, y + dt / 2 * k1)
        k3 = f(um, y + dt / 2 * k2)
        k4 = f(u1, y + dt * k3)
        y = y + dt / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
        return y, y

    _, ys = jax.lax.scan(step, y0, jnp.arange(steps))
    traj = jnp.concatenate([y0[:, None], jnp.transpose(ys, (1, 0, 2))], axis=1)
    return operand_round(traj, operand_dtype)


def half_step_times(dt: float, start_steps: np.ndarray, steps: int):
    """The canonical half-step grid t = (dt/2)(2k + j), in float64 on the
    host and rounded to float32 once: (B, 2*steps+1)."""
    idx = 2 * np.asarray(start_steps, np.int64)[:, None] + np.arange(
        2 * steps + 1, dtype=np.int64)
    return (0.5 * np.float64(dt) * idx).astype(np.float32)


# ---------------------------------------------------------------------------
# Training: the fit cell's loss and optimizer
# ---------------------------------------------------------------------------

def soft_dtw(x, y, gamma: float):
    """Soft-DTW of (n, d) and (m, d) series under the summed-|.| cost,
    by the anti-diagonal recursion R[i,j] = D[i,j] + softmin(R[i-1,j],
    R[i,j-1], R[i-1,j-1]), R[0,0] = D[0,0]."""
    cost = jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)
    n, m = cost.shape
    rows = jnp.arange(n)

    def softmin(a, b, c):
        return -gamma * jax.nn.logsumexp(-jnp.stack([a, b, c]) / gamma, axis=0)

    def body(carry, k):
        prev, prev2 = carry                       # diagonals k-1 and k-2
        j = k - rows
        valid = (j >= 0) & (j < m)
        d = jnp.where(valid, cost[rows, jnp.clip(j, 0, m - 1)], BIG)
        shifted = lambda r: jnp.concatenate([jnp.full((1,), BIG), r[:-1]])
        best = softmin(prev, shifted(prev), shifted(prev2))
        r = jnp.where(valid, d + best, BIG)
        return (r, prev), None

    first = jnp.full((n,), BIG).at[0].set(cost[0, 0])
    (last, _), _ = jax.lax.scan(body, (first, jnp.full((n,), BIG)),
                                jnp.arange(1, n + m - 1))
    return last[n - 1]


def segment_loss(field, params, y0s, ys_seg, dt: float, gamma: float,
                 operand_dtype=None):
    """l1 + 0.1 * mean soft-DTW / (L+1) over the shooting segments."""
    steps = ys_seg.shape[1] - 1
    uh = jnp.zeros((y0s.shape[0], 2 * steps + 1, 0), jnp.float32)
    preds = rk4_rollout(field, params, y0s, uh, dt, steps, operand_dtype)
    l1 = jnp.mean(jnp.abs(preds - ys_seg))
    sdtw = jnp.mean(jax.vmap(lambda p, t: soft_dtw(p, t, gamma))(preds, ys_seg))
    return l1 + 0.1 * sdtw / ys_seg.shape[1]


def fit_reference(field, params, ys_seg, key, *, steps: int, dt: float,
                  lr: float, noise_std: float, gamma: float = 0.1,
                  b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                  operand_dtype=None):
    """``steps`` Adam steps on the noisy-initial-state segment loss.

    Each step splits the key and perturbs the segments' initial states by
    ``noise_std`` * N(0, 1) drawn from the split-off key.  Returns
    (losses (steps,), params, mu, nu) after the last step."""
    tree = jax.tree_util.tree_map
    zeros = tree(jnp.zeros_like, params)

    def step(carry, i):
        p, mu, nu, key = carry
        key, sub = jax.random.split(key)
        y0s = ys_seg[:, 0] + noise_std * jax.random.normal(
            sub, ys_seg[:, 0].shape)
        loss, g = jax.value_and_grad(segment_loss, argnums=1)(
            field, p, y0s, ys_seg, dt, gamma, operand_dtype)
        mu = tree(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = tree(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        t = (i + 1).astype(jnp.float32)
        p = tree(lambda w, m, v: w - lr * (m / (1 - b1 ** t))
                 / (jnp.sqrt(v / (1 - b2 ** t)) + eps), p, mu, nu)
        return (p, mu, nu, key), loss

    (p, mu, nu, _), losses = jax.lax.scan(
        step, (params, zeros, zeros, key), jnp.arange(steps))
    return losses, p, mu, nu

"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.losses import BIG, _dtw_scan, _hardmin, _softmin


# ---------------------------------------------------------------------------
# fused ODE-MLP trajectory solve
# ---------------------------------------------------------------------------

def mlp_fwd(weights: list[jax.Array], biases: list[jax.Array],
            x: jax.Array) -> jax.Array:
    for i, (w, b) in enumerate(zip(weights, biases)):
        x = x @ w + b
        if i < len(weights) - 1:
            x = jnp.maximum(x, 0.0)
    return x


def fused_node_rollout_ref(y0: jax.Array, u_half: jax.Array,
                           weights: list[jax.Array], biases: list[jax.Array],
                           dt: float) -> jax.Array:
    """RK4 rollout of dy/dt = MLP([u(t), y]) (drive optional).

    y0: (B, D); u_half: drive sampled at half-steps, (2T+1, Du) shared or
    (B, 2T+1, Du) per-sample (Du may be 0); returns (T+1, B, D).
    """
    B = y0.shape[0]
    per_sample = u_half.ndim == 3
    if per_sample:
        u_half = jnp.transpose(u_half, (1, 0, 2))   # time-major (2T+1, B, Du)
    T = (u_half.shape[0] - 1) // 2
    du = u_half.shape[-1]

    def f(u, y):
        if du > 0:
            if not per_sample:
                u = jnp.broadcast_to(u[None, :], (B, du))
            inp = jnp.concatenate([u, y], axis=-1)
        else:
            inp = y
        return mlp_fwd(weights, biases, inp)

    def step(y, t):
        u0 = u_half[2 * t]
        um = u_half[2 * t + 1]
        u1 = u_half[2 * t + 2]
        k1 = f(u0, y)
        k2 = f(um, y + dt / 2 * k1)
        k3 = f(um, y + dt / 2 * k2)
        k4 = f(u1, y + dt * k3)
        y = y + dt / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
        return y, y

    _, ys = lax.scan(step, y0, jnp.arange(T))
    return jnp.concatenate([y0[None], ys], axis=0)


# ---------------------------------------------------------------------------
# neural CDE field and its RK4 rollout
# ---------------------------------------------------------------------------

def cde_field_ref(weights: list[jax.Array], biases: list[jax.Array],
                  z: jax.Array, dxdt: jax.Array) -> jax.Array:
    """The neural CDE field of Kidger et al. (arXiv:2005.08926, the
    ``FinalTanh`` field of ``experiments/speech_commands.py``): Linear,
    ReLU, ..., Linear, tanh on the (B, D) state, the (B, D·Du) output read
    row-major as (B, D, Du) and contracted with the (B, Du) control
    derivative."""
    g = jnp.tanh(mlp_fwd(weights, biases, z))
    g = g.reshape(z.shape[0], z.shape[1], dxdt.shape[-1])
    return jnp.einsum("bic,bc->bi", g, dxdt)


def cde_rollout_ref(z0: jax.Array, u_half: jax.Array,
                    weights: list[jax.Array], biases: list[jax.Array],
                    dt: float) -> jax.Array:
    """RK4 rollout of dz/dt = f(z) · dX/dt in float32 at ``highest``
    matmul precision: (B, D) states and (B, 2T+1, Du) control derivatives
    on the half-step grid -> (T+1, B, D).

    Departures from the source: the control is whatever path the caller
    samples (a seeded analytic path, not a spline of recorded audio); the
    solver is fixed-step RK4, one step per observation interval, not an
    adaptive one; the initial map z0 = ζ(X(0)) and the readout are not
    part of the rollout (the twin serves z).
    """
    u_half = jnp.transpose(u_half, (1, 0, 2))         # (2T+1, B, Du)
    T = (u_half.shape[0] - 1) // 2

    def f(u, z):
        return cde_field_ref(weights, biases, z, u)

    def step(z, t):
        u0, um, u1 = u_half[2 * t], u_half[2 * t + 1], u_half[2 * t + 2]
        k1 = f(u0, z)
        k2 = f(um, z + dt / 2 * k1)
        k3 = f(um, z + dt / 2 * k2)
        k4 = f(u1, z + dt * k3)
        z = z + dt / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
        return z, z

    with jax.default_matmul_precision("highest"):
        _, zs = lax.scan(step, z0, jnp.arange(T))
    return jnp.concatenate([z0[None], zs], axis=0)


# ---------------------------------------------------------------------------
# crossbar differential-pair VMM
# ---------------------------------------------------------------------------

def crossbar_matmul_ref(x: jax.Array, gp: jax.Array, gm: jax.Array,
                        inv_scale: float, clamp: float | None) -> jax.Array:
    """y = x @ (gp - gm) / scale, clamped (float-programmed arrays)."""
    y = (x.astype(jnp.float32) @
         (gp.astype(jnp.float32) - gm.astype(jnp.float32))) * inv_scale
    if clamp is not None:
        y = jnp.clip(y, -clamp, clamp)
    return y


def crossbar_matmul_q_ref(x: jax.Array, gp_idx: jax.Array, gm_idx: jax.Array,
                          g_step: float, inv_scale: float,
                          clamp: float | None) -> jax.Array:
    """Quantised-storage variant: uint8 level indices dequantised on the fly.

    gp - gm = (idx_p - idx_m) * g_step  (G_min offsets cancel in the pair).
    """
    g = (gp_idx.astype(jnp.float32) - gm_idx.astype(jnp.float32)) * g_step
    y = (x.astype(jnp.float32) @ g) * inv_scale
    if clamp is not None:
        y = jnp.clip(y, -clamp, clamp)
    return y


# ---------------------------------------------------------------------------
# soft-DTW wavefront DP
# ---------------------------------------------------------------------------

def diag_layout(D: jax.Array) -> jax.Array:
    """(n, m) cost matrix -> (n+m-1, n) anti-diagonal layout, BIG-padded."""
    n, m = D.shape
    rows = jnp.arange(n)
    ks = jnp.arange(n + m - 1)
    j = ks[:, None] - rows[None, :]
    valid = (j >= 0) & (j < m)
    return jnp.where(valid, D[rows[None, :], jnp.clip(j, 0, m - 1)], BIG)


def softdtw_ref(D: jax.Array, gamma: float, hard: bool = False) -> jax.Array:
    """Accumulated (soft-)DTW cost of a (n, m) distance matrix."""
    return _dtw_scan(D, gamma, _hardmin if hard else _softmin)


def softdtw_batch_ref(D: jax.Array, gamma: float,
                      hard: bool = False) -> jax.Array:
    return jax.vmap(lambda d: softdtw_ref(d, gamma, hard))(D)


def softdtw_grad_ref(D, gamma: float):
    """Closed-form E-matrix (dSDTW/dD) by the reverse DP of Cuturi &
    Blondel 2017, Alg. 2 — the numpy oracle for ``softdtw_bwd_pallas``.

    Pads R and D with +inf borders so every child weight
    exp((R_child - R - D_child) / gamma) vanishes outside the matrix.
    """
    import numpy as np
    D = np.asarray(D, dtype=np.float64)
    n, m = D.shape
    # forward DP in float64
    R = np.full((n, m), np.inf)
    for i in range(n):
        for j in range(m):
            if i == 0 and j == 0:
                R[i, j] = D[i, j]
                continue
            preds = []
            if i > 0:
                preds.append(R[i - 1, j])
            if j > 0:
                preds.append(R[i, j - 1])
            if i > 0 and j > 0:
                preds.append(R[i - 1, j - 1])
            p = np.asarray(preds)
            soft = -gamma * (np.log(np.sum(np.exp(-(p - p.min()) / gamma)))
                             - p.min() / gamma)
            R[i, j] = D[i, j] + soft
    E = np.zeros((n, m))
    E[n - 1, m - 1] = 1.0
    Rp = np.full((n + 1, m + 1), np.inf)
    Rp[:n, :m] = R
    Dp = np.full((n + 1, m + 1), np.inf)
    Dp[:n, :m] = D
    Ep = np.zeros((n + 1, m + 1))
    Ep[:n, :m] = E
    for k in range(n + m - 3, -1, -1):          # reverse anti-diagonals
        for i in range(max(0, k - m + 1), min(n, k + 1)):
            j = k - i
            if i == n - 1 and j == m - 1:
                continue
            acc = 0.0
            for (ci, cj) in ((i + 1, j), (i, j + 1), (i + 1, j + 1)):
                w = np.exp((Rp[ci, cj] - R[i, j] - Dp[ci, cj]) / gamma) \
                    if np.isfinite(Dp[ci, cj]) else 0.0
                acc += Ep[ci, cj] * w
            Ep[i, j] = acc
    return Ep[:n, :m]

"""Anti-diagonal wavefront soft-DTW kernels (forward AND backward).

Forward: the DP recurrence
R[i,j] = D[i,j] + softmin(R[i-1,j], R[i,j-1], R[i-1,j-1])
serialises along both axes but is embarrassingly parallel along each
anti-diagonal — an exact match for the VPU's lane-parallel vector ops.
The cost matrix is pre-laid-out in diagonal-major order (n+m-1, n) so each
wavefront step is one contiguous VMEM row read; the two carried diagonals
live in VMEM scratch that persists across the sequential k-chunk grid
dimension (the chunking keeps arbitrarily long series within VMEM).
``return_r=True`` additionally emits the full accumulated-cost matrix R
in the same diagonal layout — the residual the backward pass needs.

Backward: the gradient of soft-DTW w.r.t. the cost matrix is the
E-matrix of Cuturi & Blondel 2017 (Alg. 2), computed by the CLOSED-FORM
reverse DP

    E[i,j] = E[i+1,j]   * exp((R[i+1,j]   - R[i,j] - D[i+1,j])  / gamma)
           + E[i,j+1]   * exp((R[i,j+1]   - R[i,j] - D[i,j+1])  / gamma)
           + E[i+1,j+1] * exp((R[i+1,j+1] - R[i,j] - D[i+1,j+1])/ gamma)

seeded with E[n-1,m-1] = 1 and swept over anti-diagonals in REVERSE
order — the same wavefront schedule as the forward, so it runs as a
second Pallas kernel (``softdtw_bwd_pallas``) with the carried E/R/D
diagonals in VMEM scratch.  No autodiff of the sequential DP is
involved anywhere.

Grid: (batch, num_k_chunks); the backward's chunk grid dimension is
index-mapped in reverse.

Mixed precision: the cost matrix ``dd`` — the only O(n·m) input — may
arrive rounded to bfloat16 (the ``"bf16"``/``"bf16_f32acc"`` policies);
the wrappers widen it to float32 before the kernel (a wavefront reads
one row at a dynamic index, which Mosaic cannot do on a bf16 slab), and
the R/E/D diagonal carries, the accumulated answer and the emitted R
and E matrices ALWAYS stay float32 — the sequential DP recurrences are
where reduced precision would compound.  The BIG padding sentinel is
detected with a half-BIG threshold because bf16 rounds ``1e10``
slightly DOWN (an exact ``>= BIG`` compare would mistake padded cells
for real ones).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.losses import BIG
from repro.kernels.platform import default_interpret

# padding-sentinel threshold: robust to BIG's bf16 rounding (see module
# docstring); real costs are pairwise distances, orders of magnitude
# below BIG/2
BIG_CUT = BIG * 0.5


def _kernel(dd_ref, *refs, n: int, m: int, chunk: int, nkc: int,
            gamma: float, hard: bool, with_r: bool):
    if with_r:
        out_ref, r_dd_ref = refs[0], refs[1]
        scratch = refs[2:]
    else:
        out_ref = refs[0]
        scratch = refs[1:]
    rp_ref, rp2_ref, ans_ref = scratch
    kc = pl.program_id(1)

    @pl.when(kc == 0)
    def _init():
        rp_ref[...] = jnp.full_like(rp_ref, BIG)
        rp2_ref[...] = jnp.full_like(rp2_ref, BIG)
        ans_ref[...] = jnp.zeros_like(ans_ref)

    def minop(a, b, c):
        if hard:
            return jnp.minimum(jnp.minimum(a, b), c)
        s = jnp.stack([a, b, c], axis=0)
        return -gamma * jax.nn.logsumexp(-s / gamma, axis=0)

    big_head = jnp.full((1,), BIG, dtype=jnp.float32)

    def body(r, _):
        k = kc * chunk + r
        d_k = dd_ref[0, r]
        rp = rp_ref[...]
        rp2 = rp2_ref[...]
        up = rp
        left = jnp.concatenate([big_head, rp[:-1]])
        diag = jnp.concatenate([big_head, rp2[:-1]])
        best = minop(up, left, diag)
        invalid = d_k >= BIG_CUT
        r_k = d_k + jnp.where(invalid, 0.0, best)
        r_k = jnp.where(k == 0, d_k, r_k)          # (0,0) has no predecessor
        r_k = jnp.where(invalid, BIG, r_k)
        rp2_ref[...] = rp
        rp_ref[...] = r_k
        if with_r:
            r_dd_ref[0, r] = r_k
        # the last anti-diagonal holds the answer at row n-1; keep the
        # whole row (Mosaic cannot store a scalar to VMEM)
        ans_ref[...] = jnp.where(k == n + m - 2, r_k, ans_ref[...])
        return 0

    lax.fori_loop(0, chunk, body, 0)

    @pl.when(kc == nkc - 1)
    def _finish():
        out_ref[...] = ans_ref[...].reshape(out_ref.shape)


def softdtw_pallas(
    dd: jax.Array,           # (B, KD_pad, n) diagonal-major costs, BIG-padded
    n: int, m: int,
    *,
    gamma: float = 1.0,
    hard: bool = False,
    chunk: int = 256,
    interpret: bool | None = None,
    return_r: bool = False,
):
    """Batched accumulated (soft-)DTW from diagonal-layout costs -> (B,).

    ``dd`` may be float32 or bfloat16 (the reduced-precision policies
    round the costs to bf16); it is widened to float32 before the
    kernel, because Mosaic cannot read one bf16 row at a dynamic index
    (two rows share a sublane).  The DP carries and the output are
    always float32.  ``return_r=True`` also returns the
    accumulated-cost matrix R (float32) in the same (B, KD_pad, n)
    diagonal layout — the backward pass's residual.  ``interpret=None``
    auto-detects like the fused kernels (compiled on TPU).
    """
    if interpret is None:
        interpret = default_interpret()
    B, kd_pad, n_ = dd.shape
    assert n_ == n and kd_pad % chunk == 0
    dd = dd.astype(jnp.float32)
    nkc = kd_pad // chunk
    kernel = functools.partial(_kernel, n=n, m=m, chunk=chunk, nkc=nkc,
                               gamma=float(gamma), hard=hard,
                               with_r=return_r)
    # one (1, n) block per series holding its last anti-diagonal (the
    # answer is entry n-1): Mosaic refuses a (1,) block of a rank-1 (B,)
    # output, while a block equal to the trailing dims is always legal
    out_shape = [jax.ShapeDtypeStruct((B, 1, n), jnp.float32)]
    out_specs = [pl.BlockSpec((1, 1, n), lambda b, kc: (b, 0, 0))]
    if return_r:
        out_shape.append(jax.ShapeDtypeStruct((B, kd_pad, n), jnp.float32))
        out_specs.append(pl.BlockSpec((1, chunk, n), lambda b, kc: (b, kc, 0)))
    outs = pl.pallas_call(
        kernel,
        grid=(B, nkc),
        in_specs=[pl.BlockSpec((1, chunk, n), lambda b, kc: (b, kc, 0))],
        out_specs=out_specs if return_r else out_specs[0],
        out_shape=out_shape if return_r else out_shape[0],
        scratch_shapes=[pltpu.VMEM((n,), jnp.float32),
                        pltpu.VMEM((n,), jnp.float32),
                        pltpu.VMEM((n,), jnp.float32)],
        name="softdtw_fwd",
        interpret=interpret,
    )(dd)
    if return_r:
        return outs[0][:, 0, n - 1], outs[1]
    return outs[:, 0, n - 1]


def _bwd_kernel(dd_ref, rd_ref, e_dd_ref, e1_ref, e2_ref, r1_ref, r2_ref,
                d1_ref, d2_ref, *, n: int, m: int, chunk: int, nkc: int,
                gamma: float):
    """Reverse anti-diagonal sweep computing the E-matrix.

    Diagonal layout: layout[k, i] holds cell (i, k-i).  The children of
    cell (i, j) on diag k sit at layout[k+1, i+1] ((i+1, j)),
    layout[k+1, i] ((i, j+1)) and layout[k+2, i+1] ((i+1, j+1)) — so the
    sweep carries the two PREVIOUSLY processed (later) diagonals of E, R
    and D in VMEM scratch, exactly mirroring the forward's carry but
    walking k downwards (the chunk grid dimension is index-mapped in
    reverse)."""
    kc_rev = pl.program_id(1)
    inv_g = 1.0 / gamma
    # one-hot of cell row n-1, which is row 0 of the reversed layout
    # (1-D iota is not lowerable on TPU)
    seed_row = jnp.concatenate([jnp.ones((1,), jnp.float32),
                                jnp.zeros((n - 1,), jnp.float32)])

    @pl.when(kc_rev == 0)
    def _init():
        e1_ref[...] = jnp.zeros_like(e1_ref)
        e2_ref[...] = jnp.zeros_like(e2_ref)
        r1_ref[...] = jnp.full_like(r1_ref, BIG)
        r2_ref[...] = jnp.full_like(r2_ref, BIG)
        d1_ref[...] = jnp.full_like(d1_ref, BIG)
        d2_ref[...] = jnp.full_like(d2_ref, BIG)

    def shift(x, pad):
        """cell row i -> i+1 (children live one row down), i.e. one
        lane up in the reversed layout."""
        return jnp.concatenate([jnp.full((1,), pad, x.dtype), x[:-1]])

    def body(s, _):
        r = chunk - 1 - s
        k = (nkc - 1 - kc_rev) * chunk + r
        d_k = dd_ref[0, r]
        r_k = rd_ref[0, r]
        e1, e2 = e1_ref[...], e2_ref[...]
        r1, r2 = r1_ref[...], r2_ref[...]
        d1, d2 = d1_ref[...], d2_ref[...]

        def term(ev, rv, dv):
            w = jnp.exp((rv - r_k - dv) * inv_g)
            return jnp.where(dv < BIG_CUT, ev * w, 0.0)

        e_k = (term(shift(e1, 0.0), shift(r1, BIG), shift(d1, BIG))  # down
               + term(e1, r1, d1)                                    # right
               + term(shift(e2, 0.0), shift(r2, BIG), shift(d2, BIG)))  # diag
        e_k = jnp.where(d_k < BIG_CUT, e_k, 0.0)
        # seed: dF/dR[n-1,m-1] = 1 (F = R[n-1,m-1])
        e_k = e_k + jnp.where(k == n + m - 2, seed_row, 0.0)
        e2_ref[...] = e1
        e1_ref[...] = e_k
        r2_ref[...] = r1
        r1_ref[...] = r_k
        d2_ref[...] = d1
        d1_ref[...] = d_k
        e_dd_ref[0, r] = e_k
        return 0

    lax.fori_loop(0, chunk, body, 0)


def softdtw_bwd_pallas(
    dd: jax.Array,           # (B, KD_pad, n) diagonal-major costs
    rd: jax.Array,           # (B, KD_pad, n) diagonal-major R (from forward)
    n: int, m: int,
    *,
    gamma: float = 1.0,
    chunk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """E-matrix (dSDTW/dD) in diagonal layout, (B, KD_pad, n) float32.

    ``dd`` may be bfloat16 (matching the forward's reduced-precision
    cost slab; widened here as in the forward); ``rd`` is the forward's
    float32 R and the E/R/D diagonal carries stay float32.
    ``interpret=None`` auto-detects.

    The kernel runs on the row-reversed layout (lane p holds cell row
    n-1-p), so every read of a child one row down is a shift by one lane
    toward the end — the direction Mosaic lowers; the result is flipped
    back here."""
    if interpret is None:
        interpret = default_interpret()
    B, kd_pad, n_ = dd.shape
    assert n_ == n and kd_pad % chunk == 0 and rd.shape == dd.shape
    dd = dd.astype(jnp.float32)[..., ::-1]
    rd = rd[..., ::-1]
    nkc = kd_pad // chunk
    kernel = functools.partial(_bwd_kernel, n=n, m=m, chunk=chunk, nkc=nkc,
                               gamma=float(gamma))
    rev = lambda b, kc: (b, nkc - 1 - kc, 0)
    return pl.pallas_call(
        kernel,
        grid=(B, nkc),
        in_specs=[pl.BlockSpec((1, chunk, n), rev),
                  pl.BlockSpec((1, chunk, n), rev)],
        out_specs=pl.BlockSpec((1, chunk, n), rev),
        out_shape=jax.ShapeDtypeStruct((B, kd_pad, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n,), jnp.float32)] * 6,
        name="softdtw_bwd",
        interpret=interpret,
    )(dd, rd)[..., ::-1]

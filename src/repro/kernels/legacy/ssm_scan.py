"""State-resident selective-SSM scan — the paper's in-memory-computing
insight applied to Mamba's recurrence.

The XLA chunked scan materialises the (B, chunk, d_inner, N) decay/input
tensors in HBM at every associative-scan level (~d_inner*N = 128k f32 per
token); this kernel keeps the SSM state h (d_tile, N) resident in VMEM
across the whole sequence and builds da/dbx on the fly in registers — HBM
traffic collapses to exactly the functional inputs/outputs:

    reads  : dt, x (S, d_tile), B, C (S, N), A (d_tile, N)
    writes : y (S, d_tile), final state (d_tile, N)

i.e. ~(2*d+2N) floats/token instead of ~14*d*N — the same
"weights/state stationary, operands flow" structure as the memristive
crossbar loop (DESIGN.md §2).

Grid: (batch, d_inner / d_tile); sequential ``fori_loop`` over S inside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, y_ref, hout_ref, h_scr,
            *, seq_len: int):
    a = a_ref[...]                                    # (dtile, N)
    h_scr[...] = jnp.zeros_like(h_scr)

    def body(t, _):
        dt_t = dt_ref[0, t]                           # (dtile,)
        b_t = b_ref[0, t]                             # (N,)
        c_t = c_ref[0, t]                             # (N,)
        x_t = x_ref[0, t]                             # (dtile,)
        da = jnp.exp(dt_t[:, None] * a)               # (dtile, N)
        dbx = (dt_t * x_t)[:, None] * b_t[None, :]
        h = da * h_scr[...] + dbx
        h_scr[...] = h
        y_ref[0, t] = jnp.sum(h * c_t[None, :], axis=1)
        return 0

    lax.fori_loop(0, seq_len, body, 0)
    hout_ref[0] = h_scr[...]


def ssm_scan(dt: jax.Array, b: jax.Array, c: jax.Array, x: jax.Array,
             a: jax.Array, *, d_tile: int = 512,
             interpret: bool = True):
    """Selective scan: h_t = exp(dt*A)h_{t-1} + dt*B*x; y_t = <h_t, C>.

    dt, x: (BATCH, S, DI) f32; b, c: (BATCH, S, N) f32; a: (DI, N) f32.
    Returns (y (BATCH, S, DI) f32, h_final (BATCH, DI, N) f32).
    """
    bsz, s, di = dt.shape
    n = b.shape[-1]
    d_tile = min(d_tile, di)
    assert di % d_tile == 0
    grid = (bsz, di // d_tile)

    kernel = functools.partial(_kernel, seq_len=s)
    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, d_tile), lambda i, j: (i, 0, j)),   # dt
            pl.BlockSpec((1, s, n), lambda i, j: (i, 0, 0)),        # B
            pl.BlockSpec((1, s, n), lambda i, j: (i, 0, 0)),        # C
            pl.BlockSpec((1, s, d_tile), lambda i, j: (i, 0, j)),   # x
            pl.BlockSpec((d_tile, n), lambda i, j: (j, 0)),         # A
        ],
        out_specs=[
            pl.BlockSpec((1, s, d_tile), lambda i, j: (i, 0, j)),   # y
            pl.BlockSpec((1, d_tile, n), lambda i, j: (i, j, 0)),   # h_out
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), jnp.float32),
            jax.ShapeDtypeStruct((bsz, di, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d_tile, n), jnp.float32)],
        name="ssm_scan",
        interpret=interpret,
    )(dt, b, c, x, a)
    return y, h_final


def ssm_scan_ref(dt, b, c, x, a):
    """Pure-jnp oracle (sequential lax.scan)."""
    def one(dt_g, b_g, c_g, x_g):
        def step(h, inp):
            dt_t, b_t, c_t, x_t = inp
            da = jnp.exp(dt_t[:, None] * a)
            dbx = (dt_t * x_t)[:, None] * b_t[None, :]
            h = da * h + dbx
            return h, jnp.sum(h * c_t[None, :], axis=1)

        h0 = jnp.zeros((dt_g.shape[-1], a.shape[-1]), jnp.float32)
        h, ys = lax.scan(step, h0, (dt_g, b_g, c_g, x_g))
        return ys, h

    return jax.vmap(one)(dt, b, c, x)

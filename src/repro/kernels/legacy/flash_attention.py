"""Fused causal flash attention — the VMEM-residency fix for the memory
term that dominates every attention cell in §Roofline.

The pure-XLA flash schedule (models/flash.py) re-materialises the
(B,H,qc,kc) score tile and rewrites the (B,H,qc,dv) accumulator in HBM on
every kv step.  Here the accumulator/max/denominator live in VMEM scratch
across the sequential kv grid dimension and scores never leave VMEM —
per-layer HBM traffic collapses to Q/K/V in + O out, the same
state-resident structure as kernels/legacy/ssm_scan.py (and the paper's
crossbar loop).

Grid: (batch, q_heads, nq, nk) with nk innermost (sequential, scratch
carries); GQA handled by indexing the kv head as h // group in the K/V
BlockSpecs.  Causal banding: fully-masked tiles are skipped with
``@pl.when`` (no MXU work, no DMA use of the loaded tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, nk: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal banding: skip tiles strictly above the diagonal
    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, dv)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + \
            jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _epilogue():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           scale: float | None = None,
                           bq: int = 256, bk: int = 256,
                           interpret: bool = True) -> jax.Array:
    """Causal GQA flash attention.

    q: (B, H, S, d); k, v: (B, Hkv, S, d) with Hkv | H.
    Returns (B, H, S, dv) in q.dtype.
    """
    b, h, s, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    group = h // hkv
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0
    nq, nk = s // bq, s // bk
    scale = scale if scale is not None else d ** -0.5

    kernel = functools.partial(_kernel, bq=bq, bk=bk, nk=nk,
                               scale=float(scale))
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki, g=group:
                         (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda bi, hi, qi, ki, g=group:
                         (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)


def flash_attention_pallas_ref(q, k, v, *, scale: float | None = None):
    """Oracle: dense causal softmax attention."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    sgrid = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       kk.astype(jnp.float32)) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    sgrid = jnp.where(mask[None, None], sgrid, NEG_INF)
    p = jax.nn.softmax(sgrid, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      vv.astype(jnp.float32)).astype(q.dtype)


def hbm_traffic_bytes(b, h, hkv, s, d, dv, dtype_bytes=2) -> dict:
    """The kernel's DMA contract (used for the §Perf projection)."""
    q_io = b * h * s * d * dtype_bytes
    kv_io = 2 * b * hkv * s * d * dtype_bytes
    # k/v re-read once per q block row is avoided by the sequential nk
    # dim revisiting the same block; worst case: nq re-reads
    o_io = b * h * s * dv * dtype_bytes
    return {"q": q_io, "kv": kv_io, "out": o_io,
            "total": q_io + kv_io + o_io}

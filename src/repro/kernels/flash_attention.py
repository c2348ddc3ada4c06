"""Pallas TPU kernel: flash (blockwise-softmax) attention forward.

Needed by every prefill_32k cell: materializing 32k x 32k score matrices per
head is ~2 GB each — chunked online softmax is mandatory. The framework's
default under pjit is the pure-JAX blockwise path (models/attention.py) which
GSPMD shards; this kernel is the single-core TPU hot path (selectable via
``kernel_impl='pallas'``) with explicit VMEM tiling for the MXU, and is
validated against the jnp oracle in interpret mode.

Grid: (batch*q_heads, q_blocks, kv_blocks) — kv minor, classic online
softmax with running (max, denom, acc) scratch carried across kv blocks.
Causal masking is positional; fully-masked kv blocks are skipped via
``pl.when`` (upper triangle costs nothing). GQA is handled in the BlockSpec
index map (q head h reads kv head h // group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, kv_blocks, bq, bk):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block():
        q = q_ref[0].astype(jnp.float32) * scale      # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]                           # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # skip blocks entirely above the diagonal
        pl.when(kb * bk <= qb * bq + bq - 1)(block)
    else:
        block()

    @pl.when(kb == kv_blocks - 1)
    def emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret", "scale")
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, T, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    causal: bool = True,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq = min(bq, t)
    bk = min(bk, s)
    assert t % bq == 0 and s % bk == 0, "pad seq lens to block multiples"

    qf = q.reshape(b * hq, t, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    kv_blocks = s // bk

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, kv_blocks=kv_blocks, bq=bq, bk=bk
    )

    def kv_head(bh):
        # flat q index -> flat kv index (GQA)
        return (bh // hq) * hkv + (bh % hq) // group

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, t // bq, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qb, kb: (bh, qb, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qb, kb: (kv_head(bh), kb, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qb, kb: (kv_head(bh), kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qb, kb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(qf, kf, vf)
    return out.reshape(b, hq, t, d)

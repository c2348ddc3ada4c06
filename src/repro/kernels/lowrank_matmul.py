"""Pallas TPU kernel: fused low-rank matmul  y = x @ (P @ Vt).

This is the SLR serving hot path: after SALAAD+HPA a weight is deployed as
``P (K, r)`` and ``Vt (r, M)`` with r << min(K, M). Computing ``x @ P @ Vt``
as two XLA matmuls materializes the intermediate ``t = x @ P`` (T, r) in HBM
and reads it back. This kernel keeps ``t`` in a VMEM scratch accumulator per
row-tile and streams it straight into the second matmul — one HBM round-trip
saved, both matmuls on the MXU.

Phase-based grid: for each row tile ``i`` the minor grid axis runs
``K_tiles`` accumulate phases (t += x_blk @ p_blk) followed by ``M_tiles``
emit phases (y_blk = t @ vt_blk). Index maps clamp so each operand stays
resident when unused; the output block for column j is only mapped (and
written) during its emit phase, so every y block is written exactly once.

VMEM budget per step (f32, defaults bm=bk=bn=128, r<=1024):
  x (128,128) + p (128,r) + vt (r,128) + y (128,128) + t (128,r)  < 1.3 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, p_ref, vt_ref, y_ref, t_ref, *, k_tiles: int):
    phase = pl.program_id(1)

    @pl.when(phase < k_tiles)
    def accumulate():
        @pl.when(phase == 0)
        def init():
            t_ref[...] = jnp.zeros_like(t_ref)

        t_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            p_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(phase >= k_tiles)
    def emit():
        y_ref[...] = jnp.dot(
            t_ref[...], vt_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def lowrank_matmul_pallas(
    x: jax.Array,    # (T, K)
    p: jax.Array,    # (K, r)
    vt: jax.Array,   # (r, M)
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    t_dim, k_dim = x.shape
    r = p.shape[1]
    m_dim = vt.shape[1]
    assert p.shape[0] == k_dim and vt.shape[0] == r

    bm = min(bm, t_dim)
    bk = min(bk, k_dim)
    bn = min(bn, m_dim)

    # Zero-pad every dim to a tile multiple: out-of-bounds block padding is
    # undefined (NaN in interpret mode), and zeros are accumulation-neutral.
    def pad_to(a, mults):
        pads = [(0, -d % mult) for d, mult in zip(a.shape, mults)]
        return jnp.pad(a, pads) if any(p[1] for p in pads) else a

    x = pad_to(x, (bm, bk))
    p = pad_to(p, (bk, 1))
    t_pad, k_pad = x.shape
    vt = pad_to(vt, (1, bn))
    m_pad = vt.shape[1]

    k_tiles = k_pad // bk
    m_tiles = m_pad // bn
    grid = (t_pad // bm, k_tiles + m_tiles)

    kernel = functools.partial(_kernel, k_tiles=k_tiles)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # x: row tile i, K tile = phase while accumulating (clamped after)
            pl.BlockSpec((bm, bk), lambda i, ph: (i, jnp.minimum(ph, k_tiles - 1))),
            # p: K tile while accumulating; full r is resident
            pl.BlockSpec((bk, r), lambda i, ph: (jnp.minimum(ph, k_tiles - 1), 0)),
            # vt: column tile while emitting
            pl.BlockSpec(
                (r, bn), lambda i, ph: (0, jnp.clip(ph - k_tiles, 0, m_tiles - 1))
            ),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda i, ph: (i, jnp.clip(ph - k_tiles, 0, m_tiles - 1))
        ),
        out_shape=jax.ShapeDtypeStruct((t_pad, m_pad), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(x, p, vt)[:t_dim, :m_dim]

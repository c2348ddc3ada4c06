"""Pallas TPU kernel: block-CSR sparse matmul  y = x @ S.

TPU adaptation of SALAAD's unstructured sparse component (DESIGN.md §3): the
MXU consumes 128x128 tiles, so unstructured S is re-tiled at deployment into
block-CSR — only tiles containing surviving entries are stored. HPA's
magnitude truncation concentrates the support, so occupancy is measured (and
reported by ``kernels.ops.bsr_occupancy``), not assumed.

Layout (column-major over output blocks, padded to a fixed per-column count):
    counts  (JB,)              int32 — live blocks feeding output column jb
    rows    (JB, MAXB)         int32 — input row-block index of each block
    vals    (JB, MAXB, bs, bs) float — the tile data (zero-padded)

Kernel: grid (row_tiles_of_x, JB, MAXB); the scalar-prefetched ``rows`` table
drives the x BlockSpec index map, so the correct (bt, bs) slice of x is
DMA'd for each stored tile — the gather happens in the DMA engine, not the
VPU. Accumulation stays in a VMEM scratch; y is written once per (i, jb).
Padded slots multiply by zero tiles (cheap relative to DMA savings, and the
x index map clamps to a valid block so no out-of-bounds DMA occurs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["BsrMatrix", "bsr_from_dense", "bsr_to_dense", "bsr_matmul_pallas"]


class BsrMatrix:
    """Static-shape block-CSC container (named Bsr for familiarity).

    ``shape`` is the ORIGINAL dense (n, m); dims that do not divide
    ``block_size`` are zero-padded at conversion time, so the block tables
    cover ``ceil(n/bs) x ceil(m/bs)`` tiles and matmul callers slice the
    padded output columns back off. ``empty`` is STATIC deploy-time metadata
    (no live blocks at all) so jitted callers can skip the sparse phase
    entirely instead of burning one DMA+matmul per column block on the
    MAXB >= 1 padding slot.
    """

    def __init__(self, counts, rows, vals, shape, block_size, empty=False):
        self.counts = counts          # (JB,) int32
        self.rows = rows              # (JB, MAXB) int32
        self.vals = vals              # (JB, MAXB, bs, bs)
        self.shape = shape            # original dense (n, m), pre-padding
        self.block_size = block_size
        self.empty = empty            # static: no live blocks anywhere

    def tree_flatten(self):
        return (self.counts, self.rows, self.vals), (
            self.shape, self.block_size, self.empty
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def padded_shape(self) -> tuple[int, int]:
        """Block-aligned dims the tables actually cover."""
        bs = self.block_size
        n, m = self.shape
        return (-(-n // bs) * bs, -(-m // bs) * bs)

    @property
    def occupancy(self) -> float:
        """Fraction of (padded) dense tiles actually stored."""
        n_pad, m_pad = self.padded_shape
        bs = self.block_size
        total = (n_pad // bs) * (m_pad // bs)
        return float(np.sum(np.asarray(self.counts))) / max(total, 1)


jax.tree_util.register_pytree_node(
    BsrMatrix, BsrMatrix.tree_flatten, BsrMatrix.tree_unflatten
)


def bsr_from_dense(s: np.ndarray, block_size: int = 128, maxb: int | None = None) -> BsrMatrix:
    """Eager (deploy-time) conversion of a dense sparse matrix to block-CSC.

    Dims that do not divide ``block_size`` are zero-padded to the next block
    boundary (the padding tiles are all-zero, so they are never stored) —
    odd hidden sizes deploy as ``bsr``/``fused`` instead of asserting.
    ``maxb`` forces the per-column slot count (>= the live maximum) so
    several matrices can share one stacked table layout.
    """
    s = np.asarray(s)
    n, m = s.shape
    bs = block_size
    if n % bs or m % bs:
        s = np.pad(s, ((0, -n % bs), (0, -m % bs)))
    ib, jb = s.shape[0] // bs, s.shape[1] // bs
    tiles = s.reshape(ib, bs, jb, bs).transpose(0, 2, 1, 3)  # (ib, jb, bs, bs)
    live = np.abs(tiles).max(axis=(2, 3)) > 0                # (ib, jb)
    counts = live.sum(axis=0).astype(np.int32)               # per column block
    live_max = int(counts.max()) if counts.size else 0
    if maxb is None:
        maxb = max(live_max, 1)
    elif maxb < max(live_max, 1):
        raise ValueError(f"maxb={maxb} < live maximum {live_max}")
    rows = np.zeros((jb, maxb), np.int32)
    vals = np.zeros((jb, maxb, bs, bs), s.dtype)
    for j in range(jb):
        live_rows = np.nonzero(live[:, j])[0]
        rows[j, : len(live_rows)] = live_rows
        vals[j, : len(live_rows)] = tiles[live_rows, j]
    return BsrMatrix(
        jnp.asarray(counts), jnp.asarray(rows), jnp.asarray(vals), (n, m), bs,
        empty=live_max == 0,
    )


def bsr_to_dense(bsr: BsrMatrix) -> jax.Array:
    n, m = bsr.shape
    n_pad, _ = bsr.padded_shape
    bs = bsr.block_size
    jb, maxb = bsr.rows.shape
    dense = jnp.zeros((n_pad // bs, jb, bs, bs), bsr.vals.dtype)
    slot = jnp.arange(maxb)[None, :] < bsr.counts[:, None]  # (jb, maxb)
    vals = jnp.where(slot[:, :, None, None], bsr.vals, 0)
    for t in range(maxb):
        dense = dense.at[bsr.rows[:, t], jnp.arange(jb)].add(vals[:, t])
    return dense.transpose(0, 2, 1, 3).reshape(n_pad, jb * bs)[:n, :m]


def _kernel(scalars_ref, x_ref, vals_ref, y_ref, acc_ref, *, maxb: int):
    # scalar buffer layout: [counts (JB,), rows (JB*MAXB,)]
    t = pl.program_id(2)
    jb = pl.program_id(1)

    @pl.when(t == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Padded slots hold zero tiles, so skipping them is a pure MXU saving.
    @pl.when(t < scalars_ref[jb])
    def accumulate():
        acc_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            vals_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(t == maxb - 1)
    def emit():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def bsr_matmul_pallas(
    x: jax.Array, bsr: BsrMatrix, bt: int = 128, *, interpret: bool
) -> jax.Array:
    """y = x @ S for block-CSC S. x: (T, n) -> y: (T, m)."""
    t_dim, n = x.shape
    n_s, m = bsr.shape
    assert n == n_s, (x.shape, bsr.shape)
    n_pad, m_pad = bsr.padded_shape
    bs = bsr.block_size
    jb, maxb = bsr.rows.shape
    bt = min(bt, t_dim)
    pad_t, pad_n = -t_dim % bt, n_pad - n
    if pad_t or pad_n:
        x = jnp.pad(x, ((0, pad_t), (0, pad_n)))
    t_pad = x.shape[0]

    # scalar prefetch buffer: counts then flattened rows
    scalars = jnp.concatenate([bsr.counts, bsr.rows.reshape(-1)]).astype(jnp.int32)

    grid = (t_pad // bt, jb, maxb)
    kernel = functools.partial(_kernel, maxb=maxb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # x row-tile i, column block chosen by the rows table (clamped by
            # the slot-live predicate inside the kernel; padded slots reuse
            # slot 0's row which is always a valid block index)
            pl.BlockSpec(
                (bt, bs),
                lambda i, j, t, rows: (i, rows[jb + j * maxb + t]),
            ),
            pl.BlockSpec((1, 1, bs, bs), lambda i, j, t, rows: (j, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bs), lambda i, j, t, rows: (i, j)),
        scratch_shapes=[pltpu.VMEM((bt, bs), jnp.float32)],
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, m_pad), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
    )(scalars, x, bsr.vals)
    return y[:t_dim, :m]

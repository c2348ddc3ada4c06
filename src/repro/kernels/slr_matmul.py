"""Pallas TPU kernel: fused SLR matmul  y = x @ P @ Vt + x @ S_bsr.

The SALAAD serving hot path evaluates a weight deployed as ``W ~= P @ Vt + S``
(low-rank + sparse) at every linear site of every decode tick. Running
``lowrank_matmul`` and ``bsr_matmul`` as two Pallas calls streams ``x`` from
HBM twice, writes two partial ``y``s back, and re-adds them in XLA. This
kernel does both phases in ONE pass over activation row tiles: ``x`` is read
once, ``y`` written once per tile.

The tables carry a leading selector axis (``BsrStack``: counts ``(L, JB)``,
rows ``(L, JB, MAXB)``, vals ``(L, JB, MAXB, bs, bs)``, p ``(L, K, r)``, vt
``(L, r, M)``). Which slice a row tile uses is a prefetched scalar read
inside the DMA index maps, so no XLA gather of the stack ever runs: the
layer id for the layer-``lax.scan``-able stacked call, ``ids[i // tiles]``
for the multi-adapter call (slot ``b`` of a batch runs adapter ``ids[b]``),
and slice 0 of a one-layer stack for the per-matrix call. All three run the
same kernel body over the same grid.

Per row tile ``i`` the minor grid axis runs ``chunks + JB * groups`` steps,
each moving one large tile, since a Pallas grid step has a fixed cost that a
128x128 tile does not amortize:

  resident x          the row tile's whole ``x`` sits in VMEM as
                      ``(k_tiles, bt, bs)``; its index does not depend on the
                      minor axis, so it is fetched once per row tile and each
                      block is a leading-axis index ``x_ref[kb]``
  ph < chunks         low-rank chunk: ``P`` arrives in ``(bk, r)`` chunks of
                      ``bk / bs`` blocks; ``t += x[kb] @ P[kb]`` over them
                      (``t`` is a VMEM ``(bt, r)`` float32 scratch)
  ph >= chunks        slot group ``g`` of column window ``j``: ``vals``
                      arrives ``G`` slots at a time; group 0 seeds the
                      accumulator with ``t @ Vt[:, j]``, each slot with
                      ``g * G + t < counts[j]`` adds ``x[rows[j, t]] @
                      vals[j, t]``, the last group writes ``y[i, j]``
  dead groups         a group with no live slot maps its ``vals`` index to
                      the column's last live group, so the pipeline sees an
                      unchanged block and skips the copy: a sparse tier does
                      not stream its padding

``bk`` and ``G`` follow from the shapes and dtypes (``fused_grid``): the
largest divisor of the K blocks or of ``MAXB`` whose tile is at most
``TILE_BYTES``. Accumulation is float32 and in slot order: the same sums
in the same order as a grid of one block a step. The VMEM limit is set from
the tile sizes (``FusedGrid.vmem_limit_bytes``).

Callers pick decode-width row tiles (``bt`` rounded to the sublane tile, not
padded to 128) so a 4-row decode batch doesn't burn 32x padding FLOPs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bsr_matmul import BsrMatrix

__all__ = [
    "BsrStack",
    "stack_bsr",
    "slr_matmul_pallas",
    "slr_matmul_stacked_pallas",
    "slr_matmul_multi_pallas",
    "row_tile",
    "fused_grid",
    "FusedGrid",
    "TILE_BYTES",
]


class BsrStack:
    """Layer-stacked block-CSC: L per-layer tables padded to a common MAXB.

    Same layout contract as ``BsrMatrix`` with a leading layer axis:
        counts  (L, JB)                int32
        rows    (L, JB, MAXB)          int32
        vals    (L, JB, MAXB, bs, bs)  float
    ``shape`` is the per-layer ORIGINAL dense (n, m) and ``empty`` is static
    deploy-time metadata meaning no layer holds any live block.
    """

    def __init__(self, counts, rows, vals, shape, block_size, empty=False):
        self.counts = counts
        self.rows = rows
        self.vals = vals
        self.shape = shape
        self.block_size = block_size
        self.empty = empty

    def tree_flatten(self):
        return (self.counts, self.rows, self.vals), (
            self.shape, self.block_size, self.empty
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def num_layers(self) -> int:
        return self.counts.shape[0]

    @property
    def padded_shape(self) -> tuple[int, int]:
        bs = self.block_size
        n, m = self.shape
        return (-(-n // bs) * bs, -(-m // bs) * bs)

    def at_layer(self, layer: int) -> BsrMatrix:
        """Eager per-layer view (testing/debug; kernels take the stack)."""
        return BsrMatrix(
            self.counts[layer], self.rows[layer], self.vals[layer],
            self.shape, self.block_size, empty=self.empty,
        )


jax.tree_util.register_pytree_node(
    BsrStack, BsrStack.tree_flatten, BsrStack.tree_unflatten
)


def stack_bsr(mats: list[BsrMatrix]) -> BsrStack:
    """Stack per-layer BsrMatrix tables, padding every layer to the max MAXB.

    Padding slots hold row 0 / zero tiles — the same always-dead convention
    as within one matrix, so the kernels' ``t < counts[j]`` predicate covers
    them for free.
    """
    assert mats, "stack_bsr needs at least one layer"
    shape, bs = mats[0].shape, mats[0].block_size
    assert all(m.shape == shape and m.block_size == bs for m in mats), (
        [m.shape for m in mats]
    )
    maxb = max(m.rows.shape[1] for m in mats)

    def pad_slots(a):
        pad = maxb - a.shape[1]
        if not pad:
            return a
        widths = [(0, 0)] * a.ndim
        widths[1] = (0, pad)
        return jnp.pad(a, widths)

    return BsrStack(
        jnp.stack([m.counts for m in mats]),
        jnp.stack([pad_slots(m.rows) for m in mats]),
        jnp.stack([pad_slots(m.vals) for m in mats]),
        shape, bs, empty=all(m.empty for m in mats),
    )


def _sublanes(dtype) -> int:
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def row_tile(t_dim: int, dtype, cap: int = 128) -> int:
    """Decode-width row tile: round T up to the dtype's sublane tile, cap at
    ``cap``. A 4-row decode batch runs at bt=8 instead of padding to 128."""
    sub = _sublanes(dtype)
    return min(cap, -(-t_dim // sub) * sub)


# The most bytes one grid step moves for a P chunk or a vals slot group:
# large enough that a step's fixed cost is small beside its copy (2 MiB
# take about 2.6 us at 819 GB/s), small enough that both double-buffered
# stay well inside VMEM.
TILE_BYTES = 2 << 20


def _vmem_tile_bytes(shape, dtype) -> int:
    """Bytes of a VMEM buffer: the minor dims padded to the (sublane, 128)
    tile of ``dtype``."""
    sub = _sublanes(dtype)
    *lead, rows, lanes = shape
    n = -(-rows // sub) * sub * (-(-lanes // 128) * 128) * jnp.dtype(dtype).itemsize
    for d in lead:
        n *= d
    return n


def _largest_divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap`` (at least 1)."""
    return max((d for d in range(1, min(n, max(cap, 1)) + 1) if n % d == 0),
               default=1)


class FusedGrid:
    """The grid and tiles of one fused SLR call, from shapes and dtypes alone.

    ``chunk`` K blocks make one P chunk, ``group`` slots one vals group; a
    row tile runs ``chunks + jb * groups`` grid steps.
    """

    def __init__(self, *, bt, row_tiles, bs, k_tiles, r, jb, maxb,
                 x_dtype, p_dtype, vals_dtype):
        self.bt, self.row_tiles, self.bs = bt, row_tiles, bs
        self.k_tiles, self.jb, self.maxb = k_tiles, jb, maxb
        self.chunk = _largest_divisor(
            k_tiles, TILE_BYTES // _vmem_tile_bytes((bs, r), p_dtype))
        self.group = _largest_divisor(
            maxb, TILE_BYTES // _vmem_tile_bytes((bs, bs), vals_dtype))
        f32 = jnp.float32
        # double-buffered blocks, then the two scratch accumulators
        blocks = (
            _vmem_tile_bytes((k_tiles, bt, bs), x_dtype)
            + _vmem_tile_bytes((self.chunk * bs, r), p_dtype)
            + _vmem_tile_bytes((r, bs), p_dtype)
            + _vmem_tile_bytes((self.group, bs, bs), vals_dtype)
            + _vmem_tile_bytes((bt, bs), x_dtype)
        )
        scratch = _vmem_tile_bytes((bt, r), f32) + _vmem_tile_bytes((bt, bs), f32)
        self.tile_bytes = {
            "p": _vmem_tile_bytes((self.chunk * bs, r), p_dtype),
            "vals": _vmem_tile_bytes((self.group, bs, bs), vals_dtype),
        }
        self.vmem_bytes = 2 * blocks + scratch
        # the body's float32 copies of one P chunk and one slot group
        work = (_vmem_tile_bytes((self.chunk * bs, r), f32)
                + _vmem_tile_bytes((self.group, bs, bs), f32))
        self.vmem_limit_bytes = -(-(self.vmem_bytes + work) // (1 << 20)) << 20

    @property
    def chunks(self) -> int:
        return self.k_tiles // self.chunk

    @property
    def groups(self) -> int:
        return self.maxb // self.group

    @property
    def steps_per_row_tile(self) -> int:
        return self.chunks + self.jb * self.groups

    @property
    def grid(self) -> tuple[int, int]:
        return (self.row_tiles, self.steps_per_row_tile)

    @property
    def steps(self) -> int:
        return self.row_tiles * self.steps_per_row_tile


def fused_grid(t_dim: int, stack, r: int, *, x_dtype, p_dtype,
               slots: int = 1, bt_cap: int = 128) -> FusedGrid:
    """The grid of a fused call over ``slots`` groups of ``t_dim`` rows (one
    for the stacked and per-matrix calls, the batch for the multi-adapter
    call) against the tables of ``stack`` (a ``BsrStack`` or ``BsrMatrix``)
    at rank ``r``, with P and Vt in ``p_dtype``."""
    bs = stack.block_size
    n_pad, _ = stack.padded_shape
    jb, maxb = stack.rows.shape[-2:]
    bt = row_tile(t_dim, x_dtype, cap=bt_cap)
    return FusedGrid(
        bt=bt, row_tiles=slots * (-(-t_dim // bt)), bs=bs, k_tiles=n_pad // bs,
        r=r, jb=jb, maxb=maxb, x_dtype=x_dtype, p_dtype=p_dtype,
        vals_dtype=stack.vals.dtype,
    )


def _kernel(sc_ref, x_ref, p_ref, vt_ref, vals_ref, y_ref, t_ref, acc_ref, *,
            g: FusedGrid, tiles: int, counts_base: int, rows_base: int):
    # scalar buffer: [selector per row-tile group, counts (N*JB,),
    #                 rows (N*JB*MAXB,)]
    f32 = jnp.float32
    bs, chunk, group = g.bs, g.chunk, g.group
    ph = pl.program_id(1)
    sel = sc_ref[pl.program_id(0) // tiles]

    def dot(a, b):
        return jnp.dot(a.astype(f32), b.astype(f32), preferred_element_type=f32)

    @pl.when(ph < g.chunks)
    def lowrank_chunk():
        @pl.when(ph == 0)
        def init():
            t_ref[...] = jnp.zeros_like(t_ref)

        t = t_ref[...]
        for u in range(chunk):
            t = t + dot(x_ref[ph * chunk + u], p_ref[0, u * bs:(u + 1) * bs, :])
        t_ref[...] = t

    e = jnp.maximum(ph - g.chunks, 0)
    j, grp = e // g.groups, e % g.groups

    @pl.when(ph >= g.chunks)
    def slot_group():
        @pl.when(grp == 0)
        def lowrank_emit():
            acc_ref[...] = dot(t_ref[...], vt_ref[0])

        live = sc_ref[counts_base + sel * g.jb + j] - grp * group
        base = rows_base + (sel * g.jb + j) * g.maxb + grp * group
        acc = acc_ref[...]
        for u in range(group):
            acc = acc + jnp.where(
                u < live, dot(x_ref[sc_ref[base + u]], vals_ref[0, 0, u]), 0.0)
        acc_ref[...] = acc

        @pl.when(grp == g.groups - 1)
        def emit():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _fused(x, p, vt, stack: BsrStack, sel, bt: int, interpret: bool):
    """The fused call on (B, T, K) rows: group ``b`` of rows uses table slice
    ``sel[b]``. Returns (B, T, M)."""
    b_dim, t_dim, k_dim = x.shape
    n_s, m = stack.shape
    num_n, _, r = p.shape
    assert k_dim == n_s and p.shape[1] == k_dim and vt.shape == (num_n, r, m), (
        x.shape, p.shape, vt.shape, stack.shape
    )
    assert sel.shape == (b_dim,), (sel.shape, b_dim)
    assert r > 0, "dispatch r == 0 through zero-rank dummies (ops does)"
    g = fused_grid(t_dim, stack, r, x_dtype=x.dtype, p_dtype=p.dtype,
                   slots=b_dim, bt_cap=bt)
    bs, bt = g.bs, g.bt
    n_pad, m_pad = stack.padded_shape
    tiles = g.row_tiles // b_dim            # row tiles per selector group
    t_pad = tiles * bt

    # rows padded per group, so no row tile spans two groups; then the
    # (k_tiles, rows, bs) view whose leading index picks a K block
    x = jnp.pad(x, ((0, 0), (0, t_pad - t_dim), (0, n_pad - k_dim)))
    x = x.reshape(b_dim * t_pad, g.k_tiles, bs).transpose(1, 0, 2)
    p = jnp.pad(p, ((0, 0), (0, n_pad - k_dim), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, m_pad - m)))

    scalars = jnp.concatenate([
        jnp.asarray(sel, jnp.int32).reshape(-1),
        stack.counts.reshape(-1).astype(jnp.int32),
        stack.rows.reshape(-1).astype(jnp.int32),
    ])
    counts_base = b_dim
    rows_base = b_dim + num_n * g.jb

    def col_group(ph):
        e = jnp.maximum(ph - g.chunks, 0)
        return e // g.groups, e % g.groups

    def p_map(i, ph, sc):
        return (sc[i // tiles], jnp.minimum(ph, g.chunks - 1), 0)

    def vt_map(i, ph, sc):
        return (sc[i // tiles], 0, col_group(ph)[0])

    def vals_map(i, ph, sc):
        # a group past the column's last live one repeats that group's index,
        # so its copy is skipped
        s = sc[i // tiles]
        j, grp = col_group(ph)
        count = sc[counts_base + s * g.jb + j]
        last = jnp.maximum((count + g.group - 1) // g.group - 1, 0)
        return (s, j, jnp.minimum(grp, last), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=g.grid,
        in_specs=[
            pl.BlockSpec((g.k_tiles, bt, bs), lambda i, ph, sc: (0, i, 0)),
            pl.BlockSpec((1, g.chunk * bs, r), p_map),
            pl.BlockSpec((1, r, bs), vt_map),
            pl.BlockSpec((1, 1, g.group, bs, bs), vals_map),
        ],
        out_specs=pl.BlockSpec(
            (bt, bs), lambda i, ph, sc: (i, col_group(ph)[0])
        ),
        scratch_shapes=[
            pltpu.VMEM((bt, r), jnp.float32),
            pltpu.VMEM((bt, bs), jnp.float32),
        ],
    )
    y = pl.pallas_call(
        functools.partial(_kernel, g=g, tiles=tiles, counts_base=counts_base,
                          rows_base=rows_base),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_dim * t_pad, m_pad), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=g.vmem_limit_bytes,
        ),
    )(scalars, x, p, vt, stack.vals)
    return y.reshape(b_dim, t_pad, m_pad)[:, :t_dim, :m]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def slr_matmul_pallas(
    x: jax.Array,      # (T, K)
    p: jax.Array,      # (K, r)
    vt: jax.Array,     # (r, M)
    bsr: BsrMatrix,    # block-CSC S, shape (K, M)
    bt: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    """y = x @ P @ Vt + x @ S in one Pallas pass. x: (T, K) -> y: (T, M)."""
    assert p.shape[-1] > 0, "dispatch r == 0 to bsr_matmul (ops.slr_matmul does)"
    one = BsrStack(bsr.counts[None], bsr.rows[None], bsr.vals[None],
                   bsr.shape, bsr.block_size, empty=bsr.empty)
    return _fused(x[None], p[None], vt[None], one, jnp.zeros((1,), jnp.int32),
                  bt, interpret)[0]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def slr_matmul_stacked_pallas(
    x: jax.Array,      # (T, K)
    p: jax.Array,      # (L, K, r)
    vt: jax.Array,     # (L, r, M)
    stack: BsrStack,   # per-layer block-CSC S, shape (K, M)
    layer: jax.Array,  # () int32 — which layer's tables to use
    bt: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    """Layer-``lax.scan``-able fused SLR matmul.

    The layer id rides at the head of the scalar-prefetch buffer and selects
    the (p, vt, vals) blocks inside the DMA index maps — only layer
    ``layer``'s tiles ever leave HBM, with no XLA gather of the stack.
    """
    sel = jnp.asarray(layer, jnp.int32).reshape(1)
    return _fused(x[None], p, vt, stack, sel, bt, interpret)[0]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def slr_matmul_multi_pallas(
    x: jax.Array,      # (B, T, K) — B batch slots
    p: jax.Array,      # (N, K, r) — N resident adapters
    vt: jax.Array,     # (N, r, M)
    stack: BsrStack,   # per-adapter block-CSC S, shape (K, M)
    ids: jax.Array,    # (B,) int32 — slot -> adapter row
    bt: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    """Batched heterogeneous-adapter fused SLR matmul: y[b] uses adapter
    ``ids[b]``'s (P, Vt, S) tables.

    The slot->adapter map rides at the head of the scalar-prefetch buffer:
    the major grid axis walks ``B * tiles`` row tiles and every DMA index map
    looks up ``ids[i // tiles]`` to pick the adapter slice — one compiled
    program serves any assignment of adapters to slots. Row padding is per
    slot (each slot's T rounds up to ``bt`` independently), so no row tile
    ever spans two slots.
    """
    return _fused(x, p, vt, stack, jnp.asarray(ids, jnp.int32), bt, interpret)

"""Pallas TPU kernel: fused SLR matmul  y = x @ P @ Vt + x @ S_bsr.

The SALAAD serving hot path evaluates a weight deployed as ``W ~= P @ Vt + S``
(low-rank + sparse) at every linear site of every decode tick. Running
``lowrank_matmul`` and ``bsr_matmul`` as two Pallas calls streams ``x`` from
HBM twice, writes two partial ``y``s back, and re-adds them in XLA — three
extra HBM round-trips per site. This kernel does both phases in ONE pass over
activation row-tiles: ``x`` is read once, ``y`` written once per tile.

Per row tile ``i`` the minor grid axis runs three phase groups over
``k_tiles + JB * MAXB`` steps:

  ph < k_tiles          accumulate  t_ref += x_blk @ p_blk       (VMEM (bt, r))
  ph >= k_tiles, slot 0 low-rank emit  acc = t_ref @ vt_j        (VMEM (bt,bs))
  ph >= k_tiles, live   sparse epilogue  acc += x[rows[j,t]] @ vals[j,t]
  ph >= k_tiles, last   y[i, j] = acc                            (one write)

The sparse epilogue reuses the scalar-prefetched block-CSC gather of
``bsr_matmul``: the ``rows`` table drives the x BlockSpec index map, so the
gather happens in the DMA engine. The BSR block size doubles as both the
K tile and the output column tile (bk == bn == bs), which is what lets the
low-rank emit and the sparse accumulate share one output accumulator.

The stacked variant adds a leading layer axis to every table
(counts ``(L, JB)``, rows ``(L, JB, MAXB)``, vals ``(L, JB, MAXB, bs, bs)``,
p ``(L, K, r)``, vt ``(L, r, M)``) and prefetches the layer id as scalar 0,
so the layer slice happens in the kernel's DMA index maps — no XLA gather of
the weight stack — and `bsr`/`fused` deployments become ``lax.scan``-able
over the transformer layer stack instead of unrolling it.

The multi-adapter variant generalizes the layer axis into an *adapter* axis
selected per batch slot: tables carry a leading adapter axis ``N`` and the
scalar buffer leads with a ``(B,)`` slot->adapter map, so one decode tick
serves B slots each running a DIFFERENT (P, Vt, S) adapter — the adapter
gather again lives entirely in the DMA index maps, one compiled program for
any slot->adapter assignment.

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


def row_tile(t_dim: int, dtype, cap: int = 128) -> int:
    """Decode-width row tile: round T up to the dtype's sublane tile, cap at
    ``cap``. A 4-row decode batch runs at bt=8 instead of padding to 128."""
    sub = {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)
    return min(cap, -(-t_dim // sub) * sub)


def _kernel(scalars_ref, x_ref, p_ref, vt_ref, vals_ref, y_ref,
            t_ref, acc_ref, *, k_tiles: int, maxb: int):
    # scalar buffer layout: [counts (JB,), rows (JB*MAXB,)]
    ph = pl.program_id(1)

    @pl.when(ph < k_tiles)
    def lowrank_accumulate():
        @pl.when(ph == 0)
        def init():
            t_ref[...] = jnp.zeros_like(t_ref)

        t_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            p_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    e = jnp.maximum(ph - k_tiles, 0)
    j, t = e // maxb, e % maxb

    @pl.when(ph >= k_tiles)
    def epilogue():
        # Slot 0 of each column window seeds the accumulator with the
        # low-rank emit; live sparse slots add on top; the last slot writes.
        @pl.when(t == 0)
        def lowrank_emit():
            acc_ref[...] = jnp.dot(
                t_ref[...], vt_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )

        @pl.when(t < scalars_ref[j])
        def sparse_accumulate():
            acc_ref[...] += jnp.dot(
                x_ref[...].astype(jnp.float32),
                vals_ref[0, 0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )

        @pl.when(t == maxb - 1)
        def emit():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


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
    t_dim, k_dim = x.shape
    n_s, m = bsr.shape
    r = p.shape[1]
    assert k_dim == n_s and p.shape[0] == k_dim and vt.shape == (r, m), (
        x.shape, p.shape, vt.shape, bsr.shape
    )
    assert r > 0, "dispatch r == 0 to bsr_matmul (ops.slr_matmul does)"
    bs = bsr.block_size
    n_pad, m_pad = bsr.padded_shape
    jb, maxb = bsr.rows.shape
    bt = row_tile(t_dim, x.dtype, cap=bt)

    x = jnp.pad(x, ((0, -t_dim % bt), (0, n_pad - k_dim)))
    p = jnp.pad(p, ((0, n_pad - k_dim), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, m_pad - m)))
    t_pad = x.shape[0]

    k_tiles = n_pad // bs
    grid = (t_pad // bt, k_tiles + jb * maxb)
    scalars = jnp.concatenate([bsr.counts, bsr.rows.reshape(-1)]).astype(jnp.int32)

    def sparse_jt(ph):
        e = jnp.maximum(ph - k_tiles, 0)
        return e // maxb, e % maxb

    def x_map(i, ph, sc):
        j, t = sparse_jt(ph)
        kb = jnp.where(ph < k_tiles, ph, sc[jb + j * maxb + t])
        return (i, kb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # x column block: K tile while accumulating, rows-table gather
            # during the sparse epilogue (padded slots reuse row 0 — always a
            # valid block; the kernel predicate skips their matmul)
            pl.BlockSpec((bt, bs), x_map),
            pl.BlockSpec(
                (bs, r), lambda i, ph, sc: (jnp.minimum(ph, k_tiles - 1), 0)
            ),
            pl.BlockSpec((r, bs), lambda i, ph, sc: (0, sparse_jt(ph)[0])),
            pl.BlockSpec(
                (1, 1, bs, bs),
                lambda i, ph, sc: (*sparse_jt(ph), 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (bt, bs), lambda i, ph, sc: (i, sparse_jt(ph)[0])
        ),
        scratch_shapes=[
            pltpu.VMEM((bt, r), jnp.float32),
            pltpu.VMEM((bt, bs), jnp.float32),
        ],
    )
    y = pl.pallas_call(
        functools.partial(_kernel, k_tiles=k_tiles, maxb=maxb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, m_pad), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(scalars, x, p, vt, bsr.vals)
    return y[:t_dim, :m]


def _stacked_kernel(scalars_ref, x_ref, p_ref, vt_ref, vals_ref, y_ref,
                    t_ref, acc_ref, *, k_tiles: int, jb: int, maxb: int):
    # scalar buffer layout: [layer, counts (L*JB,), rows (L*JB*MAXB,)]
    ph = pl.program_id(1)
    layer = scalars_ref[0]

    @pl.when(ph < k_tiles)
    def lowrank_accumulate():
        @pl.when(ph == 0)
        def init():
            t_ref[...] = jnp.zeros_like(t_ref)

        t_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            p_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    e = jnp.maximum(ph - k_tiles, 0)
    j, t = e // maxb, e % maxb

    @pl.when(ph >= k_tiles)
    def epilogue():
        @pl.when(t == 0)
        def lowrank_emit():
            acc_ref[...] = jnp.dot(
                t_ref[...], vt_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )

        @pl.when(t < scalars_ref[1 + layer * jb + j])
        def sparse_accumulate():
            acc_ref[...] += jnp.dot(
                x_ref[...].astype(jnp.float32),
                vals_ref[0, 0, 0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )

        @pl.when(t == maxb - 1)
        def emit():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


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

    The layer id rides in slot 0 of the scalar-prefetch buffer and selects
    the (p, vt, vals) blocks inside the DMA index maps — only layer
    ``layer``'s tiles ever leave HBM, with no XLA gather of the stack.
    """
    t_dim, k_dim = x.shape
    n_s, m = stack.shape
    num_l, _, r = p.shape
    assert k_dim == n_s and p.shape[1] == k_dim and vt.shape == (num_l, r, m), (
        x.shape, p.shape, vt.shape, stack.shape
    )
    assert r > 0, "dispatch r == 0 to the sparse-only path (ops.slr_matmul)"
    bs = stack.block_size
    n_pad, m_pad = stack.padded_shape
    _, jb, maxb = stack.rows.shape
    bt = row_tile(t_dim, x.dtype, cap=bt)

    x = jnp.pad(x, ((0, -t_dim % bt), (0, n_pad - k_dim)))
    p = jnp.pad(p, ((0, 0), (0, n_pad - k_dim), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, m_pad - m)))
    t_pad = x.shape[0]

    k_tiles = n_pad // bs
    grid = (t_pad // bt, k_tiles + jb * maxb)
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1),
        stack.counts.reshape(-1).astype(jnp.int32),
        stack.rows.reshape(-1).astype(jnp.int32),
    ])
    rows_base = 1 + num_l * jb  # rows table offset in the scalar buffer

    def sparse_jt(ph):
        e = jnp.maximum(ph - k_tiles, 0)
        return e // maxb, e % maxb

    def x_map(i, ph, sc):
        j, t = sparse_jt(ph)
        row = sc[rows_base + (sc[0] * jb + j) * maxb + t]
        return (i, jnp.where(ph < k_tiles, ph, row))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bs), x_map),
            pl.BlockSpec(
                (1, bs, r),
                lambda i, ph, sc: (sc[0], jnp.minimum(ph, k_tiles - 1), 0),
            ),
            pl.BlockSpec(
                (1, r, bs), lambda i, ph, sc: (sc[0], 0, sparse_jt(ph)[0])
            ),
            pl.BlockSpec(
                (1, 1, 1, bs, bs),
                lambda i, ph, sc: (sc[0], *sparse_jt(ph), 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (bt, bs), lambda i, ph, sc: (i, sparse_jt(ph)[0])
        ),
        scratch_shapes=[
            pltpu.VMEM((bt, r), jnp.float32),
            pltpu.VMEM((bt, bs), jnp.float32),
        ],
    )
    y = pl.pallas_call(
        functools.partial(_stacked_kernel, k_tiles=k_tiles, jb=jb, maxb=maxb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, m_pad), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(scalars, x, p, vt, stack.vals)
    return y[:t_dim, :m]


def _multi_kernel(scalars_ref, x_ref, p_ref, vt_ref, vals_ref, y_ref,
                  t_ref, acc_ref, *, k_tiles: int, jb: int, maxb: int,
                  tiles: int, counts_base: int):
    # scalar buffer layout: [ids (B,), counts (N*JB,), rows (N*JB*MAXB,)]
    ph = pl.program_id(1)
    aid = scalars_ref[pl.program_id(0) // tiles]

    @pl.when(ph < k_tiles)
    def lowrank_accumulate():
        @pl.when(ph == 0)
        def init():
            t_ref[...] = jnp.zeros_like(t_ref)

        t_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            p_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    e = jnp.maximum(ph - k_tiles, 0)
    j, t = e // maxb, e % maxb

    @pl.when(ph >= k_tiles)
    def epilogue():
        @pl.when(t == 0)
        def lowrank_emit():
            acc_ref[...] = jnp.dot(
                t_ref[...], vt_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )

        @pl.when(t < scalars_ref[counts_base + aid * jb + j])
        def sparse_accumulate():
            acc_ref[...] += jnp.dot(
                x_ref[...].astype(jnp.float32),
                vals_ref[0, 0, 0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )

        @pl.when(t == maxb - 1)
        def emit():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


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
    b_dim, t_dim, k_dim = x.shape
    n_s, m = stack.shape
    num_n, _, r = p.shape
    assert k_dim == n_s and p.shape[1] == k_dim and vt.shape == (num_n, r, m), (
        x.shape, p.shape, vt.shape, stack.shape
    )
    assert ids.shape == (b_dim,), (ids.shape, b_dim)
    assert r > 0, "dispatch r == 0 through a zero-rank dummy (ops does)"
    bs = stack.block_size
    n_pad, m_pad = stack.padded_shape
    _, jb, maxb = stack.rows.shape
    bt = row_tile(t_dim, x.dtype, cap=bt)
    t_pad = -(-t_dim // bt) * bt

    x = jnp.pad(x, ((0, 0), (0, t_pad - t_dim), (0, n_pad - k_dim)))
    x = x.reshape(b_dim * t_pad, n_pad)
    p = jnp.pad(p, ((0, 0), (0, n_pad - k_dim), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, m_pad - m)))

    k_tiles = n_pad // bs
    tiles = t_pad // bt  # row tiles per slot
    grid = (b_dim * tiles, k_tiles + jb * maxb)
    scalars = jnp.concatenate([
        jnp.asarray(ids, jnp.int32).reshape(-1),
        stack.counts.reshape(-1).astype(jnp.int32),
        stack.rows.reshape(-1).astype(jnp.int32),
    ])
    counts_base = b_dim
    rows_base = b_dim + num_n * jb

    def sparse_jt(ph):
        e = jnp.maximum(ph - k_tiles, 0)
        return e // maxb, e % maxb

    def x_map(i, ph, sc):
        j, t = sparse_jt(ph)
        row = sc[rows_base + (sc[i // tiles] * jb + j) * maxb + t]
        return (i, jnp.where(ph < k_tiles, ph, row))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bs), x_map),
            pl.BlockSpec(
                (1, bs, r),
                lambda i, ph, sc: (
                    sc[i // tiles], jnp.minimum(ph, k_tiles - 1), 0
                ),
            ),
            pl.BlockSpec(
                (1, r, bs),
                lambda i, ph, sc: (sc[i // tiles], 0, sparse_jt(ph)[0]),
            ),
            pl.BlockSpec(
                (1, 1, 1, bs, bs),
                lambda i, ph, sc: (sc[i // tiles], *sparse_jt(ph), 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (bt, bs), lambda i, ph, sc: (i, sparse_jt(ph)[0])
        ),
        scratch_shapes=[
            pltpu.VMEM((bt, r), jnp.float32),
            pltpu.VMEM((bt, bs), jnp.float32),
        ],
    )
    y = pl.pallas_call(
        functools.partial(
            _multi_kernel, k_tiles=k_tiles, jb=jb, maxb=maxb,
            tiles=tiles, counts_base=counts_base,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_dim * t_pad, m_pad), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(scalars, x, p, vt, stack.vals)
    return y.reshape(b_dim, t_pad, m_pad)[:, :t_dim, :m]

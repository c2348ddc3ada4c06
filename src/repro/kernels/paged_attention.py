"""Pallas TPU kernels: paged decode attention (single-query and k-query).

The serving engine stores KV in one stacked pool of ``(L, num_pages, Hkv,
bs, D)`` pages; each slot's logical sequence is scattered across pages named
by its block-table row, the same pages in every layer. Gathering those pages
with ``jnp`` materializes a ``(B, Hkv, pages_per_slot * bs, D)`` copy per
layer per step — this kernel instead scalar-prefetches the block table and
the layer index so each page is DMA'd straight from its position in the
stacked pool (the gather happens in the DMA engine, like the BSR rows table
in ``bsr_matmul.py``). The layer scan passes the whole stack to every layer's
call, so no layer's pool is ever sliced out of it.

Grid: (B * Hkv, pages_per_slot) — page minor, classic online softmax with
running (max, denom, acc) VMEM scratch carried across a slot's pages. GQA is
handled by blocking q as (B * Hkv, group, D). Pages at or beyond the slot's
valid length are skipped via ``pl.when``; unmapped table entries are clamped
to a valid pool index host-side and hidden by the positional length mask.

Interpret mode (the CPU default via ``kernels.ops``) is the validation and
container fallback path; on TPU hardware prefer ``block_size`` a multiple of
128 so page tiles align with the MXU.

``paged_attention_kquery_pallas`` is the multi-query variant: each slot
carries ``kq`` queries at consecutive positions ``length .. length + kq - 1``
— the just-inserted speculative-verify window (kq = draft k) or a chunked-
prefill chunk (kq = prefill_chunk, which can span many pages). Same
online-softmax structure with a per-row position mask; the query axis is
TILED (grid ``(B * Hkv, kq / q_tile, pages_per_slot)``) so chunk-width
windows never need a ``(kq * group, bs)`` score tile in VMEM — each query
tile carries its own running (max, denom, acc) scratch across the slot's
pages, and ``kq`` pads up to the tile multiple (padded rows compute junk that
is sliced off host-side; their positions sit past the valid window so they
only ever widen the page-skip bound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _tables(block_table, lengths, layer, n):
    """The scalar-prefetch table: ``[block_table (B * nb,), lengths (B,),
    layer (1,)]``. Unmapped entries (>= n) clamp to a real page; the length
    mask hides whatever it holds."""
    return jnp.concatenate([
        jnp.minimum(block_table, n - 1).reshape(-1),
        lengths.reshape(-1),
        jnp.reshape(layer, (1,)),
    ]).astype(jnp.int32)


def _kernel(
    tables_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, bs, nb, n_kv, table_len,
):
    # tables layout: [block_table (B * nb,), lengths (B,), layer (1,)]
    bh = pl.program_id(0)
    i = pl.program_id(1)
    b = bh // n_kv

    @pl.when(i == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = tables_ref[table_len + b]

    # the decode query sits at position ``length`` (its KV was just inserted),
    # so page i holds visible keys iff i * bs <= length
    @pl.when(i * bs <= length)
    def page():
        q = q_ref[0].astype(jnp.float32) * scale        # (group, D)
        k = k_ref[0, 0, 0].astype(jnp.float32)          # (bs, D)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (group, bs)
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(pos <= length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(
    q: jax.Array,            # (B, Hq, D) single decode query per slot
    k_pages: jax.Array,      # (L, num_pages, Hkv, bs, D) stacked page pools
    v_pages: jax.Array,
    block_table: jax.Array,  # (B, pages_per_slot) int32
    lengths: jax.Array,      # (B,) int32 pre-insert valid length per slot
    layer: jax.Array | int,  # () int32 layer whose pages attention reads
    *,
    interpret: bool,
) -> jax.Array:
    b, hq, d = q.shape
    _, n, hkv, bs, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    nb = block_table.shape[1]
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * hkv, group, d)
    tables = _tables(block_table, lengths, layer, n)
    lyr = b * nb + b              # the layer index's slot in ``tables``
    # one (bs, D) page of the stacked pool: (layer, page, head, 0, 0)
    page_spec = pl.BlockSpec(
        (1, 1, 1, bs, d),
        lambda bh, i, t: (t[lyr], t[(bh // hkv) * nb + i], bh % hkv, 0, 0),
    )

    kernel = functools.partial(
        _kernel, scale=scale, bs=bs, nb=nb, n_kv=hkv, table_len=b * nb,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, nb),
        in_specs=[
            pl.BlockSpec((1, group, d), lambda bh, i, t: (bh, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, group, d), lambda bh, i, t: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(tables, qf, k_pages, v_pages)
    return out.reshape(b, hq, d)


# ------------------------------------------------------- k-query (verify) ---


def _kquery_kernel(
    tables_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, bs, nb, n_kv, q_tile, group, table_len,
):
    # tables layout: [block_table (B * nb,), lengths (B,), layer (1,)]
    bh = pl.program_id(0)
    qt = pl.program_id(1)
    i = pl.program_id(2)
    b = bh // n_kv
    rows = q_tile * group

    @pl.when(i == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = tables_ref[table_len + b]
    q0 = qt * q_tile     # first query index of this tile

    # query row r = qi * group + g sits at position length + q0 + qi; the page
    # holds visible keys for SOME row of the tile iff
    # i * bs <= length + q0 + q_tile - 1
    @pl.when(i * bs <= length + q0 + q_tile - 1)
    def page():
        q = q_ref[0].astype(jnp.float32) * scale        # (rows, D)
        k = k_ref[0, 0, 0].astype(jnp.float32)          # (bs, D)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (rows, bs)
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // group
        s = jnp.where(pos <= length + qi, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


# per-tile query rows beyond which the query axis splits into grid tiles:
# bounds the (rows, bs) score block and the running-softmax scratch in VMEM
# however wide the chunked-prefill window grows
_MAX_Q_ROWS = 128


@functools.partial(jax.jit, static_argnames=("interpret", "q_tile"))
def paged_attention_kquery_pallas(
    q: jax.Array,            # (B, Hq, kq, D) — kq queries per slot, positions
    #                          length .. length + kq - 1 (speculative-verify
    #                          window or chunked-prefill chunk)
    k_pages: jax.Array,      # (L, num_pages, Hkv, bs, D) stacked page pools
    v_pages: jax.Array,
    block_table: jax.Array,  # (B, pages_per_slot) int32
    lengths: jax.Array,      # (B,) int32 pre-insert valid length per slot
    layer: jax.Array | int,  # () int32 layer whose pages attention reads
    *,
    interpret: bool,
    q_tile: int | None = None,  # queries per grid tile; None = auto (whole
    #                             window while kq * group <= _MAX_Q_ROWS)
) -> jax.Array:
    b, hq, kq, d = q.shape
    _, n, hkv, bs, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    nb = block_table.shape[1]
    scale = 1.0 / (d ** 0.5)

    if q_tile is None:
        q_tile = kq if kq * group <= _MAX_Q_ROWS else max(_MAX_Q_ROWS // group, 1)
    q_tile = max(min(q_tile, kq), 1)
    kq_pad = -(-kq // q_tile) * q_tile
    if kq_pad != kq:
        # padded queries sit at positions length + kq .. length + kq_pad - 1:
        # past the valid window, so they only widen the page-skip bound of the
        # last tile; their junk output rows are sliced off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, kq_pad - kq), (0, 0)))
    nq = kq_pad // q_tile

    # rows ordered query-major: row = qi * group + g
    qf = q.reshape(b, hkv, group, kq_pad, d).transpose(0, 1, 3, 2, 4)
    qf = qf.reshape(b * hkv, kq_pad * group, d)
    tables = _tables(block_table, lengths, layer, n)
    lyr = b * nb + b              # the layer index's slot in ``tables``
    page_spec = pl.BlockSpec(
        (1, 1, 1, bs, d),
        lambda bh, qt, i, t: (t[lyr], t[(bh // hkv) * nb + i], bh % hkv, 0, 0),
    )

    kernel = functools.partial(
        _kquery_kernel, scale=scale, bs=bs, nb=nb, n_kv=hkv, q_tile=q_tile,
        group=group, table_len=b * nb,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, nq, nb),
        in_specs=[
            pl.BlockSpec(
                (1, q_tile * group, d), lambda bh, qt, i, t: (bh, qt, 0)
            ),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, q_tile * group, d), lambda bh, qt, i, t: (bh, qt, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((q_tile * group, 1), jnp.float32),
            pltpu.VMEM((q_tile * group, 1), jnp.float32),
            pltpu.VMEM((q_tile * group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, kq_pad * group, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(tables, qf, k_pages, v_pages)
    out = out.reshape(b, hkv, kq_pad, group, d).transpose(0, 1, 3, 2, 4)
    return out.reshape(b, hq, kq_pad, d)[:, :, :kq]

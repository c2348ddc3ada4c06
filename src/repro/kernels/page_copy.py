"""Pallas TPU kernel: batched whole-page copy over a paged KV pool.

The device half of copy-on-write prefix sharing (``serving/prefix_cache.py``):
when a slot's first divergent write would land in a page it shares read-only
with the radix prompt index, the engine allocates a private page and copies the
shared payload into it before the write. Copies are batched — one call moves
every CoW pair of an admission tick across all layers — and the pool operand is
ALIASED to the output (``input_output_aliases``), so the untouched pages are
never rewritten: the kernel only DMAs the ``n`` copied pages.

The same kernel serves every pool layout the paged cache carries: float
payloads, int8 payloads, and the f32 scale pools (trailing dim 1) — a page is
copied bit-for-bit whatever it stores. Grid is ``(n, L)`` with the src/dst page
ids scalar-prefetched, mirroring the block-table prefetch in
``paged_attention.py``: the gather/scatter happens in the DMA engine.

Pairs may be padded with (0, 0) identity entries (page 0 onto itself) so the
engine compiles only power-of-two batch widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(ids_ref, src_ref, o_ref):
    del ids_ref  # consumed by the index maps
    o_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_copy_pallas(
    pool: jax.Array,   # (L, num_pages, H, bs, D) — payload or scale pool
    src: jax.Array,    # (n,) int32 source page ids
    dst: jax.Array,    # (n,) int32 destination page ids
    *,
    interpret: bool,
) -> jax.Array:
    """``out[:, dst[i]] = pool[:, src[i]]``; every other page unchanged."""
    l, _, h, bs, d = pool.shape
    n = src.shape[0]
    ids = jnp.concatenate([src, dst]).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, l),
        in_specs=[
            pl.BlockSpec((1, 1, h, bs, d), lambda i, li, t: (li, t[i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, h, bs, d), lambda i, li, t: (li, t[n + i], 0, 0, 0)
        ),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # alias the pool into the output: only the n destination pages are
        # written, everything else stays in place (no full-pool roundtrip)
        input_output_aliases={1: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(ids, pool)

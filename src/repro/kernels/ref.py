"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def soft_threshold_ref(x: jax.Array, tau) -> jax.Array:
    tau = jnp.asarray(tau, x.dtype)
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - tau, 0)


def lowrank_matmul_ref(x: jax.Array, p: jax.Array, vt: jax.Array) -> jax.Array:
    return (x.astype(jnp.float32) @ p.astype(jnp.float32) @ vt.astype(jnp.float32)).astype(x.dtype)


def bsr_matmul_ref(x: jax.Array, bsr) -> jax.Array:
    """Scatter the blocks to dense, then dense matmul."""
    from .bsr_matmul import bsr_to_dense

    dense = bsr_to_dense(bsr)
    return (x.astype(jnp.float32) @ dense.astype(jnp.float32)).astype(x.dtype)


def slr_matmul_ref(x: jax.Array, p, vt, bsr=None) -> jax.Array:
    """y = x @ P @ Vt + x @ S, matching the fused kernel's numerics: both
    contributions accumulate in one f32 accumulator and cast to x.dtype once
    (NOT lowrank_ref + bsr_ref, which would round each partial separately)."""
    from .bsr_matmul import bsr_to_dense

    xf = x.astype(jnp.float32)
    acc = jnp.zeros((x.shape[0], vt.shape[-1] if vt is not None else bsr.shape[1]),
                    jnp.float32)
    if p is not None and p.shape[-1] > 0:
        acc = acc + xf @ p.astype(jnp.float32) @ vt.astype(jnp.float32)
    if bsr is not None and not getattr(bsr, "empty", False):
        acc = acc + xf @ bsr_to_dense(bsr).astype(jnp.float32)
    return acc.astype(x.dtype)


def slr_matmul_stacked_ref(x: jax.Array, p, vt, stack, layer) -> jax.Array:
    """Per-layer oracle for the stacked kernel: dynamic-slice layer ``layer``
    out of every table and defer to ``slr_matmul_ref``."""
    idx = lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)
    bsr = None
    if stack is not None and not getattr(stack, "empty", False):
        from .bsr_matmul import BsrMatrix

        bsr = BsrMatrix(
            idx(stack.counts), idx(stack.rows), idx(stack.vals),
            stack.shape, stack.block_size, empty=stack.empty,
        )
    return slr_matmul_ref(
        x, None if p is None else idx(p), None if vt is None else idx(vt), bsr
    )


def slr_matmul_multi_ref(x: jax.Array, p, vt, stack, ids) -> jax.Array:
    """Per-slot oracle for the multi-adapter kernel: slot ``b`` runs the
    stacked oracle with adapter ``ids[b]``'s tables."""
    ids = jnp.asarray(ids, jnp.int32)
    return jax.vmap(
        lambda xb, i: slr_matmul_stacked_ref(xb, p, vt, stack, i)
    )(x, ids)


def paged_attention_ref(
    q: jax.Array,            # (B, Hq, D) single decode query per slot
    k_pages: jax.Array,      # (L, num_pages, Hkv, bs, D) stacked page pools
    v_pages: jax.Array,
    block_table: jax.Array,  # (B, pages_per_slot) int32; >= num_pages unmapped
    lengths: jax.Array,      # (B,) pre-insert valid length per slot
    layer,                   # () layer whose pages attention reads
) -> jax.Array:
    """Gather layer ``layer``'s pages via the block table, then masked decode
    softmax.

    The query at slot b sits at position ``lengths[b]`` (its KV is already in
    the pool), so keys at positions <= lengths[b] are visible.
    """
    _, n, hkv, bs, d = k_pages.shape
    b, hq, _ = q.shape
    group = hq // hkv
    bt = jnp.minimum(block_table, n - 1)     # clamp unmapped; mask hides it
    nb = bt.shape[1]

    def gather(pages):
        g = pages[layer, bt]                 # (B, nb, Hkv, bs, D)
        return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * bs, d)

    k, v = gather(k_pages), gather(v_pages)
    scale = 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32) * scale
    sc = jnp.einsum("bhgd,bhsd->bhgs", qg, k.astype(jnp.float32))
    mask = jnp.arange(nb * bs)[None, :] <= lengths[:, None]      # (B, S)
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def paged_attention_kquery_ref(
    q: jax.Array,            # (B, Hq, kq, D) — kq decode queries per slot
    k_pages: jax.Array,      # (L, num_pages, Hkv, bs, D) stacked page pools
    v_pages: jax.Array,
    block_table: jax.Array,  # (B, pages_per_slot) int32; >= num_pages unmapped
    lengths: jax.Array,      # (B,) pre-insert valid length per slot
    layer,                   # () layer whose pages attention reads
) -> jax.Array:
    """k-query paged attention oracle (speculative-verify window AND
    chunked-prefill chunks — the kernel's query tiling must be invisible, so
    this oracle is deliberately tiling-free).

    Query i of slot b sits at position ``lengths[b] + i`` (the KV of all kq
    window tokens is already in the pool), so it sees keys at positions
    <= lengths[b] + i.
    """
    _, n, hkv, bs, d = k_pages.shape
    b, hq, kq, _ = q.shape
    group = hq // hkv
    bt = jnp.minimum(block_table, n - 1)     # clamp unmapped; mask hides it
    nb = bt.shape[1]

    def gather(pages):
        g = pages[layer, bt]                 # (B, nb, Hkv, bs, D)
        return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * bs, d)

    k, v = gather(k_pages), gather(v_pages)
    scale = 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, group, kq, d).astype(jnp.float32) * scale
    sc = jnp.einsum("bhgqd,bhsd->bhgqs", qg, k.astype(jnp.float32))
    q_pos = lengths[:, None] + jnp.arange(kq)[None, :]           # (B, kq)
    mask = jnp.arange(nb * bs)[None, None, :] <= q_pos[:, :, None]  # (B, kq, S)
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgqs,bhsd->bhgqd", w, v.astype(jnp.float32))
    return out.reshape(b, hq, kq, d).astype(q.dtype)


def page_copy_ref(
    pool: jax.Array,   # (L, num_pages, H, bs, D) — payload or scale pool
    src: jax.Array,    # (n,) int32 source page ids
    dst: jax.Array,    # (n,) int32 destination page ids
) -> jax.Array:
    """Batched whole-page copy: ``out[:, dst[i]] = pool[:, src[i]]`` with every
    other page untouched (the copy-on-write primitive of prefix sharing).
    Duplicate destinations are only ever the (0, 0) identity padding pairs, so
    scatter order cannot matter."""
    return pool.at[:, dst].set(pool[:, src])


def attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True, scale=None
) -> jax.Array:
    """Dense softmax attention with GQA broadcast — O(T*S) memory."""
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum(
        "bhtd,bhsd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t, s), bool), k=s - t)
        logits = jnp.where(mask[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", w, v.astype(jnp.float32)).astype(q.dtype)

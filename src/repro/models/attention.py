"""Attention: GQA projections + blockwise (memory-efficient) softmax.

Three execution paths, one set of weights:
  * ``blockwise_attention`` — pure-JAX flash algorithm (double lax.scan over
    q/kv chunks). This is the pjit/GSPMD default: it never materializes the
    (T, S) score matrix, which is what lets the prefill_32k cells fit HBM.
  * ``repro.kernels.flash_attention`` — Pallas TPU kernel (kernel_impl='pallas').
  * ``dense path`` — plain softmax for tiny smoke shapes (kernel_impl='dense').

Decode: single-token query against a preallocated KV cache, O(S) einsum.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import apply_rope, apply_weight

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (B, Hkv, S, D)
    v: jax.Array  # (B, Hkv, S, D)
    length: jax.Array  # () int32 valid prefix — or (B,) for per-slot lengths


class PagedLayerCache(NamedTuple):
    """One layer's view of the block-paged KV cache (serving paged path).

    The pools are the whole stacked cache, ``(L, num_pages, Hkv, block_size,
    D)``, and ``layer`` names this view's layer (a traced scalar inside the
    layer scan, a Python int when the stack is unrolled). Reads and writes
    address the stack at ``[layer, page]`` in place: no layer's pool is ever
    sliced out and written back, so a decode step moves only the pages
    attention reads and the rows it inserts.

    Token position j of slot b lives in page ``block_table[b, j // bs]`` at
    offset ``j % bs``. Entries ``>= num_pages`` mean "unmapped" — writes to
    them drop, gathers clamp (and the length mask hides whatever they read).
    When ``k_scale``/``v_scale`` are present the payload pools are int8 and
    dequantize per-(position, head) — serving/kv_quant.py layout.
    """

    k: jax.Array            # (L, num_pages, Hkv, block_size, D) page pools
    v: jax.Array
    block_table: jax.Array  # (B, pages_per_slot) int32
    length: jax.Array       # (B,) int32 valid tokens per slot
    layer: jax.Array | int  # () int32 layer index into the stacked pools
    k_scale: jax.Array | None = None  # (L, num_pages, Hkv, block_size, 1) f32
    v_scale: jax.Array | None = None


def paged_insert(cache: PagedLayerCache, kh: jax.Array, vh: jax.Array) -> PagedLayerCache:
    """Insert t decode tokens (B, Hkv, t, D) at positions length..length+t-1
    of layer ``cache.layer``: one small scatter per stacked pool, of one
    ``(D,)`` row per (slot, token, head) at ``[layer, page, head, off]``.

    t == 1 is the classic decode insert; t == k is the speculative verify
    insert (the k draft positions land in one scatter); t == chunk is the
    chunked-prefill insert (the chunk scatters in at the slot's current
    length). Unmapped pages (freed slots) and positions beyond the slot's
    table capacity map to the out-of-range sentinel, so those writes drop;
    per-slot page sets are disjoint by allocator invariant, so the scatter
    has no collisions.

    With prefix caching, a slot's table may reference SHARED pages (allocator
    refcount > 1) attached read-only from the radix index. The engine
    maintains the invariant that inserts never land in a shared page: shared
    pages are always full (attached at page granularity) and the slot's
    length starts past them, except for the one partially-resumed page that
    admission copy-on-writes (kernels/page_copy.py) and remaps BEFORE the
    first insert. This function therefore stays collision-free unchanged.
    """
    _, n, _, bs, _ = cache.k.shape
    nb = cache.block_table.shape[1]
    t = kh.shape[2]
    pos = cache.length[:, None] + jnp.arange(t)[None, :]           # (B, t)
    blk = jnp.clip(pos // bs, 0, nb - 1)
    page = jnp.take_along_axis(cache.block_table, blk, axis=1)     # (B, t)
    # positions past the table's capacity must not clamp into a REAL page
    # (that would corrupt another slot's block) — send them out of bounds
    page = jnp.where(pos < nb * bs, page, n)
    off = pos % bs
    k_tok = kh.transpose(0, 2, 1, 3)              # (B, t, Hkv, D)
    v_tok = vh.transpose(0, 2, 1, 3)
    # every axis but the last is indexed, so each update is one (D,) row:
    # the scatter then keeps the pool's own layout and writes in place
    head = jnp.arange(kh.shape[1])[None, None, :]
    page, off = page[..., None], off[..., None]   # (B, t, 1)

    def put(pool, rows):
        return pool.at[cache.layer, page, head, off].set(
            rows.astype(pool.dtype), mode="drop"
        )

    if cache.k_scale is not None:
        from ..serving.kv_quant import quantize_kv

        k_q, k_s = quantize_kv(k_tok)
        v_q, v_s = quantize_kv(v_tok)
        return cache._replace(
            k=put(cache.k, k_q), v=put(cache.v, v_q),
            k_scale=put(cache.k_scale, k_s), v_scale=put(cache.v_scale, v_s),
            length=cache.length + t,
        )
    return cache._replace(
        k=put(cache.k, k_tok), v=put(cache.v, v_tok), length=cache.length + t,
    )


def paged_gather(cache: PagedLayerCache) -> tuple[jax.Array, jax.Array]:
    """Materialize each slot's logical KV sequence of layer ``cache.layer``
    from its pages (the jnp path: int8 pages and non-Pallas kernels).

    Returns (k, v) of shape (B, Hkv, pages_per_slot * block_size, D) laid out
    so logical position j of the contiguous cache and position j here hold
    identical values — the decode einsum then matches the unpaged path.
    """
    n = cache.k.shape[1]
    bt = jnp.minimum(cache.block_table, n - 1)    # clamp unmapped; mask hides it

    def gather(pages, scale):
        g = pages[cache.layer, bt]                # (B, nb, Hkv, bs, D)
        if scale is not None:
            g = g.astype(jnp.float32) * scale[cache.layer, bt]
        b, nb, h, bs, d = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(b, h, nb * bs, d)

    return gather(cache.k, cache.k_scale), gather(cache.v, cache.v_scale)


def init_qkv(key, d_model, n_heads, n_kv, head_dim, dtype, bias=False):
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d_model)
    so = 1.0 / np.sqrt(n_heads * head_dim)
    p = {
        "q": (jax.random.normal(kq, (d_model, n_heads * head_dim)) * s).astype(dtype),
        "k": (jax.random.normal(kk, (d_model, n_kv * head_dim)) * s).astype(dtype),
        "v": (jax.random.normal(kv, (d_model, n_kv * head_dim)) * s).astype(dtype),
        "o": (jax.random.normal(ko, (n_heads * head_dim, d_model)) * so).astype(dtype),
    }
    if bias:
        p["q_bias"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["k_bias"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["v_bias"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def _proj(x, w, b=None):
    y = apply_weight(x, w)
    if b is not None:
        y = y + b
    return y


def blockwise_attention(
    q: jax.Array,  # (B, Hq, T, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,
    causal: bool = True,
    q_block: int = 512,
    kv_block: int = 1024,
    causal_offset: jax.Array | int = 0,
) -> jax.Array:
    """Flash-style attention in pure jnp; O(T*D) memory, scores never stored.

    ``causal_offset``: query position i attends to keys <= i + offset (used
    when T < S, e.g. chunked prefill against a longer cache). A scalar applies
    one offset to every row; a ``(B,)`` vector gives each batch row its own
    offset — chunked prefill over a batch of slots at ragged lengths.
    """
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    group = hq // hkv
    scale = 1.0 / np.sqrt(d)
    bq = min(q_block, t)
    bk = min(kv_block, s)
    # pad to block multiples
    t_pad, s_pad = -t % bq, -s % bk
    if t_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    if s_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
    tq, sk = q.shape[2], k.shape[2]
    nq, nk = tq // bq, sk // bk

    qb = q.reshape(b, hkv, group, nq, bq, d).astype(jnp.float32) * scale
    kb = k.reshape(b, hkv, nk, bk, d).astype(jnp.float32)
    vb = v.reshape(b, hkv, nk, bk, d).astype(jnp.float32)

    q_pos = jnp.arange(tq).reshape(nq, bq)
    k_pos = jnp.arange(sk).reshape(nk, bk)
    valid_k = (k_pos < s)  # padding mask (nk, bk)
    offset = jnp.asarray(causal_offset)

    def q_step(_, qi):
        q_i = qb[:, :, :, qi]          # (b, hkv, group, bq, d)
        qp = q_pos[qi]                  # (bq,)

        def kv_step(carry, ki):
            m, l, acc = carry
            k_i = kb[:, :, ki]          # (b, hkv, bk, d)
            v_i = vb[:, :, ki]
            sc = jnp.einsum("bhgqd,bhkd->bhgqk", q_i, k_i)
            mask = valid_k[ki][None, None, None, None, :]
            if causal:
                if offset.ndim:         # per-slot offsets: (b, bq, bk) mask
                    cm = (qp[None, :, None] + offset[:, None, None]) \
                        >= k_pos[ki][None, None, :]
                    cm = cm[:, None, None]
                else:
                    cm = ((qp[:, None] + offset) >= k_pos[ki][None, :])[
                        None, None, None
                    ]
                mask = jnp.logical_and(mask, cm)
            sc = jnp.where(mask, sc, NEG_INF)
            m_cur = jnp.max(sc, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m - m_new)
            l_new = corr * l + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = corr * acc + jnp.einsum("bhgqk,bhkd->bhgqd", p, v_i)
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((b, hkv, group, bq, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, group, bq, 1), jnp.float32),
            jnp.zeros((b, hkv, group, bq, d), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        return None, acc / jnp.maximum(l, 1e-30)

    _, out = jax.lax.scan(q_step, None, jnp.arange(nq))  # (nq, b, hkv, g, bq, d)
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, tq, d)
    return out[:, :, :t].astype(q.dtype)


def dense_attention(q, k, v, causal=True):
    """Small-shape oracle path."""
    from ..kernels.ref import attention_ref

    return attention_ref(q, k, v, causal=causal)


def attention_block(
    params: dict,
    x: jax.Array,                 # (B, T, d_model)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    positions: jax.Array | None = None,
    rope_theta: float | None = 1e4,
    causal: bool = True,
    cache: KVCache | None = None,
    kv_override: tuple[jax.Array, jax.Array] | None = None,  # cross-attention
    kernel_impl: str = "blockwise",
    q_block: int = 512,
    kv_block: int = 1024,
    causal_scheme: str = "full",
) -> tuple[jax.Array, KVCache | None]:
    """Full attention sub-block: projections + rope + attention + output.

    * training/prefill: cache is None (or preallocated for prefill fill-in)
    * decode: cache holds S past positions; x is (B, 1, d)
    * cross-attention: kv_override supplies precomputed (k, v) heads
    """
    b, t, _ = x.shape
    paged_kernel = (
        isinstance(cache, PagedLayerCache)
        and kernel_impl == "pallas"
        and cache.k_scale is None
    )
    q = _proj(x, params["q"], params.get("q_bias"))
    q = q.reshape(b, t, n_heads, head_dim)

    if kv_override is not None:
        kh, vh = kv_override  # (B, Hkv, S, D)
        new_cache = cache
    else:
        k = _proj(x, params["k"], params.get("k_bias")).reshape(b, t, n_kv, head_dim)
        v = _proj(x, params["v"], params.get("v_bias")).reshape(b, t, n_kv, head_dim)
        if positions is None:
            positions = jnp.arange(t)[None, :]
        if rope_theta is not None:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
        kh = k.transpose(0, 2, 1, 3)  # (B, Hkv, T, D)
        vh = v.transpose(0, 2, 1, 3)
        if isinstance(cache, PagedLayerCache):
            # t == 1: classic paged decode; t == k: speculative verify;
            # t == chunk: chunked prefill — the t positions insert in one
            # scatter and attend through the block table (query i sees keys
            # <= length + i): in the Pallas kernel's DMA, else a jnp gather
            new_cache = paged_insert(cache, kh, vh)
            if not paged_kernel:
                kh, vh = paged_gather(new_cache)
        elif cache is not None:
            # insert at cache.length (decode: t == 1; chunked prefill: t == chunk)
            if jnp.ndim(cache.length) == 0:
                kc = jax.lax.dynamic_update_slice(
                    cache.k, kh.astype(cache.k.dtype), (0, 0, cache.length, 0)
                )
                vc = jax.lax.dynamic_update_slice(
                    cache.v, vh.astype(cache.v.dtype), (0, 0, cache.length, 0)
                )
            else:
                # per-slot lengths (batched serving): each sequence inserts at
                # its own write position — vmapped scatter with mode='drop' so
                # rows past the buffer end are DROPPED per-position (a
                # dynamic_update_slice would clamp the whole write start back
                # into the valid region when length + t > max_len, silently
                # shifting a ragged chunk's tail over valid history)
                ins = jax.vmap(
                    lambda ck, kn, pos: ck.at[
                        :, pos + jnp.arange(kn.shape[1]), :
                    ].set(kn, mode="drop")
                )
                kc = ins(cache.k, kh.astype(cache.k.dtype), cache.length)
                vc = ins(cache.v, vh.astype(cache.v.dtype), cache.length)
            new_cache = KVCache(kc, vc, cache.length + t)
            kh, vh = kc, vc
        else:
            new_cache = None

    qh = q.transpose(0, 2, 1, 3)  # (B, Hq, T, D)

    if cache is not None and kv_override is None:
        if paged_kernel:
            # Pallas paged-decode kernels: the page gather happens in the DMA
            # engine via the scalar-prefetched block table, not a jnp gather.
            # t == 1 is the single-query decode kernel; t > 1 the multi-query
            # variant (query i attends keys <= length + i) serving both the
            # k-wide speculative verify and chunk-wide chunked prefill.
            if t == 1:
                from ..kernels.ops import paged_attention

                out = paged_attention(
                    qh[:, :, 0], new_cache.k, new_cache.v,
                    new_cache.block_table, cache.length, cache.layer,
                )[:, :, None, :]
            else:
                from ..kernels.ops import paged_attention_kquery

                out = paged_attention_kquery(
                    qh, new_cache.k, new_cache.v,
                    new_cache.block_table, cache.length, cache.layer,
                )
        elif t > 1 and not isinstance(cache, PagedLayerCache):
            # chunked prefill into a cache: the dense masked-score path would
            # materialize (T, S) scores (34 GB/device measured on zamba2
            # prefill_32k) — use the flash path with a causal offset so query
            # i attends keys <= cache.length + i.
            if jnp.ndim(cache.length) != 0:
                # per-slot lengths (batched serving): each slot's chunk sits
                # at its own offset — blockwise path with a (B,) causal
                # offset; forward-only here, so the custom-VJP wrapper is
                # unnecessary
                out = blockwise_attention(
                    qh, kh, vh, causal=True, q_block=q_block,
                    kv_block=kv_block, causal_offset=cache.length,
                )
            else:
                from .flash_vjp import flash_attention_jax

                out = flash_attention_jax(
                    qh, kh, vh, True, q_block, kv_block, cache.length, "full"
                )
        else:
            # single-token decode (and k-token paged verify): O(t*S) masked
            # einsum — query i of slot b attends keys <= length[b] + i
            s = kh.shape[2]
            scale = 1.0 / np.sqrt(head_dim)
            group = n_heads // n_kv
            qg = qh.reshape(b, n_kv, group, t, head_dim).astype(jnp.float32) * scale
            sc = jnp.einsum("bhgtd,bhsd->bhgts", qg, kh.astype(jnp.float32))
            k_idx = jnp.arange(s)
            if jnp.ndim(cache.length) == 0:
                q_idx = cache.length + jnp.arange(t)[:, None]
                mask = k_idx[None, :] <= q_idx          # (t, s) causal prefix
                sc = jnp.where(mask[None, None, None], sc, NEG_INF)
            else:
                # per-slot valid prefixes: query of slot b sits at length[b]
                q_idx = cache.length[:, None] + jnp.arange(t)[None, :]
                mask = k_idx[None, None, :] <= q_idx[..., None]  # (B, t, s)
                sc = jnp.where(mask[:, None, None], sc, NEG_INF)
            w = jax.nn.softmax(sc, axis=-1)
            out = jnp.einsum("bhgts,bhsd->bhgtd", w, vh.astype(jnp.float32))
            out = out.reshape(b, n_heads, t, head_dim).astype(x.dtype)
    elif kernel_impl == "pallas":
        from ..kernels.ops import flash_attention

        out = flash_attention(qh, kh, vh, causal=causal)
    elif kernel_impl == "dense":
        out = dense_attention(qh, kh, vh, causal=causal)
    else:
        # custom-VJP flash path: O(T) residuals (naive autodiff through the
        # blockwise scan would save the full O(T^2) probability tensors)
        from .flash_vjp import flash_attention_jax

        out = flash_attention_jax(
            qh, kh, vh, causal, q_block, kv_block, 0, causal_scheme
        )

    out = out.transpose(0, 2, 1, 3).reshape(b, t, n_heads * head_dim)
    if cache is None and kv_override is None:
        # expose the projected/rotated KV heads so prefill can build a cache
        # without re-running the projections (or a dense-score path)
        new_cache = (kh, vh)
    return apply_weight(out, params["o"]), new_cache

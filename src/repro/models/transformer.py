"""Decoder-only transformer LM (dense / MoE / VLM-backbone) with scan-stacked
layers, optional remat, KV-cache decode, and sharding constraints.

Layers are stacked along a leading axis and applied with ``jax.lax.scan`` so
the HLO (and compile time) is depth-independent — mandatory for the 80-layer
dry-run cells. SALAAD sees the stacked leaves and treats every slice as an
independent block (core/selection.py).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import constrain
from .attention import KVCache, PagedLayerCache, attention_block, init_qkv
from .layers import apply_mlp, apply_norm, apply_weight, embed, init_embedding, init_mlp, init_norm
from .moe import init_moe, moe_ffn


class LMCache(NamedTuple):
    k: jax.Array       # (L, B, Hkv, S, D)
    v: jax.Array
    length: jax.Array  # () — or (B,) for per-slot serving lengths


class PagedKVCache(NamedTuple):
    """Block-paged serving cache: a fixed pool of pages per layer plus a
    per-slot block table. Serving memory is governed by ``num_pages`` (the
    actual budget), not ``max_slots * max_len`` (the worst case). The block
    table and lengths are shared across layers; position j of slot b lives in
    page ``block_table[b, j // block_size]``, offset ``j % block_size``.

    int8 page pools (serving/kv_quant.py) carry per-(position, head) scale
    pools in ``k_scale``/``v_scale``; None means float payload.
    """

    k: jax.Array            # (L, num_pages, Hkv, block_size, D)
    v: jax.Array
    block_table: jax.Array  # (max_slots, pages_per_slot) int32; >= num_pages = unmapped
    length: jax.Array       # (max_slots,) int32
    k_scale: jax.Array | None = None  # (L, num_pages, Hkv, block_size, 1) f32
    v_scale: jax.Array | None = None

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]


def init_layer(key, cfg) -> dict:
    ka, km, kn = jax.random.split(key, 3)
    p: dict[str, Any] = {}
    p.update(
        init_qkv(
            ka, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.param_dtype, bias=cfg.qkv_bias,
        )
    )
    pre = init_norm(kn, cfg.d_model, cfg.norm_type, cfg.param_dtype)
    p["pre_attn"] = pre
    p["pre_mlp"] = init_norm(jax.random.fold_in(kn, 1), cfg.d_model, cfg.norm_type, cfg.param_dtype)
    if cfg.num_experts:
        p["moe"] = init_moe(km, cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.param_dtype)
    else:
        p.update(init_mlp(km, cfg.d_model, cfg.d_ff, cfg.mlp_type, cfg.param_dtype))
    return p


def init_lm(cfg, key) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    params = {
        "embed": init_embedding(ke, cfg.vocab_size, cfg.d_model, cfg.param_dtype),
        "layers": layers,
        "final_norm": init_norm(jax.random.fold_in(ke, 2), cfg.d_model, cfg.norm_type, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": (jax.random.normal(kh, (cfg.d_model, cfg.vocab_size)) / np.sqrt(cfg.d_model)).astype(cfg.param_dtype)
        }
    return params


def _is_layer_view(x) -> bool:
    """Duck-typed: a leaf that must not be sliced as scan xs (e.g. a stacked
    fused ``SLRLinear`` whose BSR tables are selected per layer inside the
    Pallas kernel's DMA index maps, ``serving/slr_params.py``)."""
    return getattr(x, "scan_by_index", False)


def layer_view(layers, l):
    """Per-layer view of a scan-stacked layer tree for index-driven scans.

    Ordinary stacked arrays are dynamic-sliced at layer ``l`` (exactly what
    ``lax.scan`` xs would do); ``scan_by_index`` leaves return ``at_layer(l)``
    views that keep their stacked tables whole — slicing those as xs would
    copy an entire sparse table out of HBM every layer of every tick.
    """
    def index_leaf(leaf):
        if _is_layer_view(leaf):
            return leaf.at_layer(l)
        return jax.lax.dynamic_index_in_dim(leaf, l, 0, keepdims=False)

    return jax.tree_util.tree_map(index_leaf, layers, is_leaf=_is_layer_view)


def _scan_by_index(layers) -> bool:
    return any(
        _is_layer_view(leaf)
        for leaf in jax.tree_util.tree_leaves(layers, is_leaf=_is_layer_view)
    )


def _layer_apply(lp, x, cfg, positions, cache: KVCache | None):
    """One transformer layer. Returns (x, aux_loss, new_kv)."""
    h = apply_norm(x, lp.get("pre_attn"), cfg.norm_type)
    attn_out, kv = attention_block(
        lp, h,
        n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads, head_dim=cfg.head_dim,
        positions=positions, rope_theta=cfg.rope_theta, causal=True,
        cache=cache, kernel_impl=cfg.kernel_impl,
        q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block,
        causal_scheme=cfg.causal_scheme,
    )
    x = x + attn_out
    h = apply_norm(x, lp.get("pre_mlp"), cfg.norm_type)
    if cfg.num_experts:
        mlp_out, aux = moe_ffn(
            lp["moe"], h,
            num_experts=cfg.num_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, num_groups=cfg.moe_groups,
        )
    else:
        mlp_out, aux = apply_mlp(lp, h, cfg.mlp_type), jnp.zeros((), jnp.float32)
    x = x + mlp_out
    x = constrain(x, ("data", None, None))
    return x, aux, kv


def forward(
    params: dict,
    tokens: jax.Array,               # (B, T) int32
    cfg,
    *,
    prefix_embeds: jax.Array | None = None,   # (B, P, d) VLM patch stub
    cache: LMCache | None = None,
    position_offset: jax.Array | int = 0,
    collect_kv: bool = False,
) -> tuple[jax.Array, Any, jax.Array]:
    """Returns (logits (B, T', vocab), new_cache_or_kv, aux_loss).

    * train/eval: cache=None. new_cache_or_kv = stacked (k, v) heads per layer
      (useable to build a prefill cache).
    * decode: cache given, tokens (B, 1). Returns updated LMCache.
    """
    x = embed(params["embed"], tokens)
    x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, t, _ = x.shape
    x = constrain(x, ("data", None, None))
    offset = jnp.asarray(position_offset)
    if offset.ndim:  # per-slot lengths: (B,) offsets -> (B, t) positions
        positions = offset[:, None] + jnp.arange(t)[None, :]
    else:
        positions = offset + jnp.arange(t)[None, :]

    aux_total = jnp.zeros((), jnp.float32)

    layers = params["layers"]
    unrolled = isinstance(layers, (list, tuple))
    # fused format: stacked sparse tables must not ride as scan xs — scan
    # layer indices instead and build per-layer views inside the body
    by_index = not unrolled and _scan_by_index(layers)
    layer_xs = jnp.arange(cfg.num_layers) if by_index else layers
    get_lp = (lambda xs: layer_view(layers, xs)) if by_index else (lambda xs: xs)

    if unrolled:
        # unrolled serving mode: per-layer param dicts (deployed formats whose
        # weights cannot stack under scan, e.g. block-CSR SLR matrices).
        x, aux_total, new_cache = _forward_unrolled(
            layers, x, cfg, positions, cache, collect_kv
        )
    elif cache is None:
        def body(carry, lp):
            x, aux = carry
            fn = lambda lp_, x_: _layer_apply(get_lp(lp_), x_, cfg, positions, None)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            x, a, kv = fn(lp, x)
            # train path: do NOT emit stacked KV heads (they are dead weight
            # but scan ys defeat DCE through remat -> ~70 GB/device at 4k)
            return (x, aux + a), (kv if collect_kv else None)

        (x, aux_total), kvs = jax.lax.scan(body, (x, aux_total), layer_xs, unroll=cfg.scan_unroll)
        new_cache = kvs  # (kh (L,B,H,T,D), vh (L,B,H,T,D))
    elif isinstance(cache, PagedKVCache):
        # paged decode: the stacked page pools ride the carry whole and each
        # layer reads and writes them in place at its index (no per-layer
        # slice or write-back); block table and lengths are layer-invariant
        def body(carry, inp):
            x, aux, k_p, v_p, k_s, v_s = carry
            lp, l_idx = inp
            layer_cache = PagedLayerCache(
                k_p, v_p, cache.block_table, cache.length, l_idx, k_s, v_s
            )
            x, a, kv = _layer_apply(get_lp(lp), x, cfg, positions, layer_cache)
            return (x, aux + a, kv.k, kv.v, kv.k_scale, kv.v_scale), None

        (x, aux_total, k_new, v_new, ks_new, vs_new), _ = jax.lax.scan(
            body,
            (x, aux_total, cache.k, cache.v, cache.k_scale, cache.v_scale),
            (layer_xs, jnp.arange(cfg.num_layers)),
            unroll=cfg.scan_unroll,
        )
        new_cache = PagedKVCache(
            k_new, v_new, cache.block_table, cache.length + t, ks_new, vs_new
        )
    else:
        # decode: thread the FULL stacked cache through the carry and update
        # layer slices in place — consuming cache.k as scan xs and restacking
        # ys doubles the cache residency (measured ~2x on gemma decode_32k);
        # the carry form lets XLA alias the donated buffers.
        def body(carry, inp):
            x, aux, k_full, v_full = carry
            lp, l_idx = inp
            k_l = jax.lax.dynamic_index_in_dim(k_full, l_idx, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(v_full, l_idx, 0, keepdims=False)
            layer_cache = KVCache(k_l, v_l, cache.length)
            x, a, kv = _layer_apply(get_lp(lp), x, cfg, positions, layer_cache)
            k_full = jax.lax.dynamic_update_index_in_dim(k_full, kv.k, l_idx, 0)
            v_full = jax.lax.dynamic_update_index_in_dim(v_full, kv.v, l_idx, 0)
            return (x, aux + a, k_full, v_full), None

        (x, aux_total, k_new, v_new), _ = jax.lax.scan(
            body,
            (x, aux_total, cache.k, cache.v),
            (layer_xs, jnp.arange(cfg.num_layers)),
            unroll=cfg.scan_unroll,
        )
        new_cache = LMCache(k_new, v_new, cache.length + t)

    x = apply_norm(x, params.get("final_norm"), cfg.norm_type)
    head = params["lm_head"]["w"] if "lm_head" in params else params["embed"]["embedding"].T
    logits = apply_weight(x, head)
    logits = constrain(logits, ("data", None, "model"))
    return logits, new_cache, aux_total


def _forward_unrolled(layers, x, cfg, positions, cache: LMCache | None, collect_kv: bool):
    """Python-loop layer stack for deployed formats that cannot scan.

    Semantics match the scan paths exactly: prefill (cache=None) returns
    stacked (k, v) heads when collect_kv, decode updates layer slices of the
    full LMCache, or writes the stacked page pools in place at each layer.
    """
    aux_total = jnp.zeros((), jnp.float32)
    if cache is None:
        kvs = []
        for lp in layers:
            x, a, kv = _layer_apply(lp, x, cfg, positions, None)
            aux_total = aux_total + a
            if collect_kv:
                kvs.append(kv)
        if collect_kv:
            new_cache = (
                jnp.stack([k for k, _ in kvs]), jnp.stack([v for _, v in kvs])
            )
        else:
            new_cache = None
        return x, aux_total, new_cache
    t = x.shape[1]
    if isinstance(cache, PagedKVCache):
        kv = cache
        for l_idx, lp in enumerate(layers):
            layer_cache = PagedLayerCache(
                kv.k, kv.v, cache.block_table, cache.length, l_idx,
                kv.k_scale, kv.v_scale,
            )
            x, a, kv = _layer_apply(lp, x, cfg, positions, layer_cache)
            aux_total = aux_total + a
        return x, aux_total, PagedKVCache(
            kv.k, kv.v, cache.block_table, cache.length + t,
            kv.k_scale, kv.v_scale,
        )
    k_full, v_full = cache.k, cache.v
    for l_idx, lp in enumerate(layers):
        layer_cache = KVCache(k_full[l_idx], v_full[l_idx], cache.length)
        x, a, kv = _layer_apply(lp, x, cfg, positions, layer_cache)
        aux_total = aux_total + a
        k_full = k_full.at[l_idx].set(kv.k)
        v_full = v_full.at[l_idx].set(kv.v)
    return x, aux_total, LMCache(k_full, v_full, cache.length + t)


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> LMCache:
    shape = (cfg.num_layers, batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    return LMCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        length=jnp.zeros((), jnp.int32),
    )


def init_paged_cache(
    cfg,
    max_slots: int,
    num_pages: int,
    block_size: int,
    pages_per_slot: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
) -> PagedKVCache:
    """Fixed page pool per layer; the whole block table starts unmapped."""
    pool = (cfg.num_layers, num_pages, cfg.num_kv_heads, block_size, cfg.head_dim)
    payload = jnp.int8 if quantized else dtype
    scale = (
        jnp.zeros(pool[:-1] + (1,), jnp.float32) if quantized else None
    )
    return PagedKVCache(
        k=jnp.zeros(pool, payload),
        v=jnp.zeros(pool, payload),
        block_table=jnp.full((max_slots, pages_per_slot), num_pages, jnp.int32),
        length=jnp.zeros((max_slots,), jnp.int32),
        k_scale=scale,
        v_scale=None if scale is None else jnp.zeros_like(scale),
    )


def scatter_prefill_pages(
    cache: PagedKVCache,
    kvs,                    # stacked prefill heads: (kh, vh), each (L, B, Hkv, T, D)
    page_map: jax.Array,    # (B, T // block_size) int32 page ids; >= num_pages drops
) -> PagedKVCache:
    """Write whole prompt blocks into the page pool (the prefill-side insert).

    T must be a multiple of the block size; trailing positions of a slot's
    last block may carry junk from prompt padding — the per-slot length mask
    never attends them.
    """
    kh, vh = kvs
    l, b, h, t, d = kh.shape
    bs = cache.block_size
    assert t % bs == 0, (t, bs)
    pages = page_map.reshape(-1)                       # (B * nb,)

    def scatter(pool, heads, quantize):
        # (L, B, H, T, D) -> (L, B*nb, H, bs, D) chunks aligned with ``pages``
        chunks = heads.reshape(l, b, h, t // bs, bs, d)
        chunks = chunks.transpose(0, 1, 3, 2, 4, 5).reshape(l, -1, h, bs, d)
        if quantize:
            from ..serving.kv_quant import quantize_kv

            q, s = quantize_kv(chunks)
            return (
                pool[0].at[:, pages].set(q, mode="drop"),
                pool[1].at[:, pages].set(s, mode="drop"),
            )
        return pool[0].at[:, pages].set(chunks.astype(pool[0].dtype), mode="drop"), None

    quant = cache.k_scale is not None
    k_new, k_s = scatter((cache.k, cache.k_scale), kh, quant)
    v_new, v_s = scatter((cache.v, cache.v_scale), vh, quant)
    return cache._replace(k=k_new, v=v_new, k_scale=k_s, v_scale=v_s)


def copy_cache_pages(
    cache: PagedKVCache,
    src: jax.Array,   # (n,) int32 source page ids
    dst: jax.Array,   # (n,) int32 destination page ids
) -> PagedKVCache:
    """Copy whole pages ``src[i] -> dst[i]`` across every pool the cache
    carries — k, v, and (when the pages are int8-quantized) BOTH scale pools;
    a page copied without its scales would dequantize garbage. The device half
    of copy-on-write prefix sharing (``serving/prefix_cache.py``): the engine
    remaps its block table to ``dst`` host-side after this call."""
    from ..kernels import ops

    k = ops.page_copy(cache.k, src, dst)
    v = ops.page_copy(cache.v, src, dst)
    k_s = v_s = None
    if cache.k_scale is not None:
        k_s = ops.page_copy(cache.k_scale, src, dst)
        v_s = ops.page_copy(cache.v_scale, src, dst)
    return cache._replace(k=k, v=v, k_scale=k_s, v_scale=v_s)


def cache_from_prefill(cfg, kvs, max_len: int, dtype=jnp.bfloat16) -> LMCache:
    """Build an LMCache from forward()'s stacked prefill (k, v) heads."""
    kh, vh = kvs  # (L, B, H, T, D)
    l, b, h, t, d = kh.shape
    # VLM prefill sequences include the patch prefix and may exceed the
    # nominal text max_len — grow the cache rather than truncate
    pad = max(max_len - t, 0)
    k = jnp.pad(kh.astype(dtype), ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    v = jnp.pad(vh.astype(dtype), ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    return LMCache(k=k, v=v, length=jnp.asarray(t, jnp.int32))

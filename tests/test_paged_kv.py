"""Paged KV cache + continuous batching tests.

Covers the PR 2 tentpole invariants: block-allocator accounting, paged
decode attention matching the contiguous cache bitwise, the Pallas paged
kernel matching its jnp oracle, and the paged engine producing IDENTICAL
token streams to greedy decoding through the plain model API on a fixed trace
— including under mid-stream admission, forced eviction, and int8 page
quantization.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _greedy_oracle import greedy_streams

from repro.configs.base import get_arch
from repro.kernels.ops import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.models import model as model_lib
from repro.models import transformer as transformer_lib
from repro.serving.elastic import ModelBank
from repro.serving.engine import (
    BlockAllocator,
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
    RequestRejected,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("salaad_llama_60m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = BlockAllocator(8)
        pages = a.alloc(5)
        assert len(pages) == 5 and len(set(pages)) == 5
        assert a.free_blocks == 3 and a.used_blocks == 5
        a.free(pages[:2])
        assert a.free_blocks == 5 and a.used_blocks == 3
        a.free(pages[2:])
        assert a.free_blocks == 8 and a.used_blocks == 0

    def test_no_partial_grants_and_no_double_alloc(self):
        a = BlockAllocator(4)
        p1 = a.alloc(3)
        assert a.alloc(2) is None          # only 1 free: refuse, don't shrink
        assert a.free_blocks == 1
        p2 = a.alloc(1)
        assert set(p1).isdisjoint(p2)      # a page is never handed out twice
        assert a.alloc(1) is None

    def test_double_free_rejected(self):
        a = BlockAllocator(4)
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(ValueError):
            a.free(pages)

    def test_bad_free_is_atomic(self):
        """A free list containing an unowned page must raise WITHOUT freeing
        anything: the old page-by-page loop raised mid-way, leaving
        free + used != pool for callers that caught the error."""
        a = BlockAllocator(8)
        pages = a.alloc(4)
        with pytest.raises(ValueError):
            a.free([pages[0], pages[1], 99])      # 99 was never allocated
        # nothing was freed: the invariant AND the exact ownership survive
        assert a.free_blocks + a.used_blocks == 8
        assert a.used_blocks == 4
        with pytest.raises(ValueError):
            a.free([pages[0], pages[0]])          # duplicate within one call
        assert a.used_blocks == 4
        a.free(pages)                             # the good free still works
        assert a.free_blocks == 8 and a.used_blocks == 0
        assert a.alloc(8) is not None

    def test_interchangeable_pages_no_fragmentation(self):
        """Freeing ANY n pages lets ANY n-page request through: pool capacity
        is the only constraint (no contiguity, no external fragmentation)."""
        a = BlockAllocator(6)
        held = [a.alloc(2) for _ in range(3)]
        a.free(held[0])
        a.free(held[2])                    # non-adjacent frees
        assert a.alloc(4) is not None      # still a single 4-page grant


class TestPagedAttentionKernel:
    def _pool(self, seed=0, b=3, hq=4, hkv=2, d=8, bs=4, nb=4, n=10, L=2):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, hq, d), jnp.float32)
        kp = jnp.asarray(rng.randn(L, n, hkv, bs, d), jnp.float32)
        vp = jnp.asarray(rng.randn(L, n, hkv, bs, d), jnp.float32)
        # ragged per-slot lengths; slot 1 is empty; unmapped tails everywhere
        bt = jnp.asarray([[0, 1, n, n], [2, n, n, n], [3, 4, 5, n]], jnp.int32)
        lengths = jnp.asarray([5, 0, 11], jnp.int32)
        return q, kp, vp, bt, lengths

    def test_pallas_matches_ref(self):
        q, kp, vp, bt, lengths = self._pool()
        out = paged_attention(q, kp, vp, bt, lengths, 1)
        ref = paged_attention_ref(q, kp, vp, bt, lengths, 1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_ref_matches_contiguous_masked_attention(self):
        """Gathering pages through the block table reproduces the contiguous
        per-slot decode attention exactly (same values at positions < len)."""
        q, kp, vp, bt, lengths = self._pool()
        layer = 1
        _, n, hkv, bs, d = kp.shape
        b, hq, _ = q.shape
        s = bt.shape[1] * bs
        # materialize the contiguous equivalent: position j <- page[j//bs]
        kc = np.zeros((b, hkv, s, d), np.float32)
        vc = np.zeros((b, hkv, s, d), np.float32)
        btn = np.asarray(bt)
        for bi in range(b):
            for j in range(int(lengths[bi]) + 1):
                pg = btn[bi, j // bs]
                if pg < n:
                    kc[bi, :, j] = np.asarray(kp)[layer, pg, :, j % bs]
                    vc[bi, :, j] = np.asarray(vp)[layer, pg, :, j % bs]
        group = hq // hkv
        qg = np.asarray(q).reshape(b, hkv, group, d) / np.sqrt(d)
        sc = np.einsum("bhgd,bhsd->bhgs", qg, kc)
        mask = np.arange(s)[None, :] <= np.asarray(lengths)[:, None]
        sc = np.where(mask[:, None, None], sc, -1e30)
        w = jax.nn.softmax(jnp.asarray(sc), axis=-1)
        exp = np.einsum("bhgs,bhsd->bhgd", np.asarray(w), vc).reshape(b, hq, d)
        got = np.asarray(paged_attention_ref(q, kp, vp, bt, lengths, layer))
        np.testing.assert_allclose(got, exp, atol=1e-5)


class TestPagedDecodeEquivalence:
    """Paged decode through the REAL model == contiguous-cache decode,
    bitwise, at ragged per-slot lengths."""

    def test_logits_bitwise_equal(self, tiny):
        cfg, params = tiny
        S, max_len, bs = 3, 32, 8
        nb = max_len // bs
        prompts = [[5, 7, 11, 2, 9], [3, 1], [2, 9, 4, 6, 1, 8, 3]]
        bucket = 8
        toks = np.zeros((S, bucket), np.int32)
        lens = np.ones((S,), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p
            lens[i] = len(p)

        # contiguous per-slot cache via the batched prefill
        cache = model_lib.init_cache(cfg, S, max_len, dtype=jnp.float32)
        _, pc = model_lib.prefill(
            params, {"tokens": jnp.asarray(toks)}, cfg, max_len=max_len,
            cache_dtype=jnp.float32,
        )
        cache = cache._replace(
            k=cache.k.at[:, jnp.arange(S)].set(pc.k),
            v=cache.v.at[:, jnp.arange(S)].set(pc.v),
            length=jnp.asarray(lens),
        )

        # paged cache: same prefill heads scattered into pages
        num_pages = S * nb
        paged = model_lib.init_paged_cache(
            cfg, S, num_pages, bs, nb, dtype=jnp.float32
        )
        _, kvs, _ = model_lib._forward(
            params, {"tokens": jnp.asarray(toks)}, cfg, collect_kv=True
        )
        table = np.full((S, nb), num_pages, np.int32)
        page_map = np.full((S, bucket // bs), num_pages, np.int32)
        nxt = 0
        for i, p in enumerate(prompts):
            need = -(-(len(p) + 4) // bs)          # prompt + decode room
            for j in range(need):
                table[i, j] = nxt
                if j < -(-len(p) // bs):
                    page_map[i, j] = nxt
                nxt += 1
        paged = paged._replace(
            block_table=jnp.asarray(table), length=jnp.asarray(lens)
        )
        paged = transformer_lib.scatter_prefill_pages(
            paged, kvs, jnp.asarray(page_map)
        )

        tok = jnp.asarray([[9], [4], [7]], jnp.int32)
        for _ in range(3):
            lg_c, cache = model_lib.decode_step(params, tok, cache, cfg)
            lg_p, paged = model_lib.decode_step(params, tok, paged, cfg)
            assert np.array_equal(np.asarray(lg_c), np.asarray(lg_p)), (
                "paged decode logits diverged from contiguous"
            )


class TestPagedEngine:
    PROMPTS = [[5, 7, 11], [3, 1], [2, 9, 4, 6], [8, 8, 2], [1, 2, 3, 4, 5, 6], [9, 1]]

    def _tokens(self, engine, max_new=5):
        for p in self.PROMPTS:
            engine.submit(p, max_new_tokens=max_new)
        return {r.uid: r.out_tokens for r in engine.run()}

    def test_matches_unpaged_engine_midstream_admission(self, tiny):
        """6 requests over 2 slots: admissions happen mid-stream while other
        slots are mid-decode; each uid's stream must be identical to its
        greedy decode on a private contiguous cache."""
        cfg, params = tiny
        ref = greedy_streams(cfg, params, self.PROMPTS, 5, 32)
        got = self._tokens(PagedServingEngine(
            ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32, block_size=8)
        ))
        assert got == ref
        assert all(len(t) == 5 for t in got.values())

    @pytest.mark.parametrize("policy", ["longest_remaining", "lru"])
    def test_eviction_preserves_tokens(self, tiny, policy):
        """A pool too small for two full requests forces eviction; the evicted
        request resumes by re-prefilling and must emit the same tokens."""
        cfg, params = tiny
        prompts = [[5, 7, 11], [3, 1, 4]]
        ref = greedy_streams(cfg, params, prompts, 10, 16)

        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=16, block_size=4, num_blocks=4,
            decode_reserve=1, evict_policy=policy,
        ))
        for p in prompts:
            eng.submit(p, max_new_tokens=10)
        got = {r.uid: r.out_tokens for r in eng.run()}
        assert eng.evictions >= 1, "pool was sized to force an eviction"
        assert got == ref
        assert eng.allocator.used_blocks == 0   # everything returned

    def test_pages_released_incrementally(self, tiny):
        """Finished requests return pages immediately (not at drain time)."""
        cfg, params = tiny
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=32, block_size=8
        ))
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng.submit([4, 5], max_new_tokens=12)
        seen_free = []
        while eng.has_work:
            eng.step()
            seen_free.append(eng.allocator.free_blocks)
        assert eng.allocator.used_blocks == 0
        # free count must rise strictly before the drain completes
        assert max(seen_free[:-1]) > min(seen_free[:-1])

    def test_rejects_oversized_requests(self, tiny):
        cfg, params = tiny
        for eng in (
            PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=16, block_size=8)),
            ReferenceEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=16)),
        ):
            with pytest.raises(RequestRejected):
                eng.submit(list(range(1, 20)), max_new_tokens=4)
            assert not eng.has_work  # rejection leaves the engine clean

    def test_rejects_empty_prompt_and_tiny_pool(self, tiny):
        cfg, params = tiny
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=16, block_size=4, num_blocks=2
        ))
        with pytest.raises(RequestRejected):
            eng.submit([], max_new_tokens=2)
        with pytest.raises(RequestRejected):
            # fits max_len but can never fit the 2-page pool
            eng.submit([1, 2, 3, 4, 5], max_new_tokens=8)
        eng.submit([1, 2, 3], max_new_tokens=4)      # 2 pages: fits
        assert len(eng.run()) == 1

    def test_int8_pages_match_float(self, tiny):
        """kv_dtype='int8' stores quantized pages (serving/kv_quant.py layout)
        and still greedy-decodes the same tokens at init scale."""
        cfg, params = tiny
        ref = self._tokens(PagedServingEngine(
            ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32, block_size=8)
        ))
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=32, block_size=8, kv_dtype="int8"
        ))
        assert eng.cache.k.dtype == jnp.int8 and eng.cache.k_scale is not None
        got = self._tokens(eng)
        assert got == ref

    def test_pallas_kernel_through_engine(self, tiny):
        """kernel_impl='pallas' routes paged decode through the Pallas kernel
        (interpret mode here) and emits the same tokens as the jnp gather."""
        import dataclasses

        cfg, params = tiny
        out = {}
        for impl in ("dense", "pallas"):
            c = dataclasses.replace(cfg, kernel_impl=impl)
            eng = PagedServingEngine(ModelBank.single(c, params), EngineConfig(
                max_slots=2, max_len=32, block_size=8
            ))
            eng.submit([5, 7, 11], max_new_tokens=4)
            eng.submit([3, 1], max_new_tokens=4)
            out[impl] = {r.uid: r.out_tokens for r in eng.run()}
        assert out["dense"] == out["pallas"]

    def test_one_decode_trace_and_call_per_tick(self, tiny):
        """The paged engine keeps the PR 1 invariant: ONE jitted decode step
        per tick over all slots, compiled exactly once."""
        cfg, params = tiny
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=32, block_size=8
        ))
        got = self._tokens(eng)
        total = sum(len(t) for t in got.values())
        assert eng.decode_traces == 1
        assert eng.decode_calls < total


# ------------------------------------------------ stacked pools, in place ---


def _stacked_pools(rng, hkv, n=16, bs=4, d=8, L=3, quantized=False):
    """K and V page pools stacked over L layers (int8 with f32 scale pools
    when ``quantized``), and the layer indices 0 and L - 1."""
    shape = (L, n, hkv, bs, d)
    if not quantized:
        return (jnp.asarray(rng.randn(*shape), jnp.float32),
                jnp.asarray(rng.randn(*shape), jnp.float32), None, None)
    q8 = lambda: jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
    sc = lambda: jnp.asarray(rng.rand(*shape[:-1], 1) + 0.01, jnp.float32)
    return q8(), q8(), sc(), sc()


# slot 0 ragged over two pages, slot 1 at length 0, slot 2 holds a full
# table, slot 3 a ragged table whose tail is unmapped (id >= n)
_TABLE = [[0, 1, 16], [2, 16, 16], [3, 4, 5], [6, 7, 16]]


class TestStackedPoolKernels:
    """The Pallas paged-attention kernels read layer ``layer`` of the stacked
    ``(L, num_pages, Hkv, bs, D)`` pools in place, addressed through the
    scalar-prefetch table, and match the jnp oracles at the first and the
    last layer; the int8 jnp path gathers the same layer's pages."""

    @pytest.mark.parametrize("layer", ["first", "last"])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa4"])
    @pytest.mark.parametrize("kernel", ["decode", "kquery", "int8_gather"])
    def test_stacked_pool_matches_ref(self, kernel, hq, hkv, layer):
        from repro.kernels.ops import paged_attention_kquery
        from repro.kernels.ref import paged_attention_kquery_ref
        from repro.models.attention import PagedLayerCache, paged_gather

        rng = np.random.RandomState(7)
        L, n, bs, d = 3, 16, 4, 8
        kp, vp, ks, vs = _stacked_pools(
            rng, hkv, n, bs, d, L, quantized=kernel == "int8_gather"
        )
        li = 0 if layer == "first" else L - 1
        bt = jnp.asarray(_TABLE, jnp.int32)
        if kernel == "decode":
            lengths = jnp.asarray([5, 0, 11, 6], jnp.int32)
            q = jnp.asarray(rng.randn(4, hq, d), jnp.float32)
            out = paged_attention(q, kp, vp, bt, lengths, li, interpret=True)
            ref = paged_attention_ref(q, kp, vp, bt, lengths, li)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        elif kernel == "kquery":
            lengths = jnp.asarray([5, 0, 9, 4], jnp.int32)  # slot 2 fills 12
            q = jnp.asarray(rng.randn(4, hq, 3, d), jnp.float32)
            out = paged_attention_kquery(q, kp, vp, bt, lengths, li,
                                         interpret=True)
            ref = paged_attention_kquery_ref(q, kp, vp, bt, lengths, li)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        else:
            lengths = jnp.asarray([5, 0, 11, 6], jnp.int32)
            cache = PagedLayerCache(kp, vp, bt, lengths, li, ks, vs)
            got_k, got_v = paged_gather(cache)
            for got, pool, scale in ((got_k, kp, ks), (got_v, vp, vs)):
                deq = np.asarray(pool[li], np.float32) * np.asarray(scale[li])
                table = np.minimum(np.asarray(bt), n - 1)   # clamp unmapped
                exp = deq[table]                            # (B, nb, H, bs, D)
                exp = exp.transpose(0, 2, 1, 3, 4).reshape(4, hkv, -1, d)
                np.testing.assert_array_equal(np.asarray(got), exp)

    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
    def test_insert_writes_only_new_rows_of_its_layer(self, layer, quantized):
        """``paged_insert`` scatters each new token at ``[layer, page, :,
        off]`` of the stack: every other layer and row keeps its bytes, and
        unmapped pages and positions past the table drop."""
        from repro.models.attention import PagedLayerCache, paged_insert

        rng = np.random.RandomState(3)
        L, n, hkv, bs, d = 3, 16, 2, 4, 8
        pools = _stacked_pools(rng, hkv, n, bs, d, L, quantized=quantized)
        bt = jnp.asarray(_TABLE, jnp.int32)
        # slot 0 writes 5-6 in page 1, slot 1 writes 0-1 in page 2, slot 2
        # sits at its table's end (11 lands, 12 drops), slot 3 crosses into
        # its unmapped third page (7 lands in page 7, 8 drops)
        lengths = jnp.asarray([5, 0, 11, 7], jnp.int32)
        kh = jnp.asarray(rng.randn(4, hkv, 2, d), jnp.float32)
        vh = jnp.asarray(rng.randn(4, hkv, 2, d), jnp.float32)
        new = paged_insert(PagedLayerCache(*pools[:2], bt, lengths, layer,
                                           *pools[2:]), kh, vh)
        np.testing.assert_array_equal(np.asarray(new.length), [7, 2, 13, 9])
        written = {(1, 1), (1, 2), (2, 0), (2, 1), (5, 3), (7, 3)}
        for before, after in zip(pools, (new.k, new.v, new.k_scale, new.v_scale)):
            if before is None:
                assert after is None
                continue
            b, a = np.asarray(before), np.asarray(after)
            changed = {
                (p, o) for p in range(n) for o in range(bs)
                if not np.array_equal(b[layer, p, :, o], a[layer, p, :, o])
            }
            assert changed <= written and len(changed) >= 4, changed
            others = [l for l in range(L) if l != layer]
            np.testing.assert_array_equal(b[others], a[others])


def _eqn_out_shapes(jaxpr):
    """Output shapes of every equation of ``jaxpr``, nested bodies included
    (scan, pjit, cond and Pallas kernel bodies)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)    # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _eqn_out_shapes(sub)


def _filled_paged_cache(cfg, params, quantized, slots=3, bs=8, nb=4, pages=13):
    """A paged cache whose slots hold prompts at ragged lengths, prefilled
    through the real model and scattered into a pool of ``pages`` pages."""
    prompts = [[5, 7, 11, 2, 9], [3, 1], [2, 9, 4, 6, 1, 8, 3, 4, 2, 7]]
    bucket = 16
    toks = np.zeros((slots, bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
    cache = model_lib.init_paged_cache(
        cfg, slots, pages, bs, nb, dtype=jnp.float32, quantized=quantized
    )
    _, kvs, _ = model_lib._forward(
        params, {"tokens": jnp.asarray(toks)}, cfg, collect_kv=True
    )
    table = np.full((slots, nb), pages, np.int32)
    page_map = np.full((slots, bucket // bs), pages, np.int32)
    nxt = 0
    for i, p in enumerate(prompts):
        for j in range(-(-(len(p) + 4) // bs)):    # prompt + decode room
            table[i, j] = nxt
            if j < -(-len(p) // bs):
                page_map[i, j] = nxt
            nxt += 1
    cache = cache._replace(
        block_table=jnp.asarray(table),
        length=jnp.asarray([len(p) for p in prompts], jnp.int32),
    )
    return transformer_lib.scatter_prefill_pages(cache, kvs, jnp.asarray(page_map))


def _slice_writeback_decode(params, tokens, cache, cfg):
    """Oracle: paged decode as it was before the pools were addressed in
    place. Each layer's pool is sliced out of the stack, the token inserted
    and attended on that one-layer pool, and the slice written back — by a
    scan over the stacked layers, or a Python loop over unrolled ones."""
    from repro.models.attention import PagedLayerCache
    from repro.models.layers import apply_norm, apply_weight, embed

    x = embed(params["embed"], tokens)
    x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    positions = cache.length[:, None] + jnp.arange(tokens.shape[1])[None, :]
    pools = (cache.k, cache.v, cache.k_scale, cache.v_scale)

    def layer(lp, x, pools, l_idx):
        one = [None if p is None else
               jax.lax.dynamic_index_in_dim(p, l_idx, 0, keepdims=True)
               for p in pools]
        x, _, kv = transformer_lib._layer_apply(
            lp, x, cfg, positions,
            PagedLayerCache(one[0], one[1], cache.block_table, cache.length,
                            0, one[2], one[3]),
        )
        pools = tuple(
            None if p is None else
            jax.lax.dynamic_update_index_in_dim(p, new[0], l_idx, 0)
            for p, new in zip(pools, (kv.k, kv.v, kv.k_scale, kv.v_scale))
        )
        return x, pools

    layers = params["layers"]
    if isinstance(layers, list):
        for l_idx, lp in enumerate(layers):
            x, pools = layer(lp, x, pools, l_idx)
    else:
        def body(carry, inp):
            return layer(inp[0], *carry, inp[1]), None

        (x, pools), _ = jax.lax.scan(
            body, (x, pools), (layers, jnp.arange(cfg.num_layers))
        )
    x = apply_norm(x, params.get("final_norm"), cfg.norm_type)
    head = (params["lm_head"]["w"] if "lm_head" in params
            else params["embed"]["embedding"].T)
    return apply_weight(x, head), cache._replace(
        k=pools[0], v=pools[1], k_scale=pools[2], v_scale=pools[3],
        length=cache.length + tokens.shape[1],
    )


class TestInPlacePagedDecode:
    """Paged decode addresses the stacked pools by layer and never holds one
    layer's pool as an array of its own."""

    @pytest.mark.parametrize("kv", ["bf16_pallas", "int8"])
    def test_no_array_of_one_layer_pool_shape(self, tiny, kv):
        """Trace one paged decode step: no equation may output an array of a
        single layer's pool shape ``(N, Hkv, bs, D)`` (nor its scale pool's
        ``(N, Hkv, bs, 1)``), as the old per-layer slice did."""
        import dataclasses

        cfg, params = tiny
        if kv == "bf16_pallas":
            cfg = dataclasses.replace(cfg, kernel_impl="pallas")
        n, bs = 13, 8
        cache = model_lib.init_paged_cache(
            cfg, 3, n, bs, 4, dtype=jnp.bfloat16, quantized=kv == "int8"
        )
        tok = jnp.ones((3, 1), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, t, c: model_lib.decode_step(p, t, c, cfg)
        )(params, tok, cache)
        one_layer = {(n, cfg.num_kv_heads, bs, cfg.head_dim),
                     (n, cfg.num_kv_heads, bs, 1)}
        shapes = list(_eqn_out_shapes(jaxpr.jaxpr))
        assert (cfg.num_layers, n, cfg.num_kv_heads, bs, cfg.head_dim) in shapes
        assert not one_layer & set(shapes), "a layer's pool was materialised"

    @pytest.mark.parametrize("kv", ["f32_dense", "f32_pallas", "int8"])
    @pytest.mark.parametrize("path", ["scan", "unrolled"])
    def test_bitwise_equal_to_slice_writeback(self, tiny, path, kv):
        """Three decode steps at ragged lengths: logits and every updated
        pool equal the slice-and-write-back oracle bit for bit."""
        import dataclasses

        cfg, params = tiny
        cache = _filled_paged_cache(cfg, params, quantized=kv == "int8")
        if kv == "f32_pallas":
            cfg = dataclasses.replace(cfg, kernel_impl="pallas")
        if path == "unrolled":
            params = dict(params, layers=[
                jax.tree.map(lambda a, i=i: a[i], params["layers"])
                for i in range(cfg.num_layers)
            ])
        new = jax.jit(lambda p, t, c: model_lib.decode_step(p, t, c, cfg))
        old = jax.jit(lambda p, t, c: _slice_writeback_decode(p, t, c, cfg))
        tok = jnp.asarray([[9], [4], [7]], jnp.int32)
        ref = cache
        for _ in range(3):
            lg_new, cache = new(params, tok, cache)
            lg_old, ref = old(params, tok, ref)
            np.testing.assert_array_equal(np.asarray(lg_new), np.asarray(lg_old))
            for a, b in zip(cache, ref):
                if a is None:
                    assert b is None
                else:
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            tok = jnp.argmax(lg_new[:, -1:], axis=-1).astype(jnp.int32)

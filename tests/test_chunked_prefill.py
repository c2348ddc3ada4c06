"""Chunked prefill tests (PR 4 tentpole).

The core invariant: splitting prompt processing into block-aligned chunks
interleaved with decode ticks changes WHEN prefill work happens, never WHAT
gets served — greedy token streams are identical to one-shot prefill across
chunk sizes, int8 pages, eviction/resume (including eviction landing
MID-prefill), the Pallas kernel path, and the speculative engine. At the
model level, chunk-chained prefill reproduces one-shot logits/KV to float
accumulation-order tolerance with identical argmax (the batched matmul
shapes differ, so bitwise equality is asserted on the emitted token streams,
not raw float pages).

Also covers the PR 4 satellites: ``decode_emitted_tokens`` accounting when an
eviction lands mid-prefill (the old ``1 + evictions`` convention overcounted
prefill emissions), EDF admission ordering unified across both batched
engines, monotonic-clock timestamps, and the query-tiled k-query kernel at
chunk widths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.kernels.ops import paged_attention_kquery
from repro.kernels.ref import paged_attention_kquery_ref
from repro.models import model as model_lib
from repro.models import transformer as transformer_lib
from repro.models.attention import blockwise_attention
from repro.serving.elastic import ModelBank
from repro.serving.engine import (
    EngineCapabilityError,
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
    Request,
    ServingEngine,
    decode_emitted_tokens,
)
from repro.serving.speculative import SpeculativeEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("salaad_llama_60m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# a long prompt spanning several chunks mixed with shorts that finish and
# free their slots mid-stream (slot reuse while the long one is in flight)
PROMPTS = [[5, 7, 11], [3, 1], list(range(2, 40)), [8, 8, 2],
           [1, 2, 3, 4, 5, 6], [9, 1]]


def run_tokens(engine, prompts=PROMPTS, max_new=5):
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    return {r.uid: r.out_tokens for r in engine.run()}


# ----------------------------------------------------------- model level ---


class TestChunkPrefillStep:
    def _paged(self, cfg, S, bs, nb):
        cache = model_lib.init_paged_cache(
            cfg, S, S * nb, bs, nb, dtype=jnp.float32
        )
        table = np.arange(S * nb, dtype=np.int32).reshape(S, nb)
        return cache._replace(block_table=jnp.asarray(table))

    @pytest.mark.parametrize("chunk", [8, 16])
    def test_chunk_chain_matches_oneshot(self, tiny, chunk):
        """Chaining chunk_prefill_step over an empty paged cache reproduces
        the one-shot prefill scatter: same argmax at the prompt end, same
        greedy continuation over several decode steps, KV pages equal to
        accumulation-order tolerance."""
        cfg, params = tiny
        S, bs, nb = 2, 8, 8
        prompts = [list(range(2, 40)), [7, 3, 9, 1, 4]]
        lens = np.array([len(p) for p in prompts], np.int32)
        bucket = 40

        toks = np.zeros((S, bucket), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p
        one = self._paged(cfg, S, bs, nb)
        logits1, kvs, _ = model_lib._forward(
            params, {"tokens": jnp.asarray(toks)}, cfg, collect_kv=True
        )
        page_map = np.full((S, bucket // bs), S * nb, np.int32)
        for i, p in enumerate(prompts):
            blocks = -(-len(p) // bs)
            page_map[i, :blocks] = np.asarray(one.block_table)[i, :blocks]
        one = transformer_lib.scatter_prefill_pages(
            one, kvs, jnp.asarray(page_map)
        )
        one = one._replace(length=jnp.asarray(lens))
        last1 = np.asarray(logits1)[np.arange(S), lens - 1]

        chk = self._paged(cfg, S, bs, nb)
        progress = np.zeros((S,), np.int32)
        last2 = np.zeros_like(last1)
        while (progress < lens).any():
            ck = np.zeros((S, chunk), np.int32)
            counts = np.zeros((S,), np.int32)
            for i, p in enumerate(prompts):
                c = min(chunk, len(p) - int(progress[i]))
                if c > 0:
                    ck[i, :c] = p[progress[i] : progress[i] + c]
                    counts[i] = c
            lg, chk = model_lib.chunk_prefill_step(
                params, jnp.asarray(ck), jnp.asarray(counts), chk, cfg
            )
            lg = np.asarray(lg)
            for i in range(S):
                if counts[i] and progress[i] + counts[i] >= lens[i]:
                    last2[i] = lg[i, counts[i] - 1]
            progress += counts
        assert np.array_equal(chk.length, lens)

        # prompt-end logits: identical argmax, tight float agreement
        np.testing.assert_allclose(last1, last2, atol=1e-4)
        assert np.array_equal(last1.argmax(-1), last2.argmax(-1))

        # KV at every VALID position agrees to accumulation tolerance (the
        # padded tails of the last block/chunk carry path-specific junk that
        # is never attended — masked out of the comparison)
        def valid_kv(cache):
            k = np.asarray(cache.k)              # (L, P, H, bs, D)
            bt = np.asarray(cache.block_table)
            return [
                np.stack([
                    k[:, bt[i, j // bs], :, j % bs] for j in range(int(ln))
                ])
                for i, ln in enumerate(lens)
            ]

        for a, b in zip(valid_kv(one), valid_kv(chk)):
            np.testing.assert_allclose(a, b, atol=1e-4)

        # greedy continuation: identical token streams from either cache
        t1 = last1.argmax(-1).astype(np.int32)
        t2 = last2.argmax(-1).astype(np.int32)
        for _ in range(4):
            assert np.array_equal(t1, t2)
            l1, one = model_lib.decode_step(params, jnp.asarray(t1[:, None]), one, cfg)
            l2, chk = model_lib.decode_step(params, jnp.asarray(t2[:, None]), chk, cfg)
            t1 = np.asarray(l1)[:, -1].argmax(-1).astype(np.int32)
            t2 = np.asarray(l2)[:, -1].argmax(-1).astype(np.int32)

    def test_contiguous_per_slot_chunk(self, tiny):
        """Chunked prefill against a per-slot-length CONTIGUOUS cache (the
        blockwise path with (B,) causal offsets — previously
        NotImplementedError) matches the paged chunk path's logits."""
        cfg, params = tiny
        S, max_len, chunk = 2, 32, 8
        prompts = [list(range(2, 18)), [7, 3, 9, 1, 4, 2, 8, 8, 1, 2]]
        lens = np.array([len(p) for p in prompts], np.int32)

        contig = model_lib.init_cache(cfg, S, max_len, dtype=jnp.float32)
        contig = contig._replace(length=jnp.zeros((S,), jnp.int32))
        paged = model_lib.init_paged_cache(cfg, S, S * 8, 4, 8, dtype=jnp.float32)
        paged = paged._replace(
            block_table=jnp.asarray(
                np.arange(S * 8, dtype=np.int32).reshape(S, 8)
            )
        )
        progress = np.zeros((S,), np.int32)
        while (progress < lens).any():
            ck = np.zeros((S, chunk), np.int32)
            counts = np.zeros((S,), np.int32)
            for i, p in enumerate(prompts):
                c = min(chunk, len(p) - int(progress[i]))
                if c > 0:
                    ck[i, :c] = p[progress[i] : progress[i] + c]
                    counts[i] = c
            lc, contig = model_lib.chunk_prefill_step(
                params, jnp.asarray(ck), jnp.asarray(counts), contig, cfg
            )
            lp, paged = model_lib.chunk_prefill_step(
                params, jnp.asarray(ck), jnp.asarray(counts), paged, cfg
            )
            for i in range(S):
                c = int(counts[i])
                if c:
                    np.testing.assert_allclose(
                        np.asarray(lc)[i, :c], np.asarray(lp)[i, :c],
                        atol=1e-4,
                    )
                    assert np.array_equal(
                        np.asarray(lc)[i, :c].argmax(-1),
                        np.asarray(lp)[i, :c].argmax(-1),
                    )
            progress += counts

    def test_blockwise_per_slot_offset(self):
        """(B,) causal offsets in blockwise_attention == per-row runs with
        the matching scalar offset."""
        rng = np.random.RandomState(0)
        b, hq, hkv, t, s, d = 3, 4, 2, 5, 24, 8
        q = jnp.asarray(rng.randn(b, hq, t, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, hkv, s, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, hkv, s, d), jnp.float32)
        offs = np.array([0, 7, 19], np.int32)
        out = blockwise_attention(
            q, k, v, q_block=4, kv_block=8, causal_offset=jnp.asarray(offs)
        )
        for i, o in enumerate(offs):
            row = blockwise_attention(
                q[i : i + 1], k[i : i + 1], v[i : i + 1],
                q_block=4, kv_block=8, causal_offset=int(o),
            )
            np.testing.assert_allclose(
                np.asarray(out[i]), np.asarray(row[0]), atol=1e-5
            )

    def test_contiguous_boundary_write_drops_not_clamps(self, tiny):
        """A ragged chunk whose padded tail crosses max_len on a contiguous
        per-slot cache must DROP the out-of-range rows — a clamped
        dynamic_update_slice would shift the write start back over valid
        history."""
        cfg, params = tiny
        S, max_len, C = 2, 16, 8
        cache = model_lib.init_cache(cfg, S, max_len, dtype=jnp.float32)
        cache = cache._replace(length=jnp.asarray([12, 0], jnp.int32))
        k_before = np.asarray(cache.k).copy()
        toks = np.zeros((S, C), np.int32)
        toks[0, :4] = [1, 2, 3, 4]
        toks[1, :3] = [5, 6, 7]
        _, out = model_lib.chunk_prefill_step(
            params, jnp.asarray(toks), jnp.asarray([4, 3], jnp.int32),
            cache, cfg,
        )
        # slot 0 wrote 12..15; the padded tail (16..19) dropped — history
        # at 0..11 is untouched bit-for-bit
        assert np.array_equal(
            np.asarray(out.k)[:, 0, :, :12], k_before[:, 0, :, :12]
        )
        assert np.array_equal(np.asarray(out.length), [16, 3])

    def test_rejects_stateless_families(self, tiny):
        cfg, _ = tiny
        bad = dataclasses.replace(cfg, family="ssm")
        with pytest.raises(ValueError):
            model_lib.chunk_prefill_step(
                None, jnp.zeros((1, 4), jnp.int32),
                jnp.zeros((1,), jnp.int32), None, bad
            )


# ---------------------------------------------------------------- kernel ---


class TestChunkWidthKernel:
    """The k-query Pallas kernel generalized to chunk-width queries: the
    query axis tiles across the grid, kq pads to the tile multiple, and the
    (tiling-free) jnp oracle must be reproduced exactly for every (width,
    tile) combination — including tiles that do NOT divide kq."""

    def _pool(self, seed=0, b=3, hq=4, hkv=2, d=8, bs=4, nbt=6, n=24):
        rng = np.random.RandomState(seed)
        # stacked (L, n, ...) pools; the kernels read the last layer's pages
        kp = jnp.asarray(rng.randn(2, n, hkv, bs, d), jnp.float32)
        vp = jnp.asarray(rng.randn(2, n, hkv, bs, d), jnp.float32)
        bt = jnp.asarray(
            rng.permutation(n)[: b * nbt].reshape(b, nbt), jnp.int32
        )
        lengths = jnp.asarray([5, 0, 13], jnp.int32)
        return kp, vp, bt, lengths, rng

    @pytest.mark.parametrize("kq", [1, 4, 6, 16])
    @pytest.mark.parametrize("q_tile", [None, 2, 3, 4])
    def test_kernel_matches_ref_at_chunk_widths(self, kq, q_tile):
        kp, vp, bt, lengths, rng = self._pool()
        q = jnp.asarray(rng.randn(3, 4, kq, d := 8), jnp.float32)
        ref = paged_attention_kquery_ref(q, kp, vp, bt, lengths, 1)
        out = paged_attention_kquery(q, kp, vp, bt, lengths, 1, q_tile=q_tile)
        assert out.shape == (3, 4, kq, d)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5
        )

    def test_auto_tiling_kicks_in_for_wide_chunks(self):
        """A chunk wide enough to exceed the per-tile row budget must still
        match the oracle (auto q_tile path)."""
        from repro.kernels.paged_attention import _MAX_Q_ROWS

        kp, vp, bt, lengths, rng = self._pool()
        kq = _MAX_Q_ROWS // 2 + 8          # group=2 -> rows > _MAX_Q_ROWS
        q = jnp.asarray(rng.randn(3, 4, kq, 8), jnp.float32)
        ref = paged_attention_kquery_ref(q, kp, vp, bt, lengths, 1)
        out = paged_attention_kquery(q, kp, vp, bt, lengths, 1)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5
        )


# ---------------------------------------------------------------- engine ---


class TestChunkedEngineEquivalence:
    @pytest.mark.parametrize("chunk", [8, 16, 32])
    def test_streams_match_oneshot_and_padded(self, tiny, chunk):
        """The core acceptance invariant: chunked greedy output is bitwise
        identical to one-shot paged AND slot-padded output, under slot reuse
        and mid-stream admission; the chunk program compiles exactly once and
        fully replaces the one-shot prefill program."""
        cfg, params = tiny
        ref = run_tokens(
            ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=64))
        )
        one = run_tokens(PagedServingEngine(
            ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=64, block_size=8)
        ))
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=64, block_size=8, prefill_chunk=chunk
        ))
        got = run_tokens(eng)
        assert got == ref == one
        assert eng.chunk_traces == 1
        assert eng.chunk_calls > 0 and eng.prefill_calls == 0
        # every request completed exactly one prefill (no eviction here)
        assert decode_emitted_tokens(
            [Request(0, [1], out_tokens=t, prefill_emitted=1)
             for t in got.values()]
        ) == sum(len(t) - 1 for t in got.values())

    def test_int8_pages(self, tiny):
        cfg, params = tiny
        ref = run_tokens(PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=64, block_size=8, kv_dtype="int8"
        )))
        got = run_tokens(PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=64, block_size=8, kv_dtype="int8",
            prefill_chunk=16,
        )))
        assert got == ref

    def test_pallas_kernel_path(self, tiny):
        cfg, params = tiny
        c2 = dataclasses.replace(cfg, kernel_impl="pallas")
        dense = run_tokens(PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=64, block_size=8, prefill_chunk=16
        )))
        pallas = run_tokens(PagedServingEngine(ModelBank.single(c2, params), EngineConfig(
            max_slots=2, max_len=64, block_size=8, prefill_chunk=16
        )))
        assert pallas == dense

    @pytest.mark.parametrize("mode", ["parallel", "sequential"])
    def test_speculative_engine_inherits_chunking(self, tiny, mode):
        """SpeculativeEngine chunks BOTH caches (target + draft) and still
        emits streams identical to the plain paged engine under greedy."""
        cfg, params = tiny
        ref = run_tokens(PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=64, block_size=8
        )))
        eng = SpeculativeEngine(ModelBank(cfg, [params, params]), EngineConfig(
            max_slots=2, max_len=64, block_size=8, spec_k=3,
            spec_draft_mode=mode, prefill_chunk=16,
        ))
        got = run_tokens(eng)
        assert got == ref
        assert eng.chunk_calls > 0 and eng.prefill_calls == 0

    def test_monotonic_timestamps(self, tiny):
        """Engine timestamps ride the monotonic clock: per-request ordering
        submitted <= admitted <= first_token <= finished always holds and
        token_times never decrease (an NTP step cannot break this)."""
        cfg, params = tiny
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=64, block_size=8, prefill_chunk=8
        ))
        for p in PROMPTS[:3]:
            eng.submit(p, max_new_tokens=4)
        for r in eng.run():
            assert r.submitted_at <= r.admitted_at <= r.first_token_at
            assert r.first_token_at <= r.finished_at
            assert all(a <= b for a, b in
                       zip(r.token_times, r.token_times[1:]))

    def test_invalid_chunk_rejected(self, tiny):
        cfg, params = tiny
        with pytest.raises(ValueError):
            PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
                max_slots=2, max_len=64, block_size=8, prefill_chunk=12
            ))
        with pytest.raises(ValueError):
            PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
                max_slots=2, max_len=64, block_size=8, prefill_chunk=0
            ))

    def test_capability_errors_on_non_paged_engines(self, tiny):
        """prefill_chunk is paged-only and must fail loudly elsewhere (the
        'never silently drop a requested feature' convention)."""
        cfg, params = tiny
        with pytest.raises(EngineCapabilityError):
            ServingEngine(ModelBank.single(cfg, params), EngineConfig(
                max_slots=2, max_len=64, prefill_chunk=16
            ))
        with pytest.raises(EngineCapabilityError):
            ReferenceEngine(ModelBank.single(cfg, params), EngineConfig(
                max_slots=2, max_len=64, prefill_chunk=16
            ))


class TestChunkedEviction:
    def test_decode_phase_eviction_preserves_tokens(self, tiny):
        """A pool too small for two requests forces eviction; the evicted
        request resumes by re-prefilling CHUNK-BY-CHUNK and must emit the
        same tokens. Accounting: every completed admission emitted one
        prefill token, so prefill_emitted == 1 + evictions here."""
        cfg, params = tiny
        prompts = [[5, 7, 11], [3, 1, 4]]
        e_ref = ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=16))
        ref = run_tokens(e_ref, prompts, max_new=10)

        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=16, block_size=4, num_blocks=4,
            decode_reserve=1, prefill_chunk=4,
        ))
        done = []
        for p in prompts:
            eng.submit(p, max_new_tokens=10)
        done = eng.run()
        assert {r.uid: r.out_tokens for r in done} == ref
        assert eng.evictions >= 1
        assert eng.allocator.used_blocks == 0
        for r in done:
            # decode-phase evictions: every admission reached its prompt end
            assert r.prefill_emitted == 1 + r.evictions
        assert decode_emitted_tokens(done) == sum(
            len(r.out_tokens) - 1 - r.evictions for r in done
        )

    def test_eviction_mid_prefill_resumes_correctly(self, tiny):
        """Two long prompts whose chunked prefills jointly exhaust the pool:
        one gets evicted MID-prefill (no decode-phase victim exists), loses
        its partial chunks, re-admits, and still emits the reference stream.
        Accounting regression: that request completed ONE prefill but has
        evictions >= 1, so prefill_emitted != 1 + evictions — the old
        ``len(out) - 1 - evictions`` convention would undercount its decode
        tokens."""
        cfg, params = tiny
        prompts = [list(range(2, 22)), list(range(30, 50))]   # 20 toks each
        e_ref = ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32))
        ref = run_tokens(e_ref, prompts, max_new=4)

        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=32, block_size=4, num_blocks=8,
            decode_reserve=1, prefill_chunk=4,
        ))
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        done = eng.run(max_steps=500)
        assert {r.uid: r.out_tokens for r in done} == ref
        assert eng.evictions >= 1
        assert eng.allocator.used_blocks == 0
        evicted = [r for r in done if r.evictions]
        assert evicted, "pool was sized to force a mid-prefill eviction"
        mid_prefill = [r for r in evicted
                       if r.prefill_emitted < 1 + r.evictions]
        assert mid_prefill, (
            "expected at least one eviction to land mid-prefill "
            f"(got {[(r.uid, r.evictions, r.prefill_emitted) for r in done]})"
        )
        # decode-token accounting stays exact even for that request
        total = sum(len(r.out_tokens) for r in done)
        emitted_by_prefill = sum(r.prefill_emitted for r in done)
        assert decode_emitted_tokens(done) == total - emitted_by_prefill

    def test_contending_prefills_terminate_without_livelock(self, tiny):
        """Regression: two prompts whose TOTAL page needs exceed the pool are
        both admitted (chunked admission reserves only the first chunk). An
        earlier design let each prefill's page growth evict the other — the
        two requests ping-ponged forever (measured livelock: 10k steps, zero
        completions, plus a KeyError on the ready batch). Prefill growth now
        STALLS and the all-stalled deadlock breaker evicts exactly one
        victim, so both requests finish with the reference streams."""
        cfg, params = tiny
        prompts = [list(range(1, 49)), list(range(50, 98))]   # 48 toks each
        e_ref = ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=64))
        ref = run_tokens(e_ref, prompts, max_new=4)
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=64, block_size=8, num_blocks=9,
            prefill_chunk=8,
        ))
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        done = eng.run(max_steps=500)
        assert {r.uid: r.out_tokens for r in done} == ref
        assert eng.evictions >= 1
        assert eng.allocator.used_blocks == 0

    def test_three_way_contention_survivors_absorb_freed_pages(self, tiny):
        """Regression: when the all-stalled deadlock breaker evicts a slot
        holding exactly one chunk's pages, the SURVIVORS must absorb those
        pages within the same tick — deferring to the next tick let the
        evicted request re-admit and re-reserve exactly what it freed (a
        measured 2-tick ping-pong: 3 slots, 0 completions, unbounded
        evictions)."""
        cfg, params = tiny
        prompts = [list(range(1, 41)), list(range(41, 81)),
                   list(range(81, 121))]                  # 40 toks each
        e_ref = ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=3, max_len=64))
        ref = run_tokens(e_ref, prompts, max_new=4)
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=3, max_len=64, block_size=4, num_blocks=14,
            prefill_chunk=8,
        ))
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        done = eng.run(max_steps=500)
        assert {r.uid: r.out_tokens for r in done} == ref
        assert len(done) == 3
        assert eng.evictions >= 1
        assert eng.allocator.used_blocks == 0

    def test_decode_growth_can_evict_stalled_prefill(self, tiny):
        """A nearly-finished decoder growing into a dry pool evicts the
        mid-prefill slot (longest_remaining counts its whole max_new), never
        the other way around — the decoder always finishes and frees its
        pages for the prefill to resume."""
        cfg, params = tiny
        # the short request finishes prefill immediately and decodes while
        # the long one's chunks grow into the pool
        prompts = [list(range(2, 26)), [7, 7, 7]]
        e_ref = ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32))
        ref = run_tokens(e_ref, prompts, max_new=6)
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=32, block_size=4, num_blocks=8,
            decode_reserve=1, prefill_chunk=4,
        ))
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        done = eng.run(max_steps=500)
        assert {r.uid: r.out_tokens for r in done} == ref
        assert eng.allocator.used_blocks == 0
        short_req = next(r for r in done if len(r.prompt) == 3)
        assert short_req.evictions == 0


class TestEDFAdmission:
    def _req(self, uid, deadline=None, evictions=0):
        return Request(uid, [1], deadline=deadline, evictions=evictions)

    def test_order_unified_across_engines(self, tiny):
        """Both batched engines share one EDF order: earliest deadline first,
        evicted/resumed requests break ties, then FIFO."""
        cfg, params = tiny
        for eng in (
            ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=16)),
            PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
                max_slots=2, max_len=16, block_size=8
            )),
        ):
            eng._queue = [
                self._req(1, deadline=9.0),
                self._req(2, deadline=3.0),
                self._req(3),                              # no deadline: last
                self._req(4, deadline=3.0, evictions=1),   # tie: evicted first
                self._req(5, deadline=1.0),
                self._req(6),
            ]
            eng._order_queue()
            assert [r.uid for r in eng._queue] == [5, 4, 2, 1, 3, 6]

    def test_padded_engine_admits_edf(self, tiny):
        """The slot-padded engine used to pop FIFO ignoring deadlines; now an
        urgent late submission is admitted (and finishes) first."""
        cfg, params = tiny
        eng = ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=1, max_len=32))
        eng.submit([5, 7, 11], max_new_tokens=3, deadline=100.0)
        eng.submit([3, 1], max_new_tokens=3, deadline=50.0)
        eng.submit([8, 8, 2], max_new_tokens=3, deadline=1.0)
        done = eng.run()
        assert [r.uid for r in done] == [3, 2, 1]

    def test_edf_beats_eviction_priority(self, tiny):
        """An evicted request does NOT jump an urgent fresh request with an
        earlier deadline (EDF stays primary; eviction is only a tiebreak)."""
        cfg, params = tiny
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=16, block_size=8
        ))
        eng._queue = [
            self._req(1, deadline=5.0, evictions=2),
            self._req(2, deadline=1.0),
        ]
        eng._order_queue()
        assert [r.uid for r in eng._queue] == [2, 1]

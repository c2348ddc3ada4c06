"""Prefix-sharing tests (PR 6 tentpole).

The core invariant: turning the radix prompt cache on changes WHICH pages a
slot's block table points at — shared, ref-counted, copy-on-write pages —
never WHAT gets served. Token streams with the cache on are bitwise-identical
to cache-off across one-shot and chunked prefill, fp32 and int8 pages, dense/
factored/bsr weight formats, eviction/resume, sampled decoding (the PRNG
satellite: a resumed slot keeps its fold_in stream), and the speculative
engine (whose draft pools must ride along through copy-on-write).

Underneath that sit the allocator property tests: random alloc / share /
release / free sequences against a reference model — refcount accounting, no
double grants, pool conservation (free + distinct-owned == pool), and
error paths that leave the allocator untouched.
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # bare container: deterministic-grid shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig, admm_update, init_slr_state
from repro.core.selection import SelectionConfig
from repro.models import model as model_lib
from repro.serving.elastic import ModelBank
from repro.serving.engine import (
    BlockAllocator,
    EngineCapabilityError,
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.speculative import SpeculativeEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("salaad_llama_60m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def trained():
    cfg = get_arch("olmo_1b").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    scfg = SalaadConfig(
        selection=SelectionConfig(min_dim=16), rho_constant=5.0, exact_svd=True
    )
    state, blocks = init_slr_state(params, scfg)
    for step in range(4):
        state, _ = admm_update(params, state, blocks, scfg, step)
    return cfg, params, state, blocks


# 48 tokens = 3 full pages at the default block_size 16: long enough that a
# shared prefix spans whole pages, short enough to stay fast
PREFIX = [(7 * i + 3) % 50 + 2 for i in range(48)]
# unique suffixes + one prompt that IS exactly the prefix (page-aligned, so
# its repeat resumes at plen - 1 INSIDE its final cached page — the CoW case)
SHARED = [PREFIX + [100 + 10 * i + j for j in range(5)] for i in range(3)]
SHARED.append(list(PREFIX))


def run_streams(engine, prompts, max_new=6):
    """Token streams in submission order (uids are per-engine monotonic)."""
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    return [r.out_tokens for r in sorted(engine.run(), key=lambda r: r.uid)]


def paired_engines(tiny, **kw):
    cfg, params = tiny
    mk = lambda pc: PagedServingEngine(
        ModelBank.single(cfg, params), EngineConfig(max_slots=4, max_len=96, prefix_cache=pc, **kw)
    )
    return mk(False), mk(True)


# -------------------------------------------------------------- allocator ---


class TestBlockAllocatorProperties:
    """Random op sequences vs a dict-mirror reference model."""

    @settings(max_examples=12)
    @given(st.integers(min_value=0, max_value=10_000),
           st.integers(min_value=4, max_value=16))
    def test_random_op_sequences(self, seed, pool):
        rng = np.random.RandomState(seed)
        alloc = BlockAllocator(pool)
        refs: dict[int, int] = {}      # the model: page -> holders
        granted = set()                # every page ever handed out by alloc()
        for _ in range(120):
            op = rng.randint(4)
            owned = sorted(refs)
            if op == 0:
                n = int(rng.randint(0, pool + 2))
                got = alloc.alloc(n)
                if n > pool - len(refs):
                    assert got is None, "grant beyond the free pool"
                else:
                    assert got is not None and len(got) == n
                    assert len(set(got)) == n, "duplicate pages in one grant"
                    assert not set(got) & set(refs), "double-granted page"
                    for p in got:
                        refs[p] = 1
                    granted |= set(got)
            elif op == 1 and owned:
                sub = [p for p in owned if rng.rand() < 0.5]
                alloc.share(sub)
                for p in sub:
                    refs[p] += 1
            elif op == 2 and owned:
                sub = [p for p in owned if rng.rand() < 0.5]
                freed = alloc.release(sub)
                want_freed = []
                for p in sub:
                    refs[p] -= 1
                    if refs[p] == 0:
                        del refs[p]
                        want_freed.append(p)
                assert freed == want_freed
            elif op == 3:
                sub = [p for p in owned if refs[p] == 1 and rng.rand() < 0.5]
                alloc.free(sub)
                for p in sub:
                    del refs[p]
            # conservation + accounting, after every op
            assert alloc.free_blocks == pool - len(refs)
            assert alloc.used_blocks == len(refs)
            for p in granted:
                assert alloc.refcount(p) == refs.get(p, 0)

    def test_error_paths_leave_state_untouched(self):
        alloc = BlockAllocator(4)
        pages = alloc.alloc(3)
        alloc.share([pages[0]])
        snap = (alloc.free_blocks, alloc.used_blocks,
                [alloc.refcount(p) for p in pages])

        with pytest.raises(ValueError, match="freeing shared"):
            alloc.free(pages)                     # pages[0] has refcount 2
        with pytest.raises(ValueError, match="not allocated"):
            alloc.release(pages + [3])            # 3 was never granted
        with pytest.raises(ValueError, match="not allocated"):
            alloc.share([99])
        with pytest.raises(ValueError, match="duplicate"):
            alloc.release([pages[1], pages[1]])
        assert alloc.alloc(2) is None             # only 1 free: no partial grant

        assert snap == (alloc.free_blocks, alloc.used_blocks,
                        [alloc.refcount(p) for p in pages])

    def test_share_release_lifecycle(self):
        alloc = BlockAllocator(2)
        (p,) = alloc.alloc(1)
        alloc.share([p])
        assert alloc.refcount(p) == 2
        assert alloc.release([p]) == []           # one holder remains
        assert alloc.release([p]) == [p]          # last holder frees it
        assert alloc.free_blocks == 2
        assert alloc.refcount(p) == 0


# ------------------------------------------------------------ radix index ---


class TestPrefixCacheIndex:
    BS = 2

    def _cache(self, pool=8):
        alloc = BlockAllocator(pool)
        return alloc, PrefixCache(alloc, self.BS)

    def test_publish_then_match(self):
        alloc, pc = self._cache()
        pages = alloc.alloc(2)
        pc.publish([1, 2, 3, 4], pages)
        assert pc.match([1, 2, 3, 4, 9]) == pages
        assert pc.match([1, 2, 7, 7]) == pages[:1]   # partial prefix
        assert pc.match([5, 5]) == []
        assert pc.pages == 2

    def test_publish_dedup_releases_duplicate_ref(self):
        """Two slots retiring the same prefix converge on ONE physical copy;
        the loser's transferred reference is dropped, not leaked."""
        alloc, pc = self._cache()
        first = alloc.alloc(1)
        pc.publish([1, 2], first)
        free0 = alloc.free_blocks
        dup = alloc.alloc(1)
        pc.publish([1, 2], dup)
        assert pc.match([1, 2]) == first             # index's page wins
        assert alloc.free_blocks == free0            # duplicate went back
        assert alloc.refcount(dup[0]) == 0
        # publishing the INDEXED page itself (an attached slot retiring) just
        # drops the transferred duplicate reference — no self-free
        alloc.share(first)
        pc.publish([1, 2], first)
        assert alloc.refcount(first[0]) == 1

    def test_reclaim_lru_leaf_first(self):
        alloc, pc = self._cache()
        a = alloc.alloc(2)
        pc.publish([1, 2, 3, 4], a)                  # chain a: two nodes
        b = alloc.alloc(1)
        pc.publish([9, 9], b)                        # chain b: one leaf
        pc.match([1, 2, 3, 4])                       # touch a — b is now LRU
        assert pc.reclaim(1) == 1
        assert alloc.refcount(b[0]) == 0             # b went first
        assert pc.match([1, 2, 3, 4]) == a
        # cascading: a's leaf frees first, its parent becomes a leaf
        assert pc.reclaim(5) == 2
        assert pc.pages == 0
        assert alloc.free_blocks == alloc.num_blocks

    def test_reclaim_never_touches_attached_pages(self):
        alloc, pc = self._cache()
        a = alloc.alloc(2)
        pc.publish([1, 2, 3, 4], a)
        alloc.share([a[1]])                          # a slot holds the leaf
        assert pc.reclaim(5) == 0                    # leaf pinned, parent is
        assert pc.pages == 2                         # interior: nothing frees
        assert pc.reclaimable_pages == 0             # pinned leaf taints chain
        alloc.release([a[1]])
        assert pc.reclaimable_pages == 2
        assert pc.reclaim(5) == 2


# --------------------------------------------- cache on == cache off, bits ---


class TestCacheEquivalence:
    """Two identical waves: wave 1 populates the index, wave 2 hits it."""

    def _check(self, tiny, waves=2, max_new=6, **kw):
        off, on = paired_engines(tiny, **kw)
        for _ in range(waves):
            assert run_streams(off, SHARED, max_new) \
                == run_streams(on, SHARED, max_new)
        return on

    def test_oneshot_fp32(self, tiny):
        on = self._check(tiny)
        assert on.prefix_hits > 0
        assert on.prefix_hit_tokens > 0
        assert on.cow_copies > 0          # the page-aligned repeat resumes
        #                                   at plen - 1 inside a cached page
        # conservation holds with the index holding references
        assert on.allocator.free_blocks + on.allocator.used_blocks \
            == on.num_blocks

    def test_chunked_fp32(self, tiny):
        on = self._check(tiny, prefill_chunk=16)
        assert on.prefix_hits > 0

    def test_chunked_int8(self, tiny):
        on = self._check(tiny, prefill_chunk=16, kv_dtype="int8")
        assert on.prefix_hits > 0

    def test_oneshot_int8_cow_moves_scales(self, tiny):
        """Satellite regression: copy-on-write must move the scale pool WITH
        the int8 payload pool — a CoW'd page decoded against a stale scale
        diverges from the cache-off stream immediately."""
        on = self._check(tiny, kv_dtype="int8")
        assert on.cow_copies > 0

    def test_min_hit_pages_gates_attachment(self, tiny):
        on = self._check(tiny, prefix_min_hit_pages=64)
        assert on.prefix_lookups > 0
        assert on.prefix_hits == 0        # every hit too small to attach

    def test_bfloat16_pages(self, tiny):
        on = self._check(tiny, kv_dtype="bfloat16")
        assert on.prefix_hits > 0


class TestCacheEquivalenceFormats:
    """Dense / factored / bsr deployed weights over the SAME trained state:
    prefix sharing lives entirely in the KV path, so the weight format must
    be invisible to it."""

    @pytest.mark.parametrize("fmt", ["dense", "factored", "bsr"])
    def test_formats(self, trained, fmt):
        cfg, params, state, blocks = trained
        bank = ModelBank.build(cfg, params, state, blocks, budgets=(1.0,),
                               fmt=fmt, bsr_block=32)
        mk = lambda pc: PagedServingEngine(
            bank, EngineConfig(max_slots=4, max_len=96, prefix_cache=pc)
        )
        off, on = mk(False), mk(True)
        for _ in range(2):
            assert run_streams(off, SHARED) == run_streams(on, SHARED)
        assert on.prefix_hits > 0


# -------------------------------------------------------- eviction/resume ---


def run_with_manual_evict(engine, prompts, max_new, evict_tick=4):
    """Drive step() by hand and evict slot 0 at a fixed tick — the same tick
    in both engines, so their traces stay comparable."""
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    done, tick = [], 0
    while engine.has_work:
        tick += 1
        if tick == evict_tick and 0 in engine._active:
            engine._evict(0, [])
        done += engine.step()
    return [r.out_tokens for r in sorted(done, key=lambda r: r.uid)]


class TestEvictionResume:
    def test_reattach_greedy(self, tiny):
        """An evicted slot's pages survive in the index; its re-admission
        reattaches them instead of chunked re-prefill."""
        off, on = paired_engines(tiny)
        assert run_with_manual_evict(off, SHARED, 6) \
            == run_with_manual_evict(on, SHARED, 6)
        assert on.reattached_pages > 0
        assert on.evictions == off.evictions == 1

    def test_reattach_sampled_prng_stream(self, tiny):
        """Satellite regression: a resumed slot must keep the SAME fold_in
        sampling stream as its original admission — cache-on reattaches and
        replays only the suffix, cache-off re-prefills everything, and the
        sampled tokens still agree bitwise."""
        cfg, params = tiny
        mk = lambda pc: PagedServingEngine(
            ModelBank.single(cfg, params), EngineConfig(max_slots=4, max_len=96, greedy=False,
                                      temperature=0.8, prefix_cache=pc)
        )
        off, on = mk(False), mk(True)
        assert run_with_manual_evict(off, SHARED, 6) \
            == run_with_manual_evict(on, SHARED, 6)
        assert on.reattached_pages > 0

    def test_pressure_eviction_equivalence(self, tiny):
        """Organic evictions from a tight pool: streams and eviction counts
        match cache-off exactly (reclaim drains the index's LRU tail before
        the engine touches live slots)."""
        cfg, params = tiny
        mk = lambda pc: PagedServingEngine(
            ModelBank.single(cfg, params), EngineConfig(max_slots=3, max_len=96, num_blocks=14,
                                      prefix_cache=pc)
        )
        off, on = mk(False), mk(True)
        a = run_streams(off, SHARED + SHARED, 8)
        b = run_streams(on, SHARED + SHARED, 8)
        assert a == b
        assert on.evictions == off.evictions
        assert on.allocator.free_blocks + on.allocator.used_blocks \
            == on.num_blocks


# ------------------------------------------------------------- speculative ---


class TestSpeculativeEquivalence:
    def test_spec_cache_on_off(self, tiny):
        """Draft pools share the target's block table, so CoW must remap
        BOTH: a missed draft-pool copy skews draft logits and (greedy
        verify being exact) shows up as a changed acceptance pattern."""
        cfg, params = tiny
        draft = model_lib.init_params(cfg, jax.random.PRNGKey(1))
        mk = lambda pc: SpeculativeEngine(
            ModelBank(cfg, [params, draft]),
            EngineConfig(max_slots=4, max_len=96, spec_k=3, prefix_cache=pc),
        )
        off, on = mk(False), mk(True)
        for _ in range(2):
            assert run_streams(off, SHARED) == run_streams(on, SHARED)
        assert on.prefix_hits > 0
        assert on.cow_copies > 0

    def test_spec_chunked_cache_on_off(self, tiny):
        cfg, params = tiny
        draft = model_lib.init_params(cfg, jax.random.PRNGKey(1))
        mk = lambda pc: SpeculativeEngine(
            ModelBank(cfg, [params, draft]),
            EngineConfig(max_slots=4, max_len=96, spec_k=3, prefill_chunk=16,
                         prefix_cache=pc),
        )
        off, on = mk(False), mk(True)
        for _ in range(2):
            assert run_streams(off, SHARED) == run_streams(on, SHARED)
        assert on.prefix_hits > 0


# ------------------------------------------------------------ capability ---


class TestCapabilityGates:
    def test_batched_engine_rejects_prefix_cache(self, tiny):
        """The refusal carries the structured capability report, which says
        the engine has no prefix caching."""
        cfg, params = tiny
        with pytest.raises(EngineCapabilityError,
                           match='"prefix_caching": false'):
            ReferenceEngine(ModelBank.single(cfg, params), EngineConfig(prefix_cache=True))

    def test_reference_engine_rejects_prefix_cache(self, tiny):
        cfg, params = tiny
        with pytest.raises(EngineCapabilityError, match="prefix_cache"):
            ReferenceEngine(ModelBank.single(cfg, params), EngineConfig(prefix_cache=True))

    def test_config_validates_min_hit_pages(self):
        with pytest.raises(ValueError, match="prefix_min_hit_pages"):
            EngineConfig(prefix_min_hit_pages=0)

    def test_capability_table_reports_prefix_caching(self):
        assert PagedServingEngine.capabilities()["features"]["prefix_caching"]
        assert not ReferenceEngine.capabilities()["features"]["prefix_caching"]

"""Multi-tenant adapter serving tests (PR 6 tentpole).

The core invariants: (1) a slot served through an :class:`AdapterBank` with a
single registered adapter emits greedy token streams bitwise-identical to the
same model served as a plain ``ModelBank`` tier — across deployment formats,
int8 KV pages, and chunked prefill; (2) one engine batches slots running
DIFFERENT adapters in one decode tick, and every stream is token-identical to
a single-tenant run of that adapter; (3) adapter switches are pure data
rebinds — the pool swap is an ``.at[].set`` into frozen shapes and ``sel`` is
a data leaf, so ``serve_jit_retraces_total`` stays 0 across switches; (4) LRU
residency under a tight ``max_resident_adapters`` never evicts a pinned
(streaming) adapter, and unregistering an adapter with live slots is
rejected; (5) KV allocator and prefix-cache accounting are unchanged by
adapter switching (pages never cross adapters).
"""
import jax
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig, admm_update, init_slr_state
from repro.core.selection import SelectionConfig
from repro.models import model as model_lib
from repro.serving.adapters import (
    AdapterBank,
    AdapterError,
    AdapterRegistry,
    adapterize,
)
from repro.serving.deployed import DeployedModel
from repro.serving.elastic import ModelBank
from repro.serving.engine import (
    EngineCapabilityError,
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
    RequestRejected,
)
from repro.serving.speculative import SpeculativeEngine

PROMPTS = [[5, 7, 11, 2], [3, 1, 9], [8, 8, 2, 6, 4], [1, 2]]
ASSIGN = [0, 1, 2, 1]  # slot -> adapter for the mixed-batch runs


@pytest.fixture(scope="module")
def trained():
    cfg = get_arch("olmo_1b").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    scfg = SalaadConfig(
        selection=SelectionConfig(min_dim=16), rho_constant=5.0, exact_svd=True
    )
    states = []
    for seed in range(3):
        state, blocks = init_slr_state(params, scfg)
        for step in range(2 + seed):
            state, _ = admm_update(params, state, blocks, scfg, step)
        states.append((state, blocks))
    return cfg, params, states


@pytest.fixture(scope="module")
def deployed(trained):
    """fmt -> (base DeployedModel, [3 adapterized DeployedModels])."""
    cfg, params, states = trained
    out = {}
    for fmt in ("fused", "factored", "dense"):
        models = [
            DeployedModel.build(cfg, params, st, blocks, fmt=fmt, bsr_block=32)
            for st, blocks in states
        ]
        base = models[0]
        out[fmt] = (base, [adapterize(base, m) for m in models])
    return out


def run_multi(engine, prompts=PROMPTS, assign=ASSIGN, max_new=6):
    outs = {}
    for p, aid in zip(prompts, assign):
        outs[engine.submit(p, max_new_tokens=max_new, adapter=aid)] = None
    for r in engine.run():
        outs[r.uid] = list(r.out_tokens)
    return [outs[u] for u in sorted(outs)]


def run_single(cls, cfg, model, prompts, max_new=6, **kw):
    eng = cls(ModelBank.single(cfg, model), EngineConfig(**kw))
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    byuid = {r.uid: list(r.out_tokens) for r in eng.run()}
    return [byuid[u] for u in uids]


# ------------------------------------------------------------------ parity ---


class TestSingleTenantParity:
    """AdapterBank with ONE registered adapter == plain ModelBank tier."""

    @pytest.mark.parametrize("fmt", ["fused", "factored", "dense"])
    def test_bitwise_vs_modelbank_tier(self, trained, deployed, fmt):
        cfg, _, _ = trained
        base, adapters = deployed[fmt]
        bank = AdapterBank(base, [adapters[1]])
        ekw = dict(max_slots=2, max_len=32, block_size=8)
        eng = PagedServingEngine(bank, EngineConfig(adapters=True, **ekw))
        got = run_multi(eng, PROMPTS[:2], [0, 0])
        want = run_single(PagedServingEngine, cfg, adapters[1],
                          PROMPTS[:2], **ekw)
        assert got == want

    def test_parity_int8_kv(self, trained, deployed):
        cfg, _, _ = trained
        base, adapters = deployed["fused"]
        ekw = dict(max_slots=2, max_len=32, block_size=8, kv_dtype="int8")
        eng = PagedServingEngine(AdapterBank(base, [adapters[2]]),
                                 EngineConfig(adapters=True, **ekw))
        got = run_multi(eng, PROMPTS[:2], [0, 0])
        assert got == run_single(PagedServingEngine, cfg, adapters[2],
                                 PROMPTS[:2], **ekw)

    def test_parity_chunked_prefill(self, trained, deployed):
        cfg, _, _ = trained
        base, adapters = deployed["fused"]
        prompts = [list(range(1, 20)), list(range(3, 15))]
        ekw = dict(max_slots=2, max_len=64, block_size=8, prefill_chunk=8,
                   prefix_cache=True)
        eng = PagedServingEngine(AdapterBank(base, [adapters[1]]),
                                 EngineConfig(adapters=True, **ekw))
        got = run_multi(eng, prompts, [0, 0])
        assert got == run_single(PagedServingEngine, cfg, adapters[1],
                                 prompts, **ekw)


class TestMixedAdapters:
    """One decode tick batches slots running different adapters."""

    @pytest.mark.parametrize("fmt,mode", [("fused", "batched"),
                                          ("factored", "grouped")])
    def test_mixed_streams_match_single_tenant(self, trained, deployed,
                                               fmt, mode):
        cfg, _, _ = trained
        base, adapters = deployed[fmt]
        bank = AdapterBank(base, adapters)
        assert bank.mode == mode
        ekw = dict(max_slots=4, max_len=32, block_size=8)
        eng = PagedServingEngine(bank, EngineConfig(adapters=True, **ekw))
        got = run_multi(eng)
        for aid in set(ASSIGN):
            prompts = [p for p, a in zip(PROMPTS, ASSIGN) if a == aid]
            mine = [g for g, a in zip(got, ASSIGN) if a == aid]
            assert mine == run_single(PagedServingEngine, cfg, adapters[aid],
                                      prompts, **ekw)

    def test_eight_adapters_one_engine(self, trained, deployed):
        """Acceptance: >= 8 registered adapters served concurrently, each
        stream matching its adapter's single-tenant run."""
        cfg, _, _ = trained
        base, adapters = deployed["fused"]
        bank = AdapterBank(base)
        aids = [bank.register(adapters[i % 3], name=f"tenant{i}")
                for i in range(8)]
        assert len(bank.registry.ids) == 8
        prompts = [[i + 1, 2 * i + 3, 7] for i in range(8)]
        ekw = dict(max_slots=8, max_len=32, block_size=8)
        eng = PagedServingEngine(bank, EngineConfig(adapters=True, **ekw))
        got = run_multi(eng, prompts, aids)
        for i, aid in enumerate(aids):
            want = run_single(PagedServingEngine, cfg, adapters[i % 3],
                              [prompts[i]], **ekw)
            assert [got[i]] == want

    def test_zero_retraces_across_switches(self, trained, deployed):
        """Steady state: a second mixed wave with a different slot->adapter
        assignment compiles nothing new."""
        _, _, _ = trained
        base, adapters = deployed["fused"]
        eng = PagedServingEngine(
            AdapterBank(base, adapters),
            EngineConfig(adapters=True, max_slots=4, max_len=32,
                         block_size=8))
        run_multi(eng)
        before = eng.metrics.retraces()
        run_multi(eng, PROMPTS, [2, 0, 1, 0])   # shuffled assignment
        assert eng.metrics.retraces() == before == 0


# --------------------------------------------------------------- residency ---


class TestResidency:
    def test_lru_swap_under_tight_capacity(self, deployed):
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters, max_resident=2)
        bank.materialize()
        assert bank.capacity == 2 and bank.resident == [0, 1]
        row, swapped = bank.acquire(2)
        assert swapped and bank.swaps == 1
        assert 2 in bank.resident and row is not None
        # LRU: adapter 0 (least recently acquired) was the victim
        assert 0 not in bank.resident

    def test_pinned_adapter_never_evicted(self, deployed):
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters, max_resident=2)
        bank.materialize()
        bank.pin(0)
        bank.acquire(2)                      # must evict 1, not pinned 0
        assert 0 in bank.resident and 1 not in bank.resident
        bank.pin(2)
        row, swapped = bank.acquire(1)       # every row pinned: defer
        assert row is None and not swapped
        bank.unpin(0)
        row, swapped = bank.acquire(1)
        assert row is not None and swapped

    def test_engine_serves_more_adapters_than_rows(self, trained, deployed):
        """max_resident_adapters=2 with 3 adapters in flight: the engine
        defers the overflow request and still finishes everything."""
        cfg, _, _ = trained
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters)
        ekw = dict(max_slots=2, max_len=32, block_size=8)
        eng = PagedServingEngine(
            bank, EngineConfig(adapters=True, max_resident_adapters=2, **ekw))
        got = run_multi(eng, PROMPTS[:3], [0, 1, 2])
        assert bank.swaps >= 1
        for i, aid in enumerate((0, 1, 2)):
            assert [got[i]] == run_single(PagedServingEngine, cfg,
                                          adapters[aid], [PROMPTS[i]], **ekw)

    def test_unregister_while_streaming_rejected(self, deployed):
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters)
        eng = PagedServingEngine(
            bank, EngineConfig(adapters=True, max_slots=2, max_len=32,
                               block_size=8))
        eng.submit([1, 2, 3], max_new_tokens=8, adapter=1)
        eng.step()                           # admits: adapter 1 now pinned
        with pytest.raises(AdapterError, match="streaming"):
            bank.unregister(1)
        eng.run()                            # drain: slot released, unpinned
        bank.unregister(1)
        assert 1 not in bank.registry

    def test_unknown_adapter_rejected_after_unregister(self, deployed):
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters)
        eng = PagedServingEngine(
            bank, EngineConfig(adapters=True, max_slots=2, max_len=32,
                               block_size=8))
        bank.unregister(2)
        with pytest.raises(RequestRejected):
            eng.submit([1, 2], max_new_tokens=2, adapter=2)

    def test_allocator_accounting_unchanged_by_switches(self, deployed):
        """Adapter switching moves no KV: after draining a mixed wave every
        page is back (modulo pages retained by the per-adapter prefix
        caches, which remain reclaimable)."""
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters)
        eng = PagedServingEngine(
            bank, EngineConfig(adapters=True, max_slots=4, max_len=32,
                               block_size=8, prefix_cache=True))
        total = eng.allocator.free_blocks
        run_multi(eng)
        cached = sum(pc.pages for pc in eng._all_prefixes())
        assert eng.allocator.free_blocks + cached == total
        assert sum(pc.reclaimable_pages for pc in eng._all_prefixes()) \
            == cached
        # prefix caches are PER ADAPTER: every adapter that streamed has its
        # own cache, so one tenant's pages can never serve another's prompt
        assert set(eng._prefix_caches) == set(ASSIGN)
        run_multi(eng)   # second wave reuses/republishes, still balanced
        cached = sum(pc.pages for pc in eng._all_prefixes())
        assert eng.allocator.free_blocks + cached == total


# -------------------------------------------------------------- validation ---


class TestValidation:
    def test_registry_rejects_bsr(self, trained):
        cfg, params, states = trained
        st, blocks = states[0]
        bsr = DeployedModel.build(cfg, params, st, blocks, fmt="bsr",
                                  bsr_block=32)
        with pytest.raises(AdapterError, match="bsr"):
            AdapterRegistry(bsr)

    def test_config_validation(self):
        with pytest.raises(ValueError):
            EngineConfig(max_resident_adapters=4)          # needs adapters
        with pytest.raises(ValueError):
            EngineConfig(adapters=True, max_resident_adapters=0)

    def test_bank_and_flag_must_agree(self, trained, deployed):
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters)
        with pytest.raises(ValueError):
            PagedServingEngine(bank, EngineConfig(max_slots=2, max_len=32,
                                                  block_size=8))
        cfg, params, _ = trained
        with pytest.raises(ValueError):
            PagedServingEngine(ModelBank.single(cfg, params),
                               EngineConfig(adapters=True, max_slots=2,
                                            max_len=32))

    def test_adapter_kwarg_without_bank_rejected(self, trained):
        cfg, params, _ = trained
        eng = PagedServingEngine(ModelBank.single(cfg, params),
                                 EngineConfig(max_slots=2, max_len=32))
        with pytest.raises(RequestRejected):
            eng.submit([1, 2], max_new_tokens=2, adapter=0)

    def test_reference_engine_has_no_adapters(self, trained):
        cfg, params, _ = trained
        eng = ReferenceEngine(ModelBank.single(cfg, params),
                              EngineConfig(max_slots=1, max_len=16))
        with pytest.raises(RequestRejected):
            eng.submit([1, 2], max_new_tokens=2, adapter=0)
        assert not ReferenceEngine.capabilities()["features"][
            "multi_tenant_adapters"]

    def test_speculative_engine_rejects_bank(self, deployed):
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters)
        with pytest.raises(EngineCapabilityError):
            SpeculativeEngine(bank, EngineConfig(
                adapters=True, max_slots=1, max_len=16, block_size=8,
                spec_k=2))

    def test_adapter_telemetry_counters(self, deployed):
        base, adapters = deployed["fused"]
        bank = AdapterBank(base, adapters, max_resident=2)
        eng = PagedServingEngine(
            bank, EngineConfig(adapters=True, max_resident_adapters=2,
                               max_slots=2, max_len=32, block_size=8,
                               telemetry=True))
        run_multi(eng, PROMPTS[:3], [0, 1, 2])
        assert int(eng.metrics.adapter_swaps.total()) == bank.swaps >= 1
        assert int(eng.metrics.adapter_tokens.total()) == 18  # 3 x 6 tokens

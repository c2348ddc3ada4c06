"""Elastic serving tests (PR 5 tentpole): budget tiers as a serving dimension.

The core invariants: (1) ONE engine serves several budget tiers concurrently
from a single shared ModelBank, and a slot pinned to tier b emits token
streams bitwise-identical to a fixed single-budget engine built at budget b —
across deployment formats, int8 KV pages, and chunked prefill; (2) a
mid-stream tier switch (the pressure controller's downshift) is pure host
bookkeeping: no recompilation (each tier's program compiles exactly once) and
no KV movement (the block table and pages are tier-agnostic); (3) the old
``Engine(arch_cfg, params, ecfg)`` constructors are gone — they raise a
TypeError pointing at ``ModelBank.single``.

Also covers the PR 5 satellites: EngineConfig construction-time validation,
structured ``capabilities()`` dicts inside EngineCapabilityError messages,
and the Engine protocol that all front ends program against.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig, admm_update, init_slr_state
from repro.core.selection import SelectionConfig
from repro.models import model as model_lib
from repro.serving.deployed import DeployedModel
from repro.serving.elastic import (
    Engine,
    ModelBank,
    TierController,
    TierControllerConfig,
    format_capability_table,
)
from repro.serving.engine import (
    EngineCapabilityError,
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
    RequestRejected,
    ServingEngine,
)
from repro.serving.speculative import SpeculativeEngine

BUDGETS = (1.0, 0.6, 0.3)


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


@pytest.fixture(scope="module")
def banks(trained):
    """One ModelBank per deployment format over the SAME trained state."""
    cfg, params, state, blocks = trained
    return {
        fmt: ModelBank.build(cfg, params, state, blocks, budgets=BUDGETS,
                             fmt=fmt, bsr_block=32)
        for fmt in ("dense", "factored", "bsr")
    }


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("salaad_llama_60m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def run_tokens(engine, prompts, max_new=4, tiers=None):
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=max_new,
                      tier=None if tiers is None else tiers[i])
    return {r.uid: r.out_tokens for r in engine.run()}


# ------------------------------------------------------------------- bank ---


class TestModelBank:
    def test_tiers_ordered_largest_first(self, banks):
        bank = banks["factored"]
        assert [t.keep for t in bank] == sorted(BUDGETS, reverse=True)
        assert [t.index for t in bank] == [0, 1, 2]
        # the factored view shrinks with the budget (HPA removed structure)
        sizes = [t.param_bytes for t in bank]
        assert sizes == sorted(sizes, reverse=True)
        assert sizes[0] > sizes[-1]

    def test_shared_base_across_tiers(self, banks):
        """Leaves HPA never touches (embeddings, norms) are the SAME arrays
        in every tier — the bank holds the weights once, not per budget."""
        bank = banks["factored"]
        shared = bank.shared_base_bytes()
        assert shared > 0
        rep = bank.report()
        assert rep["num_tiers"] == 3
        assert rep["shared_base_bytes"] == shared
        assert all(r["param_bytes"] > 0 for r in rep["tiers"])

    def test_build_rejects_bad_budgets(self, trained):
        cfg, params, state, blocks = trained
        with pytest.raises(ValueError):
            ModelBank.build(cfg, params, state, blocks, budgets=())
        with pytest.raises(ValueError):
            ModelBank.build(cfg, params, state, blocks, budgets=(0.5, 0.5))
        with pytest.raises(ValueError):
            ModelBank.build(cfg, params, state, blocks, budgets=(1.0, 0.0))
        with pytest.raises(ValueError):
            ModelBank.build(cfg, params, state, blocks, budgets=(1.2,))

    def test_resolve_and_negative_indexing(self, banks):
        bank = banks["dense"]
        assert bank.resolve(-1) == 2
        assert bank[-1].index == 2
        with pytest.raises(ValueError):
            bank.resolve(3)
        with pytest.raises(ValueError):
            bank.resolve(-4)

    def test_single_wraps_raw_tree(self, tiny):
        cfg, params = tiny
        bank = ModelBank.single(cfg, params)
        assert len(bank) == 1
        assert isinstance(bank[0].model, DeployedModel)
        assert bank[0].params is params
        assert bank.shared_base_bytes() == 0   # one tier: nothing to share

    def test_mismatched_metadata_rejected(self, tiny):
        cfg, params = tiny
        with pytest.raises(ValueError):
            ModelBank(cfg, [params], keeps=[1.0, 0.5])
        with pytest.raises(ValueError):
            ModelBank(cfg, [])


# ---------------------------------------------------------- config checks ---


class TestEngineConfigValidation:
    """Satellite: a bad config raises a clear ValueError at CONSTRUCTION,
    not a shape/jit failure deep inside the first prefill."""

    @pytest.mark.parametrize("kw,field", [
        (dict(max_slots=0), "max_slots"),
        (dict(max_len=0), "max_len"),
        (dict(block_size=0), "block_size"),
        (dict(block_size=-4), "block_size"),
        (dict(num_blocks=0), "num_blocks"),
        (dict(num_blocks=-1), "num_blocks"),
        (dict(kv_dtype="fp8"), "kv_dtype"),
        (dict(evict_policy="random"), "evict_policy"),
        (dict(decode_reserve=0), "decode_reserve"),
        (dict(prefill_chunk=0), "prefill_chunk"),
        (dict(prefill_chunk=12, block_size=8), "prefill_chunk"),
        (dict(tier_policy="adaptive"), "tier_policy"),
        (dict(tier_target_free=0.0), "tier_target_free"),
        (dict(tier_target_free=1.5), "tier_target_free"),
        (dict(tier_gain=0.0), "tier_gain"),
        (dict(tier_ema=1.0), "tier_ema"),
        (dict(spec_k=-1), "spec_k"),
        (dict(spec_draft_mode="jacobi"), "spec_draft_mode"),
        (dict(spec_draft_kv_dtype="fp4"), "spec_draft_kv_dtype"),
        (dict(min_bucket=0), "min_bucket"),
    ])
    def test_bad_field_raises_naming_the_field(self, kw, field):
        with pytest.raises(ValueError, match=field):
            EngineConfig(**kw)

    def test_valid_configs_still_construct(self):
        EngineConfig()
        EngineConfig(kv_dtype="int8", prefill_chunk=32, block_size=16)
        EngineConfig(tier_policy="pressure", tier_target_free=0.3)

    def test_block_aligned_chunk_accepted(self):
        ecfg = EngineConfig(block_size=8, prefill_chunk=24)
        assert ecfg.prefill_chunk == 24


# ----------------------------------------------------------------- protocol ---


class TestEngineProtocol:
    def test_all_engines_implement_protocol(self, tiny):
        cfg, params = tiny
        bank = ModelBank.single(cfg, params)
        engines = [
            PagedServingEngine(bank, EngineConfig(max_slots=1, max_len=16,
                                                  block_size=8)),
            ReferenceEngine(bank, EngineConfig(max_slots=1, max_len=16)),
            SpeculativeEngine(bank, EngineConfig(max_slots=1, max_len=16,
                                                 block_size=8, spec_k=2)),
        ]
        for eng in engines:
            assert isinstance(eng, Engine), type(eng).__name__

    def test_capabilities_are_structured(self):
        for cls in (PagedServingEngine, ReferenceEngine, SpeculativeEngine):
            caps = cls.capabilities()
            assert caps["engine"] == cls.__name__
            assert isinstance(caps["families"], list)
            assert isinstance(caps["features"], dict)
            json.dumps(caps)                      # serializable by contract
        assert PagedServingEngine.capabilities()["features"]["chunked_prefill"]
        assert not ReferenceEngine.capabilities()["features"]["chunked_prefill"]
        assert "int8" in PagedServingEngine.capabilities()["features"]["kv_dtype"]
        assert "ssm" in ReferenceEngine.capabilities()["families"]
        assert SpeculativeEngine.capabilities()["features"]["speculative"]

    def test_serving_engine_name_is_the_paged_engine(self):
        """``ServingEngine`` is only another name for the batched engine, so
        code that patches it (the benchmark's falsification tests) patches
        the engine that serves."""
        assert ServingEngine is PagedServingEngine

    @pytest.mark.parametrize("family", ["ssm", "hybrid", "encdec"])
    def test_batched_engine_refuses_families_without_kv_lengths(self, tiny,
                                                                family):
        """Families without a per-slot-length KV cache are served by
        ReferenceEngine; the batched engine refuses them at construction and
        names the engine to use."""
        cfg, params = tiny
        bank = ModelBank.single(dataclasses.replace(cfg, family=family),
                                params)
        with pytest.raises(ValueError, match="ReferenceEngine"):
            PagedServingEngine(bank, EngineConfig(max_slots=1, max_len=16,
                                                  block_size=8))

    def test_capability_table_renders(self):
        table = format_capability_table({
            "paged": PagedServingEngine, "reference": ReferenceEngine,
        })
        assert "paged" in table and "chunked_prefill" in table

    def test_reference_engine_steps(self, tiny):
        """ReferenceEngine gained step() (Engine protocol): stepping by hand
        reproduces run()."""
        cfg, params = tiny
        bank = ModelBank.single(cfg, params)
        a = ReferenceEngine(bank, EngineConfig(max_slots=1, max_len=16))
        a.submit([1, 2, 3], max_new_tokens=3)
        stepped = []
        while a.has_work:
            stepped.extend(a.step())
        b = ReferenceEngine(bank, EngineConfig(max_slots=1, max_len=16))
        b.submit([1, 2, 3], max_new_tokens=3)
        assert [r.out_tokens for r in stepped] == \
            [r.out_tokens for r in b.run()]


# ------------------------------------------------------- capability errors ---


class TestStructuredCapabilityErrors:
    """Satellite: EngineCapabilityError messages carry the structured
    capabilities() dict — which features are paged-only is data, not prose."""

    def test_reference_family_error_reports_capabilities(self, tiny):
        cfg, params = tiny
        ssm_cfg = dataclasses.replace(cfg, family="ssm")
        with pytest.raises(EngineCapabilityError) as ei:
            ReferenceEngine(ModelBank.single(ssm_cfg, params),
                            EngineConfig(kv_dtype="int8"))
        msg = str(ei.value)
        assert "'ssm'" in msg
        payload = json.loads(msg[msg.index("{"):])
        assert payload["engine"] == "ReferenceEngine"
        assert payload["features"]["kv_dtype"] == ["float32"]

    def test_spec_k_error_reports_capabilities(self, tiny):
        cfg, params = tiny
        with pytest.raises(EngineCapabilityError) as ei:
            PagedServingEngine(ModelBank.single(cfg, params),
                               EngineConfig(spec_k=4))
        assert '"speculative": false' in str(ei.value)

    def test_pressure_policy_needs_page_pool(self, tiny):
        cfg, params = tiny
        bank = ModelBank.single(cfg, params)
        with pytest.raises(EngineCapabilityError):
            ReferenceEngine(bank, EngineConfig(tier_policy="pressure"))
        # the paged engine accepts it
        PagedServingEngine(bank, EngineConfig(tier_policy="pressure",
                                              max_slots=1, max_len=16,
                                              block_size=8))

    def test_bad_tier_rejected_at_submit(self, tiny):
        cfg, params = tiny
        eng = PagedServingEngine(ModelBank.single(cfg, params),
                                 EngineConfig(max_slots=1, max_len=16,
                                              block_size=8))
        with pytest.raises(RequestRejected):
            eng.submit([1, 2], max_new_tokens=2, tier=5)

    def test_spec_engine_rejects_non_target_tiers(self, banks):
        bank = banks["dense"]
        eng = SpeculativeEngine(bank, EngineConfig(
            max_slots=1, max_len=16, block_size=8, spec_k=2,
        ))
        with pytest.raises(EngineCapabilityError):
            eng.submit([1, 2], max_new_tokens=2, tier=1)
        # out-of-range tiers reject like every other engine (protocol
        # contract: submit failures are RequestRejected, never a bare
        # ValueError)
        with pytest.raises(RequestRejected):
            eng.submit([1, 2], max_new_tokens=2, tier=7)
        # target tier (and None = default) both pass validation
        eng.submit([1, 2], max_new_tokens=2, tier=0)
        eng.submit([1, 2], max_new_tokens=2)


# -------------------------------------------------------- removed ctors ---


class TestRemovedCtors:
    def test_old_ctor_raises_and_message_names_bank(self, tiny):
        cfg, params = tiny
        with pytest.raises(TypeError, match="ModelBank"):
            PagedServingEngine(cfg, params, EngineConfig(max_slots=2,
                                                         max_len=32))
        # the replacement form serves fine
        new = PagedServingEngine(ModelBank.single(cfg, params),
                                 EngineConfig(max_slots=2, max_len=32))
        assert run_tokens(new, [[5, 7, 11], [3, 1]])

    def test_old_paged_and_spec_ctors_raise(self, tiny):
        cfg, params = tiny
        with pytest.raises(TypeError, match="ModelBank"):
            PagedServingEngine(cfg, params, EngineConfig(
                max_slots=1, max_len=16, block_size=8))
        with pytest.raises(TypeError, match="ModelBank"):
            SpeculativeEngine(cfg, params, params, EngineConfig(
                max_slots=1, max_len=16, block_size=8, spec_k=2))

    def test_misuse_raises_type_error(self, tiny):
        cfg, params = tiny
        bank = ModelBank.single(cfg, params)
        with pytest.raises(TypeError):
            PagedServingEngine(bank, params)          # weights after a bank
        with pytest.raises(TypeError):
            PagedServingEngine(cfg, EngineConfig())   # old form, no weights
        with pytest.raises(TypeError):
            PagedServingEngine(params)                # raw tree: no arch cfg

    def test_keyword_ecfg_accepted(self, tiny):
        """The documented call shape Engine(bank, ecfg=...) must work by
        keyword exactly as it does positionally (regression: the resolver
        used to mistake keyword ecfg for the deprecated third argument)."""
        cfg, params = tiny
        bank = ModelBank.single(cfg, params)
        eng = PagedServingEngine(bank, ecfg=EngineConfig(max_slots=1,
                                                         max_len=16))
        assert eng.ecfg.max_slots == 1
        spec = SpeculativeEngine(bank, ecfg=EngineConfig(
            max_slots=1, max_len=16, block_size=8, spec_k=2))
        assert spec.ecfg.spec_k == 2

    def test_spec_engine_rejects_pressure_policy(self, tiny):
        """Every spec slot is pinned to the target tier, so the pressure
        controller's downshift would be a silent no-op — reject loudly."""
        cfg, params = tiny
        with pytest.raises(EngineCapabilityError):
            SpeculativeEngine(ModelBank.single(cfg, params), EngineConfig(
                max_slots=1, max_len=16, block_size=8, spec_k=2,
                tier_policy="pressure"))


# -------------------------------------------------------- tier equivalence ---


class TestTierEquivalence:
    """Acceptance: one engine, >= 3 tiers in flight, each slot's greedy
    stream bitwise-identical to a fixed single-budget engine at that
    budget."""

    PROMPTS = [[5, 7, 11], [3, 1], [2, 9, 4, 6]]

    def _multi_vs_fixed(self, bank, ecfg_kw, max_new=4):
        eng = PagedServingEngine(bank, EngineConfig(**ecfg_kw))
        for i, p in enumerate(self.PROMPTS):
            eng.submit(p, max_new_tokens=max_new, tier=i)
        multi = {r.tier: r.out_tokens for r in eng.run()}
        assert len(multi) == len(bank) == 3
        for t in range(len(bank)):
            fixed = PagedServingEngine(
                ModelBank.single(bank.cfg, bank[t].model),
                EngineConfig(**ecfg_kw),
            )
            fixed.submit(self.PROMPTS[t], max_new_tokens=max_new)
            ref = fixed.run()[0].out_tokens
            assert multi[t] == ref, (t, multi[t], ref)
        return eng

    @pytest.mark.parametrize("fmt", ["dense", "factored", "bsr"])
    def test_pinned_tier_matches_fixed_budget_engine(self, banks, fmt):
        eng = self._multi_vs_fixed(
            banks[fmt], dict(max_slots=3, max_len=32, block_size=8)
        )
        # one compiled decode program per tier, never re-traced (dense tiers
        # share shapes, so they may share ONE trace; factored/bsr trace one
        # per live-rank signature)
        assert eng.decode_traces <= 3

    def test_equivalence_under_int8_kv(self, banks):
        self._multi_vs_fixed(
            banks["factored"],
            dict(max_slots=3, max_len=32, block_size=8, kv_dtype="int8"),
        )

    def test_equivalence_under_chunked_prefill(self, banks):
        eng = self._multi_vs_fixed(
            banks["factored"],
            dict(max_slots=3, max_len=64, block_size=8, prefill_chunk=8),
        )
        assert eng.chunk_calls > 0     # the chunk path actually ran

    def test_reference_engine_serves_tiers(self, banks):
        bank = banks["dense"]
        eng = ReferenceEngine(bank, EngineConfig(max_slots=2, max_len=16))
        eng.submit([5, 7, 11], max_new_tokens=2, tier=0)
        eng.submit([5, 7, 11], max_new_tokens=2, tier=2)
        by_tier = {r.tier: r.out_tokens for r in eng.run()}
        fixed = ReferenceEngine(ModelBank.single(bank.cfg, bank[2].model),
                                EngineConfig(max_slots=1, max_len=16))
        fixed.submit([5, 7, 11], max_new_tokens=2)
        assert by_tier[2] == fixed.run()[0].out_tokens

    def test_spec_engine_from_bank_matches_paged(self, banks):
        """Target/draft as two tiers of one bank: greedy speculative output
        == the non-speculative paged engine at the target tier."""
        bank = banks["dense"]
        ecfg_kw = dict(max_slots=2, max_len=32, block_size=8)
        ref = PagedServingEngine(bank, EngineConfig(**ecfg_kw))
        want = run_tokens(ref, self.PROMPTS[:2], max_new=5)
        spec = SpeculativeEngine(bank, EngineConfig(**ecfg_kw, spec_k=3))
        assert spec.draft_params is bank[-1].params
        assert spec.params is bank[0].params
        assert run_tokens(spec, self.PROMPTS[:2], max_new=5) == want


# ------------------------------------------------------ mid-stream switch ---


class TestTierSwitching:
    def test_downshift_mid_stream_no_retrace(self, banks):
        """Acceptance: switching a decoding slot's tier mid-stream re-uses
        the already-compiled program of the destination tier (no re-jit) and
        the shared paged KV (no migration) — the stream simply continues."""
        bank = banks["factored"]
        eng = PagedServingEngine(bank, EngineConfig(
            max_slots=2, max_len=64, block_size=8,
        ))
        # warm every tier's decode program with pinned short requests
        for t in range(len(bank)):
            eng.submit([1 + t, 2], max_new_tokens=2, tier=t)
        eng.run()
        traces = eng.decode_traces
        assert traces <= len(bank)

        eng.submit([5, 7, 11], max_new_tokens=12, tier=0)
        for _ in range(4):
            eng.step()
        assert eng.tier_switches == 0
        eng._tier_shift = 2            # what the pressure controller does
        done = eng.run()
        assert eng.tier_switches >= 1
        assert eng.decode_traces == traces     # NO recompilation on switch
        assert len(done) == 1 and len(done[0].out_tokens) == 12

    def test_pressure_controller_downshifts_before_evicting(self, banks):
        """A pool sized so three decoding requests squeeze it: the
        controller must observe pressure and downshift (cheaper tiers serve
        the tail) and every request still completes."""
        bank = banks["factored"]
        eng = PagedServingEngine(bank, EngineConfig(
            max_slots=3, max_len=64, block_size=8, num_blocks=9,
            tier_policy="pressure", tier_target_free=0.4, tier_gain=8.0,
            tier_ema=0.0,
        ))
        assert eng.tier_controller is not None
        for i in range(3):
            eng.submit([1 + i, 2, 3], max_new_tokens=10, tier=0)
        done = eng.run()
        assert len(done) == 3
        assert all(len(r.out_tokens) == 10 for r in done)
        assert eng.downshift_ticks > 0
        assert eng.tier_switches > 0
        assert eng.decode_traces <= len(bank)

    def test_static_policy_never_shifts(self, banks):
        bank = banks["factored"]
        eng = PagedServingEngine(bank, EngineConfig(
            max_slots=2, max_len=32, block_size=8,
        ))
        run_tokens(eng, [[1, 2, 3], [4, 5]], max_new=6)
        assert eng.tier_controller is None
        assert eng.downshift_ticks == 0 and eng.tier_switches == 0


class TestTierController:
    def test_integral_feedback(self):
        c = TierController(4, TierControllerConfig(
            target_free_frac=0.25, gain=4.0, ema=0.0))
        for _ in range(50):
            c.update(0.0)              # total pressure: shift to the floor
        assert c.shift == 3
        for _ in range(50):
            c.update(1.0)              # pressure cleared: shift decays away
        assert c.shift == 0

    def test_single_tier_never_shifts(self):
        c = TierController(1)
        for _ in range(20):
            assert c.update(0.0) == 0

    def test_bad_config_rejected(self):
        with pytest.raises(ValueError):
            TierController(0)
        with pytest.raises(ValueError):
            TierController(2, TierControllerConfig(target_free_frac=1.0))

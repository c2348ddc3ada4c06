"""Elastic self-speculative decoding tests.

Covers the PR 3 tentpole invariants: the k-wide paged verify path matches
sequential single-token decode, the k-query Pallas kernel matches its jnp
oracle, exact rejection sampling preserves the target distribution
(property-tested through the hypothesis shim), and the speculative engine
emits token streams IDENTICAL to the non-speculative paged engine under
greedy decoding — including with an adversarial (zero-acceptance) draft,
mid-stream admission, forced eviction, and int8 target pages.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # bare container: deterministic-grid shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import get_arch
from repro.kernels.ops import paged_attention, paged_attention_kquery
from repro.kernels.ref import paged_attention_kquery_ref
from repro.models import model as model_lib
from repro.models import transformer as transformer_lib
from repro.serving.elastic import ModelBank
from repro.serving.engine import (
    EngineCapabilityError,
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
    RequestRejected,
    ServingEngine,
)
from repro.serving.speculative import (
    SpecController,
    SpeculativeEngine,
    rejection_sample,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("salaad_llama_60m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    # an independently-initialized "draft": agrees with the target on
    # (essentially) nothing, so every accept/reject/rollback path is exercised
    adversarial = model_lib.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params, adversarial


# ------------------------------------------------------- k-wide verify path ---


class TestMultiTokenPagedVerify:
    """decode_step with (S, k) tokens against the paged cache must reproduce
    k sequential single-token decode steps: same logits (up to shape-dependent
    XLA fusion rounding), same greedy tokens, same cache lengths."""

    def _paged(self, cfg, params, prompts, S, bs, nb):
        bucket = 8
        toks = np.zeros((S, bucket), np.int32)
        lens = np.ones((S,), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p
            lens[i] = len(p)
        num_pages = S * nb
        paged = model_lib.init_paged_cache(cfg, S, num_pages, bs, nb, dtype=jnp.float32)
        _, kvs, _ = model_lib._forward(
            params, {"tokens": jnp.asarray(toks)}, cfg, collect_kv=True
        )
        table = np.full((S, nb), num_pages, np.int32)
        page_map = np.full((S, bucket // bs), num_pages, np.int32)
        nxt = 0
        for i, p in enumerate(prompts):
            for j in range(nb):
                table[i, j] = nxt
                if j < -(-len(p) // bs):
                    page_map[i, j] = nxt
                nxt += 1
        paged = paged._replace(block_table=jnp.asarray(table), length=jnp.asarray(lens))
        return transformer_lib.scatter_prefill_pages(paged, kvs, jnp.asarray(page_map))

    def test_kwide_matches_sequential(self, tiny):
        cfg, params, _ = tiny
        S, bs, nb, k = 3, 8, 4, 4
        prompts = [[5, 7, 11, 2, 9], [3, 1], [2, 9, 4, 6, 1, 8, 3]]
        vtoks = jnp.asarray([[9, 3, 7, 1], [4, 2, 8, 5], [7, 6, 1, 2]], jnp.int32)

        c_seq = self._paged(cfg, params, prompts, S, bs, nb)
        seq = []
        for j in range(k):
            lg, c_seq = model_lib.decode_step(params, vtoks[:, j : j + 1], c_seq, cfg)
            seq.append(np.asarray(lg[:, 0]))
        seq = np.stack(seq, axis=1)                        # (S, k, V)

        c_multi = self._paged(cfg, params, prompts, S, bs, nb)
        lg_multi, c_multi = model_lib.decode_step(params, vtoks, c_multi, cfg)
        lg_multi = np.asarray(lg_multi)

        np.testing.assert_allclose(lg_multi, seq, atol=1e-5, rtol=1e-5)
        assert np.array_equal(np.argmax(lg_multi, -1), np.argmax(seq, -1))
        assert np.array_equal(np.asarray(c_seq.length), np.asarray(c_multi.length))
        np.testing.assert_allclose(
            np.asarray(c_seq.k), np.asarray(c_multi.k), atol=1e-5
        )

    def test_writes_past_capacity_drop(self, tiny):
        """A k-window straddling the table's capacity must not clamp into a
        real page (that would corrupt another slot's block)."""
        cfg, params, _ = tiny
        S, bs, nb, k = 2, 4, 2, 4                          # capacity: 8 tokens
        prompts = [[5, 7, 11], [3, 1, 4]]
        cache = self._paged(cfg, params, prompts, S, bs, nb)
        before = np.asarray(cache.k).copy()
        # lengths (3, 3): writes hit positions 3..6; slot 0's page set is
        # pages {0, 1}, slot 1's {2, 3} — corruption would cross-write
        vtoks = jnp.asarray([[9, 3, 7, 1], [4, 2, 8, 5]], jnp.int32)
        _, cache = model_lib.decode_step(params, vtoks, cache, cfg)
        after = np.asarray(cache.k)
        # slot 0 wrote only pages 0/1 positions 3..6; pages 2/3 rows outside
        # slot 1's own writes are untouched (and vice versa): check prompt KV
        # of each slot survived bitwise
        for slot, plen in ((0, 3), (1, 3)):
            for pos in range(plen):
                page = slot * nb + pos // bs
                assert np.array_equal(
                    before[:, page, :, pos % bs], after[:, page, :, pos % bs]
                )


class TestKQueryKernel:
    def _pool(self, seed=0, b=3, hq=4, hkv=2, d=8, bs=4, nb=4, n=10, kq=3):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, hq, kq, d), jnp.float32)
        # stacked (L, n, ...) pools; the kernels read the last layer's pages
        kp = jnp.asarray(rng.randn(2, n, hkv, bs, d), jnp.float32)
        vp = jnp.asarray(rng.randn(2, n, hkv, bs, d), jnp.float32)
        bt = jnp.asarray([[0, 1, 6, n], [2, 7, n, n], [3, 4, 5, n]], jnp.int32)
        lengths = jnp.asarray([5, 0, 9], jnp.int32)
        return q, kp, vp, bt, lengths

    def test_pallas_matches_ref(self):
        q, kp, vp, bt, lengths = self._pool()
        out = paged_attention_kquery(q, kp, vp, bt, lengths, 1)
        ref = paged_attention_kquery_ref(q, kp, vp, bt, lengths, 1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_kq1_matches_single_query_kernel(self):
        q, kp, vp, bt, lengths = self._pool(kq=1)
        out = paged_attention_kquery(q, kp, vp, bt, lengths, 1)[:, :, 0]
        ref = paged_attention(q[:, :, 0], kp, vp, bt, lengths, 1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_per_query_causal_window(self):
        """Query i must see exactly one more key than query i-1: zero out the
        extra key's value and the two queries coincide."""
        q, kp, vp, bt, lengths = self._pool()
        ref = paged_attention_kquery_ref(q, kp, vp, bt, lengths, 1)
        # query 1 of slot 0 attends positions <= lengths[0] + 1 = 6; query 0
        # attends <= 5 — masking is enforced by construction in the oracle,
        # the kernel must agree even at ragged lengths incl. the empty slot
        out = paged_attention_kquery(q, kp, vp, bt, lengths, 1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        assert np.all(np.isfinite(np.asarray(out)))


# --------------------------------------------------------- rejection sampling ---


def _norm_rows(x):
    return x / np.sum(x, axis=-1, keepdims=True)


class TestRejectionSampling:
    @settings(max_examples=8)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_identical_dists_accept_all(self, seed):
        """draft == target => every draft token accepted, deterministically."""
        rng = np.random.RandomState(seed)
        s, k, v = 4, 5, 16
        p = jnp.asarray(_norm_rows(rng.rand(s, k, v) + 1e-3), jnp.float32)
        drafts = jnp.asarray(rng.randint(0, v, size=(s, k)), jnp.int32)
        out, a = rejection_sample(jax.random.PRNGKey(seed), drafts, p, p)
        assert np.all(np.asarray(a) == k)
        assert np.array_equal(np.asarray(out), np.asarray(drafts))

    @settings(max_examples=4)
    @given(st.floats(min_value=0.2, max_value=5.0),
           st.integers(min_value=0, max_value=100))
    def test_emitted_matches_target_distribution(self, sharpness, seed):
        """The first emitted token is exactly target-distributed regardless of
        how far the draft distribution is from the target (temperature > 0)."""
        rng = np.random.RandomState(seed)
        v, n = 5, 4096
        q_row = _norm_rows(rng.rand(v) ** sharpness + 1e-3)
        p_row = _norm_rows(rng.rand(v) ** (1.0 / sharpness) + 1e-3)
        q = jnp.asarray(np.tile(q_row, (n, 1, 1)), jnp.float32)   # (n, 1, v)
        p = jnp.asarray(np.tile(p_row, (n, 1, 1)), jnp.float32)
        # drafts ~ p per row (the scheme's precondition)
        drafts = jax.random.categorical(
            jax.random.PRNGKey(seed + 1), jnp.log(p[:, 0]), axis=-1
        )[:, None].astype(jnp.int32)
        out, _ = rejection_sample(jax.random.PRNGKey(seed + 2), drafts, p, q)
        emitted = np.asarray(out[:, 0])
        freq = np.bincount(emitted, minlength=v) / n
        # 4096 draws: ~3.5 sigma of a p=0.5 bernoulli frequency is ~0.027
        np.testing.assert_allclose(freq, q_row, atol=0.05)

    def test_prefix_structure(self):
        """out[:, :a] are the drafts verbatim; position a is the corrective."""
        rng = np.random.RandomState(3)
        s, k, v = 8, 4, 12
        p = jnp.asarray(_norm_rows(rng.rand(s, k, v) + 1e-3), jnp.float32)
        q = jnp.asarray(_norm_rows(rng.rand(s, k, v) + 1e-3), jnp.float32)
        drafts = jnp.asarray(rng.randint(0, v, size=(s, k)), jnp.int32)
        out, a = rejection_sample(jax.random.PRNGKey(0), drafts, p, q)
        out, a, d = np.asarray(out), np.asarray(a), np.asarray(drafts)
        for i in range(s):
            assert np.array_equal(out[i, : a[i]], d[i, : a[i]])
            assert 0 <= a[i] <= k


# ----------------------------------------------------------------- engine ---


class TestSpeculativeEngine:
    PROMPTS = [[5, 7, 11], [3, 1], [2, 9, 4, 6], [8, 8, 2], [1, 2, 3, 4, 5, 6], [9, 1]]

    def _tokens(self, engine, max_new=5):
        for p in self.PROMPTS:
            engine.submit(p, max_new_tokens=max_new)
        return {r.uid: r.out_tokens for r in engine.run()}

    def _spec(self, cfg, params, draft, **kw):
        base = dict(max_slots=2, max_len=32, block_size=8, spec_k=4)
        base.update(kw)
        return SpeculativeEngine(ModelBank(cfg, [params, draft]), EngineConfig(**base))

    @pytest.mark.parametrize("mode", ["parallel", "sequential"])
    def test_greedy_identical_draft_matches_paged(self, tiny, mode):
        """Acceptance criterion: greedy spec == greedy non-spec, token for
        token, across mid-stream admissions (6 requests over 2 slots) — under
        BOTH draft schedules."""
        cfg, params, _ = tiny
        ref = self._tokens(PagedServingEngine(
            ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32, block_size=8)
        ))
        eng = self._spec(cfg, params, params, spec_draft_mode=mode)
        got = self._tokens(eng)
        assert got == ref
        if mode == "sequential":
            # identical draft + sequential proposals: every token accepted,
            # so device round trips collapse by ~k
            assert eng.acceptance_rate == 1.0
            total = sum(len(t) for t in got.values())
            assert eng.decode_calls < total / 2

    @pytest.mark.parametrize("mode", ["parallel", "sequential"])
    def test_greedy_adversarial_draft_still_exact(self, tiny, mode):
        """A draft that agrees with the target on ~nothing costs throughput,
        never correctness: every tick rolls back and emits the target's own
        greedy token."""
        cfg, params, adversarial = tiny
        ref = self._tokens(PagedServingEngine(
            ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32, block_size=8)
        ))
        eng = self._spec(cfg, params, adversarial, spec_draft_mode=mode)
        got = self._tokens(eng)
        assert got == ref
        assert eng.acceptance_rate < 0.3

    @pytest.mark.parametrize("mode", ["parallel", "sequential"])
    def test_one_spec_trace_per_k(self, tiny, mode):
        """The whole tick (draft + verify + accept + rollback) is ONE jitted
        program, compiled once per distinct k."""
        cfg, params, _ = tiny
        eng = self._spec(cfg, params, params, spec_draft_mode=mode)
        got = self._tokens(eng)
        total = sum(len(t) for t in got.values())
        assert eng.decode_traces == 1
        assert eng.decode_calls < total

    @pytest.mark.parametrize("policy", ["longest_remaining", "lru"])
    def test_eviction_preserves_tokens(self, tiny, policy):
        """Pool pressure under speculation: eviction + re-prefill resume (of
        BOTH caches) reproduces the non-speculative streams exactly."""
        cfg, params, adversarial = tiny
        prompts = [[5, 7, 11], [3, 1, 4]]
        e_ref = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=16, block_size=4
        ))
        for p in prompts:
            e_ref.submit(p, max_new_tokens=10)
        ref = {r.uid: r.out_tokens for r in e_ref.run()}

        eng = SpeculativeEngine(ModelBank(cfg, [params, adversarial]), EngineConfig(
            max_slots=2, max_len=16, block_size=4, num_blocks=4,
            decode_reserve=1, evict_policy=policy, spec_k=3,
        ))
        for p in prompts:
            eng.submit(p, max_new_tokens=10)
        got = {r.uid: r.out_tokens for r in eng.run()}
        assert eng.evictions >= 1, "pool was sized to force an eviction"
        assert got == ref
        assert eng.allocator.used_blocks == 0

    def test_int8_target_pages(self, tiny):
        """Quantized target pages + speculation: the k-wide quantized insert
        must match the baseline int8 paged engine token-for-token."""
        cfg, params, _ = tiny
        ref = self._tokens(PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=2, max_len=32, block_size=8, kv_dtype="int8"
        )))
        eng = self._spec(cfg, params, params, kv_dtype="int8")
        assert eng.cache.k.dtype == jnp.int8
        got = self._tokens(eng)
        assert got == ref

    def test_pallas_kquery_through_engine(self, tiny):
        """kernel_impl='pallas' routes the k-wide verify through the k-query
        kernel (interpret mode) and emits the same tokens as the jnp gather."""
        import dataclasses

        cfg, params, adversarial = tiny
        out = {}
        for impl in ("dense", "pallas"):
            c = dataclasses.replace(cfg, kernel_impl=impl)
            eng = self._spec(c, params, adversarial, spec_k=3)
            eng.submit([5, 7, 11], max_new_tokens=4)
            eng.submit([3, 1], max_new_tokens=4)
            out[impl] = {r.uid: r.out_tokens for r in eng.run()}
        assert out["dense"] == out["pallas"]

    def test_sampled_decode_completes(self, tiny):
        """temperature > 0 runs the rejection-sampling path end to end and
        emits exactly max_new tokens per request."""
        cfg, params, adversarial = tiny
        eng = self._spec(cfg, params, adversarial, greedy=False, temperature=1.0)
        done = self._tokens(eng, max_new=6)
        assert all(len(t) == 6 for t in done.values())
        assert 0.0 <= eng.acceptance_rate <= 1.0

    def test_adaptive_k_shrinks_on_rejection(self, tiny):
        """The integral controller pulls the draft window down when the draft
        is useless — and holds it at max when the draft is the target."""
        cfg, params, adversarial = tiny
        bad = self._spec(cfg, params, adversarial, max_len=64, spec_k=6,
                         spec_adaptive=True)
        bad.submit(list(range(1, 5)), max_new_tokens=40)
        bad.run()
        assert bad._k < 6

        good = self._spec(cfg, params, params, max_len=64, spec_k=4,
                          spec_adaptive=True, spec_draft_mode="sequential")
        good.submit(list(range(1, 5)), max_new_tokens=24)
        good.run()
        assert good._k == 4

    def test_rejects_spec_k_zero(self, tiny):
        cfg, params, _ = tiny
        with pytest.raises(ValueError):
            SpeculativeEngine(ModelBank(cfg, [params, params]), EngineConfig(spec_k=0))

    def test_k1_auto_routes_to_sequential(self, tiny):
        """A k=1 parallel window has no verifiable guess (two forwards per
        emitted token): auto mode falls back to sequential, explicit parallel
        is rejected."""
        cfg, params, _ = tiny
        eng = self._spec(cfg, params, params, spec_k=1)
        assert not eng._parallel
        with pytest.raises(ValueError):
            self._spec(cfg, params, params, spec_k=1,
                       spec_draft_mode="parallel")


class TestSpecController:
    def test_integral_feedback(self):
        c = SpecController(k_init=4, k_max=8)
        for _ in range(50):
            c.update(1.0)              # perfect acceptance: window grows
        assert c.k == 8
        for _ in range(50):
            c.update(0.0)              # zero acceptance: window collapses
        assert c.k == 1

    def test_parallel_floor_avoids_latch(self, tiny):
        """The parallel schedule keeps k >= 2: a k=1 window carries no
        verifiable guess, so its acceptance signal would read 0 forever and
        the controller could never grow the window back."""
        c = SpecController(k_init=6, k_max=6, k_min=2)
        for _ in range(50):
            c.update(0.0)
        assert c.k == 2
        cfg, params, adversarial = tiny
        eng = SpeculativeEngine(ModelBank(cfg, [params, adversarial]), EngineConfig(
            max_slots=2, max_len=64, block_size=8, spec_k=6, spec_adaptive=True
        ))
        assert eng._parallel and eng.controller.k_min == 2
        eng.submit(list(range(1, 5)), max_new_tokens=30)
        eng.run()
        assert eng._k >= 2


# ------------------------------------------------- PRNG + capability errors ---


class TestPerSlotPRNG:
    def test_slot_id_keys_streams(self, tiny):
        """Same logits + same slot id => same sample; different slot ids =>
        independent streams (and the greedy path ignores slots entirely)."""
        cfg, params, _ = tiny
        eng = ServingEngine(ModelBank.single(cfg, params), EngineConfig(
            max_slots=4, max_len=32, greedy=False, temperature=1.0
        ))
        logits = jnp.tile(
            jax.random.normal(jax.random.PRNGKey(0), (1, cfg.vocab_size)), (4, 1)
        )
        step = jnp.asarray(3, jnp.int32)
        same = eng._sample(logits, step, salt=0, slots=jnp.asarray([2, 2, 2, 2]))
        assert len(set(np.asarray(same).tolist())) == 1
        mixed = eng._sample(logits, step, salt=0, slots=jnp.asarray([0, 1, 2, 3]))
        assert len(set(np.asarray(mixed).tolist())) > 1
        # row order must not matter — only the slot id does
        perm = eng._sample(logits, step, salt=0, slots=jnp.asarray([3, 2, 1, 0]))
        assert np.asarray(mixed).tolist() == np.asarray(perm)[::-1].tolist()

    def test_greedy_untouched(self, tiny):
        cfg, params, _ = tiny
        eng = ServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32))
        logits = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.vocab_size))
        out = eng._sample(logits, jnp.asarray(0), salt=0)
        assert np.array_equal(np.asarray(out), np.asarray(jnp.argmax(logits, -1)))


class TestReferenceEngineCapabilities:
    def test_paged_only_features_rejected(self, tiny):
        cfg, params, _ = tiny
        with pytest.raises(EngineCapabilityError):
            ReferenceEngine(ModelBank.single(cfg, params), EngineConfig(kv_dtype="int8"))
        with pytest.raises(EngineCapabilityError):
            ReferenceEngine(ModelBank.single(cfg, params), EngineConfig(spec_k=4))

    def test_non_speculative_engines_reject_spec_k(self, tiny):
        """spec_k must never be silently ignored: only SpeculativeEngine
        consumes it, every other engine fails loudly."""
        cfg, params, _ = tiny
        for cls in (ServingEngine, PagedServingEngine):
            with pytest.raises(EngineCapabilityError):
                cls(ModelBank.single(cfg, params), EngineConfig(max_slots=2, spec_k=4))

    def test_capability_error_is_request_rejected(self):
        """One error path for callers: capability errors reject like requests."""
        assert issubclass(EngineCapabilityError, RequestRejected)

    def test_plain_reference_engine_still_serves(self, tiny):
        cfg, params, _ = tiny
        eng = ReferenceEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=1, max_len=16))
        eng.submit([1, 2, 3], max_new_tokens=2)
        assert len(eng.run()) == 1

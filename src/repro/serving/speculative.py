"""Elastic self-speculative decoding on the paged serving engine.

SALAAD's elasticity claim — one training run yields a continuous spectrum of
deployable capacities (HPA, §4.3) — means every deployment already ships its
own draft model: a low-HPA-budget truncation of the SAME SLR weights. This
module turns that spectrum into decode throughput. A cheap draft proposes
``k`` tokens per slot per tick; the full-budget target model scores all ``k``
positions of all active slots in ONE k-wide paged verify pass; exact
(rejection-sampled) acceptance keeps the emitted distribution identical to
the target model's own sampling. The entire tick — k draft decode steps, the
k-wide verify, acceptance, and KV rollback — is ONE jitted device program, so
an accepted burst of k tokens costs the same host/device round-trip budget as
a single non-speculative decode step.

Two draft schedules share the verify/rollback machinery (per tick, context
length n, last emitted token ``t_last``):

``parallel`` (greedy default) — both models run ONE k-wide forward over the
same guess window, so a tick costs ~2 forwards regardless of k:

  window:  [t_last, g_1, .., g_{k-1}] — g_i are the draft's predictions from
           the PREVIOUS tick (zeros on a fresh slot; they warm up in one tick)
  verify:  target forward over the window -> greedy chain t_0..t_{k-1};
           guess g_i is confirmed iff g_i == t_{i-1} (prefix-cumulative, so
           every confirmed token is conditioned on real context only);
           emitted = confirmed guesses + t_a (the target's own next token) —
           between 1 and k tokens from one target forward
  draft:   draft forward over the SAME window -> prediction chain d_0..d_{k-1};
           the host re-aligns it as next tick's guesses
  This is Jacobi-style lookahead with the elastic low-budget deployment as
  the guess generator: the draft's agreement with the target is exactly what
  makes guesses survive verification.

``sequential`` (sampled default) — the draft autoregresses k proposals
(k single-token decodes inlined in the same program), the target verifies
the proposal window k-wide, and exact rejection sampling
(:func:`rejection_sample`) preserves the target distribution token-for-token.

Either way, target KV for the k window positions lands at n..n+k-1 of the
target pools, draft KV in the draft pools, and both caches share ONE block
table + length vector — so rollback is a per-slot length reset to
n + emitted, and rejected positions are simply overwritten by the next
tick's k-wide insert (which starts exactly at the new length).

The draft KV lives in its own (smaller, ``spec_draft_kv_dtype``) page pools
but shares the target's block table and allocator, so admission, page growth,
eviction, and resume are inherited from :class:`PagedServingEngine`
unchanged — one allocation covers both caches.

Acceptance-rate feedback (``spec_adaptive``) reuses the integral-controller
style of ``core/controller.py``: the draft window k integrates the tracking
error between observed per-slot acceptance and a target rate, clamped to
[1, spec_k]. k is a static shape, so adaptation retraces at most
``spec_k`` distinct programs over an engine's lifetime.
"""
from __future__ import annotations

import json
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..models import model as model_lib
from ..models import transformer as transformer_lib
from .deployed import DeployedModel
from .elastic import ModelBank
from .engine import (
    EngineCapabilityError,
    EngineConfig,
    PagedServingEngine,
    Request,
    RequestRejected,
)

__all__ = [
    "SpeculativeEngine",
    "SpecController",
    "SpecControllerConfig",
    "rejection_sample",
]

_DRAFT_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
}

_SLOT_EMA = 0.8   # per-slot acceptance smoothing (feeds the k controller)


def _copy_draft_pools(pools, src, dst):
    """Copy-on-write for the draft page pools: draft KV rides the target's
    block table AND page ids, so the same (src, dst) pairs that privatized a
    shared target page must privatize its draft twin — payload and, when the
    draft pool is int8, both scale pools."""
    k, v, k_s, v_s = pools
    k = ops.page_copy(k, src, dst)
    v = ops.page_copy(v, src, dst)
    if k_s is not None:
        k_s = ops.page_copy(k_s, src, dst)
        v_s = ops.page_copy(v_s, src, dst)
    return (k, v, k_s, v_s)


# ------------------------------------------------------- rejection sampling ---


def rejection_sample(
    key: jax.Array,
    drafts: jax.Array,        # (S, k) int32 — draft proposals d_1..d_k
    draft_probs: jax.Array,   # (S, k, V) — p_i: draft dist at each position
    target_probs: jax.Array,  # (S, k, V) — q_i: target dist at each position
) -> tuple[jax.Array, jax.Array]:
    """Exact speculative rejection sampling (Leviathan et al. '23 scheme).

    Position i accepts d_{i+1} with probability min(1, q_i(d)/p_i(d)); the
    first rejected position resamples from the residual norm(max(q_i - p_i,
    0)), which makes every emitted token exactly target-distributed. Returns
    ``(out, accepted)``: ``out[:, :a]`` are the accepted drafts, ``out[:, a]``
    the corrective token when ``a < k``; entries past that are padding. Each
    slot consumes its own PRNG stream (slot id folded into the key), so
    per-slot acceptance never correlates across the batch.

    When p == q the ratio is 1 and u < 1 always accepts — identical draft and
    target models accept all k tokens deterministically.
    """
    s, k = drafts.shape
    p_tok = jnp.take_along_axis(draft_probs, drafts[..., None], axis=-1)[..., 0]
    q_tok = jnp.take_along_axis(target_probs, drafts[..., None], axis=-1)[..., 0]
    ku, kr = jax.random.split(key)
    slot_ids = jnp.arange(s)
    u = jax.vmap(
        lambda i: jax.random.uniform(jax.random.fold_in(ku, i), (k,))
    )(slot_ids)
    accept = u < jnp.minimum(q_tok / jnp.maximum(p_tok, 1e-30), 1.0)
    acc = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    a = jnp.sum(acc, axis=1)                                   # (S,) in 0..k

    # residual distribution at the first rejected position (clamped index is
    # only read when a < k); p == q everywhere degenerates the residual to 0 —
    # fall back to q itself (any sample there is already target-distributed)
    ai = jnp.minimum(a, k - 1)
    q_a = jnp.take_along_axis(target_probs, ai[:, None, None], axis=1)[:, 0]
    p_a = jnp.take_along_axis(draft_probs, ai[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(q_a - p_a, 0.0)
    tot = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(tot > 0, resid / jnp.where(tot > 0, tot, 1.0), q_a)
    corrective = jax.vmap(
        lambda i, pr: jax.random.categorical(
            jax.random.fold_in(kr, i), jnp.log(jnp.maximum(pr, 1e-38))
        )
    )(slot_ids, resid)

    idx = jnp.arange(k)[None, :]
    out = jnp.where(
        idx < a[:, None],
        drafts,
        jnp.where(idx == a[:, None], corrective[:, None].astype(drafts.dtype), 0),
    )
    return out.astype(jnp.int32), a.astype(jnp.int32)


# --------------------------------------------------------------- controller ---


@dataclass(frozen=True)
class SpecControllerConfig:
    target_accept: float = 0.7  # per-draft-token acceptance the window aims at
    gain: float = 2.0           # integral gain: tokens of k per unit error
    ema: float = 0.8            # smoothing of the observed acceptance rate


class SpecController:
    """I-controller over the draft window k (``core/controller.py`` style).

    Integrates the tracking error between the observed (EMA-smoothed)
    acceptance rate and the target:  k_f <- clip(k_f + gain * (acc - target),
    1, k_max).  High acceptance grows the window (each verify amortizes more
    tokens); low acceptance shrinks it (rejected drafts are wasted draft AND
    verify compute). The float state is quantized to an int k at read time so
    the engine compiles at most ``k_max`` distinct programs.
    """

    def __init__(self, k_init: int, k_max: int, k_min: int = 1,
                 cfg: SpecControllerConfig = SpecControllerConfig()):
        self.cfg = cfg
        self.k_min = int(k_min)     # parallel schedule floors at 2: a k=1
        #                             window has no verifiable guess, so the
        #                             acceptance signal would latch at 0
        self.k_max = int(k_max)
        self.k_f = float(k_init)
        self.accept_ema = cfg.target_accept     # neutral start: no transient

    @property
    def k(self) -> int:
        return int(round(self.k_f))

    def update(self, accept_rate: float) -> int:
        c = self.cfg
        self.accept_ema = c.ema * self.accept_ema + (1.0 - c.ema) * accept_rate
        self.k_f = float(
            np.clip(self.k_f + c.gain * (self.accept_ema - c.target_accept),
                    self.k_min, self.k_max)
        )
        return self.k


# -------------------------------------------------------------------- engine ---


class SpeculativeEngine(PagedServingEngine):
    """Paged engine with elastic self-speculation: a low-budget draft of the
    same SLR weights proposes k tokens per slot, the full-budget target
    verifies them all in one jitted k-wide paged step.

    The draft/target pair is TWO TIERS of one :class:`~repro.serving.elastic.
    ModelBank` — the elastic spectrum's two ends: ``ecfg.spec_target_tier``
    (default 0, the largest capacity) verifies, ``ecfg.spec_draft_tier``
    (default -1, the cheapest) drafts. Both tiers share the architecture
    config, so the draft KV pages have identical geometry and ride the
    target's block table. Greedy decoding emits token streams identical to
    the non-speculative paged engine; sampled decoding preserves the target
    distribution exactly via :func:`rejection_sample`.
    """

    _speculative = True

    def __init__(self, model, params=None, draft_params=None,
                 ecfg: EngineConfig | None = None):
        if isinstance(model, (ModelBank, DeployedModel)):
            if draft_params is not None or (
                params is not None and ecfg is not None
            ):
                raise TypeError(
                    "SpeculativeEngine(bank, ecfg): the draft comes from the "
                    "bank (ecfg.spec_draft_tier), not a separate argument"
                )
            cfg_arg = params if params is not None else ecfg
            if cfg_arg is not None and not isinstance(cfg_arg, EngineConfig):
                raise TypeError(
                    "SpeculativeEngine(bank, ecfg): second argument must be "
                    f"an EngineConfig, got {type(cfg_arg).__name__}"
                )
            bank = model if isinstance(model, ModelBank) \
                else ModelBank.single(model.cfg, model)
            ecfg = cfg_arg if cfg_arg is not None else EngineConfig()
        else:
            raise TypeError(
                "SpeculativeEngine(arch_cfg, target_params, draft_params, "
                "ecfg) was removed: build a ModelBank (serving/elastic.py) "
                "whose tiers carry the target and draft budgets and "
                "construct SpeculativeEngine(bank, ecfg)"
            )
        if ecfg.spec_k < 1:
            raise ValueError(
                f"SpeculativeEngine needs spec_k >= 1, got {ecfg.spec_k}"
            )
        if ecfg.tier_policy == "pressure":
            # every slot is pinned to the target tier (_effective_tier), so
            # the inherited controller's downshift would be a silent no-op —
            # fail loudly instead of reporting downshifts that never happen
            raise EngineCapabilityError(
                "SpeculativeEngine serves every slot at its target tier; the "
                "page-pressure tier controller (tier_policy='pressure') "
                "needs PagedServingEngine. Engine capabilities: "
                f"{json.dumps(self.capabilities(), sort_keys=True)}"
            )
        greedy = ecfg.greedy or ecfg.temperature <= 0
        if ecfg.spec_draft_mode == "auto":
            # a k=1 parallel window carries no verifiable guess (two forwards
            # per tick to emit one token) — degenerate; sequential k=1 at
            # least verifies one real proposal
            self._parallel = greedy and ecfg.spec_k >= 2
        elif ecfg.spec_draft_mode in ("parallel", "sequential"):
            self._parallel = ecfg.spec_draft_mode == "parallel"
        else:
            raise ValueError(
                f"unknown spec_draft_mode {ecfg.spec_draft_mode!r}"
            )
        if self._parallel and not greedy:
            raise ValueError(
                "the parallel draft schedule verifies greedy guess chains; "
                "temperature sampling needs spec_draft_mode='sequential' "
                "(exact rejection sampling over autoregressive proposals)"
            )
        if self._parallel and ecfg.spec_k < 2:
            raise ValueError(
                "the parallel draft schedule needs spec_k >= 2 (a k=1 window "
                "has no verifiable guess); use spec_draft_mode='sequential'"
            )
        super().__init__(bank, ecfg)
        try:
            self._target_tier = bank.resolve(ecfg.spec_target_tier)
            self._draft_tier = bank.resolve(ecfg.spec_draft_tier)
        except ValueError as e:
            raise ValueError(f"spec tier: {e}") from None
        # every slot serves at the target tier; the bank's cheap end drafts
        self._default_tier = self._target_tier
        self.params = self._tier_params[self._target_tier]
        self.draft_params = self._tier_params[self._draft_tier]

        quantized = ecfg.spec_draft_kv_dtype == "int8"
        if not quantized and ecfg.spec_draft_kv_dtype not in _DRAFT_DTYPES:
            raise ValueError(
                f"unknown spec_draft_kv_dtype {ecfg.spec_draft_kv_dtype!r}"
            )
        dcache = model_lib.init_paged_cache(
            self.cfg, ecfg.max_slots, self.num_blocks, self._bs, self._nb_slot,
            dtype=jnp.float32 if quantized
            else _DRAFT_DTYPES[ecfg.spec_draft_kv_dtype],
            quantized=quantized,
        )
        # draft pools share the target's block table + lengths; only the
        # payload (and scale) pools persist host-side between ticks
        dpools = (dcache.k, dcache.v, dcache.k_scale, dcache.v_scale)
        if self.mesh is not None:
            # draft pools shard the head axis over 'model' exactly like the
            # target's pools (same block ids, head-replicated bookkeeping)
            dpools = jax.device_put(dpools, self.mesh.cache_shardings(dpools))
        self._dpools = dpools

        self._k = ecfg.spec_k
        self._write_window = self._k          # _pre_decode covers k positions
        self.controller = (
            SpecController(
                k_init=ecfg.spec_k, k_max=ecfg.spec_k,
                k_min=2 if self._parallel and ecfg.spec_k >= 2 else 1,
            )
            if ecfg.spec_adaptive else None
        )

        # acceptance accounting (benchmarks + adaptive feedback): drafted /
        # accepted live in the metrics registry (see the properties below);
        # the ADAPTIVE feedback reads only the plain host-side _accept_ema,
        # so telemetry=False never changes scheduling behavior
        self.spec_ticks = 0
        self._accept_ema = np.full((ecfg.max_slots,), np.nan)
        # parallel schedule: per-slot guess window for the NEXT tick (host
        # mirror of the draft's latest prediction chain; zeros = no guess)
        self._guess = np.zeros((ecfg.max_slots, max(ecfg.spec_k - 1, 0)), np.int32)

        self._spec = jax.jit(
            self._spec_seq_fn, static_argnames=("k",), donate_argnums=(3, 4),
        )
        self._spec_par = jax.jit(
            self._spec_parallel_fn, donate_argnums=(3, 4),
        )
        self._prefill2 = jax.jit(self._prefill2_fn, donate_argnums=(6, 7))
        self._chunk2 = jax.jit(self._chunk2_fn, donate_argnums=(6, 7))
        self._dcopy = jax.jit(_copy_draft_pools, donate_argnums=(0,))

    @classmethod
    def capabilities(cls) -> dict:
        caps = PagedServingEngine.capabilities.__func__(cls)
        caps["kv"] = "paged + draft pools"
        caps["features"].update(
            speculative=True,
            # the bank's tiers ARE in play — as the fixed target/draft pair —
            # but per-REQUEST tier pinning and the pressure controller are
            # not: every slot verifies at spec_target_tier
            elastic_tiers=False,
            tier_pressure_controller=False,
            multi_tenant_adapters=False,
        )
        return caps

    def _resolve_tier(self, tier: int | None) -> int:
        """Every slot serves at the verify target's tier; a request pinned
        elsewhere would silently verify at the wrong capacity — fail loudly
        instead (the 'never silently drop a requested feature' convention).
        Like every engine, submit-time tier errors are RequestRejected."""
        if tier is None:
            return self._target_tier
        try:
            t = self.bank.resolve(tier)
        except ValueError as e:
            raise RequestRejected(str(e)) from None
        if t == self._target_tier:
            return t
        raise EngineCapabilityError(
            f"SpeculativeEngine serves every slot at its target tier "
            f"{self._target_tier} (spec_target_tier); per-request tiers need "
            f"PagedServingEngine. Requested tier: {tier}"
        )

    def _effective_tier(self, req: Request) -> int:
        return self._target_tier

    # ------------------------------------------------------------- metrics ---

    @property
    def drafted_tokens(self) -> int:
        """Draft proposals offered to the verifier, lifetime (registry-backed
        view over serve_spec_tokens_total{kind="drafted"})."""
        return int(self.metrics.counter_value(self.metrics.spec_tokens,
                                              "drafted"))

    @property
    def accepted_tokens(self) -> int:
        """Draft proposals the verifier accepted, lifetime."""
        return int(self.metrics.counter_value(self.metrics.spec_tokens,
                                              "accepted"))

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens accepted by the verifier, lifetime."""
        return self.accepted_tokens / max(self.drafted_tokens, 1)

    @property
    def slot_acceptance(self) -> np.ndarray:
        """Per-slot EMA acceptance rate (nan = slot never speculated)."""
        return self._accept_ema.copy()

    # ----------------------------------------------------- device programs ---

    def _prefill2_fn(self, tparams, dparams, tokens, lengths, slot_ids,
                     page_map, cache, dpools, step):
        """Admission prefill for BOTH caches in one program: the prompt runs
        through the target (yielding the first sampled token, exactly like
        the non-speculative engine) and through the draft, each scattering
        whole prompt blocks into its own page pools."""
        self.prefill_traces += 1
        logits, kvs, _ = model_lib._forward(
            tparams, {"tokens": tokens}, self.cfg, collect_kv=True
        )
        cache = transformer_lib.scatter_prefill_pages(cache, kvs, page_map)
        new_len = cache.length.at[slot_ids].set(lengths, mode="drop")
        last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)
        first_tok = self._sample(last[:, 0], step, salt=1, slots=slot_ids)
        cache = cache._replace(length=new_len)

        _, dkvs, _ = model_lib._forward(
            dparams, {"tokens": tokens}, self.cfg, collect_kv=True
        )
        dcache = transformer_lib.PagedKVCache(
            dpools[0], dpools[1], cache.block_table, new_len,
            dpools[2], dpools[3],
        )
        dcache = transformer_lib.scatter_prefill_pages(dcache, dkvs, page_map)
        return first_tok, cache, (dcache.k, dcache.v, dcache.k_scale, dcache.v_scale)

    def _chunk2_fn(self, tparams, dparams, tokens, counts, slot_ids, starts,
                   cache, dpools, step):
        """Chunked-prefill tick for BOTH caches in one program: the target
        side is the base engine's ``_chunk_target`` verbatim (sampling the
        next token where a prompt ends, exactly like the one-shot
        ``_prefill2_fn``), then the draft runs the same chunk at the SAME
        pre-chunk lengths through the shared block table, so the two caches
        stay position-aligned chunk by chunk."""
        self.chunk_traces += 1
        tok, cache, n0 = self._chunk_target(
            tparams, tokens, counts, slot_ids, starts, cache, step
        )
        dcache = transformer_lib.PagedKVCache(
            dpools[0], dpools[1], cache.block_table, n0, dpools[2], dpools[3]
        )
        _, dcache = model_lib.chunk_prefill_step(
            dparams, tokens, counts, dcache, self.cfg
        )
        return tok, cache, (dcache.k, dcache.v, dcache.k_scale, dcache.v_scale)

    def _spec_parallel_fn(self, tparams, dparams, window, cache, dpools,
                          active, step):
        """ONE parallel-schedule tick: a k-wide target verify of the guess
        window plus a k-wide draft forward that produces next tick's guesses —
        ~2 forwards per tick however large k is. Greedy only (the emitted
        chain is the target's own argmax chain by construction). Returns
        (out (S, k), guesses (S, k), emitted (S,), confirmed (S,), cache,
        dpools)."""
        self.decode_traces += 1
        k = window.shape[1]
        n0 = cache.length
        dcache = transformer_lib.PagedKVCache(
            dpools[0], dpools[1], cache.block_table, n0, dpools[2], dpools[3]
        )

        # target verify over [t_last, g_1..g_{k-1}]: position i's greedy token
        # t_i is the target's prediction for position n+i+1
        logits, cache = model_lib.decode_step(tparams, window, cache, self.cfg)
        t_chain = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (S, k)
        # guess g_{i+1} is confirmed iff it equals t_i AND every earlier guess
        # was confirmed — so each confirmed token saw only real context
        conf = (window[:, 1:] == t_chain[:, :-1]).astype(jnp.int32)  # (S, k-1)
        a = jnp.sum(jnp.cumprod(conf, axis=1), axis=1) if k > 1 else \
            jnp.zeros((window.shape[0],), jnp.int32)
        # emitted = confirmed guesses + the target's own next token t_a; the
        # confirmed guesses ARE t_0..t_{a-1}, so the output is just t_chain
        m = jnp.where(active, a + 1, 0).astype(jnp.int32)

        # draft forward over the REFINED window [t_last, t_0..t_{k-2}]: the
        # verify chain is real for all confirmed positions, so the draft's
        # prediction d_m (the first guess the next tick needs) is conditioned
        # on the full accepted prefix including the corrective token t_a
        d_window = jnp.concatenate([window[:, :1], t_chain[:, :-1]], axis=1)
        dlogits, dcache = model_lib.decode_step(dparams, d_window, dcache, self.cfg)
        d_chain = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)     # (S, k)

        cache = cache._replace(length=n0 + m)       # rollback = length reset
        return t_chain, d_chain, m, a, cache, (
            dcache.k, dcache.v, dcache.k_scale, dcache.v_scale
        )

    def _spec_seq_fn(self, tparams, dparams, tokens, cache, dpools, active, step, *, k):
        """ONE sequential-schedule tick on device: k draft steps, one k-wide
        verify, acceptance, rollback. Returns (out (S, k), emitted (S,),
        accepted (S,), cache, dpools)."""
        self.decode_traces += 1
        greedy = self.ecfg.greedy or self.ecfg.temperature <= 0
        n0 = cache.length
        dcache = transformer_lib.PagedKVCache(
            dpools[0], dpools[1], cache.block_table, n0, dpools[2], dpools[3]
        )

        # ---- draft: k sequential single-token decodes (inlined in-program) --
        def draft_step(carry, i):
            tok, dc = carry
            logits, dc = model_lib.decode_step(dparams, tok, dc, self.cfg)
            lg = logits[:, -1]
            nxt = self._sample(lg, step, salt=2 + i)
            probs = (
                None if greedy
                else jax.nn.softmax(
                    lg.astype(jnp.float32) / self.ecfg.temperature, axis=-1
                )
            )
            return (nxt[:, None], dc), (nxt, probs)

        (_, dcache), (drafts_k, dprobs_k) = jax.lax.scan(
            draft_step, (tokens, dcache), jnp.arange(k)
        )
        drafts = drafts_k.T                                     # (S, k)

        # ---- verify: ONE k-wide paged forward through the target ----------
        vtoks = jnp.concatenate([tokens, drafts[:, : k - 1]], axis=1)  # (S, k)
        logits, cache = model_lib.decode_step(tparams, vtoks, cache, self.cfg)

        if greedy:
            out = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S, k)
            acc = jnp.cumprod((drafts == out).astype(jnp.int32), axis=1)
            a = jnp.sum(acc, axis=1)
        else:
            qprobs = jax.nn.softmax(
                logits.astype(jnp.float32) / self.ecfg.temperature, axis=-1
            )
            dprobs = jnp.transpose(dprobs_k, (1, 0, 2))          # (S, k, V)
            key = jax.random.fold_in(
                jax.random.fold_in(self._base_key, step), 2 + k
            )
            out, a = rejection_sample(key, drafts, dprobs, qprobs)

        # a < k: a accepted drafts + 1 corrective token; a == k: all k drafts
        # (no bonus position — the k-th draft's KV is nowhere yet, it simply
        # becomes the next tick's t_last, keeping both caches exactly aligned)
        m = jnp.where(a < k, a + 1, k).astype(jnp.int32)
        m = jnp.where(active, m, 0)
        new_len = jnp.where(active, n0 + m, n0)     # rollback = length reset
        cache = cache._replace(length=new_len)
        return out, m, a, cache, (dcache.k, dcache.v, dcache.k_scale, dcache.v_scale)

    # ------------------------------------------------------------- steps ---

    def _prefill_admitted(self, tokens, lengths, slot_ids, page_map, step,
                          tier: int = 0, rows=None):
        # `tier` is the base engine's grouping hook; here it is always the
        # target tier (the draft prefills alongside in the same program).
        # `rows` is the adapter-pool map — always None here: the engine's
        # constructor rejects AdapterBanks on speculative engines
        del rows
        real = slot_ids < slot_ids.size
        with self.metrics.measure_program(
            f"prefill[{tokens.shape[1]}]", tier,
            traces=lambda: self.prefill_traces,
            rows=int(np.count_nonzero(real)),
            tokens=int(lengths[real].sum()), positions=tokens.size,
        ):
            first, self.cache, self._dpools = self._prefill2(
                self.params, self.draft_params, jnp.asarray(tokens),
                jnp.asarray(lengths), jnp.asarray(slot_ids),
                jnp.asarray(page_map), self.cache, self._dpools,
                jnp.asarray(step, jnp.int32),
            )
            self.prefill_calls += 1
            with self.metrics.span("serve.fetch"):
                return np.asarray(first)

    def _chunk_call(self, tokens, counts, slot_ids, starts, step,
                    tier: int = 0):
        with self.metrics.measure_program(
            f"chunk[{tokens.shape[1]}]", tier,
            traces=lambda: self.chunk_traces,
            rows=int(np.count_nonzero(counts)),
            tokens=int(counts.sum()), positions=tokens.size,
        ):
            first, self.cache, self._dpools = self._chunk2(
                self.params, self.draft_params, jnp.asarray(tokens),
                jnp.asarray(counts), jnp.asarray(slot_ids),
                jnp.asarray(starts), self._device_cache(), self._dpools,
                jnp.asarray(step, jnp.int32),
            )
            self.chunk_calls += 1
            with self.metrics.span("serve.fetch"):
                return np.asarray(first)

    def _release(self, slot: int):
        super()._release(slot)
        self._guess[slot, :] = 0        # fresh/resumed slots restart guessing
        self._accept_ema[slot] = np.nan  # ... and restart their rate estimate

    def _apply_cow(self, src, dst):
        super()._apply_cow(src, dst)
        self._dpools = self._dcopy(self._dpools, src, dst)

    def _decode_tick(self, active, free, done):
        """ONE speculative tick's device portion: the jitted draft + k-wide
        verify over all slots — up to k tokens per slot for a single
        host/device round trip. (Admission/page growth ran in the shared
        ``step()`` skeleton; ``_write_window`` sized growth for k writes.)"""
        s = self.ecfg.max_slots
        k = self._k
        tokens = np.zeros((s, k if self._parallel else 1), np.int32)
        for slot in self._active:
            if slot in self._progress:   # mid-prefill slots don't decode
                continue
            tokens[slot, 0] = self._last_token[slot]
            if self._parallel:
                tokens[slot, 1:] = self._guess[slot, : k - 1]
        step_arr = jnp.asarray(self._steps, jnp.int32)
        with self.metrics.measure_program(
            f"spec_decode[k={k}]", self._target_tier,
            traces=lambda: self.decode_traces,
            rows=int(np.count_nonzero(active)),
        ):
            if self._parallel:
                out, guesses, emitted, accepted, self.cache, self._dpools = \
                    self._spec_par(
                        self.params, self.draft_params, jnp.asarray(tokens),
                        self._device_cache(), self._dpools,
                        jnp.asarray(active), step_arr,
                    )
                with self.metrics.span("serve.fetch"):
                    guess_np = np.asarray(guesses)
                drafted = max(k - 1, 1)  # k-1 verifiable guesses per window
            else:
                out, emitted, accepted, self.cache, self._dpools = self._spec(
                    self.params, self.draft_params, jnp.asarray(tokens),
                    self._device_cache(), self._dpools, jnp.asarray(active),
                    step_arr, k=k,
                )
                guess_np = None
                drafted = k
            self.decode_calls += 1
            with self.metrics.span("serve.fetch"):
                out_np = np.asarray(out)            # ONE host sync per tick
                emitted_np = np.asarray(emitted)
                accepted_np = np.asarray(accepted)

        with self.metrics.span("serve.record"):
            ema_sum = 0.0
            n_active = 0
            tick_drafted = 0
            tick_accepted = 0
            tr = self.tracer
            for slot, req in list(self._active.items()):
                if slot in self._progress:   # drafted nothing this tick
                    continue
                n_active += 1
                m = int(emitted_np[slot])
                rate = float(accepted_np[slot]) / drafted
                prev = self._accept_ema[slot]
                self._accept_ema[slot] = (
                    rate if np.isnan(prev)
                    else _SLOT_EMA * prev + (1.0 - _SLOT_EMA) * rate
                )
                ema_sum += self._accept_ema[slot]
                tick_drafted += drafted
                tick_accepted += int(accepted_np[slot])
                if tr is not None:
                    tr.instant(slot, "spec_accept", uid=req.uid,
                               drafted=drafted,
                               accepted=int(accepted_np[slot]), emitted=m)
                if guess_np is not None:
                    # d_chain[i] predicts position n+i+1; next window starts
                    # at n+m, so its guesses are d_chain[m:]; the tail
                    # (positions the draft has not seen yet) falls back to
                    # no-guess zeros
                    tail = guess_np[slot, m : m + k - 1]
                    self._guess[slot, : len(tail)] = tail
                    self._guess[slot, len(tail):] = 0
                for j in range(m):
                    if req.done:
                        break                           # max_new/eos mid-burst
                    self._record(slot, req, int(out_np[slot, j]), free, done)
            self.spec_ticks += 1
            if n_active:
                self.metrics.on_spec_tick(tick_drafted, tick_accepted,
                                          ema_sum / n_active, self._k)
            if self.controller is not None and n_active:
                # the window integrates the observed PER-SLOT acceptance
                # (EMA per slot, mean over currently-active slots)
                self._k = self.controller.update(ema_sum / n_active)
                self._write_window = self._k

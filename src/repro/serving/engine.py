"""Serving engines: continuous batching over jitted programs.

``PagedServingEngine`` is the batched engine. Each tick it admits what fits,
advances mid-prefill slots by one chunk, grows page allocations, and runs one
jitted decode per active tier over the decode-phase slots.

KV lives in a fixed pool of ``num_blocks`` pages of ``block_size`` tokens
(``models.transformer.PagedKVCache``); a host-side :class:`BlockAllocator`
hands pages to slots on demand. Requests admit whenever free pages cover their
prompt plus a decode reservation (admission is re-tried every tick, so
requests join mid-stream), finished or evicted slots return pages
immediately, and when the pool runs dry a victim (longest-remaining or LRU)
is evicted back to the queue and later resumes by re-prefilling prompt +
generated tokens. Decode attention reads pages through the block table
(pure-JAX gather, or the Pallas ``kernels/paged_attention.py`` kernel under
``kernel_impl='pallas'``); ``kv_dtype='int8'`` stores pages quantized via
``serving/kv_quant.py``.

Chunked prefill (``EngineConfig.prefill_chunk``): prompt processing is split
into block-aligned chunks interleaved with decode ticks. Each tick advances
every mid-prefill slot by at most ONE chunk (a single jitted ``(S, chunk)``
program over the paged cache — the chunk's queries attend previously written
pages, its KV scatters in at the slot's current length), so a long prompt no
longer head-of-line-blocks active decoders, and eviction-resume re-prefills
its prompt + generated history chunk-by-chunk instead of in one monolithic
call. A slot whose next chunk cannot get pages simply stalls and resumes from
the last completed chunk once pages free up. Greedy output is
bitwise-identical to one-shot prefill (tests/test_chunked_prefill.py).

Prefix caching (``EngineConfig.prefix_cache``): the :class:`BlockAllocator`
ref-counts pages and a radix index over token prefixes
(``serving/prefix_cache.py``) lets requests that share a prompt prefix share
the PHYSICAL pages holding it — admission attaches matching full pages
read-only and prefills only the unmatched suffix; the one divergent-write
case (a fully-cached prompt resuming inside its final hit page) is privatized
by a batched copy-on-write page copy (``kernels/page_copy.py``) before any
program runs. Retired and evicted slots publish their pages back, so
eviction-resume reattaches surviving pages, and index-only pages form an LRU
tail reclaimed under pressure before any live slot is evicted. Streams are
bitwise-identical to cache-off (tests/test_prefix_cache.py).

Device programs (all shapes static, so serving never recompiles):
  * ``prefill[bucket]`` — batched prompt forward; whole prompt pages and the
    first sampled token scatter into place inside the same jitted call
  * ``chunk`` — (params, tokens (S, chunk), counts (S,), slot_ids, cache,
    step) -> (first_tokens (S,), cache); at most ONE call per tick covering
    every mid-prefill slot (chunked mode replaces ``prefill`` entirely)
  * ``decode`` — (params, tokens (S, 1), cache, active (S,), step)
    -> (next_tokens (S,), cache); one call per active tier per tick

Weights may be a raw param tree (dense) or a ``DeployedModel`` serving SLR
(L + S) weights in any deployment format (``serving/deployed.py``) — the
programs are format-agnostic because every linear site goes through
``models.layers.apply_weight``.

``ReferenceEngine`` keeps the seed per-slot, per-token path: it serves the
cache families without per-slot lengths (ssm/hybrid/encdec), and it is the
batched engine's test oracle, sharing none of its paged code.

Elastic tiers (``serving/elastic.py``): every engine is constructed from a
``ModelBank`` — the trained SLR weights held once, materialized as an ordered
set of budget tiers — instead of one fixed-budget parameter tree. A request
pins a tier at ``submit`` (or takes the engine default); each tick the engine
groups decode-phase slots by their *effective* tier and runs one jitted
decode per active tier over the SHARED cache (block table and pages are
tier-agnostic, so a slot can switch tiers mid-stream with no KV copy, and
each tier's program compiles exactly once, on first use).
``tier_policy='pressure'`` runs a :class:`~repro.serving.elastic.
TierController`: under page pressure the serving tier downshifts (cheaper
steps, sooner completions, sooner frees) BEFORE the engine resorts to
eviction, and upshifts when pressure clears.

Multi-tenant adapters (``serving/adapters.py``): constructing an engine from
an :class:`~repro.serving.adapters.AdapterBank` (with ``EngineConfig.
adapters=True``) serves N registered (L+S) adapters over one shared base.
Requests pick an adapter at ``submit``; admission pins it into the bank's
fixed-capacity device pool (LRU swap-in for non-resident adapters, counted
by ``serve_adapter_swaps_total``), and each tick either batches slots running
DIFFERENT adapters through one fused multi-adapter program (``batched`` mode,
fused format) or runs one program per distinct resident adapter (``grouped``
mode). Pool swaps and per-call ``sel`` binds are data-only, so adapter
switches never retrace; under the prefix cache each adapter gets its own
radix index (KV pages are adapter-specific) over the one shared allocator.
"""
from __future__ import annotations

import contextlib
import json
import logging
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..models import model as model_lib
from ..models import transformer as transformer_lib
from ..parallel.sharding import ServingMesh, parse_mesh_spec
from .adapters import AdapterBank, AdapterError
from .deployed import DeployedModel
from .elastic import ModelBank, TierController, TierControllerConfig
from .prefix_cache import PrefixCache
from .telemetry import EngineTelemetry, NullTelemetry
from .trace import RequestTracer

log = logging.getLogger(__name__)

# All INTERNAL timestamps (submitted_at, admitted_at, first_token_at,
# token_times, finished_at) use the monotonic clock: an NTP step during a run
# must never yield a negative TTFT/ITL. Only ``Request.deadline`` stays on the
# wall clock — it is an absolute SLO contract handed in by the caller.
_now = time.monotonic

BATCHED_FAMILIES = ("dense", "moe", "vlm")  # cache families with per-slot lengths

# float payload dtypes; "int8" is also accepted: the engine then stores int8
# payload pools + f32 scale pools (never a bare int8 cache)
_KV_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
}
_EVICT_POLICIES = ("longest_remaining", "lru")


class RequestRejected(ValueError):
    """Raised by ``submit`` when a request can never be served by this engine
    (too long for the cache, or larger than the whole page pool). A graceful
    error path — the engine keeps serving everything already accepted."""


class EngineCapabilityError(RequestRejected):
    """A paged-only feature (quantized KV pages, speculative decoding) was
    requested on an engine/cache family that cannot provide it. Subclasses
    :class:`RequestRejected` so callers handle both through one error path."""


def _validate_request(prompt: list[int], max_new_tokens: int, max_len: int):
    if len(prompt) < 1:
        raise RequestRejected("empty prompt")
    if len(prompt) + max_new_tokens > max_len:
        raise RequestRejected(
            f"prompt({len(prompt)}) + max_new({max_new_tokens}) exceeds "
            f"cache capacity {max_len}"
        )


@dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False
    # timestamps below are time.monotonic() values (see _now above) — compare
    # them to each other, never to the wall clock
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0      # TTFT = first_token_at - submitted_at
    finished_at: float = 0.0
    token_times: list[float] = field(default_factory=list)
    deadline: float | None = None    # absolute WALL-CLOCK SLO deadline
    tier: int = 0                    # requested ModelBank tier (0 = largest)
    adapter: int | None = None       # AdapterBank adapter id (multi-tenant
    #                                  serving; always None on plain banks)
    evictions: int = 0
    requeued_at: float = 0.0         # last eviction's re-queue stamp — the
    #                                  basis for a RE-admission's queue wait
    # tokens this request emitted from a PREFILL/CHUNK program (one per
    # admission that reached the end of its prompt; a mid-prefill eviction
    # emits nothing, so this is NOT simply 1 + evictions)
    prefill_emitted: int = 0


@dataclass
class EngineConfig:
    max_slots: int = 4        # concurrent sequences (decode batch)
    max_len: int = 256        # max prompt+generation length per request
    greedy: bool = True
    temperature: float = 1.0  # used when greedy=False (on-device sampling)
    eos_token: int | None = None
    seed: int = 0
    min_bucket: int = 8       # smallest prefill length bucket
    # the KV page pool:
    block_size: int = 16      # tokens per KV page
    num_blocks: int | None = None   # page pool size; None = max_slots * max_len worth
    kv_dtype: str = "float32"       # float32 | bfloat16 | int8 (paged pages quantized)
    evict_policy: str = "longest_remaining"  # or "lru"
    decode_reserve: int | None = None  # decode headroom (tokens) required to admit;
    #                                    None = one block
    prefill_chunk: int | None = None   # split prompt processing into
    #                                    block-aligned chunks of this many
    #                                    tokens, interleaved with decode ticks
    #                                    (None = one-shot prefill; must be a
    #                                    positive multiple of block_size)
    # prefix cache (serving/prefix_cache.py):
    prefix_cache: bool = False      # radix prompt index over ref-counted KV
    #                                 pages: admissions attach matching full
    #                                 pages read-only and prefill only the
    #                                 unmatched suffix; retired slots publish
    #                                 their pages back
    prefix_min_hit_pages: int = 1   # smallest radix match worth attaching
    #                                 (shorter hits prefill from scratch)
    # elastic tiers (serving/elastic.py):
    default_tier: int = 0           # bank tier used when submit(tier=None)
    tier_policy: str = "static"     # static | pressure (downshift the
    #                                 serving tier under page pressure before
    #                                 resorting to eviction)
    tier_target_free: float = 0.25  # pressure setpoint: free-page fraction
    tier_gain: float = 4.0          # controller integral gain (tiers/error)
    tier_ema: float = 0.5           # smoothing of the free-fraction signal
    # speculative engine only (serving/speculative.py):
    spec_k: int = 0                 # draft tokens per tick; 0 = speculation off
    spec_adaptive: bool = False     # adapt k from observed acceptance rate
    spec_draft_mode: str = "auto"   # auto | parallel (greedy lookahead draft)
    #                                 | sequential (autoregressive proposals)
    spec_draft_kv_dtype: str = "bfloat16"  # draft page-pool payload (its own,
    #                                        smaller pool; never affects the
    #                                        target distribution)
    spec_target_tier: int = 0       # bank tier the verifier serves
    spec_draft_tier: int = -1       # bank tier that drafts (-1 = cheapest)
    # observability (serving/telemetry.py, serving/trace.py) — host-side
    # only, never touches the device path:
    telemetry: bool = True          # metrics registry + per-program timing /
    #                                 retrace detection; False = every hook is
    #                                 a no-op (NullTelemetry)
    trace: bool = False             # per-request span tracer (Chrome-trace /
    #                                 JSONL export via engine.tracer)
    # tensor-parallel serving (parallel/sharding.ServingMesh): a mesh spec
    # string like "model=2,data=1". Kept as a STRING so EngineConfig stays
    # dataclasses.asdict / JSON-safe (engine_provenance) and never touches
    # jax device state at construction; the engine builds the ServingMesh.
    mesh: str | None = None
    # multi-tenant adapter serving (serving/adapters.py): True means the
    # engine's bank IS an AdapterBank (and vice versa — the flag keeps
    # multi-tenancy explicit in config / provenance dumps, never inferred)
    adapters: bool = False
    max_resident_adapters: int | None = None  # device adapter-pool rows;
    #                                           None = all registered resident

    def __post_init__(self):
        """Validate at CONSTRUCTION: a bad config used to surface as a
        downstream shape/jit failure deep inside the first prefill (or worse,
        as a silently-degenerate pool). Every check here raises a ValueError
        that names the field and the constraint."""
        for name in ("max_slots", "max_len", "block_size", "min_bucket"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{name}={v!r} must be a positive int")
        if self.num_blocks is not None and self.num_blocks < 1:
            raise ValueError(
                f"num_blocks={self.num_blocks} must be positive (or None for "
                f"a max_slots * max_len worth of pages)"
            )
        if self.kv_dtype not in _KV_DTYPES and self.kv_dtype != "int8":
            raise ValueError(
                f"unknown kv_dtype {self.kv_dtype!r}; expected one of "
                f"{sorted(_KV_DTYPES) + ['int8']}"
            )
        if self.evict_policy not in _EVICT_POLICIES:
            raise ValueError(
                f"unknown evict_policy {self.evict_policy!r}; "
                f"expected one of {_EVICT_POLICIES}"
            )
        if self.decode_reserve is not None and self.decode_reserve < 1:
            raise ValueError(
                f"decode_reserve={self.decode_reserve} must be positive "
                f"(or None for one block)"
            )
        if self.prefill_chunk is not None and (
            self.prefill_chunk < 1 or self.prefill_chunk % self.block_size
        ):
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be a positive "
                f"multiple of block_size={self.block_size} (chunks scatter "
                f"whole pages)"
            )
        if not isinstance(self.prefix_min_hit_pages, int) \
                or self.prefix_min_hit_pages < 1:
            raise ValueError(
                f"prefix_min_hit_pages={self.prefix_min_hit_pages!r} must be "
                "a positive int (a zero-page hit is not a hit)"
            )
        if self.tier_policy not in ("static", "pressure"):
            raise ValueError(
                f"unknown tier_policy {self.tier_policy!r}; "
                f"expected 'static' or 'pressure'"
            )
        if not 0.0 < self.tier_target_free < 1.0:
            raise ValueError(
                f"tier_target_free={self.tier_target_free} outside (0, 1)"
            )
        if self.tier_gain <= 0:
            raise ValueError(f"tier_gain={self.tier_gain} must be positive")
        if not 0.0 <= self.tier_ema < 1.0:
            raise ValueError(f"tier_ema={self.tier_ema} outside [0, 1)")
        if self.spec_k < 0:
            raise ValueError(f"spec_k={self.spec_k} must be >= 0")
        if self.spec_draft_mode not in ("auto", "parallel", "sequential"):
            raise ValueError(
                f"unknown spec_draft_mode {self.spec_draft_mode!r}; "
                f"expected auto | parallel | sequential"
            )
        if (self.spec_draft_kv_dtype not in _KV_DTYPES
                and self.spec_draft_kv_dtype != "int8"):
            raise ValueError(
                f"unknown spec_draft_kv_dtype {self.spec_draft_kv_dtype!r}; "
                f"expected one of {sorted(_KV_DTYPES) + ['int8']}"
            )
        if self.mesh is not None:
            if not isinstance(self.mesh, str):
                raise ValueError(
                    f"mesh={self.mesh!r} must be a spec string like "
                    f"'model=2,data=1' (or None for single-device)"
                )
            # format-only validation (raises field-naming ValueErrors);
            # device-count and head-divisibility checks need the arch + real
            # devices and happen in the engine constructor
            parse_mesh_spec(self.mesh)
        if self.max_resident_adapters is not None:
            if not isinstance(self.max_resident_adapters, int) \
                    or self.max_resident_adapters < 1:
                raise ValueError(
                    f"max_resident_adapters={self.max_resident_adapters!r} "
                    "must be a positive int (or None for every registered "
                    "adapter resident)"
                )
            if not self.adapters:
                raise ValueError(
                    "max_resident_adapters sizes the AdapterBank device pool "
                    "and needs adapters=True"
                )


def decode_emitted_tokens(done: list[Request]) -> int:
    """Tokens these requests emitted from DECODE steps: every (re-)admission
    that completes its prefill emits one token from the prefill/chunk program,
    the rest amortize over decode calls. Counted via ``Request.
    prefill_emitted`` rather than ``1 + evictions`` because an eviction that
    lands MID-PREFILL emits nothing for that admission (chunked prefill made
    that state reachable). The convention lives here so benchmark/launcher
    metrics (tokens-per-step) cannot drift from the engines that define it."""
    return sum(len(r.out_tokens) - r.prefill_emitted for r in done)


def _resolve_engine_args(name: str, model, params=None, ecfg=None):
    """Resolve the Engine-protocol constructor contract.

    ``Engine(bank, ecfg)`` where ``bank`` is a :class:`~repro.serving.
    elastic.ModelBank` — including a multi-tenant :class:`~repro.serving.
    adapters.AdapterBank` — or a bare ``DeployedModel`` (accepted as a
    single-tier convenience). The pre-elastic ``Engine(arch_cfg, params,
    ecfg)`` form was removed after its deprecation cycle and now raises a
    ``TypeError`` naming the replacement.
    """
    if isinstance(model, (ModelBank, DeployedModel)):
        if params is not None and ecfg is not None:
            raise TypeError(
                f"{name}(bank, ecfg) takes no third argument; per-tier "
                "weights live in the ModelBank"
            )
        cfg_arg = params if params is not None else ecfg  # positional OR ecfg=
        if cfg_arg is not None and not isinstance(cfg_arg, EngineConfig):
            raise TypeError(
                f"{name}(bank, ecfg): second argument must be an "
                f"EngineConfig, got {type(cfg_arg).__name__}"
            )
        bank = model if isinstance(model, ModelBank) \
            else ModelBank.single(model.cfg, model)
        return bank, cfg_arg if cfg_arg is not None else EngineConfig()
    if not hasattr(model, "family"):
        raise TypeError(
            f"{name} expects a ModelBank (serving.elastic) or DeployedModel "
            f"first argument, got {type(model).__name__}"
        )
    raise TypeError(
        f"{name}(arch_cfg, params, ecfg) was removed: build a ModelBank "
        f"(serving/elastic.py) — or a serving.adapters.AdapterBank for "
        f"multi-tenant serving — and construct {name}(bank, ecfg); "
        f"ModelBank.single(arch_cfg, params) wraps one weight tree"
    )


def _bank_tier_state(bank: ModelBank, ecfg: EngineConfig):
    """Per-tier parameter list + validated default tier, shared by every
    engine's constructor (keeps the error contract in one place)."""
    tier_params = [t.params for t in bank]
    try:
        default = bank.resolve(ecfg.default_tier)
    except ValueError as e:
        raise ValueError(f"default_tier: {e}") from None
    return tier_params, default


def _resolve_request_tier(bank: ModelBank, default: int,
                          tier: int | None) -> int:
    """Validated bank tier for a request (None = the engine default).
    Submit-time tier errors are RequestRejected, per the Engine protocol."""
    if tier is None:
        return default
    try:
        return bank.resolve(tier)
    except ValueError as e:
        raise RequestRejected(str(e)) from None


def _capability_error(engine_cls, family: str, missing: list[str]):
    """An :class:`EngineCapabilityError` that carries the engine's structured
    ``capabilities()`` dict, so callers (and ``launch/serve.py`` users) see
    WHICH features are paged-only instead of a bare string."""
    caps = engine_cls.capabilities()
    return EngineCapabilityError(
        f"family {family!r} serves through {engine_cls.__name__}; requested "
        f"feature(s) unavailable: {', '.join(missing)}. Engine capabilities: "
        f"{json.dumps(caps, sort_keys=True)}"
    )


def _resolve_serving_mesh(ecfg: EngineConfig, arch_cfg, bank: ModelBank):
    """Build + validate the ServingMesh for ``ecfg.mesh`` (None = unsharded).

    Every check raises a ValueError naming the field and the constraint:
    the 'model' axis must divide both head counts (the KV pools and the
    shard_map'd paged kernels split the head axis), and the Pallas BSR /
    fused formats are rejected — their block-CSR tables are addressed by a
    global block grid that the scalar-prefetched DMA index maps walk, which
    no axis partition can split.
    """
    if ecfg.mesh is None:
        return None
    smesh = ServingMesh.from_spec(ecfg.mesh)
    m = smesh.model_size
    if m > 1:
        heads = arch_cfg.num_heads
        kv_heads = arch_cfg.num_kv_heads or heads
        for fname, h in (("num_heads", heads), ("num_kv_heads", kv_heads)):
            if not h or h % m:
                raise ValueError(
                    f"mesh={ecfg.mesh!r}: model axis size {m} must divide "
                    f"{fname}={h} (KV pools and paged attention shard the "
                    f"head axis)"
                )
        for tier in bank:
            fmt = getattr(tier.model, "fmt", "dense")
            if fmt in ("bsr", "fused"):
                raise ValueError(
                    f"mesh={ecfg.mesh!r}: deployment format {fmt!r} cannot "
                    f"shard over the model axis (its BSR block grid is "
                    f"indexed globally by the Pallas DMA index maps); serve "
                    f"'dense' or 'factored' tiers under a mesh"
                )
    return smesh


def _device_put_tiers(tier_params: list, smesh: ServingMesh) -> list:
    """Materialize every bank tier against ONE sharded base.

    Leaves shared across tiers by object identity (the bank's shared dense
    base: embeddings, norms, unselected matrices) are device_put ONCE and the
    same placed array is re-used in every tier's tree — so elastic banks keep
    one physical copy per device and ``ModelBank.shared_base_bytes`` (an
    ``id()`` intersection) still reports the sharing.
    """
    placed: dict[int, jax.Array] = {}
    out = []
    for tree in tier_params:
        shardings = smesh.params_shardings(tree)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        shard_leaves = jax.tree_util.tree_leaves(shardings)
        assert len(leaves) == len(shard_leaves)
        new = []
        for leaf, sh in zip(leaves, shard_leaves):
            key = id(leaf)
            if key not in placed:
                placed[key] = jax.device_put(leaf, sh)
            new.append(placed[key])
        out.append(jax.tree_util.tree_unflatten(treedef, new))
    return out


def _kv_pool_device_bytes(cache) -> dict[str, int]:
    """Per-device KV payload bytes, from the placed pools' actual shards —
    the number behind the ``serve_kv_pool_device_bytes`` gauge and
    BENCH_shard.json's 1/N-scaling check."""
    per_dev: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(cache):
        if getattr(leaf, "ndim", 0) != 5:  # payload/scale pools only
            continue
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            per_dev[key] = per_dev.get(key, 0) + int(np.prod(shard.data.shape)) \
                * leaf.dtype.itemsize
    return per_dev


# ------------------------------------------------------------------ paged ---


class BlockAllocator:
    """Host-side REF-COUNTED allocator over a fixed pool of KV pages.

    Pages are interchangeable — any free page can map any (slot, block)
    position, so there is no external fragmentation by construction; the only
    waste is internal (the partially-filled last block of each sequence).

    Prefix sharing (``serving/prefix_cache.py``) lets one physical page back
    several logical (slot, block) positions plus the radix index, so ownership
    is a per-page reference count: ``alloc`` grants pages at refcount 1,
    ``share`` adds a holder, ``release`` drops one and returns the page to the
    pool when the count hits zero. ``free`` keeps its strict pre-refcount
    contract — it only accepts EXCLUSIVE pages (refcount exactly 1), so a
    caller that believes it is the sole owner fails loudly if it is not.

    Invariants (asserted in tests): a page is never handed out twice, every
    mutation validates its whole argument list BEFORE touching state (a bad
    call leaves the allocator untouched), and free + distinct-owned always
    equals the pool whatever the refcounts are.
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))
        self._refs: dict[int, int] = {}    # page -> holders (absent = free)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Distinct owned pages (a shared page counts once)."""
        return len(self._refs)

    def refcount(self, page: int) -> int:
        """Holders of ``page`` (0 = free)."""
        return self._refs.get(page, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def _validate_owned(self, pages: list[int], verb: str):
        bad = sorted({p for p in pages if p not in self._refs})
        if bad:
            raise ValueError(f"{verb} page(s) {bad} that are not allocated")
        if len(set(pages)) != len(pages):
            raise ValueError(
                f"duplicate page(s) in {verb} list {sorted(pages)}"
            )

    def alloc(self, n: int) -> list[int] | None:
        """n pages at refcount 1, or None if the pool cannot cover them (no
        partial grants)."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def share(self, pages: list[int]):
        """Add one holder to each page — all of them or none of them (the
        whole list validates before any count moves)."""
        self._validate_owned(pages, "sharing")
        for p in pages:
            self._refs[p] += 1

    def release(self, pages: list[int]) -> list[int]:
        """Drop one holder from each page; pages reaching zero return to the
        pool. Validates the whole list first, then returns the pages actually
        freed (callers use it to account reclaim)."""
        self._validate_owned(pages, "releasing")
        freed: list[int] = []
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
                freed.append(p)
        return freed

    def free(self, pages: list[int]):
        """Return EXCLUSIVE pages to the pool — all of them or none of them.

        The whole list is validated BEFORE any state changes: a bad entry
        (unowned page, a duplicate within the list, or a page somebody else
        still holds a reference to) used to raise mid-loop with the earlier
        pages already freed, leaving free + used != pool for every caller that
        caught the error. Now a bad free raises without mutating anything, so
        the allocator invariant survives."""
        self._validate_owned(pages, "free")
        shared = sorted({p for p in pages if self._refs[p] != 1})
        if shared:
            raise ValueError(
                f"freeing shared page(s) {shared} (refcount > 1); drop "
                "references with release() instead"
            )
        for p in pages:
            del self._refs[p]
        self._free.extend(pages)


class PagedServingEngine:
    """The batched engine: continuous batching over a block-paged KV cache.

    Serving memory is ``num_blocks * block_size`` tokens of KV shared by all
    slots — a request holds the pages it fills, not ``max_len``. Admission
    happens whenever a slot AND enough free pages exist (checked every tick);
    decode allocations grow one page at a time, and pool exhaustion evicts a
    victim back to the queue (it resumes later by re-prefilling prompt +
    generated tokens, which under greedy decoding reproduces the same
    continuation).

    With ``prefill_chunk`` set, admission only reserves pages for the FIRST
    chunk and marks the slot mid-prefill; each tick then advances every
    mid-prefill slot by one chunk through a single jitted ``(S, chunk)``
    program (``models.model.chunk_prefill_step``: scatter into pages at the
    slot's current length, causal mask offset by it) while decode-phase slots
    keep decoding. Pages are reserved chunk-by-chunk; a chunk that cannot get
    pages stalls its slot at the last completed chunk (no progress lost)
    rather than blocking the tick.

    With ``prefix_cache`` set, admission walks the radix prompt index first
    and attaches cached prefix pages read-only (see ``_admit``/``_retire``
    and ``serving/prefix_cache.py``); page ownership then counts references,
    copy-on-write privatizes the one page a hit admission may write into,
    and the index's unreferenced LRU tail is the first thing ``_alloc``
    reclaims under pressure.
    """

    _speculative = False   # only serving.speculative.SpeculativeEngine drafts
    _write_window = 1      # KV positions one decode tick writes per slot (the
    #                        speculative engine widens it to its k drafts)

    def __init__(self, model, params=None, ecfg: EngineConfig | None = None):
        bank, ecfg = _resolve_engine_args(type(self).__name__, model, params,
                                          ecfg)
        arch_cfg = bank.cfg
        if arch_cfg.family not in BATCHED_FAMILIES:
            raise ValueError(
                f"{type(self).__name__} needs a KV-cache family, got "
                f"{arch_cfg.family!r}; use ReferenceEngine for "
                "ssm/hybrid/encdec"
            )
        if ecfg.spec_k and not self._speculative:
            # never silently drop a requested feature: spec_k is only
            # consumed by serving.speculative.SpeculativeEngine
            raise EngineCapabilityError(
                f"{type(self).__name__} does not speculate "
                f"(spec_k={ecfg.spec_k} requested); use SpeculativeEngine. "
                f"Engine capabilities: "
                f"{json.dumps(self.capabilities(), sort_keys=True)}"
            )
        # multi-tenant adapters: the bank type and the config flag must agree
        # — neither a silently-ignored AdapterBank nor a flag with no pool
        self._adapters: AdapterBank | None = \
            bank if isinstance(bank, AdapterBank) else None
        if ecfg.adapters != (self._adapters is not None):
            raise ValueError(
                "adapters=True needs an AdapterBank (serving.adapters) as the "
                "engine's bank, and an AdapterBank needs adapters=True — got "
                f"adapters={ecfg.adapters} with {type(bank).__name__}"
            )
        if self._adapters is not None:
            if ecfg.mesh is not None:
                raise ValueError(
                    f"mesh={ecfg.mesh!r} + adapters is unsupported: the "
                    "pooled adapter tables are indexed by scalar-prefetched "
                    "DMA maps no axis partition can split; serve adapters "
                    "unsharded"
                )
            if self._speculative:
                raise EngineCapabilityError(
                    "SpeculativeEngine does not serve AdapterBanks (draft + "
                    "verify would each need a pool); use PagedServingEngine"
                )
            self._adapters.materialize(ecfg.max_resident_adapters)
        self.cfg = arch_cfg
        self.ecfg = ecfg
        self.bank = bank
        self._tier_params, self._default_tier = _bank_tier_state(bank, ecfg)
        # tensor parallelism: resolve the mesh spec, then materialize ALL
        # tiers against one sharded base (shared leaves placed once) so
        # elastic / speculative / prefix-cached serving inherit TP for free
        self.mesh = _resolve_serving_mesh(ecfg, arch_cfg, bank)
        if self.mesh is not None:
            self._tier_params = _device_put_tiers(self._tier_params, self.mesh)
        # back-compat alias: the default tier's tree (the speculative engine
        # re-points it at the verify target's tier)
        self.params = self._tier_params[self._default_tier]
        # effective tier per slot (requested tier + controller downshift),
        # refreshed every tick; decode groups by this
        self._slot_tier = np.zeros(ecfg.max_slots, np.int64)
        # multi-tenant: adapter-pool row per slot (batched decode binds this
        # map verbatim) + the adapter id each slot pinned (unpinned when the
        # slot releases, so LRU never swaps out a streaming adapter)
        self._slot_pool = np.zeros(ecfg.max_slots, np.int32)
        self._slot_adapter: dict[int, int] = {}
        self._tier_shift = 0
        self.tier_controller: TierController | None = None
        if ecfg.tier_policy == "pressure":
            self.tier_controller = TierController(
                len(bank),
                TierControllerConfig(
                    target_free_frac=ecfg.tier_target_free,
                    gain=ecfg.tier_gain, ema=ecfg.tier_ema,
                ),
            )
        self._queue: list[Request] = []
        self._active: dict[int, Request] = {}   # slot -> request
        # slot -> tokens prefilled so far; a slot present here is MID-PREFILL
        # (chunked prefill only) and does not participate in decode ticks
        self._progress: dict[int, int] = {}
        self._uid = 0
        self._steps = 0
        self._last_token = np.zeros(ecfg.max_slots, np.int64)
        self._base_key = jax.random.PRNGKey(ecfg.seed)

        # instrumentation: device calls vs (re)traces — tests assert the
        # decode loop is one device call per step and compiles exactly once.
        # The ``*_calls``/``*_traces`` pairs stay PLAIN INTS on purpose: the
        # trace counters increment as a python side effect INSIDE traced
        # functions (so they count traces only), which a registry-backed
        # property could not express; the retrace detector reads their deltas
        self.decode_calls = 0
        self.decode_traces = 0
        self.prefill_calls = 0
        self.prefill_traces = 0
        self.chunk_calls = 0
        self.chunk_traces = 0

        # observability: the unified metrics registry (+ optional tracer).
        # The legacy counter attributes (evictions, tier_switches,
        # downshift_ticks, prefix_*, cow_copies, ...) are now read-only
        # properties over this registry — one metrics substrate everywhere
        self.metrics = (EngineTelemetry if ecfg.telemetry
                        else NullTelemetry)(type(self).__name__)
        if self._adapters is not None:
            self.metrics.set_resident_adapters(len(self._adapters.resident))
        for tier in bank:
            if tier.slr_kernel is not None:
                self.metrics.set_slr_kernel(tier.index, ecfg.max_slots,
                                            tier.slr_kernel)
        self.tracer: RequestTracer | None = None
        if ecfg.trace:
            self.start_trace()

        # the page pool: block tables are ``max_len`` rounded up to whole pages
        bs = ecfg.block_size
        self._bs = bs
        self._max_len = -(-ecfg.max_len // bs) * bs
        self._nb_slot = self._max_len // bs          # block-table width
        self.num_blocks = ecfg.num_blocks or ecfg.max_slots * self._nb_slot
        self.allocator = BlockAllocator(self.num_blocks)
        self._quantized = ecfg.kv_dtype == "int8"
        # EngineConfig checks the chunk is a positive multiple of block_size
        self._chunk = ecfg.prefill_chunk
        if self._chunk is not None:
            self._chunk = min(self._chunk, self._max_len)
        self.cache = model_lib.init_paged_cache(
            arch_cfg, ecfg.max_slots, self.num_blocks, bs, self._nb_slot,
            dtype=jnp.float32 if self._quantized else _KV_DTYPES[ecfg.kv_dtype],
            quantized=self._quantized,
        )
        # host mirror of the block table; pushed to device only when dirty
        self._table = np.full(
            (ecfg.max_slots, self._nb_slot), self.num_blocks, np.int32
        )
        self._table_dirty = False
        self._pages: dict[int, list[int]] = {}       # slot -> page ids
        self._ptarget: dict[int, int] = {}           # slot -> prefill target len
        # prefix sharing (serving/prefix_cache.py): radix index over prompt
        # prefixes at page granularity + the CoW copy program. Multi-tenant:
        # cached KV depends on the adapter's weights, so pages must never
        # match across adapters — one index PER ADAPTER ID (created on
        # demand by _prefix_of), all holding references in the ONE shared
        # allocator; an unregistered adapter's index simply stops being
        # consulted and its pages age out through the shared LRU reclaim
        self._prefix = PrefixCache(self.allocator, bs) \
            if ecfg.prefix_cache and self._adapters is None else None
        self._prefix_caches: dict[int, PrefixCache] = {}
        # slot -> device-length reset applied at the next _device_cache push:
        # a hit admission's length is stale until its first chunk program
        # runs, and junk rows written meanwhile must not land in pages the
        # slot attached read-only
        self._len_reset: dict[int, int] = {}
        self._place_cache()
        self._decode = jax.jit(self._decode_fn, donate_argnums=(2,))
        self._prefill = jax.jit(self._prefill_fn, donate_argnums=(5,))
        self._chunk_prog = jax.jit(self._chunk_fn, donate_argnums=(5,))
        self._copy_prog = jax.jit(
            transformer_lib.copy_cache_pages, donate_argnums=(0,)
        )
        # fixed-shape scatter for _len_reset (OOB pad indices drop)
        self._len_prog = jax.jit(
            lambda length, idx, val: length.at[idx].set(val, mode="drop")
        )

    @classmethod
    def capabilities(cls) -> dict:
        """Structured capability report (Engine protocol): which cache
        families this engine serves, its KV layout, and feature availability
        — the data behind ``EngineCapabilityError`` messages and the
        ``launch/serve.py --help`` table."""
        return {
            "engine": cls.__name__,
            "families": list(BATCHED_FAMILIES),
            "kv": "paged",
            "features": {
                "kv_dtype": ["float32", "bfloat16", "int8"],
                "continuous_batching": True,
                "deadlines_edf": True,
                "chunked_prefill": True,
                "eviction_resume": True,
                "speculative": False,
                "elastic_tiers": True,
                "tier_pressure_controller": True,
                "prefix_caching": True,
                "tensor_parallel": True,
                "multi_tenant_adapters": True,
            },
        }

    # ----------------------------------------------------- observability ---

    def start_trace(self, tracer: RequestTracer | None = None) -> RequestTracer:
        """Attach a per-request span tracer (serving/trace.py); subsequent
        activity records slot-track spans. Returns the tracer
        (``tracer.save_chrome(path)`` / ``save_jsonl(path)``)."""
        self.tracer = tracer if tracer is not None \
            else RequestTracer(type(self).__name__)
        return self.tracer

    # Migrated ad-hoc counters: read-only views over the metrics registry
    # (the registry is the single writer — see the hooks at the old
    # increment sites). With telemetry=False these all read 0.

    @property
    def evictions(self) -> int:
        return int(self.metrics.counter_value(self.metrics.evictions))

    @property
    def tier_switches(self) -> int:
        return int(self.metrics.counter_value(self.metrics.tier_switches))

    @property
    def downshift_ticks(self) -> int:
        return int(self.metrics.counter_value(self.metrics.downshift_ticks))

    # Prefix-cache counters: registry-backed read-only views (the hooks in
    # ``_admit`` are the single writers). With telemetry=False these read 0.

    @property
    def prefix_lookups(self) -> int:
        return int(self.metrics.counter_value(self.metrics.prefix, "lookups"))

    @property
    def prefix_hits(self) -> int:
        return int(self.metrics.counter_value(self.metrics.prefix, "hits"))

    @property
    def prefix_hit_tokens(self) -> int:
        """Prompt tokens served from the index instead of prefill compute."""
        return int(self.metrics.counter_value(self.metrics.tokens,
                                              "prefix_hit"))

    @property
    def cow_copies(self) -> int:
        """Pages privatized by copy-on-write."""
        return int(self.metrics.counter_value(self.metrics.prefix,
                                              "cow_copies"))

    @property
    def reattached_pages(self) -> int:
        """Pages evicted slots got back on resume."""
        return int(self.metrics.counter_value(self.metrics.prefix,
                                              "reattached_pages"))

    def stats_snapshot(self) -> dict:
        """Host-side serving stats: scheduler/jit counters plus the full
        metrics-registry snapshot. ``launch/serve.py`` derives its summary
        from this, and the Prometheus exporter serves the same registry."""
        return {
            "engine": type(self).__name__,
            "steps": self._steps,
            "decode_calls": self.decode_calls,
            "decode_traces": self.decode_traces,
            "prefill_calls": self.prefill_calls,
            "prefill_traces": self.prefill_traces,
            "jit_retraces": self.metrics.retraces(),
            "slowest_step": self.metrics.slowest_step(),
            "metrics": self.metrics.snapshot(),
        }

    def lower_decode(self, tier: int = 0) -> jax.stages.Lowered:
        """Tier ``tier``'s decode program lowered at the engine's live
        argument shapes, for inspection (compiled text, memory analysis)."""
        s = self.ecfg.max_slots
        with self._mesh_scope():
            return self._decode.lower(
                self._call_params(tier, self._slot_pool),
                jnp.zeros((s, 1), jnp.int32), self._device_cache(),
                jnp.zeros((s,), bool), jnp.asarray(self._steps, jnp.int32),
            )

    # ------------------------------------------------------------ intake ---

    def submit(self, prompt: list[int], max_new_tokens: int = 16,
               deadline: float | None = None,
               tier: int | None = None,
               submitted_at: float | None = None,
               adapter: int | None = None) -> int:
        """Enqueue a request. ``submitted_at`` (monotonic clock) lets open-
        loop harnesses backdate the submission to the SCHEDULED arrival, so
        TTFT/queue-wait metrics share one basis however the driver batches
        its submits; None = now. ``adapter`` picks a registered AdapterBank
        adapter (None = the bank's default; rejected on plain-bank engines)."""
        try:
            self._validate(prompt, max_new_tokens)
            tier_r = self._resolve_tier(tier)
            adapter_r = self._resolve_adapter(adapter)
        except RequestRejected:
            self.metrics.on_reject()
            raise
        self._uid += 1
        self._queue.append(
            Request(self._uid, list(prompt), max_new_tokens,
                    submitted_at=_now() if submitted_at is None
                    else submitted_at,
                    deadline=deadline, tier=tier_r, adapter=adapter_r)
        )
        self.metrics.on_submit()
        return self._uid

    def _validate(self, prompt: list[int], max_new_tokens: int):
        _validate_request(prompt, max_new_tokens, self.ecfg.max_len)
        need = -(-(len(prompt) + max_new_tokens) // self._bs)
        if need > self.num_blocks:
            raise RequestRejected(
                f"request needs {need} KV pages but the whole pool holds "
                f"{self.num_blocks}"
            )

    def _resolve_tier(self, tier: int | None) -> int:
        return _resolve_request_tier(self.bank, self._default_tier, tier)

    def _resolve_adapter(self, adapter: int | None) -> int | None:
        """Validated adapter id (None = the bank's default). Submit-time
        errors are ``RequestRejected``, like tiers; an adapter unregistered
        AFTER submit is caught at admission instead (the request finishes
        rejected — the graceful error path)."""
        if self._adapters is None:
            if adapter is not None:
                raise RequestRejected(
                    f"adapter={adapter} needs an AdapterBank engine "
                    "(serving.adapters); this engine serves a plain ModelBank"
                )
            return None
        aid = self._adapters.default_adapter if adapter is None else adapter
        if aid not in self._adapters.registry:
            raise RequestRejected(f"unknown adapter id {aid}")
        return aid

    # ------------------------------------------------------------- tiers ---

    def _effective_tier(self, req: Request) -> int:
        """Requested tier plus the controller's downshift, clamped to the
        cheap end of the bank (downshift only ever moves toward smaller
        capacities; it never upgrades a request past what it asked for)."""
        return min(req.tier + self._tier_shift, len(self._tier_params) - 1)

    def _update_tier_shift(self):
        """Integrate page pressure into the serving-tier downshift (BEFORE
        ``_pre_decode`` can evict anyone — the controller spends capacity
        quality first, requests last). Index-only cached pages count as free:
        they are one ``reclaim`` away from the pool, so a cache-warm engine
        must not read as a starved one."""
        if self.tier_controller is None:
            return
        free_like = self.allocator.free_blocks + sum(
            pc.reclaimable_pages for pc in self._all_prefixes()
        )
        self._tier_shift = self.tier_controller.update(
            free_like / self.num_blocks
        )
        if self._tier_shift > 0:
            self.metrics.inc(self.metrics.downshift_ticks)

    def _refresh_slot_tiers(self):
        """Recompute each active slot's effective tier. A change is pure
        host-side bookkeeping — the KV cache is tier-agnostic (no copy) and
        every tier's program is already compiled after its first use, so a
        mid-stream switch costs nothing on device."""
        for slot, req in self._active.items():
            eff = self._effective_tier(req)
            if eff != self._slot_tier[slot]:
                self.metrics.inc(self.metrics.tier_switches)
                if self.tracer is not None:
                    self.tracer.instant(slot, "tier_switch", uid=req.uid,
                                        frm=int(self._slot_tier[slot]), to=eff)
                self._slot_tier[slot] = eff

    def _tier_groups(self, slots) -> list[tuple]:
        """Active slots grouped by program key (ascending): the effective
        tier, widened — grouped adapter mode only — to ``(tier, pool row)``
        so every call serves ONE adapter through the single-tenant ops.
        Batched adapter mode keeps the plain tier key: one multi-adapter
        program covers mixed-adapter slots."""
        grouped = self._adapters is not None \
            and self._adapters.mode == "grouped"
        groups: dict = {}
        for s in slots:
            key = (int(self._slot_tier[s]), int(self._slot_pool[s])) \
                if grouped else int(self._slot_tier[s])
            groups.setdefault(key, []).append(s)
        return sorted(groups.items())

    # ---------------------------------------------------------- adapters ---

    def _adapter_admit(self, req: Request, done: list[Request]):
        """Acquire + pin ``req``'s adapter into the device pool. Returns
        ``("ok", row)`` (``row`` is None on plain banks); ``("busy", None)``
        when every pool row is pinned by a streaming slot — keep the request
        queued and retry next tick; ``("gone", None)`` when the adapter was
        unregistered after submit — the request finishes rejected."""
        if self._adapters is None:
            return "ok", None
        try:
            row, swapped = self._adapters.acquire(req.adapter)
        except AdapterError:
            req.done = True
            req.finished_at = _now()
            self.metrics.on_reject()
            done.append(req)
            return "gone", None
        if row is None:
            return "busy", None
        if swapped:
            self.metrics.inc(self.metrics.adapter_swaps)
        self._adapters.pin(req.adapter)
        self.metrics.set_resident_adapters(len(self._adapters.resident))
        return "ok", row

    def _call_params(self, key, rows=None):
        """The parameter tree for ONE program call: the tier's tree on plain
        banks; on AdapterBanks a fresh ``bind`` of the live pool — a grouped
        key carries its pool row (one adapter per call, scalar sel), batched
        calls bind the ``rows`` map (slot- or group-indexed, matching the
        program's row convention). Binds are data-only: same treedef and
        shapes every call, so programs never retrace across adapters."""
        if self._adapters is None:
            return self._tier_params[key]
        if isinstance(key, tuple):            # grouped: (tier, pool row)
            return self._adapters.bind(key[1])
        return self._adapters.bind(np.asarray(rows, np.int32))

    def _order_queue(self):
        """Earliest-deadline-first admission order. Tiebreaks are stable:
        among equal deadlines (or no deadlines at all) evicted/resumed requests go first — they already
        spent pool time, and finishing them releases memory soonest — then
        FIFO by uid. EDF stays primary: an evicted request with a LATER
        deadline does not jump an urgent fresh one (the old paged queue-head
        insert did exactly that, and the pre-admit re-sort then dropped it)."""
        self._queue.sort(
            key=lambda r: (
                r.deadline is None, r.deadline or 0.0, -r.evictions, r.uid
            )
        )

    @property
    def has_work(self) -> bool:
        return bool(self._queue or self._active)

    @property
    def queued(self) -> int:
        return len(self._queue)

    def _prefix_of(self, aid: int | None) -> PrefixCache | None:
        """The radix index serving adapter ``aid``: the shared one on plain
        banks, a per-adapter index under multi-tenant serving (created on
        first use — cached KV is adapter-specific). None with the cache off."""
        if not self.ecfg.prefix_cache:
            return None
        if self._adapters is None:
            return self._prefix
        pc = self._prefix_caches.get(aid)
        if pc is None:
            pc = self._prefix_caches[aid] = PrefixCache(self.allocator,
                                                        self._bs)
        return pc

    def _all_prefixes(self) -> list[PrefixCache]:
        if self._prefix is not None:
            return [self._prefix]
        return list(self._prefix_caches.values())

    # ----------------------------------------------------- device programs ---

    def _sample(self, logits: jax.Array, step: jax.Array, salt: int,
                slots: jax.Array | None = None) -> jax.Array:
        """Greedy or temperature sampling, on device. logits: (S, vocab).

        ``salt`` separates the prefill / decode / draft / verify streams — all
        can sample within the same engine tick and must not share gumbel
        noise. Each row additionally folds its slot id (``slots``; default row
        index) into the key, so slots carry independent streams: eviction /
        re-prefill resume and draft-vs-verify sampling never correlate across
        slots. The greedy path is untouched.
        """
        if self.ecfg.greedy or self.ecfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        key = jax.random.fold_in(jax.random.fold_in(self._base_key, step), salt)
        if slots is None:
            slots = jnp.arange(logits.shape[0])
        keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(slots)
        g = jax.vmap(lambda k: jax.random.gumbel(k, logits.shape[-1:]))(keys)
        return jnp.argmax(logits / self.ecfg.temperature + g, axis=-1).astype(jnp.int32)

    def _decode_fn(self, params, tokens, cache, active, step):
        self.decode_traces += 1  # python side effect: counts traces only
        logits, new_cache = model_lib.decode_step(params, tokens, cache, self.cfg)
        # only active slots advance their valid prefix; inactive slots wrote a
        # junk row at their frozen position — the next real token overwrites
        # it, and a slot with no pages writes to an unmapped page (dropped)
        new_len = jnp.where(active, new_cache.length, cache.length)
        next_tok = self._sample(logits[:, -1], step, salt=0)
        return next_tok, new_cache._replace(length=new_len)

    def _prefill_fn(self, params, tokens, lengths, slot_ids, page_map, cache, step):
        self.prefill_traces += 1
        logits, kvs, _ = model_lib._forward(
            params, {"tokens": tokens}, self.cfg, collect_kv=True
        )
        cache = transformer_lib.scatter_prefill_pages(cache, kvs, page_map)
        new_len = cache.length.at[slot_ids].set(lengths, mode="drop")
        last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)
        first_tok = self._sample(last[:, 0], step, salt=1, slots=slot_ids)
        return first_tok, cache._replace(length=new_len)

    def _chunk_target(self, params, tokens, counts, slot_ids, starts, cache,
                      step):
        """Shared device body of a prefill chunk (the speculative engine's
        two-model chunk program reuses it for the target side, so the two
        engines cannot drift). Rows are slot-indexed (tokens[b] lands at
        positions starts[b]..starts[b]+counts[b]-1 of slot b), queries attend
        previously written pages plus the chunk itself. ``starts`` is the
        host-tracked prefill progress — rows with counts > 0 RESET their
        device length to it (a freshly admitted slot may inherit a stale
        length from the slot's previous occupant; chunk 1 must insert at 0,
        exactly as the one-shot prefill sets lengths outright). Rows with
        counts == 0 (decode-phase or stalled slots) keep their length frozen
        and write a junk row there — masked by the length and overwritten by
        the next real insert, exactly like inactive decode rows. Returns
        (sampled next token per row — meaningful only where a prompt ended —
        updated cache, pre-chunk lengths)."""
        n0 = jnp.where(counts > 0, starts, cache.length)
        cache = cache._replace(length=n0)
        logits, cache = model_lib.chunk_prefill_step(
            params, tokens, counts, cache, self.cfg
        )
        last = jnp.take_along_axis(
            logits, jnp.maximum(counts - 1, 0)[:, None, None], axis=1
        )
        tok = self._sample(last[:, 0], step, salt=1, slots=slot_ids)
        return tok, cache, n0

    def _chunk_fn(self, params, tokens, counts, slot_ids, starts, cache, step):
        self.chunk_traces += 1
        tok, cache, _ = self._chunk_target(
            params, tokens, counts, slot_ids, starts, cache, step
        )
        return tok, cache

    # ------------------------------------------------------------- steps ---

    def _bucket(self, n: int) -> int:
        """Prefill width for an ``n``-token prompt: the next power-of-two
        multiple of ``min_bucket``, rounded up to whole pages and capped at
        the block table's span."""
        b = self.ecfg.min_bucket
        while b < n:
            b *= 2
        b = min(b, self.ecfg.max_len)
        return min(-(-max(b, self._bs) // self._bs) * self._bs, self._max_len)

    def _admit(self, free: list[int], done: list[Request], step: int):
        """Admit every queued request that a free slot + free pages can cover
        (earliest deadline first — ``_order_queue``). One-shot mode prefills
        the whole prompt here; chunked mode only reserves the first chunk's
        pages and hands the slot to ``_prefill_progress``.

        With the prefix cache on, admission first walks the radix index:
        matching full pages attach READ-ONLY (``allocator.share``) and only
        the unmatched suffix is prefilled — through the chunk program, the
        one program that can start at an offset. Later writes never land in
        an attached page: the suffix starts at ``s0`` and all writes happen
        at positions >= s0, while attached pages only cover positions < s0 —
        EXCEPT when a fully-cached prompt resumes at ``plen - 1`` inside its
        final hit page, which is exactly the copy-on-write case handled
        below (the page is privatized via one batched device copy before any
        program runs)."""
        if not self._queue or not free:
            return
        self._order_queue()
        reserve = self.ecfg.decode_reserve or self._bs
        admitted: list[tuple[int, Request, list[int], int, int]] = []
        cow_pairs: list[tuple[int, int]] = []
        while self._queue and free:
            req = self._queue[0]
            astat, arow = self._adapter_admit(req, done)
            if astat == "gone":           # unregistered after submit:
                self._queue.pop(0)        # finished rejected, next request
                continue
            if astat == "busy":           # every pool row pinned by a
                break                     # streaming slot: retry next tick
            ptoks = req.prompt + req.out_tokens      # evicted requests resume
            plen = len(ptoks)
            hit: list[int] = []
            s0 = 0           # prefill resumes here; tokens < s0 are cached
            pc = self._prefix_of(req.adapter)
            if pc is not None:
                self.metrics.prefix_event("lookups")
                hit = pc.match(ptoks)
                if len(hit) < self.ecfg.prefix_min_hit_pages:
                    hit = []
                if hit:
                    # the LAST prompt position is always (re)computed — its
                    # logits seed the first sampled token — so a fully-cached
                    # prompt resumes at plen - 1 inside its final hit page
                    s0 = min(len(hit) * self._bs, plen - 1)
                    if self._chunk is not None and plen > self._chunk:
                        # chunk-aligned so the chunked state machine starts
                        # at the hit boundary (and never rewrites a hit page)
                        s0 = s0 // self._chunk * self._chunk
                    hit = hit[: -(-s0 // self._bs)]
                    if not hit:
                        s0 = 0
            if self._chunk is not None and plen - s0 > self._chunk:
                want = s0 + self._chunk              # first chunk only; the
                #                                      rest reserves chunk-by-
                #                                      chunk as prefill advances
            else:
                remaining = max(req.max_new_tokens - len(req.out_tokens), 1)
                want = plen + min(max(reserve, 1), remaining)
            blocks = min(-(-want // self._bs), self._nb_slot)
            cow = bool(hit) and s0 % self._bs != 0   # the suffix's first write
            #                                          lands inside hit[-1]
            fresh_n = max(blocks - len(hit), 0) + (1 if cow else 0)
            if hit:
                # pin the hit FIRST: _alloc may reclaim index-only pages and
                # must not cannibalize the chain being attached
                self.allocator.share(hit)
            fresh = self._alloc(fresh_n)
            if fresh is None:
                if hit:
                    self.allocator.release(hit)
                if self._adapters is not None:       # undo the admit pin —
                    self._adapters.unpin(req.adapter)  # the slot never took
                break                                # pool full: stay queued
            pages = list(hit)
            if cow:
                copy = fresh.pop()
                cow_pairs.append((pages[-1], copy))
                self.allocator.release([pages[-1]])  # drop the shared ref —
                pages[-1] = copy                     # the index keeps its own
                self.metrics.prefix_event("cow_copies")
            pages += fresh
            self._queue.pop(0)
            slot = free.pop()
            now = _now()
            # prefill_compute = the suffix this admission actually schedules
            # through a prefill/chunk program (the hit share never recomputes)
            self.metrics.on_admit(req, slot, now,
                                  prefill_tokens=plen - s0, hit_tokens=s0)
            req.admitted_at = now
            self._active[slot] = req
            self._slot_tier[slot] = self._effective_tier(req)
            if arow is not None:
                self._slot_pool[slot] = arow
                self._slot_adapter[slot] = req.adapter
            self._pages[slot] = pages
            self._table[slot, : len(pages)] = pages
            self._table_dirty = True
            tr = self.tracer
            if tr is not None:
                tr.request_begin(slot, req.uid, t=now, tier=req.tier,
                                 resume=bool(req.evictions))
                tr.begin_span(slot, "prefill", t=now, tokens=plen - s0)
                if hit:
                    tr.instant(slot, "prefix_hit", t=now, pages=len(hit),
                               tokens=s0)
                if cow:
                    tr.instant(slot, "cow", t=now)
            if hit:
                self.metrics.prefix_event("hits")
                if req.evictions:
                    self.metrics.prefix_event("reattached_pages", len(hit))
                # the slot's device length is stale (previous occupant) until
                # its first chunk program resets it; junk rows written by
                # other programs this tick must not land in attached pages
                self._len_reset[slot] = s0
            admitted.append((slot, req, pages, plen, s0))
        if not admitted:
            return
        if cow_pairs:
            self._cow_copy(cow_pairs)
        if self._chunk is not None:
            # chunked mode: no prefill program at admission — mark the slots
            # mid-prefill AT THE HIT BOUNDARY; this same tick's
            # _prefill_progress runs the first unmatched chunk
            for slot, req, _, plen, s0 in admitted:
                self._progress[slot] = s0
                self._ptarget[slot] = plen
            return

        s = self.ecfg.max_slots
        by_slot = {slot: (req, pages, plen)
                   for slot, req, pages, plen, s0 in admitted if s0 == 0}
        for key, slots in self._tier_groups(by_slot):
            group = [(slot, *by_slot[slot]) for slot in slots]
            bucket = self._bucket(max(plen for _, _, _, plen in group))
            nb_bucket = bucket // self._bs
            tokens = np.zeros((s, bucket), np.int32)
            lengths = np.ones((s,), np.int32)
            slot_ids = np.full((s,), s, np.int32)
            page_map = np.full((s, nb_bucket), self.num_blocks, np.int32)
            rows = np.zeros((s,), np.int32)      # GROUP-indexed pool rows
            for i, (slot, req, pages, plen) in enumerate(group):
                ptoks = req.prompt + req.out_tokens
                tokens[i, :plen] = ptoks
                lengths[i] = plen
                slot_ids[i] = slot
                rows[i] = self._slot_pool[slot]
                prompt_blocks = -(-plen // self._bs)
                page_map[i, :prompt_blocks] = pages[:prompt_blocks]
            firsts = self._prefill_admitted(
                tokens, lengths, slot_ids, page_map, step, key, rows
            )
            with self.metrics.span("serve.record"):
                for i, (slot, req, _, _) in enumerate(group):
                    req.prefill_emitted += 1
                    self._record(slot, req, int(firsts[i]), free, done)
        # prefix hits prefill ONLY the unmatched suffix, through the chunk
        # program (slot-indexed rows starting at s0). The sample key
        # (step, salt=1, slot) matches the one-shot prefill's exactly, so a
        # hit admission's stream — greedy or sampled — is identical to what
        # a cache-off full prefill would have emitted this tick
        hits = {slot: (req, plen, s0)
                for slot, req, _, plen, s0 in admitted if s0 > 0}
        for key, slots in self._tier_groups(hits):
            width = self._bucket(max(hits[x][1] - hits[x][2] for x in slots))
            tokens = np.zeros((s, width), np.int32)
            counts = np.zeros((s,), np.int32)
            slot_ids = np.full((s,), s, np.int32)
            starts = np.zeros((s,), np.int32)
            for slot in slots:
                req, plen, s0 = hits[slot]
                ptoks = req.prompt + req.out_tokens
                tokens[slot, : plen - s0] = ptoks[s0:]
                counts[slot] = plen - s0
                slot_ids[slot] = slot
                starts[slot] = s0
            firsts = self._chunk_call(tokens, counts, slot_ids, starts, step,
                                      key)
            with self.metrics.span("serve.record"):
                for slot in slots:
                    req = hits[slot][0]
                    req.prefill_emitted += 1
                    self._record(slot, req, int(firsts[slot]), free, done)

    def _alloc(self, n: int) -> list[int] | None:
        """Pool allocation with the prefix cache as the reclaim tail: when
        the free list cannot cover ``n``, index-only cached pages are
        reclaimed LRU-first — BEFORE any caller resorts to evicting live
        slots. Multi-tenant serving reclaims across EVERY adapter's index
        (including indexes of since-unregistered adapters — that is how
        their orphaned pages drain)."""
        pages = self.allocator.alloc(n)
        if pages is None:
            for pc in self._all_prefixes():
                pc.reclaim(n - self.allocator.free_blocks)
                if self.allocator.free_blocks >= n:
                    break
            pages = self.allocator.alloc(n)
        return pages

    def _cow_copy(self, pairs: list[tuple[int, int]]):
        """ONE batched device page copy for this tick's CoW pairs (the block
        table was already remapped host-side). Pairs pad to a power of two
        with (0, 0) identity entries, so the program compiles O(log) shapes."""
        n = 1
        while n < len(pairs):
            n *= 2
        pad = pairs + [(0, 0)] * (n - len(pairs))
        src = jnp.asarray([p for p, _ in pad], jnp.int32)
        dst = jnp.asarray([q for _, q in pad], jnp.int32)
        with self.metrics.measure_program(f"page_copy[{n}]", rows=len(pairs)):
            self._apply_cow(src, dst)

    def _apply_cow(self, src: jax.Array, dst: jax.Array):
        """Hook: the speculative engine also copies its draft pools here —
        they ride the target's block table and page ids, so the same pairs
        remap both caches."""
        self.cache = self._copy_prog(self.cache, src, dst)

    def _prefill_admitted(self, tokens, lengths, slot_ids, page_map, step,
                          tier: int = 0, rows=None):
        """Device portion of admission (hook: the speculative engine also
        prefills the draft page pools here). ``rows`` is the GROUP-indexed
        adapter-pool row map (batched adapter mode only; the prefill batch
        is group-indexed, unlike the slot-indexed decode/chunk programs).
        Returns first tokens (host)."""
        real = slot_ids < slot_ids.size
        with self.metrics.measure_program(
            f"prefill[{tokens.shape[1]}]", tier,
            traces=lambda: self.prefill_traces,
            rows=int(np.count_nonzero(real)),
            tokens=int(lengths[real].sum()), positions=tokens.size,
        ):
            first, self.cache = self._prefill(
                self._call_params(tier, rows), jnp.asarray(tokens),
                jnp.asarray(lengths), jnp.asarray(slot_ids),
                jnp.asarray(page_map), self.cache,
                jnp.asarray(step, jnp.int32),
            )
            self.prefill_calls += 1
            with self.metrics.span("serve.fetch"):
                return np.asarray(first)

    def _prefill_progress(self, free: list[int], done: list[Request],
                          step: int):
        """Advance every mid-prefill slot by ONE chunk (a single jitted call
        per active tier covers all of them). Per slot: reserve pages for the
        chunk (plus the decode headroom when it is the final chunk). Prefill
        growth never
        evicts — a slot whose chunk cannot get pages STALLS at its last
        completed chunk and resumes once decode-phase slots finish and free
        pages (eviction here would let two contending prefills ping-pong each
        other forever: each re-admits with one chunk's pages and evicts the
        other's progress — measured livelock, see tests). The one deadlock
        case — EVERY active slot is a stalled prefill, so nothing will ever
        free a page — evicts the least-progressed stalled slot and lets the
        SURVIVORS absorb the freed pages within this same tick (deferring to
        the next tick would hand them straight back to the evicted request at
        re-admission — its first chunk can need exactly what eviction freed,
        a measured ping-pong that starves everyone forever); survivor page
        counts therefore grow monotonically and some prefill always
        completes. Slots whose prompt completes emit their first token here
        (the chunked counterpart of the one-shot admission prefill)."""
        if not self._progress:
            return
        reserve = self.ecfg.decode_reserve or self._bs
        while True:
            ready: list[int] = []
            stalled: list[int] = []
            for slot in sorted(self._progress):
                req = self._active.get(slot)
                if req is None:
                    continue
                p = self._progress[slot]
                target = self._ptarget[slot]
                c = min(self._chunk, target - p)
                if p + c >= target:      # final chunk: also reserve headroom
                    remaining = max(req.max_new_tokens - len(req.out_tokens), 1)
                    want = target + min(max(reserve, 1), remaining)
                else:
                    want = p + c
                need = min(-(-want // self._bs), self._nb_slot)
                while len(self._pages[slot]) < need:
                    page = self._alloc(1)
                    if page is None:
                        break
                    idx = len(self._pages[slot])
                    self._pages[slot].append(page[0])
                    self._table[slot, idx] = page[0]
                    self._table_dirty = True
                (ready if len(self._pages[slot]) >= need
                 else stalled).append(slot)
            if ready or not stalled:
                break
            if not all(s in self._progress for s in self._active):
                return   # a decoder is still running: it is bounded by
                #          max_new_tokens and will free its pages — stall
            # total stall, nothing will free a page on its own: evict the
            # least-progressed slot and retry so the survivors take the
            # freed pages NOW (each pass removes one slot, so this loop is
            # bounded by max_slots; a lone survivor always fits by the
            # submit-time validation)
            self._evict(min(stalled, key=lambda s: (self._progress[s], s)),
                        free)
        if not ready:
            return
        s = self.ecfg.max_slots
        for tier, tier_slots in self._tier_groups(ready):
            tokens = np.zeros((s, self._chunk), np.int32)
            counts = np.zeros((s,), np.int32)
            slot_ids = np.full((s,), s, np.int32)
            starts = np.zeros((s,), np.int32)
            for slot in tier_slots:
                req = self._active[slot]
                p = self._progress[slot]
                c = min(self._chunk, self._ptarget[slot] - p)
                ptoks = req.prompt + req.out_tokens
                tokens[slot, :c] = ptoks[p : p + c]
                counts[slot] = c
                slot_ids[slot] = slot
                starts[slot] = p
            t0 = _now()
            firsts = self._chunk_call(tokens, counts, slot_ids, starts, step,
                                      tier)
            if self.tracer is not None:
                t1 = _now()
                for slot in tier_slots:
                    if slot in self._active:
                        self.tracer.begin_span(slot, "prefill_chunk", t=t0,
                                               start=int(starts[slot]),
                                               tokens=int(counts[slot]))
                        self.tracer.end_span(slot, "prefill_chunk", t=t1)
            with self.metrics.span("serve.record"):
                for slot in tier_slots:
                    req = self._active.get(slot)
                    if req is None:
                        continue
                    self._progress[slot] += int(counts[slot])
                    if self._progress[slot] >= self._ptarget[slot]:
                        del self._progress[slot]
                        del self._ptarget[slot]
                        req.prefill_emitted += 1
                        self._record(slot, req, int(firsts[slot]), free, done)

    def _chunk_call(self, tokens, counts, slot_ids, starts, step,
                    tier: int = 0):
        """Device portion of a chunk tick (hook: the speculative engine also
        runs the draft's chunk here). Chunk rows are SLOT-indexed, so the
        batched adapter bind uses the slot→pool-row map directly. Returns
        sampled tokens (host)."""
        with self.metrics.measure_program(
            f"chunk[{tokens.shape[1]}]", tier,
            traces=lambda: self.chunk_traces,
            rows=int(np.count_nonzero(counts)),
            tokens=int(counts.sum()), positions=tokens.size,
        ):
            first, self.cache = self._chunk_prog(
                self._call_params(tier, self._slot_pool), jnp.asarray(tokens),
                jnp.asarray(counts), jnp.asarray(slot_ids),
                jnp.asarray(starts), self._device_cache(),
                jnp.asarray(step, jnp.int32),
            )
            self.chunk_calls += 1
            with self.metrics.span("serve.fetch"):
                return np.asarray(first)

    def _record(self, slot: int, req: Request, tok: int, free, done):
        now = _now()
        req.out_tokens.append(tok)
        req.token_times.append(now)
        first = req.first_token_at == 0.0
        if first:
            req.first_token_at = now
        # the ONE emission point: serve_tokens_total{kind="emitted"} counts
        # each generated token exactly once, however many times eviction
        # re-prefills its context (re-work lands in kind="prefill_compute")
        self.metrics.on_token(req, now, first)
        if self._adapters is not None:
            self.metrics.inc(self.metrics.adapter_tokens, 1, str(req.adapter))
        tr = self.tracer
        if tr is not None and tr.has_open(slot, "prefill"):
            # prefill (or resume re-prefill) just yielded its token: close
            # the prefill span and open the decode envelope
            tr.end_span(slot, "prefill", t=now)
            if first:
                tr.instant(slot, "first_token", t=now, uid=req.uid)
            tr.begin_span(slot, "decode", t=now, uid=req.uid)
        self._last_token[slot] = tok
        if len(req.out_tokens) >= req.max_new_tokens or (
            self.ecfg.eos_token is not None and tok == self.ecfg.eos_token
        ):
            req.done = True
            req.finished_at = now
            self.metrics.on_finish()
            if tr is not None:
                if tr.has_open(slot, "decode"):
                    tr.end_span(slot, "decode", t=now)
                tr.request_end(slot, req.uid, t=now,
                               tokens=len(req.out_tokens),
                               evictions=req.evictions)
            done.append(req)
            self._retire(slot, req)
            del self._active[slot]
            free.append(slot)
            self._release(slot)

    def _pre_decode(self, free: list[int], done: list[Request]):
        """Grow each active slot's pages to cover this tick's KV writes; evict
        when the pool is dry. The next decode writes the KV of the latest
        sampled token at position len(prompt) + len(out) - 1; the speculative
        engine widens the window (``_write_window`` > 1) to cover all k draft
        positions. Writes past the table's capacity drop device-side, so the
        need is capped at the table width."""
        window = self._write_window
        for slot in list(self._active):
            req = self._active.get(slot)
            if req is None or slot in self._progress:
                continue                 # mid-prefill slots grow in their own
                #                          chunk scheduler, not here
            write_pos = len(req.prompt) + len(req.out_tokens) - 1 + (window - 1)
            need = min(write_pos // self._bs + 1, self._nb_slot)
            while slot in self._active and len(self._pages[slot]) < need:
                page = self._alloc(1)
                if page is not None:
                    idx = len(self._pages[slot])
                    self._pages[slot].append(page[0])
                    self._table[slot, idx] = page[0]
                    self._table_dirty = True
                    continue
                victim = self._choose_victim()
                if victim is None:
                    break
                self._evict(victim, free)

    def _choose_victim(self) -> int | None:
        """Pick an eviction victim for DECODE-phase page growth (prefill
        growth stalls instead of evicting — see ``_prefill_progress``). Under
        ``longest_remaining`` a mid-prefill slot counts its full
        ``max_new_tokens`` as remaining, so it is naturally preferred over a
        nearly-finished decoder — its pages stay pinned longest otherwise."""
        if not self._active:
            return None
        if self.ecfg.evict_policy == "lru":
            # least-recently admitted slot
            return min(self._active, key=lambda s: (self._active[s].admitted_at, s))
        # longest_remaining: its pages stay pinned for the longest otherwise
        return max(
            self._active,
            key=lambda s: (
                self._active[s].max_new_tokens - len(self._active[s].out_tokens), s
            ),
        )

    def _evict(self, slot: int, free: list[int]):
        """Return the slot's pages and re-queue its request; it re-prefills
        prompt + generated tokens on re-admission (resumed requests sort
        ahead of fresh ones with the same deadline — see ``_order_queue``)."""
        req = self._active.pop(slot)
        req.evictions += 1
        req.requeued_at = _now()
        self.metrics.on_evict()
        tr = self.tracer
        if tr is not None:
            now = req.requeued_at
            for name in ("decode", "prefill"):
                while tr.has_open(slot, name):
                    tr.end_span(slot, name, t=now, aborted=True)
            tr.instant(slot, "evicted", t=now, uid=req.uid)
            tr.end_span(slot, "request", t=now, uid=req.uid, evicted=True)
        self._retire(slot, req)
        self._release(slot)
        self._queue.append(req)
        free.append(slot)

    def _retire(self, slot: int, req: Request):
        """Publish the slot's FULL pages into the radix index — the KV that
        was actually written: a decode-phase slot has everything but the last
        sampled token's position, an evicted mid-prefill slot its chunk
        progress. The published pages' references TRANSFER to the index
        (``_release`` then only frees the exclusive tail), so finish and
        eviction both leave the prefix warm; eviction-resume reattaches these
        pages instead of chunked re-prefill. Multi-tenant serving publishes
        into the slot's ADAPTER's index — the KV is conditioned on that
        adapter's weights and must never serve another tenant."""
        pc = self._prefix_of(req.adapter)
        if pc is None:
            return
        pages = self._pages.get(slot)
        if not pages:
            return
        ptoks = req.prompt + req.out_tokens
        written = self._progress.get(slot, len(ptoks) - 1)
        n_full = min(written // self._bs, len(pages))
        if n_full <= 0:
            return
        pc.publish(ptoks, pages[:n_full])
        del pages[:n_full]

    def _release(self, slot: int):
        """Return the slot's pages and unpin its adapter (LRU residency may
        swap the adapter out once no slot streams with it)."""
        if self._adapters is not None:
            aid = self._slot_adapter.pop(slot, None)
            if aid is not None:
                self._adapters.unpin(aid)
        pages = self._pages.pop(slot, None)
        if pages:
            # release, not free: attached pages fall back to their remaining
            # holders (the radix index), exclusive pages return to the pool
            self.allocator.release(pages)
        self._table[slot, :] = self.num_blocks
        self._table_dirty = True
        self._len_reset.pop(slot, None)
        self._progress.pop(slot, None)
        self._ptarget.pop(slot, None)

    def _device_cache(self):
        if self._table_dirty:
            # under a mesh the host table is pushed with an explicit
            # replicated placement — block ids are head-replicated, and a
            # committed single-device array would reshard (and retrace) the
            # decode program
            table = (
                jax.device_put(self._table, self.mesh.replicated())
                if self.mesh is not None else jnp.asarray(self._table)
            )
            self.cache = self.cache._replace(block_table=table)
            self._table_dirty = False
        if self._len_reset:
            # pending hit-admission length resets (see _admit): applied before
            # any length-addressed program can write a junk row via a stale
            # length into a page the slot only shares. Padded to a fixed
            # (max_slots,) shape with out-of-range indices (dropped by the
            # scatter) so the jitted program compiles exactly once
            s = self.ecfg.max_slots
            idx = np.full((s,), s, np.int32)
            val = np.zeros((s,), np.int32)
            for i, (slot, s0) in enumerate(self._len_reset.items()):
                idx[i], val[i] = slot, s0
            self.cache = self.cache._replace(
                length=self._len_prog(self.cache.length, idx, val)
            )
            self._len_reset.clear()
        return self.cache

    def _place_cache(self):
        """Shard the KV cache over the mesh (head axis over 'model'; block
        tables / lengths replicated) and record the per-device pool bytes
        gauge. No-op without a mesh — single-device arrays stay as-is."""
        if self.mesh is not None:
            self.cache = jax.device_put(
                self.cache, self.mesh.cache_shardings(self.cache)
            )
        if self.metrics.enabled:
            self.metrics.set_pool_device_bytes(_kv_pool_device_bytes(self.cache))

    def _mesh_scope(self):
        """The mesh context for one tick: activates the ServingMesh so
        ``parallel.sharding.constrain`` and the shard_map-wrapped paged
        kernels see it at trace time; a null context when unsharded."""
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def step(self) -> list[Request]:
        """ONE engine tick: admit whatever fits, refresh effective tiers
        (pressure controller first — downshift precedes any eviction),
        advance mid-prefill slots by one chunk, then one jitted decode step
        per active tier over the decode-phase slots. Returns requests that
        finished this tick."""
        with self.metrics.measure_tick(self._steps + 1, len(self._active),
                                       len(self._queue)):
            with self._mesh_scope():
                done = self._step_inner()
            self._update_gauges()
        return done

    def _step_inner(self) -> list[Request]:
        done: list[Request] = []
        s = self.ecfg.max_slots
        self._steps += 1
        free = [x for x in range(s) if x not in self._active]
        with self.metrics.span("serve.admit"):
            self._admit(free, done, self._steps)
        if not self._active:
            return done
        self._update_tier_shift()
        self._refresh_slot_tiers()
        self._prefill_progress(free, done, self._steps)
        self._pre_decode(free, done)
        active = np.zeros((s,), bool)
        for slot in self._active:
            if slot not in self._progress:   # mid-prefill slots don't decode
                active[slot] = True
        if active.any():
            self._decode_tick(active, free, done)
        return done

    def _update_gauges(self):
        """End-of-tick pool/queue gauges — host counters only, no device
        reads. Short-circuits when telemetry is off so gauge ARGUMENTS cost
        nothing either. ``_prefix.pages`` is an O(1) count of index-held
        pages, NOT ``reclaimable_pages`` — that walks the whole radix tree,
        and a per-tick walk is exactly the overhead telemetry promises not to
        add."""
        if not self.metrics.enabled:
            return
        self.metrics.set_pool(queue=len(self._queue),
                              active=len(self._active),
                              shift=self._tier_shift,
                              free=self.allocator.free_blocks,
                              cached=sum(pc.pages
                                         for pc in self._all_prefixes()))
        if self._adapters is not None:
            self.metrics.set_resident_adapters(len(self._adapters.resident))

    def _decode_tick(self, active: np.ndarray, free: list[int],
                     done: list[Request]):
        """Device portion of a tick (hook: the speculative engine replaces
        this with its draft + k-wide verify program): ONE jitted decode per
        active tier, every call masked to its tier's slots over the shared
        cache. A single-tier bank degenerates to exactly one call per tick;
        each tier's program compiles once, on first use, so a slot switching
        tiers mid-stream never triggers a retrace."""
        s = self.ecfg.max_slots
        tokens = np.zeros((s, 1), np.int32)
        decode_slots = [int(x) for x in np.nonzero(active)[0]]
        for slot in decode_slots:
            tokens[slot, 0] = self._last_token[slot]
        tok_dev = jnp.asarray(tokens)
        step_dev = jnp.asarray(self._steps, jnp.int32)
        out = np.zeros((s,), np.int64)
        for key, slots in self._tier_groups(decode_slots):
            mask = np.zeros((s,), bool)
            mask[slots] = True
            with self.metrics.measure_program(
                "decode", key, traces=lambda: self.decode_traces,
                rows=len(slots),
            ):
                nxt, self.cache = self._decode(
                    self._call_params(key, self._slot_pool), tok_dev,
                    self._device_cache(), jnp.asarray(mask), step_dev,
                )
                self.decode_calls += 1
                with self.metrics.span("serve.fetch"):
                    toks = np.asarray(nxt)   # one host sync per active tier
            out[slots] = toks[slots]
        with self.metrics.span("serve.record"):
            for slot, req in list(self._active.items()):
                if slot in self._progress:
                    continue
                self._record(slot, req, int(out[slot]), free, done)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Drive everything to completion (batch mode)."""
        done: list[Request] = []
        steps = 0
        while self.has_work and steps < max_steps:
            steps += 1
            done.extend(self.step())
        return done


# The benchmark's own tests (bench/tests/test_bench_check.py and
# bench/tests/test_bench_phi3.py) patch ``ServingEngine._record``; the name
# stays for them and nothing else uses it.
ServingEngine = PagedServingEngine


# -------------------------------------------------------------- reference ---


class ReferenceEngine:
    """The seed per-slot, per-token engine (scalar-length cache, slot slicing
    with host write-back, one ``int()`` sync per slot per token).

    Kept as (a) the serving path for cache families without per-slot lengths
    and (b) the test oracle for :class:`PagedServingEngine`, sharing none of
    its paged code.
    Implements the :class:`~repro.serving.elastic.Engine` protocol, including
    per-request bank tiers (each slot decodes with its requested tier's
    parameter tree — no pressure controller, there is no page pool to feel
    pressure from).
    """

    def __init__(self, model, params=None, ecfg: EngineConfig | None = None):
        bank, ecfg = _resolve_engine_args(type(self).__name__, model, params,
                                          ecfg)
        arch_cfg = bank.cfg
        missing = []
        if ecfg.kv_dtype != "float32":
            missing.append(f"kv_dtype={ecfg.kv_dtype!r}")
        if ecfg.spec_k:
            missing.append(f"speculative decoding (spec_k={ecfg.spec_k})")
        if ecfg.prefill_chunk is not None:
            missing.append(
                f"chunked prefill (prefill_chunk={ecfg.prefill_chunk})"
            )
        if ecfg.tier_policy == "pressure":
            missing.append(
                f"tier_policy={ecfg.tier_policy!r} (page-pressure controller)"
            )
        if ecfg.prefix_cache:
            missing.append("prefix_cache=True (radix prompt cache)")
        if ecfg.mesh is not None:
            missing.append(
                f"mesh={ecfg.mesh!r} (tensor-parallel serving needs "
                "PagedServingEngine)"
            )
        if ecfg.adapters or isinstance(bank, AdapterBank):
            missing.append(
                "multi-tenant adapters (AdapterBank serving needs "
                "PagedServingEngine)"
            )
        if missing:
            raise _capability_error(type(self), arch_cfg.family, missing)
        log.info(
            "ReferenceEngine serving family %r: per-slot per-token loop, "
            "contiguous float32 cache — no paged features (kv_dtype, "
            "speculation, eviction/resume)",
            arch_cfg.family,
        )
        self.cfg = arch_cfg
        self.ecfg = ecfg
        self.bank = bank
        self._tier_params, self._default_tier = _bank_tier_state(bank, ecfg)
        self.params = self._tier_params[self._default_tier]
        self._queue: list[Request] = []
        self._active: dict[int, Request] = {}   # slot -> request
        self._uid = 0
        self._steps = 0
        self._slot_len = [0] * ecfg.max_slots

        self.cache = model_lib.init_cache(
            arch_cfg, ecfg.max_slots, ecfg.max_len, dtype=jnp.float32
        )
        self.decode_calls = 0
        self.decode_traces = 0   # python side effect below: counts traces only

        def _decode_fn(p, tok, cache):
            self.decode_traces += 1
            return model_lib.decode_step(p, tok, cache, arch_cfg)

        self._decode = jax.jit(_decode_fn)
        # the same telemetry schema as the batched engine (the registry is
        # shared infrastructure, not a paged-engine feature)
        self.metrics = (EngineTelemetry if ecfg.telemetry
                        else NullTelemetry)(type(self).__name__)
        self.tracer: RequestTracer | None = None
        if ecfg.trace:
            self.start_trace()

    @classmethod
    def capabilities(cls) -> dict:
        return {
            "engine": cls.__name__,
            "families": ["dense", "moe", "vlm", "ssm", "hybrid", "encdec"],
            "kv": "contiguous",
            "features": {
                "kv_dtype": ["float32"],
                "continuous_batching": False,
                "deadlines_edf": False,
                "chunked_prefill": False,
                "eviction_resume": False,
                "speculative": False,
                "elastic_tiers": True,
                "tier_pressure_controller": False,
                "prefix_caching": False,
                "tensor_parallel": False,
                "multi_tenant_adapters": False,
            },
        }

    # ----------------------------------------------------- observability ---

    start_trace = PagedServingEngine.start_trace

    def stats_snapshot(self) -> dict:
        """Same shape as the batched engine's snapshot; the reference loop
        has no prefill program (prompts insert token-by-token through the
        decode step), so the prefill counters report 0."""
        return {
            "engine": type(self).__name__,
            "steps": self._steps,
            "decode_calls": self.decode_calls,
            "decode_traces": self.decode_traces,
            "prefill_calls": 0,
            "prefill_traces": 0,
            "jit_retraces": self.metrics.retraces(),
            "slowest_step": self.metrics.slowest_step(),
            "metrics": self.metrics.snapshot(),
        }

    # ------------------------------------------------------------ intake ---

    def submit(self, prompt: list[int], max_new_tokens: int = 16,
               deadline: float | None = None,
               tier: int | None = None,
               submitted_at: float | None = None,
               adapter: int | None = None) -> int:
        try:
            _validate_request(prompt, max_new_tokens, self.ecfg.max_len)
            t = _resolve_request_tier(self.bank, self._default_tier, tier)
            if adapter is not None:
                raise RequestRejected(
                    f"adapter={adapter}: ReferenceEngine serves no adapters "
                    "(AdapterBank needs PagedServingEngine)"
                )
        except RequestRejected:
            self.metrics.on_reject()
            raise
        self._uid += 1
        self._queue.append(
            Request(self._uid, list(prompt), max_new_tokens,
                    submitted_at=_now() if submitted_at is None
                    else submitted_at,
                    deadline=deadline, tier=t)
        )
        self.metrics.on_submit()
        return self._uid

    @property
    def has_work(self) -> bool:
        return bool(self._queue or self._active)

    # ------------------------------------------------------------- steps ---

    def _prefill_into_slot(self, slot: int, req: Request):
        """Per-token insertion into this slot's cache rows (the LAST prompt
        token is fed by the first decode step, so prefill stops one short)."""
        self._slot_len[slot] = 0
        for tok in req.prompt[:-1]:
            self._step_slot(slot, tok)

    def step(self) -> list[Request]:
        """One engine tick (Engine protocol): admit into free slots, then one
        token for every active slot (the seed per-slot loop — one device call
        and one host sync per slot)."""
        with self.metrics.measure_tick(self._steps + 1, len(self._active),
                                       len(self._queue)):
            done = self._step_inner()
            self.metrics.set_pool(queue=len(self._queue),
                                  active=len(self._active))
        return done

    def _step_inner(self) -> list[Request]:
        done: list[Request] = []
        self._steps += 1
        tr = self.tracer
        free = [s for s in range(self.ecfg.max_slots) if s not in self._active]
        with self.metrics.span("serve.admit"):
            while self._queue and free:
                slot = free.pop()
                req = self._queue.pop(0)
                now = _now()
                self.metrics.on_admit(req, slot, now,
                                      prefill_tokens=len(req.prompt))
                req.admitted_at = now
                self._active[slot] = req
                if tr is not None:
                    tr.request_begin(slot, req.uid, t=now, tier=req.tier)
                    tr.begin_span(slot, "prefill", t=now,
                                  tokens=len(req.prompt))
                self._prefill_into_slot(slot, req)
        tokens = {slot: self._step_slot(slot, (req.out_tokens or req.prompt)[-1])
                  for slot, req in self._active.items()}
        with self.metrics.span("serve.record"):
            for slot, nxt in tokens.items():
                req = self._active[slot]
                req.out_tokens.append(int(nxt))
                now = _now()
                req.token_times.append(now)
                first = req.first_token_at == 0.0
                if first:
                    req.first_token_at = now
                self.metrics.on_token(req, now, first)
                if tr is not None and tr.has_open(slot, "prefill"):
                    tr.end_span(slot, "prefill", t=now)
                    if first:
                        tr.instant(slot, "first_token", t=now, uid=req.uid)
                    tr.begin_span(slot, "decode", t=now, uid=req.uid)
                if (
                    len(req.out_tokens) >= req.max_new_tokens
                    or (self.ecfg.eos_token is not None
                        and nxt == self.ecfg.eos_token)
                ):
                    req.done = True
                    req.finished_at = now
                    self.metrics.on_finish()
                    if tr is not None:
                        if tr.has_open(slot, "decode"):
                            tr.end_span(slot, "decode", t=now)
                        tr.request_end(slot, req.uid, t=now,
                                       tokens=len(req.out_tokens))
                    done.append(req)
                    del self._active[slot]
        return done

    def run(self, max_steps: int = 10_000) -> list[Request]:
        done: list[Request] = []
        steps = 0
        while self.has_work and steps < max_steps:
            steps += 1
            done.extend(self.step())
        return done

    def _step_slot(self, slot: int, token: int) -> int:
        """One decode step for one slot (per-slot cache view + write-back),
        with the slot's REQUESTED tier's parameters — each tier's program
        traces once, like any other shape."""
        sub_cache = jax.tree.map(
            lambda x: x[:, slot : slot + 1] if x.ndim >= 2 and x.shape[1] == self.ecfg.max_slots else x,
            self.cache,
        )
        sub_cache = sub_cache._replace(length=jnp.asarray(self._slot_len[slot], jnp.int32))
        tok = jnp.asarray([[token]], jnp.int32)
        req = self._active[slot]
        params = self._tier_params[req.tier]

        def write_back(full, sub):
            if full.ndim >= 2 and full.shape[1] == self.ecfg.max_slots:
                return full.at[:, slot : slot + 1].set(sub)
            return full

        with self.metrics.measure_program(
            "decode_ref", req.tier, traces=lambda: self.decode_traces, rows=1
        ):
            logits, new_sub = self._decode(params, tok, sub_cache)
            self.decode_calls += 1
            with self.metrics.span("serve.fetch"):
                nxt = int(jnp.argmax(logits[0, -1]))
        updated = jax.tree.map(write_back, self.cache, new_sub)
        self.cache = updated._replace(length=self.cache.length)
        self._slot_len[slot] += 1
        return nxt

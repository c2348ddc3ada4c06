"""Elastic deployment as a serving-time dimension: one weight bank, many tiers.

SALAAD's headline claim — one training run yields a *continuous spectrum* of
deployable capacities (HPA, §4.3) — used to live only offline in this repo:
``benchmarks/fig3_elastic.py`` swept budgets, but every engine was built
around ONE fixed-budget ``DeployedModel`` and changing capacity meant
rebuilding (and re-jitting) the whole engine. This module makes the spectrum
a first-class serving dimension:

``ModelBank``
    Holds the trained SLR (L + S) weights ONCE and materializes an ordered
    set of budget **tiers** — each a :class:`~repro.serving.deployed.
    DeployedModel` view produced by HPA truncation of the same state. Tier 0
    is the largest capacity; indices grow toward the cheap end of the
    spectrum. Leaves that HPA does not touch (embeddings, norms, any
    unselected block) are the *same array objects* in every tier — the bank
    reports that shared base alongside per-tier ``param_bytes``.

``Engine`` (protocol)
    The front-end contract every serving engine implements:
    ``submit / step / run / has_work / capabilities``. ``capabilities`` is a
    structured dict (families, KV layout, per-feature availability) — it
    feeds ``EngineCapabilityError`` messages and the ``launch/serve.py
    --help`` table, so "that feature is paged-only" is data, not prose.

``TierController``
    The serving-time counterpart of ``core/controller.py``'s I-controller:
    it integrates the tracking error between the free-page fraction of the
    paged engine's pool and a setpoint, and emits a tier *downshift* — under
    page pressure every slot serves at a cheaper tier (faster steps, sooner
    completions, sooner frees) BEFORE the engine resorts to eviction; when
    pressure clears the shift decays back to zero and slots return to their
    requested tiers. Because the paged KV's block table and page pools are
    tier-agnostic, a slot switches tiers mid-stream with no KV copy and no
    recompilation (each tier's program compiles once, on first use).

The same tier-agnosticism extends to the radix prompt cache
(serving/prefix_cache.py): shared KV pages carry no tier tag, so a prefix
prefilled while serving at one tier is reattached by admissions pinned to any
other — exactly the approximation a mid-stream tier switch already makes. The
controller's pressure signal counts the cache's reclaimable LRU tail as free
capacity, so a warm prefix cache does not read as scarcity and trigger
spurious downshifts.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterator, Protocol, runtime_checkable

import jax
import numpy as np

from ..core.hpa import hpa_keep_ratio
from .deployed import DeployedModel, SlrKernelPlan
from .slr_params import SLRLinear

__all__ = [
    "Engine",
    "ModelBank",
    "Tier",
    "TierController",
    "TierControllerConfig",
    "format_capability_table",
]


# ------------------------------------------------------------------- bank ---


@dataclass(frozen=True)
class Tier:
    """One budget tier of a :class:`ModelBank` (ordered: 0 = largest)."""

    index: int
    name: str
    keep: float | None          # HPA keep-ratio, None for wrapped weights
    model: DeployedModel
    param_bytes: int            # total served bytes of this tier's view
    slr_kernel: SlrKernelPlan | None = None  # fused SLR grid, deploy time

    @property
    def params(self) -> Any:
        return self.model.params


def _tier_bytes(model: DeployedModel) -> int:
    return model.param_bytes()["total_bytes"]


class ModelBank:
    """The trained SLR weights held once, served at an ordered set of tiers.

    Construction either wraps already-deployed models (``ModelBank(cfg,
    models)`` — the caller's order IS the tier order, largest first) or
    materializes the spectrum from one (params, SLR state) pair
    (:meth:`build`). Either way the bank replaces the old ``(arch_cfg,
    params)`` engine constructor contract: engines take ``(bank, ecfg)`` and
    read the architecture config and every tier's parameter tree from here.
    """

    def __init__(self, cfg, models, keeps=None, names=None):
        if not models:
            raise ValueError("ModelBank needs at least one tier")
        keeps = list(keeps) if keeps is not None else [None] * len(models)
        names = list(names) if names is not None else [None] * len(models)
        if len(keeps) != len(models) or len(names) != len(models):
            raise ValueError(
                f"{len(models)} tier model(s) but {len(keeps)} keep(s) / "
                f"{len(names)} name(s)"
            )
        self.cfg = cfg
        self._tiers: list[Tier] = []
        for i, model in enumerate(models):
            if not isinstance(model, DeployedModel):
                # raw param tree (e.g. a dense init): serve it as-is
                model = DeployedModel(cfg, model, fmt="dense")
            name = names[i] or (
                f"keep={keeps[i]:g}" if keeps[i] is not None else f"tier{i}"
            )
            self._tiers.append(
                Tier(index=i, name=name, keep=keeps[i], model=model,
                     param_bytes=_tier_bytes(model),
                     slr_kernel=model.slr_kernel_plan())
            )

    # ------------------------------------------------------------- build ---

    @classmethod
    def build(
        cls,
        cfg,
        params: Any,
        state,
        blocks,
        budgets=(1.0,),
        *,
        kappa: float = 0.7,
        fmt: str = "factored",
        bsr_block: int = 128,
    ) -> "ModelBank":
        """Materialize the elastic spectrum: one HPA truncation + deployment
        per budget, all views over the same base ``params`` tree. Budgets are
        sorted descending (tier 0 = largest capacity) and must be unique and
        in (0, 1]."""
        budgets = [float(b) for b in budgets]
        if not budgets:
            raise ValueError("ModelBank.build needs at least one budget")
        if len(set(budgets)) != len(budgets):
            raise ValueError(f"duplicate budgets in {budgets}")
        for b in budgets:
            if not 0.0 < b <= 1.0:
                raise ValueError(f"budget {b} outside (0, 1]")
        budgets = sorted(budgets, reverse=True)
        models = []
        for keep in budgets:
            slr_c, _ = hpa_keep_ratio(state, blocks, keep, kappa)
            models.append(
                DeployedModel.build(cfg, params, slr_c, blocks, fmt=fmt,
                                    bsr_block=bsr_block)
            )
        return cls(cfg, models, keeps=budgets)

    @classmethod
    def single(cls, cfg, weights) -> "ModelBank":
        """Wrap one already-deployed model (or a raw param tree) as a
        single-tier bank — the shortest path from one weight tree to an
        engine constructor."""
        return cls(cfg, [weights])

    # ------------------------------------------------------------ access ---

    def __len__(self) -> int:
        return len(self._tiers)

    def __iter__(self) -> Iterator[Tier]:
        return iter(self._tiers)

    def __getitem__(self, i: int) -> Tier:
        return self._tiers[self.resolve(i)]

    @property
    def tiers(self) -> tuple[Tier, ...]:
        return tuple(self._tiers)

    @property
    def num_tiers(self) -> int:
        return len(self._tiers)

    def resolve(self, tier: int) -> int:
        """Validated tier index (negative indices count from the cheap end)."""
        t = int(tier)
        n = len(self._tiers)
        if not -n <= t < n:
            raise ValueError(
                f"tier {tier} out of range for a {n}-tier bank "
                f"({[x.name for x in self._tiers]})"
            )
        return t % n

    def params(self, tier: int) -> Any:
        return self._tiers[self.resolve(tier)].params

    # -------------------------------------------------------- accounting ---

    def shared_base_bytes(self) -> int:
        """Bytes of leaves that are the SAME array object in every tier —
        the weight memory one bank amortizes across the whole spectrum
        (embeddings, norms, unselected blocks: HPA never copies them)."""
        if len(self._tiers) == 1:
            return 0

        def leaf_ids(tree) -> dict[int, Any]:
            is_slr = lambda x: isinstance(x, SLRLinear)  # noqa: E731
            return {
                id(leaf): leaf
                for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_slr)
                if not isinstance(leaf, SLRLinear)
            }

        common = None
        first = leaf_ids(self._tiers[0].params)
        for tier in self._tiers[1:]:
            ids = set(leaf_ids(tier.params))
            common = ids if common is None else common & ids
        common &= set(first)
        return sum(
            int(np.prod(first[i].shape)) * first[i].dtype.itemsize
            for i in common
        )

    def report(self, widths=()) -> dict:
        """Per-tier served bytes + the shared base, for provenance payloads.
        A tier that runs the fused SLR kernel also reports its grid steps
        per program of each of ``widths`` token rows and the live share of
        the S tiles it streams (``SlrKernelPlan``)."""
        return {
            "num_tiers": len(self._tiers),
            "tiers": [
                {
                    "index": t.index,
                    "name": t.name,
                    "keep": t.keep,
                    "fmt": t.model.fmt,
                    "param_bytes": t.param_bytes,
                    "slr_kernel": None if t.slr_kernel is None
                    else t.slr_kernel.report(widths),
                }
                for t in self._tiers
            ],
            "shared_base_bytes": self.shared_base_bytes(),
        }


# --------------------------------------------------------------- protocol ---


@runtime_checkable
class Engine(Protocol):
    """The serving front-end contract. ``launch/serve.py``, the ``serve_*``
    benchmarks, and the examples program against THIS, not a concrete class —
    it is the seam the ROADMAP's remaining serving items (sharded serving,
    ssm/hybrid/encdec engines) plug into."""

    def submit(self, prompt: list[int], max_new_tokens: int = 16,
               deadline: float | None = None,
               tier: int | None = None,
               adapter: int | None = None,
               submitted_at: float | None = None) -> int:
        """Enqueue a request; returns its uid. ``tier`` pins the request to a
        bank tier (None = the engine's default tier). ``adapter`` names a
        registered adapter when the engine serves an ``AdapterBank``
        (None = the bank's default adapter). ``submitted_at``
        (monotonic clock) lets open-loop harnesses backdate the submission to
        the SCHEDULED arrival — the one timestamp basis every TTFT metric
        uses (None = now). Raises ``RequestRejected`` when the request can
        never be served."""
        ...

    def step(self) -> list:
        """One engine tick; returns the requests that finished this tick."""
        ...

    def run(self, max_steps: int = 10_000) -> list:
        """Drive everything to completion (batch mode)."""
        ...

    @property
    def has_work(self) -> bool: ...

    @classmethod
    def capabilities(cls) -> dict:
        """Structured capability report: which cache families this engine
        serves, its KV layout, and per-feature availability."""
        ...

    def stats_snapshot(self) -> dict:
        """Host-side serving stats: scheduler/jit counters plus the
        ``serving/telemetry.py`` metrics-registry snapshot. Every engine
        also carries ``engine.metrics`` (an ``EngineTelemetry`` — one metric
        schema across engines, Prometheus-exportable) and ``start_trace()``
        (a ``serving/trace.py`` span tracer with Chrome-trace export)."""
        ...


def format_capability_table(engines: dict[str, type]) -> str:
    """Render ``capabilities()`` of several engine classes as a text table
    (the ``launch/serve.py --help`` epilog)."""
    caps = {name: cls.capabilities() for name, cls in engines.items()}
    features = sorted({f for c in caps.values() for f in c["features"]})
    rows = [["engine", "families", "kv"] + features]
    for name, c in caps.items():
        fam = ",".join(c["families"])
        rows.append(
            [name, fam, c["kv"]]
            + [_fmt_feature(c["features"][f]) for f in features]
        )
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    lines = ["  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
             for row in rows]
    lines.insert(1, "-" * len(lines[0]))
    return "\n".join(lines)


def _fmt_feature(v) -> str:
    if isinstance(v, bool):
        return "yes" if v else "no"
    if isinstance(v, (list, tuple)):
        return ",".join(str(x) for x in v)
    return str(v)


# ------------------------------------------------------------- controller ---


@dataclass(frozen=True)
class TierControllerConfig:
    target_free_frac: float = 0.25   # free-page fraction setpoint
    gain: float = 4.0                # integral gain: tiers per unit pressure
    ema: float = 0.5                 # smoothing of the observed free fraction


class TierController:
    """I-controller over the serving-tier downshift (``core/controller.py``
    style, like the speculative window's ``SpecController``).

    Integrates the tracking error between the setpoint and the observed
    (EMA-smoothed) free-page fraction of the paged pool:

        shift_f <- clip(shift_f + gain * (target_free - free_frac), 0, T-1)

    Pressure (free fraction below the setpoint) accumulates a positive shift:
    every slot serves ``shift`` tiers below its requested tier, so decode
    steps get cheaper, sequences finish sooner, and pages return to the pool
    — the engine spends capacity *quality* before it spends *requests*
    (eviction stays the last resort when the pool actually runs dry). When
    pressure clears the error changes sign and the shift decays back to 0.
    The float state quantizes to an int at read time, so the engine runs at
    most ``num_tiers`` distinct (already-compiled) programs.
    """

    def __init__(self, num_tiers: int,
                 cfg: TierControllerConfig = TierControllerConfig()):
        if num_tiers < 1:
            raise ValueError(f"num_tiers must be >= 1, got {num_tiers}")
        if not 0.0 < cfg.target_free_frac < 1.0:
            raise ValueError(
                f"target_free_frac {cfg.target_free_frac} outside (0, 1)"
            )
        self.cfg = cfg
        self.num_tiers = int(num_tiers)
        self.shift_f = 0.0
        self.free_ema: float | None = None

    @property
    def shift(self) -> int:
        return int(round(self.shift_f))

    def update(self, free_frac: float) -> int:
        """One integral step on the observed free-page fraction."""
        c = self.cfg
        self.free_ema = (
            float(free_frac) if self.free_ema is None
            else c.ema * self.free_ema + (1.0 - c.ema) * float(free_frac)
        )
        err = c.target_free_frac - self.free_ema
        self.shift_f = float(
            np.clip(self.shift_f + c.gain * err, 0.0, self.num_tiers - 1)
        )
        return self.shift

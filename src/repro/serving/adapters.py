"""Multi-tenant adapter serving: one shared base, N registered (L+S) adapters.

SALAAD's factored deployment form is structurally a LoRA-style delta over a
shared dense base — ``W ~= W_base``-preserving leaves plus per-tenant
``(P, Vt, S)`` tables at the selected linear sites. One pool of serving
hardware should therefore serve *many* fine-tuned adapters from one base,
with per-request adapter selection, not just budget tiers of one model.

``AdapterRegistry``
    Host-side bookkeeping: ``register``/``unregister`` of deployed adapter
    models over one base :class:`~repro.serving.deployed.DeployedModel`. The
    base defines the *site schema* — which param-tree paths are per-adapter
    (the SLR sites) — and every registered adapter must match the base
    everywhere else (the shared pytree is stored ONCE).

``AdapterizedLinear``
    One pooled linear site as a registered pytree: every resident adapter's
    padded tables stacked over a leading adapter axis (rank padded to a
    common MAXR, sparse tables to a common MAXB/cap — padding is exact:
    zero rank columns and dead BSR slots contribute nothing), plus a ``sel``
    leaf the bank re-binds per program call. Two modes:

      * ``batched`` (fused format): ``sel`` is a per-slot ``(S,)`` row map
        and ONE ``kernels.ops.slr_matmul_multi`` call serves slots running
        different adapters — the adapter gather lives in the kernel's
        scalar-prefetched DMA index maps, one compiled program for any
        slot→adapter assignment.
      * ``grouped`` (dense/factored fallback, and any shape the batched
        kernel rejects): ``sel`` is a scalar pool row and the scheduler runs
        one program per distinct resident adapter — op-for-op identical to
        the single-tenant tier path, so a single-adapter bank is
        bitwise-indistinguishable from a plain ``ModelBank`` tier.

``AdapterBank``
    A single-tier :class:`~repro.serving.elastic.ModelBank` whose tier params
    are the pooled tree. It owns a fixed-capacity on-device adapter pool
    (``max_resident`` rows) with LRU residency: ``acquire`` swaps a
    non-resident adapter's host tables into a pool row (a pure ``.at[].set``
    — shapes are frozen at :meth:`materialize`, so swaps never retrace),
    ``pin``/``unpin`` track streaming slots so LRU never evicts an adapter
    mid-request, and ``bind`` stamps ``sel`` into every pooled site for one
    program call (a data-only rebind: zero retraces across adapter switches).
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core import sparse
from ..kernels.slr_matmul import BsrStack
from .deployed import DeployedModel, _LINEAR_KEYS
from .elastic import ModelBank, Tier
from .slr_params import SLRLinear

__all__ = [
    "AdapterBank",
    "AdapterError",
    "AdapterRegistry",
    "AdapterizedLinear",
    "adapterize",
]


class AdapterError(RuntimeError):
    """Adapter lifecycle violation (unknown id, unregister-while-streaming,
    post-freeze registration that exceeds the pool's padded dims)."""


# ------------------------------------------------------------ pooled site ---


@dataclass
class AdapterizedLinear:
    """One pooled linear site: per-adapter tables stacked over a leading
    adapter axis + the ``sel`` leaf the bank binds per program call.

    Stacked fused sites flatten (adapter, layer) into one leading axis of
    size ``A * L`` — the kernels stay adapter/layer-agnostic and callers
    index row ``sel * L + layer``.
    """

    w: jax.Array | None            # dense fmt: (A, [L,] n, m)
    p: jax.Array | None            # (A*L | A, n, MAXR) fused; (A, [L,] n, MAXR) factored
    vt: jax.Array | None
    s_coo: sparse.CooMatrix | None  # factored fmt: values/idx (A, [L,] cap)
    s_stack: BsrStack | None       # fused fmt: leading A*L (stacked) or A
    sel: jax.Array | None          # (S,) batched / () grouped — bound per call
    shape: tuple[int, int]
    fmt: str                       # 'dense' | 'factored' | 'fused'
    mode: str                      # 'batched' | 'grouped'
    stacked: bool                  # site lives inside the layer scan
    layers: int                    # L (1 for unstacked sites)

    # ---- transformer integration: duck-typed like SLRLinear ----

    @property
    def scan_by_index(self) -> bool:
        """Stacked sites must not be sliced as scan xs (that would copy the
        whole pool out of HBM per layer) — the forward scans layer indices
        and takes :meth:`at_layer` views, exactly like fused SLRLinears."""
        return self.stacked

    def at_layer(self, layer) -> "_AdapterLayerView":
        assert self.stacked
        return _AdapterLayerView(self, layer)

    def apply(self, x: jax.Array, kernel: bool | None = None) -> jax.Array:
        """Unstacked sites (e.g. a selected LM head) apply directly."""
        return self._apply(x, None)

    @property
    def dtype(self):
        for part in (self.w, self.p,
                     self.s_coo and self.s_coo.values,
                     self.s_stack and self.s_stack.vals):
            if part is not None:
                return part.dtype
        return jnp.float32

    def with_sel(self, sel) -> "AdapterizedLinear":
        return replace(self, sel=sel)

    # ---- apply paths ----

    def _apply(self, x: jax.Array, layer) -> jax.Array:
        assert self.sel is not None, "AdapterBank.bind() must run per call"
        if self.mode == "grouped":
            return self._apply_grouped(x, layer)
        return self._apply_batched(x, layer)

    def _apply_batched(self, x: jax.Array, layer) -> jax.Array:
        # fused only: one multi-adapter kernel pass, slots pick their adapter
        from ..kernels.ops import slr_matmul_multi

        assert x.ndim == 3, x.shape
        ids = self.sel
        if self.stacked:
            ids = ids * self.layers + layer
        return slr_matmul_multi(x, self.p, self.vt, self.s_stack, ids)

    def _apply_grouped(self, x: jax.Array, layer) -> jax.Array:
        # every program call serves ONE adapter (scalar sel): index the pool
        # and run the exact single-tenant ops — bitwise-parity path
        a = self.sel

        def idx(t):
            return jax.lax.dynamic_index_in_dim(t, a, keepdims=False)

        def idx_l(t):
            out = idx(t)
            if layer is not None:
                out = jax.lax.dynamic_index_in_dim(out, layer, keepdims=False)
            return out

        if self.fmt == "dense":
            return x @ idx_l(self.w)
        if self.fmt == "factored":
            y = None
            if self.p is not None:
                y = (x @ idx_l(self.p)) @ idx_l(self.vt)
            if self.s_coo is not None:
                coo = sparse.CooMatrix(
                    idx_l(self.s_coo.values), idx_l(self.s_coo.idx),
                    self.s_coo.shape,
                )
                s_dense = sparse.to_dense(coo).astype(x.dtype)
                y = x @ s_dense if y is None else y + x @ s_dense
            if y is None:
                y = jnp.zeros((*x.shape[:-1], self.shape[1]), x.dtype)
            return y
        # fused
        from ..kernels.ops import slr_matmul, slr_matmul_stacked

        flat = x.reshape(-1, x.shape[-1])
        if self.stacked:
            lid = a * self.layers + layer
            y = slr_matmul_stacked(flat, self.p, self.vt, self.s_stack, lid)
        else:
            p = None if self.p is None else idx(self.p)
            vt = None if self.vt is None else idx(self.vt)
            bsr = None if self.s_stack is None else self.s_stack.at_layer(a)
            y = slr_matmul(flat, p, vt, bsr)
        return y.reshape(*x.shape[:-1], self.shape[1])


jax.tree_util.register_dataclass(
    AdapterizedLinear,
    data_fields=["w", "p", "vt", "s_coo", "s_stack", "sel"],
    meta_fields=["shape", "fmt", "mode", "stacked", "layers"],
)


class _AdapterLayerView:
    """Layer ``l`` of a stacked pooled site — deliberately NOT a pytree,
    built inside the layer-scan body like ``SLRLayerView``."""

    __slots__ = ("lin", "layer")

    def __init__(self, lin: AdapterizedLinear, layer):
        self.lin = lin
        self.layer = layer

    def apply(self, x: jax.Array) -> jax.Array:
        return self.lin._apply(x, self.layer)

    @property
    def dtype(self):
        return self.lin.dtype


def adapterize(base: DeployedModel, model: DeployedModel) -> DeployedModel:
    """Normalize a fine-tuned deployment into a registrable adapter: the
    model's tree with every NON-SITE leaf replaced by the base's leaf.

    SALAAD selection may cover non-linear blocks (e.g. the embedding), which
    deploy as materialized dense leaves that differ per fine-tune; a
    multi-tenant bank shares those with the base — only the linear sites
    carry per-adapter tables. Parity is defined against the RETURNED model
    (it is what the bank actually serves), so single-tenant references in
    tests/benchmarks must use it too.
    """
    if model.fmt != base.fmt:
        raise AdapterError(
            f"adapter fmt {model.fmt!r} != base fmt {base.fmt!r}"
        )
    is_slr = lambda x: isinstance(x, SLRLinear)  # noqa: E731

    def pick(path, b, m):
        if base.fmt == "dense":
            return m if _is_pool_path(path) else b
        return m if isinstance(m, SLRLinear) else b

    tree = jax.tree_util.tree_map_with_path(
        pick, base.params, model.params, is_leaf=is_slr
    )
    return DeployedModel(model.cfg, tree, model.fmt)


# -------------------------------------------------------------- site spec ---


def _is_pool_path(path) -> bool:
    key = path[-1]
    name = getattr(key, "key", getattr(key, "name", None))
    return name in _LINEAR_KEYS


def _leaves_equal(a, b) -> bool:
    if a is b:
        return True
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(a, b)


class _Site:
    """Padding schema + pool builder for ONE per-adapter site.

    ``observe`` accumulates the max padded dims (rank, BSR MAXB, COO cap)
    over pre-freeze registrations; ``freeze`` fixes them; ``pad`` turns one
    adapter's leaf into pool-row tables; ``build``/``set_row`` create and
    update the device-side :class:`AdapterizedLinear`.
    """

    def __init__(self, path_key: str, base_leaf, fmt: str, mode: str):
        self.key = path_key
        self.fmt = fmt
        self.mode = mode
        if fmt == "dense":
            self.shape = tuple(base_leaf.shape[-2:])
            self.stacked = base_leaf.ndim == 3
            self.layers = base_leaf.shape[0] if self.stacked else 1
            self.dtype = base_leaf.dtype
        else:
            assert isinstance(base_leaf, SLRLinear), type(base_leaf)
            self.shape = base_leaf.shape
            self.stacked = base_leaf.ndim == 3
            self.layers = (
                _leading_dim(base_leaf) if self.stacked else 1
            )
            self.dtype = base_leaf.dtype
        self.maxr = 0
        self.maxb = 0
        self.cap = 0
        self.block_size = None
        self.any_sparse = False
        self.frozen = False
        # pool dtypes track the adapters' own table dtypes: an upcast would
        # change matmul numerics vs the single-tenant path
        self.p_dtype = np.dtype(np.float32)
        self.s_dtype = np.dtype(np.float32)

    # ------------------------------------------------------------ observe --

    def check(self, leaf):
        """Validate one adapter's leaf against the schema; post-freeze, its
        padded dims must fit the frozen pool."""
        if self.fmt == "dense":
            want = (self.layers, *self.shape) if self.stacked else self.shape
            if tuple(leaf.shape) != want:
                raise AdapterError(
                    f"site {self.key}: shape {tuple(leaf.shape)} != {want}"
                )
            return
        if not isinstance(leaf, SLRLinear):
            raise AdapterError(
                f"site {self.key}: expected an SLRLinear, got {type(leaf).__name__}"
            )
        if leaf.shape != self.shape or (leaf.ndim == 3) != self.stacked:
            raise AdapterError(
                f"site {self.key}: shape {leaf.shape} != {self.shape}"
            )
        r, maxb, cap, bs = _leaf_dims(leaf)
        if bs is not None and self.block_size is not None and bs != self.block_size:
            raise AdapterError(
                f"site {self.key}: BSR block size {bs} != {self.block_size}"
            )
        if self.frozen and (r > self.maxr or maxb > self.maxb or cap > self.cap):
            raise AdapterError(
                f"site {self.key}: adapter dims (r={r}, maxb={maxb}, cap={cap}) "
                f"exceed the frozen pool (r={self.maxr}, maxb={self.maxb}, "
                f"cap={self.cap}); build the AdapterBank with this adapter "
                "registered up front"
            )

    def observe(self, leaf):
        self.check(leaf)
        if self.fmt == "dense":
            return
        r, maxb, cap, bs = _leaf_dims(leaf)
        self.maxr = max(self.maxr, r)
        self.maxb = max(self.maxb, maxb)
        self.cap = max(self.cap, cap)
        if bs is not None:
            self.block_size = bs
        self.any_sparse = self.any_sparse or maxb > 0 or cap > 0
        if leaf.p is not None:
            self.p_dtype = np.dtype(leaf.p.dtype)
        tab = leaf.s_stack if leaf.s_stack is not None else leaf.s_bsr
        if tab is not None:
            self.s_dtype = np.dtype(tab.vals.dtype)
        elif leaf.s_coo is not None:
            self.s_dtype = np.dtype(leaf.s_coo.values.dtype)

    def freeze(self):
        self.frozen = True

    # ---------------------------------------------------------------- pad --

    def pad(self, leaf) -> dict[str, np.ndarray]:
        """One adapter's leaf → zero-padded pool-row tables (host arrays).
        Padding is exact: zero rank columns and dead sparse slots add 0."""
        self.check(leaf)
        L, (n, m) = self.layers, self.shape
        lead = (L,) if self.stacked else ()
        out = {}
        if self.fmt == "dense":
            out["w"] = np.asarray(leaf, self.dtype)
            return out
        if self.maxr:
            p = np.zeros((*lead, n, self.maxr), self.p_dtype)
            vt = np.zeros((*lead, self.maxr, m), self.p_dtype)
            if leaf.p is not None:
                r = leaf.p.shape[-1]
                p[..., :r] = np.asarray(leaf.p, self.p_dtype)
                vt[..., :r, :] = np.asarray(leaf.vt, self.p_dtype)
            out["p"], out["vt"] = p, vt
        if self.fmt == "factored" and self.any_sparse:
            vals = np.zeros((*lead, self.cap), self.s_dtype)
            idx = np.full((*lead, self.cap), -1, np.int32)
            if leaf.s_coo is not None:
                c = leaf.s_coo.values.shape[-1]
                vals[..., :c] = np.asarray(leaf.s_coo.values, self.s_dtype)
                idx[..., :c] = np.asarray(leaf.s_coo.idx, np.int32)
            out["coo_vals"], out["coo_idx"] = vals, idx
        if self.fmt == "fused" and self.any_sparse:
            bs = self.block_size
            jb = -(-m // bs)
            counts = np.zeros((*lead, jb), np.int32)
            rows = np.zeros((*lead, jb, self.maxb), np.int32)
            vals = np.zeros((*lead, jb, self.maxb, bs, bs), self.s_dtype)
            tab = leaf.s_stack if self.stacked else leaf.s_bsr
            if tab is not None:
                b = tab.rows.shape[-1]
                counts[...] = np.asarray(tab.counts, np.int32)
                rows[..., :b] = np.asarray(tab.rows, np.int32)
                vals[..., :b, :, :] = np.asarray(tab.vals, self.s_dtype)
            out["counts"], out["rows"], out["vals"] = counts, rows, vals
        return out

    def _zero_tables(self) -> dict[str, np.ndarray]:
        """Tables of an unoccupied pool row (never selected by any request)."""
        L, (n, m) = self.layers, self.shape
        lead = (L,) if self.stacked else ()
        if self.fmt == "dense":
            return {"w": np.zeros((*lead, n, m), self.dtype)}
        out = {}
        if self.maxr:
            out["p"] = np.zeros((*lead, n, self.maxr), self.p_dtype)
            out["vt"] = np.zeros((*lead, self.maxr, m), self.p_dtype)
        if self.fmt == "factored" and self.any_sparse:
            out["coo_vals"] = np.zeros((*lead, self.cap), self.s_dtype)
            out["coo_idx"] = np.full((*lead, self.cap), -1, np.int32)
        if self.fmt == "fused" and self.any_sparse:
            bs = self.block_size
            jb = -(-m // bs)
            out["counts"] = np.zeros((*lead, jb), np.int32)
            out["rows"] = np.zeros((*lead, jb, self.maxb), np.int32)
            out["vals"] = np.zeros((*lead, jb, self.maxb, bs, bs), self.s_dtype)
        return out

    # --------------------------------------------------------- pool build --

    @property
    def _flat(self) -> bool:
        # fused pools flatten (adapter, layer) -> one leading A*L axis so
        # the stacked/multi kernels index row sel*L + layer directly
        return self.fmt == "fused" and self.stacked

    def build(self, row_leaves: list) -> AdapterizedLinear:
        """Stack ``capacity`` pool rows (``None`` rows = zero tables) into
        the device-side pooled site."""
        tables = [
            self.pad(leaf) if leaf is not None else self._zero_tables()
            for leaf in row_leaves
        ]

        def pool(field):
            if field not in tables[0]:
                return None
            stackd = np.stack([t[field] for t in tables])
            if self._flat:
                stackd = stackd.reshape(-1, *stackd.shape[2:])
            return jnp.asarray(stackd)

        kw = dict(w=None, p=None, vt=None, s_coo=None, s_stack=None, sel=None,
                  shape=self.shape, fmt=self.fmt, mode=self.mode,
                  stacked=self.stacked, layers=self.layers)
        if self.fmt == "dense":
            kw["w"] = pool("w")
        else:
            kw["p"] = pool("p")
            kw["vt"] = pool("vt")
            if self.fmt == "factored" and self.any_sparse:
                kw["s_coo"] = sparse.CooMatrix(
                    pool("coo_vals"), pool("coo_idx"), self.shape
                )
            if self.fmt == "fused" and self.any_sparse:
                kw["s_stack"] = BsrStack(
                    pool("counts"), pool("rows"), pool("vals"),
                    self.shape, self.block_size, empty=False,
                )
        return AdapterizedLinear(**kw)

    def set_row(self, lin: AdapterizedLinear, row: int, leaf) -> AdapterizedLinear:
        """Swap one adapter's tables into pool row ``row`` (pure .at[].set —
        same shapes, so jitted programs never retrace)."""
        t = self.pad(leaf)
        L = self.layers

        def put(pool, field):
            if pool is None:
                return None
            v = jnp.asarray(t[field])
            if self._flat:
                return pool.at[row * L:(row + 1) * L].set(v)
            return pool.at[row].set(v)

        kw = {
            "w": put(lin.w, "w"),
            "p": put(lin.p, "p"),
            "vt": put(lin.vt, "vt"),
        }
        if lin.s_coo is not None:
            kw["s_coo"] = sparse.CooMatrix(
                put(lin.s_coo.values, "coo_vals"),
                put(lin.s_coo.idx, "coo_idx"), lin.s_coo.shape,
            )
        if lin.s_stack is not None:
            st = lin.s_stack
            kw["s_stack"] = BsrStack(
                put(st.counts, "counts"), put(st.rows, "rows"),
                put(st.vals, "vals"), st.shape, st.block_size,
                empty=st.empty,
            )
        return replace(lin, **kw)


def _leading_dim(lin: SLRLinear) -> int:
    for part in (lin.p, lin.s_coo and lin.s_coo.values, lin.s_stack and lin.s_stack.counts):
        if part is not None:
            return part.shape[0]
    raise AdapterError(f"cannot infer layer count of {lin}")


def _leaf_dims(lin: SLRLinear):
    """(live rank, BSR MAXB, COO cap, block size) of one SLRLinear."""
    r = 0 if lin.p is None else lin.p.shape[-1]
    maxb, bs = 0, None
    tab = lin.s_stack if lin.s_stack is not None else lin.s_bsr
    if tab is not None:
        maxb, bs = tab.rows.shape[-1], tab.block_size
    cap = 0 if lin.s_coo is None else lin.s_coo.values.shape[-1]
    return r, maxb, cap, bs


# --------------------------------------------------------------- registry ---


class AdapterRegistry:
    """Host-side adapter lifecycle over one shared base ``DeployedModel``.

    The base's param tree defines the site schema: for ``factored``/``fused``
    formats the per-adapter sites are exactly the ``SLRLinear`` leaves; for
    ``dense`` they are the matmul-consumed leaves (``q/k/v/o/gate/up/down/w``).
    Registered adapters must match the base at every OTHER leaf — the shared
    base is stored once, adapters contribute only their site tables.
    """

    def __init__(self, base: DeployedModel):
        if not isinstance(base, DeployedModel):
            raise TypeError(f"base must be a DeployedModel, got {type(base)!r}")
        if base.fmt not in ("dense", "factored", "fused"):
            raise AdapterError(
                f"AdapterRegistry does not support fmt={base.fmt!r} (the "
                "'bsr' unrolled format has per-matrix tables that cannot "
                "be pooled; deploy adapters as 'fused' instead)"
            )
        self.base = base
        self.fmt = base.fmt
        self._site_paths = self._find_sites(base.params)
        self._adapters: OrderedDict[int, dict[str, Any]] = OrderedDict()
        self._names: dict[int, str] = {}
        self._next = 0

    def _find_sites(self, params) -> list[str]:
        paths = []
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                params, is_leaf=lambda x: isinstance(x, SLRLinear)):
            if self.fmt == "dense":
                if _is_pool_path(path):
                    paths.append(jax.tree_util.keystr(path))
            elif isinstance(leaf, SLRLinear):
                paths.append(jax.tree_util.keystr(path))
        if not paths:
            raise AdapterError("base model has no per-adapter sites")
        return paths

    def _extract(self, model: DeployedModel) -> dict[str, Any]:
        """Split one adapter into site tables, validating the shared rest."""
        if model.fmt != self.fmt:
            raise AdapterError(
                f"adapter fmt {model.fmt!r} != bank fmt {self.fmt!r}"
            )
        sites = {}
        is_slr = lambda x: isinstance(x, SLRLinear)  # noqa: E731
        base_by_key = {
            jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_leaves_with_path(
                self.base.params, is_leaf=is_slr)
        }
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                model.params, is_leaf=is_slr):
            key = jax.tree_util.keystr(path)
            if key in self._site_paths:
                sites[key] = leaf
            else:
                ref = base_by_key.get(key)
                if (ref is None or isinstance(leaf, SLRLinear)
                        or not _leaves_equal(leaf, ref)):
                    raise AdapterError(
                        f"adapter differs from the base at non-site leaf "
                        f"{key} — only the SLR linear sites may vary per "
                        "adapter (same block selection as the base)"
                    )
        missing = [k for k in self._site_paths if k not in sites]
        if missing:
            raise AdapterError(f"adapter missing site leaves: {missing}")
        return sites

    # ---------------------------------------------------------- lifecycle --

    def register(self, model: DeployedModel, name: str | None = None) -> int:
        sites = self._extract(model)
        aid = self._next
        self._next += 1
        self._adapters[aid] = sites
        self._names[aid] = name or f"adapter{aid}"
        return aid

    def unregister(self, aid: int):
        if aid not in self._adapters:
            raise AdapterError(f"unknown adapter id {aid}")
        del self._adapters[aid]
        del self._names[aid]

    def __contains__(self, aid) -> bool:
        return aid in self._adapters

    def __len__(self) -> int:
        return len(self._adapters)

    @property
    def ids(self) -> list[int]:
        return list(self._adapters)

    def name(self, aid: int) -> str:
        return self._names[aid]

    def sites(self, aid: int) -> dict[str, Any]:
        return self._adapters[aid]

    @property
    def site_paths(self) -> list[str]:
        return list(self._site_paths)


# ------------------------------------------------------------------- bank ---


class AdapterBank(ModelBank):
    """N registered (L+S) adapters over one shared base, served as ONE
    single-tier bank: engines read the pooled param tree as tier 0 and bind
    the per-call adapter selection through :meth:`bind`.

    ``max_resident`` caps the on-device pool; the rest of the registry lives
    host-side and swaps in LRU-style on demand (``acquire``). Pool shapes
    freeze at :meth:`materialize` (the engine calls it with
    ``EngineConfig.max_resident_adapters``), so residency swaps and ``sel``
    rebinds are data-only — zero retraces across adapter switches.
    """

    def __init__(self, base: DeployedModel, adapters=(), names=None, *,
                 max_resident: int | None = None, mode: str | None = None):
        self.registry = AdapterRegistry(base)
        names = list(names) if names is not None else [None] * len(adapters)
        if len(names) != len(adapters):
            raise ValueError(
                f"{len(adapters)} adapter(s) but {len(names)} name(s)"
            )
        for model, name in zip(adapters, names):
            self.registry.register(model, name=name)
        if mode is None:
            mode = "batched" if base.fmt == "fused" else "grouped"
        if mode not in ("batched", "grouped"):
            raise ValueError(f"unknown adapter mode {mode!r}")
        if mode == "batched" and base.fmt != "fused":
            raise AdapterError(
                f"batched adapter mode needs the 'fused' format (one "
                f"multi-adapter kernel); fmt={base.fmt!r} serves grouped"
            )
        self.mode = mode
        self._max_resident = max_resident
        self._sites: list[_Site] = []
        self._device = None
        self._rows: list[int | None] = []
        self._row_of: dict[int, int] = {}
        self._pins: dict[int, int] = {}
        self._lru: OrderedDict[int, None] = OrderedDict()
        self.swaps = 0
        super().__init__(base.cfg, [base])

    # ------------------------------------------------------------ access ---

    @property
    def materialized(self) -> bool:
        return self._device is not None

    @property
    def capacity(self) -> int:
        return len(self._rows)

    @property
    def default_adapter(self) -> int:
        ids = self.registry.ids
        if not ids:
            raise AdapterError("no adapters registered")
        return ids[0]

    @property
    def resident(self) -> list[int]:
        return [aid for aid in self._rows if aid is not None]

    # ------------------------------------------------------- materialize ---

    def materialize(self, max_resident: int | None = None) -> "AdapterBank":
        """Freeze padded pool shapes and build the on-device pool. Idempotent
        for a matching capacity; engines call this before first use."""
        cap = max_resident or self._max_resident or len(self.registry)
        if self.materialized:
            if cap != self.capacity:
                raise AdapterError(
                    f"bank already materialized with max_resident="
                    f"{self.capacity}, re-requested {cap}"
                )
            return self
        if len(self.registry) == 0:
            raise AdapterError("register at least one adapter first")
        if cap < 1:
            raise ValueError(f"max_resident must be >= 1, got {cap}")
        self._max_resident = cap

        base_by_key = {
            jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_leaves_with_path(
                self.registry.base.params,
                is_leaf=lambda x: isinstance(x, SLRLinear))
        }
        for key in self.registry.site_paths:
            site = _Site(key, base_by_key[key], self.registry.fmt, self.mode)
            for aid in self.registry.ids:
                site.observe(self.registry.sites(aid)[key])
            site.freeze()
            if site.fmt != "dense" and not site.maxr and not site.any_sparse:
                # every registered adapter's tables here are empty (e.g. an
                # untrained all-zero SLR state): the site is identically
                # zero for all tenants, so keep the base's own leaf — an
                # AdapterizedLinear whose only array leaf is ``sel`` would
                # be sliced per layer by the scan and serves nothing
                continue
            self._sites.append(site)

        residents = self.registry.ids[:cap]
        self._rows = residents + [None] * (cap - len(residents))
        self._row_of = {aid: i for i, aid in enumerate(residents)}
        self._lru = OrderedDict((aid, None) for aid in residents)

        site_by_key = {s.key: s for s in self._sites}

        def build_leaf(path, leaf):
            key = jax.tree_util.keystr(path)
            site = site_by_key.get(key)
            if site is None:
                return leaf
            return site.build([
                None if aid is None else self.registry.sites(aid)[key]
                for aid in self._rows
            ])

        tree = jax.tree_util.tree_map_with_path(
            build_leaf, self.registry.base.params,
            is_leaf=lambda x: isinstance(x, SLRLinear))
        self._device = jax.device_put(tree)
        model = DeployedModel(self.cfg, self._device, fmt=self.registry.fmt)
        self._tiers = [Tier(index=0, name="adapters", keep=None, model=model,
                            param_bytes=self._pool_bytes())]
        return self

    def _pool_bytes(self) -> int:
        return sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(self._device)
        )

    # --------------------------------------------------------- residency ---

    def acquire(self, aid: int) -> tuple[int | None, bool]:
        """Pool row of ``aid``, swapping its host tables in if non-resident
        (LRU victim among unpinned rows). Returns ``(row, swapped)``;
        ``(None, False)`` means every row is pinned — the caller should keep
        the request queued and retry next tick."""
        if aid not in self.registry:
            raise AdapterError(f"unknown adapter id {aid}")
        assert self.materialized, "materialize() the bank first"
        row = self._row_of.get(aid)
        if row is not None:
            self._lru.move_to_end(aid)
            return row, False
        row = self._victim_row()
        if row is None:
            return None, False
        old = self._rows[row]
        if old is not None:
            del self._row_of[old]
            self._lru.pop(old, None)
        self._install(row, aid)
        self._rows[row] = aid
        self._row_of[aid] = row
        self._lru[aid] = None
        self.swaps += 1
        return row, True

    def _victim_row(self) -> int | None:
        for i, aid in enumerate(self._rows):
            if aid is None:
                return i
        for aid in self._lru:  # least-recent first
            if not self._pins.get(aid):
                return self._row_of[aid]
        return None

    def _install(self, row: int, aid: int):
        sites = self.registry.sites(aid)
        leaves, treedef = jax.tree_util.tree_flatten(
            self._device, is_leaf=lambda x: isinstance(x, AdapterizedLinear))
        out, si = [], 0
        for leaf in leaves:
            if isinstance(leaf, AdapterizedLinear):
                site = self._sites[si]
                out.append(site.set_row(leaf, row, sites[site.key]))
                si += 1
            else:
                out.append(leaf)
        assert si == len(self._sites)
        self._device = jax.tree_util.tree_unflatten(treedef, out)
        self._tiers[0].model.params = self._device

    def pin(self, aid: int):
        self._pins[aid] = self._pins.get(aid, 0) + 1

    def unpin(self, aid: int):
        n = self._pins.get(aid, 0) - 1
        if n <= 0:
            self._pins.pop(aid, None)
        else:
            self._pins[aid] = n

    def pinned(self, aid: int) -> int:
        return self._pins.get(aid, 0)

    # --------------------------------------------------------- lifecycle ---

    def register(self, model: DeployedModel, name: str | None = None) -> int:
        """Register a new adapter (host-side; becomes resident on demand).
        After materialize, its padded dims must fit the frozen pool."""
        if self.materialized:
            sites = self.registry._extract(model)
            for site in self._sites:
                site.check(sites[site.key])
        return self.registry.register(model, name=name)

    def unregister(self, aid: int):
        """Remove an adapter. Raises ``AdapterError`` while any slot streams
        with it (unregister-while-streaming rejection)."""
        if self._pins.get(aid):
            raise AdapterError(
                f"adapter {aid} is streaming on {self._pins[aid]} slot(s); "
                "drain it before unregistering"
            )
        self.registry.unregister(aid)
        row = self._row_of.pop(aid, None)
        if row is not None:
            self._rows[row] = None
            self._lru.pop(aid, None)

    # -------------------------------------------------------------- bind ---

    def bind(self, sel) -> Any:
        """The pooled param tree with ``sel`` stamped into every site: a
        ``(S,)`` slot→pool-row map (batched) or a scalar row (grouped).
        Data-only — every call yields the same treedef and shapes."""
        sel = jnp.asarray(sel, jnp.int32)
        return jax.tree_util.tree_map(
            lambda x: x.with_sel(sel) if isinstance(x, AdapterizedLinear) else x,
            self._device,
            is_leaf=lambda x: isinstance(x, AdapterizedLinear))

    # -------------------------------------------------------- accounting ---

    def adapter_report(self) -> dict:
        return {
            "fmt": self.registry.fmt,
            "mode": self.mode,
            "registered": len(self.registry),
            "capacity": self.capacity,
            "resident": self.resident,
            "swaps": self.swaps,
            "pool_bytes": self._pool_bytes() if self.materialized else 0,
            "sites": len(self._sites),
        }

    def report(self, widths=()) -> dict:
        out = super().report(widths)
        out["adapters"] = self.adapter_report()
        return out

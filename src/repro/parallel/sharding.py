"""Sharding rules: logical-axis constraints + parameter partition specs.

The mesh has axes ("data", "model") single-pod or ("pod", "data", "model")
multi-pod. Batch-like logical axes map to ("pod", "data") jointly so the pod
axis folds into data parallelism (cross-pod traffic = gradient all-reduce
only, which is DCN-friendly); "model" carries TP/EP.

``constrain`` is a no-op outside a mesh context, so every model runs
unmodified on a single CPU device (tests) and sharded under the production
mesh (dry-run/training) with the same code.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "constrain",
    "param_spec",
    "param_sharding_tree",
    "logical_to_mesh",
    "ServingMesh",
    "parse_mesh_spec",
    "serving_param_spec",
    "serving_shardings",
    "kv_cache_shardings",
    "current_mesh",
    "dp_axes",
    "dp_size",
    "batch_shardings",
]


def current_mesh() -> AbstractMesh | None:
    """The mesh made active by ``jax.set_mesh`` (or a :class:`ServingMesh`
    context), or None. Readable at trace time, inside ``jit``."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def dp_axes(mesh: Mesh):
    """The batch-carrying mesh axes present in ``mesh`` (pod folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None


def dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in ("pod", "data") if a in mesh.axis_names]))


def batch_shardings(specs: dict, mesh: Mesh) -> dict:
    """Leading-dim (batch) shardings for a dict of abstract batch arrays."""
    dp = dp_axes(mesh)
    dpn = dp_size(mesh)
    out = {}
    for k, v in specs.items():
        ax0 = dp if v.shape[0] % dpn == 0 else None
        rest = (None,) * (len(v.shape) - 1)
        out[k] = NamedSharding(mesh, P(ax0, *rest))
    return out


def logical_to_mesh(axis: str | None, mesh: Mesh) -> Any:
    """Map a logical axis name to mesh axes present in ``mesh``."""
    if axis is None:
        return None
    names = mesh.axis_names
    if axis == "data":
        got = tuple(a for a in ("pod", "data") if a in names)
        return got if len(got) > 1 else (got[0] if got else None)
    if axis in names:
        return axis
    return None


def constrain(x: jax.Array, logical: tuple[str | None, ...]) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = P(*(logical_to_mesh(a, mesh) for a in logical))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------- parameter rules --

# (path regex, ndim) -> logical spec for the trailing dims; leading stacked
# dims are unsharded (None). First match wins.
_RULES: list[tuple[str, tuple[str | None, ...]]] = [
    # MoE experts (E, d_in, d_out): expert-parallel over model + FSDP over
    # data on the first matrix dim. The shard_map EP path declares
    # P('model', None, None), so entering it all-gathers the 'data' shards —
    # exactly the FSDP weight gather, one layer at a time under the scan.
    (r"experts/(gate|up|w1|w3)$", ("model", "data", None)),
    (r"experts/(down|w2)$", ("model", "data", None)),
    (r"router$", (None, None)),
    # attention: column-parallel QKV, row-parallel O (+ FSDP on the other dim)
    (r"(^|/)(q|k|v)$", ("data", "model")),
    (r"(^|/)o$", ("model", "data")),
    # MLP: column-parallel up/gate, row-parallel down
    (r"(gate|up)$", ("data", "model")),
    (r"down$", ("model", "data")),
    # SSM projections
    (r"in_proj$", ("data", "model")),
    (r"out_proj$", ("model", "data")),
    (r"(x_proj|dt_proj)$", ("data", "model")),
    # embeddings / output head: d_model over model, vocab REPLICATED.
    # Sharding vocab over 'data' collides with the batch axis of the logits
    # (both want 'data') and forces GSPMD to materialize full-vocab logits
    # (13 GB/device measured on olmo_1b); a replicated vocab slice costs
    # <=131 MB/device (internvl2) and keeps logits sharded (data, :, model).
    (r"embedding$", (None, "model")),
    (r"lm_head/w$", (None, "model")),
    # generic fallbacks for any other 2-D matrix
    (r".*", ("data", "model")),
]


def _rule_tail(name: str) -> tuple[str | None, ...]:
    for pat, logical in _RULES:
        if re.search(pat, name):
            return logical
    return ("data", "model")


def _resolve_tail(
    shape: tuple[int, ...],
    tail: tuple[str | None, ...],
    mesh: Mesh,
    *,
    drop_data: bool = False,
) -> P:
    """Resolve a logical tail spec against ``mesh``: leading stacked dims are
    unsharded, axes that don't divide the dim evenly are dropped (replicate),
    and ``drop_data`` removes the FSDP-style 'data' weight sharding (serving
    keeps weights replicated across data replicas; batch rides 'data')."""
    n_stack = len(shape) - len(tail)
    full = (None,) * n_stack + tuple(tail)
    resolved = []
    for dim, ax in zip(shape, full):
        if drop_data and ax == "data":
            ax = None
        mesh_ax = logical_to_mesh(ax, mesh)
        if mesh_ax is None:
            resolved.append(None)
            continue
        size = (
            int(np.prod([mesh.shape[a] for a in mesh_ax]))
            if isinstance(mesh_ax, tuple)
            else mesh.shape[mesh_ax]
        )
        resolved.append(mesh_ax if dim % size == 0 else None)
    return P(*resolved)


def param_spec(name: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for a parameter leaf, by path + shape."""
    if len(shape) < 2:
        return P()  # vectors replicated
    return _resolve_tail(shape, _rule_tail(name), mesh)


def param_sharding_tree(params: Any, mesh: Mesh) -> Any:
    """NamedSharding tree matching ``params`` (works on ShapeDtypeStructs)."""
    from repro.core.selection import path_str

    def one(path, leaf):
        return NamedSharding(mesh, param_spec(path_str(path), tuple(leaf.shape), mesh))

    return jax.tree_util.tree_map_with_path(one, params)


# ------------------------------------------------------- serving shardings --

# SLRLinear pytree fields holding index-addressed sparse tables. Their row /
# column ids are global, so a partition by array position is meaningless —
# the tables replicate, and GSPMD reshards their dense scatter on use.
_SLR_TABLE_FIELDS = frozenset({"s_coo", "s_bsr", "s_stack"})


def serving_param_spec(name: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Serving-time PartitionSpec for one param leaf (dense or inside an
    ``SLRLinear``).

    Differences from the training rules in :func:`param_spec`:

    * weights shard over 'model' only — 'data' carries the request batch, and
      FSDP-style weight sharding would all-gather weights every decode tick;
    * ``SLRLinear`` factors follow the dense weight they replace: ``p`` takes
      the row (contraction) axis — sharded over 'model' at row-parallel sites
      (o/down) so x@p partial-sums exactly like x@W — and ``vt`` takes the
      column axis — 'model' at column-parallel sites (q/k/v/gate/up); the
      rank dim is never sharded;
    * sparse tables (COO / block-CSR / BsrStack) replicate.
    """
    parts = name.split("/")
    last = parts[-1]
    if last in ("p", "vt") and len(parts) > 1:
        if len(shape) < 2:
            return P()
        row, col = _rule_tail("/".join(parts[:-1]))[-2:]
        sub = (row, None) if last == "p" else (None, col)
        return _resolve_tail(shape, sub, mesh, drop_data=True)
    if any(f in parts for f in _SLR_TABLE_FIELDS):
        return P()
    if len(shape) < 2:
        return P()
    return _resolve_tail(shape, _rule_tail(name), mesh, drop_data=True)


def serving_shardings(params: Any, mesh: Mesh) -> Any:
    """NamedSharding tree matching a DeployedModel param tree (descends into
    SLRLinear / CooMatrix / BsrMatrix pytrees)."""
    from repro.core.selection import path_str

    def one(path, leaf):
        return NamedSharding(
            mesh, serving_param_spec(path_str(path), tuple(leaf.shape), mesh)
        )

    return jax.tree_util.tree_map_with_path(one, params)


def kv_cache_shardings(cache: Any, mesh: Mesh) -> Any:
    """NamedSharding tree for an LMCache / PagedKVCache.

    Payload pools — (L, B, H, S, D) slot caches, (L, pages, H, bs, D) paged
    pools, and their (L, pages, H, bs, 1) int8 scales — shard the KV-head
    axis (dim 2) over 'model'. Everything else (block tables, lengths) is
    host bookkeeping: replicated, so the BlockAllocator / prefix cache / CoW
    logic never sees the mesh.
    """
    model_n = int(mesh.shape["model"]) if "model" in mesh.axis_names else 1

    def one(leaf):
        s = tuple(leaf.shape)
        if len(s) == 5 and model_n > 1 and s[2] % model_n == 0:
            return NamedSharding(mesh, P(None, None, "model", None, None))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(one, cache)


# --------------------------------------------------------------- ServingMesh --


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """Parse ``"model=N,data=M"`` → axis sizes (missing axes default to 1).

    Pure string validation — never touches jax device state, so
    ``EngineConfig.__post_init__`` can call it eagerly.
    """
    sizes = {"data": 1, "model": 1}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        key, eq, val = tok.partition("=")
        key = key.strip()
        if not eq or key not in sizes:
            raise ValueError(
                f"mesh={spec!r} must be comma-separated axis=N terms with axis "
                f"in ('data', 'model'); got {tok!r}"
            )
        try:
            n = int(val)
        except ValueError:
            n = 0
        if n < 1:
            raise ValueError(f"mesh={spec!r}: size for {key!r} must be a positive int")
        sizes[key] = n
    return sizes


class ServingMesh:
    """The ("data", "model") device mesh for serving, plus its sharding rules.

    ONE axis-naming authority: ``launch.mesh`` and the serving engines build
    meshes only through here. 'model' carries tensor parallelism (head / ffn
    splits); 'data' (optionally preceded by 'pod') carries the batch. Used as
    a context manager it activates the mesh so :func:`constrain` and the
    shard_map-wrapped kernels see it at trace time.
    """

    AXES = ("data", "model")

    def __init__(self, mesh: Mesh):
        extra = [a for a in mesh.axis_names if a not in ("pod",) + self.AXES]
        if extra:
            raise ValueError(
                f"mesh axis names {tuple(mesh.axis_names)} must be drawn from "
                f"('pod', 'data', 'model'); got unknown {extra}"
            )
        self.mesh = mesh
        self._scopes: list[jax.set_mesh] = []

    @classmethod
    def create(cls, *, data: int = 1, model: int = 1, devices=None) -> "ServingMesh":
        if devices is None:
            devices = jax.devices()
        need = data * model
        if need > len(devices):
            raise ValueError(
                f"mesh data*model={need} exceeds the {len(devices)} available "
                f"device(s)"
            )
        grid = np.asarray(devices[:need]).reshape(data, model)
        return cls(Mesh(grid, cls.AXES))

    @classmethod
    def from_spec(cls, spec: str, devices=None) -> "ServingMesh":
        sizes = parse_mesh_spec(spec)
        return cls.create(data=sizes["data"], model=sizes["model"], devices=devices)

    # ------------------------------------------------------------ topology --

    @property
    def model_size(self) -> int:
        return int(self.mesh.shape["model"]) if "model" in self.mesh.axis_names else 1

    @property
    def data_size(self) -> int:
        return dp_size(self.mesh)

    @property
    def num_devices(self) -> int:
        return int(self.mesh.size)

    def describe(self) -> dict:
        """JSON-safe topology record (for ``engine_provenance`` / BENCH_*.json)."""
        return {
            "axis_names": list(self.mesh.axis_names),
            "shape": {a: int(self.mesh.shape[a]) for a in self.mesh.axis_names},
            "num_devices": int(self.mesh.size),
        }

    # ------------------------------------------------------------ shardings --

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def params_shardings(self, params: Any) -> Any:
        return serving_shardings(params, self.mesh)

    def cache_shardings(self, cache: Any) -> Any:
        return kv_cache_shardings(cache, self.mesh)

    # -------------------------------------------------------------- context --

    def __enter__(self) -> "ServingMesh":
        self._scopes.append(jax.set_mesh(self.mesh))
        return self

    def __exit__(self, *exc):
        self._scopes.pop().__exit__(*exc)

"""Deployment-time SLR parameters: the paper's point is that L + S is what
ships. Four deployment formats, increasing TPU-specialization:

  * ``dense``    — materialize X_hat = L + S (baseline; no memory savings,
                   used for perplexity parity checks)
  * ``factored`` — keep (p, vt) + COO S; linears run as x@p@vt + sparse part
                   via dense scatter per call (XLA path, shards under GSPMD)
  * ``bsr``      — factored L + 128x128 block-CSR S for the Pallas kernels
                   (single-core TPU hot path; DESIGN.md §3 hardware adaptation)
  * ``fused``    — ONE Pallas pass per linear site (x @ P @ Vt + x @ S in a
                   shared accumulator, ``kernels/slr_matmul.py``) with
                   layer-STACKED block-CSC tables, so the transformer layer
                   stack stays ``lax.scan``-able (no per-layer unrolling)

``deployment_report`` accounts bytes for each format — the numbers behind
EXPERIMENTS.md's memory-reduction table (paper Table 1 PRM columns).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core import sparse
from ..core.admm import SLRState, surrogate_params
from ..core.selection import BlockInfo
from ..kernels.bsr_matmul import BsrMatrix, bsr_from_dense
from ..kernels.slr_matmul import BsrStack, stack_bsr


@dataclass
class SLRLinear:
    """One deployed SLR weight.

    Registered as a jax pytree so it can live *inside* a model parameter tree
    and flow through jit / scan: ``models.layers.apply_weight`` dispatches to
    ``apply`` wherever a dense weight is expected. Stacked blocks (leading
    layer axis on p/vt/s_coo) slice correctly under ``lax.scan``. ``use_kernel``
    is static metadata choosing the Pallas hot path at trace time.
    """

    p: jax.Array | None          # (n, r_live) — or (L, n, r_live) stacked
    vt: jax.Array | None         # (r_live, m) — or (L, r_live, m) stacked
    s_coo: sparse.CooMatrix | None
    s_bsr: BsrMatrix | None
    shape: tuple[int, int]
    use_kernel: bool = False     # static: route apply() through Pallas kernels
    s_stack: BsrStack | None = None  # layer-stacked block-CSC (fused format)
    fuse: bool = False           # static: one fused Pallas pass per apply

    def apply(self, x: jax.Array, kernel: bool | None = None) -> jax.Array:
        """y = x @ (L + S)."""
        if kernel is None:
            kernel = self.use_kernel
        if (self.p is None and self.s_coo is None and self.s_bsr is None
                and self.s_stack is None):
            # fully-truncated block (extreme HPA budgets): y = x @ 0
            return jnp.zeros((*x.shape[:-1], self.shape[1]), x.dtype)
        if self.fuse and kernel:
            assert self.s_stack is None, (
                "stacked fused weights are applied per layer: the forward "
                "scans layer indices and calls at_layer(l) (scan_by_index)"
            )
            from ..kernels.ops import slr_matmul

            flat = x.reshape(-1, x.shape[-1])
            y = slr_matmul(flat, self.p, self.vt, self.s_bsr)
            return y.reshape(*x.shape[:-1], self.shape[1])
        y = 0.0
        if self.p is not None:
            if kernel:
                from ..kernels.ops import lowrank_matmul

                flat = x.reshape(-1, x.shape[-1])
                y = lowrank_matmul(flat, self.p, self.vt).reshape(*x.shape[:-1], -1)
            else:
                y = (x @ self.p) @ self.vt
        if self.s_bsr is not None and kernel:
            from ..kernels.ops import bsr_matmul

            flat = x.reshape(-1, x.shape[-1])
            y = y + bsr_matmul(flat, self.s_bsr).reshape(*x.shape[:-1], self.shape[1])
        elif self.s_coo is not None:
            s_dense = sparse.to_dense(self.s_coo).astype(x.dtype)
            y = y + x @ s_dense
        return y

    @property
    def scan_by_index(self) -> bool:
        """Stacked fused weight: the layer scan must NOT slice this leaf as
        scan xs (that would copy the whole BSR table out of HBM every layer
        of every tick) — it scans ``jnp.arange(L)`` instead and takes
        :meth:`at_layer` views, which select the layer inside the kernel's
        scalar-prefetched DMA index maps."""
        return self.fuse and self.s_stack is not None

    def at_layer(self, layer) -> "SLRLayerView":
        """View of layer ``layer`` of a stacked fused weight (traced index)."""
        assert self.scan_by_index
        return SLRLayerView(self, layer)

    @property
    def dtype(self):
        for part in (self.p, self.s_coo and self.s_coo.values,
                     self.s_bsr and self.s_bsr.vals,
                     self.s_stack and self.s_stack.vals):
            if part is not None:
                return part.dtype
        return jnp.float32

    @property
    def ndim(self) -> int:
        """Logical ndim of the dense weight this object replaces (stack-aware)."""
        if self.p is not None:
            return self.p.ndim
        if self.s_coo is not None:
            return self.s_coo.values.ndim + 1
        if self.s_stack is not None:
            return 3  # stacked by construction
        return 2  # only s_bsr left, and block-CSR is per-matrix by construction

    @property
    def param_bytes(self) -> int:
        total = 0
        if self.p is not None:
            total += self.p.size * self.p.dtype.itemsize
            total += self.vt.size * self.vt.dtype.itemsize
        if self.s_stack is not None:
            total += self.s_stack.vals.size * self.s_stack.vals.dtype.itemsize
            total += self.s_stack.rows.size * 4 + self.s_stack.counts.size * 4
        elif self.s_bsr is not None:
            total += self.s_bsr.vals.size * self.s_bsr.vals.dtype.itemsize
            total += self.s_bsr.rows.size * 4 + self.s_bsr.counts.size * 4
        elif self.s_coo is not None:
            nnz = int(np.sum(np.asarray(self.s_coo.idx) >= 0))
            total += nnz * (self.s_coo.values.dtype.itemsize + 4)
        return total


# `shape`/`use_kernel`/`fuse` are static metadata; everything else traces.
jax.tree_util.register_dataclass(
    SLRLinear,
    data_fields=["p", "vt", "s_coo", "s_bsr", "s_stack"],
    meta_fields=["shape", "use_kernel", "fuse"],
)


class SLRLayerView:
    """Layer ``l`` of a stacked fused :class:`SLRLinear` — deliberately NOT a
    pytree. It is built *inside* the layer-scan body
    (``models.transformer.layer_view``) with a traced layer index, and
    ``models.layers.apply_weight`` duck-dispatches on its ``apply``. The
    stacked tables stay captured whole; only the layer id varies per step.
    """

    __slots__ = ("lin", "layer")

    def __init__(self, lin: SLRLinear, layer):
        self.lin = lin
        self.layer = layer

    def apply(self, x: jax.Array) -> jax.Array:
        from ..kernels.ops import slr_matmul_stacked

        lin = self.lin
        flat = x.reshape(-1, x.shape[-1])
        y = slr_matmul_stacked(flat, lin.p, lin.vt, lin.s_stack, self.layer)
        return y.reshape(*x.shape[:-1], lin.shape[1])

    @property
    def dtype(self):
        return self.lin.dtype


def _fit_block(n: int, m: int, bsr_block: int) -> int:
    """``bsr_block``, or one block spanning both dims (rounded up to the
    sublane tile) when the matrix is smaller than that.

    The kernels' x and y blocks take the block size as their lane dimension,
    which Mosaic tiles by 128 unless the block spans the whole array — so the
    size is never shrunk to divide a dim (512x1376 would fall to 32, refused
    on TPU); ``bsr_from_dense`` zero-pads the trailing partial blocks instead.
    On TPU ``bsr_block`` must be a multiple of 128 (the default is)."""
    return min(bsr_block, -(-max(n, m) // 8) * 8)


def coo_to_bsr(s_coo: sparse.CooMatrix, bsr_block: int) -> BsrMatrix:
    """Dense-ify an unstacked COO matrix and re-tile as block-CSC.

    Ragged shapes zero-pad the trailing partial blocks (the padding tiles
    are all-zero so they are never stored) — every shape converts.
    """
    dense_s = np.asarray(sparse.to_dense(s_coo), np.float32)
    n, m = dense_s.shape
    return bsr_from_dense(dense_s, _fit_block(n, m, bsr_block))


def coo_to_bsr_stack(s_coo: sparse.CooMatrix, bsr_block: int) -> BsrStack:
    """Dense-ify a layer-STACKED COO matrix and re-tile every layer as
    block-CSC with one shared (block size, MAXB) layout — the table shapes
    the stacked fused kernel scans over."""
    dense_s = np.asarray(sparse.to_dense(s_coo), np.float32)
    num_l, n, m = dense_s.shape
    bs = _fit_block(n, m, bsr_block)
    return stack_bsr([bsr_from_dense(dense_s[l], bs) for l in range(num_l)])


def _live_rank_slice(blk, info: BlockInfo):
    """Trim factored L to live singular values (per slice; stacked blocks keep
    the max live rank across slices so shapes stay static)."""
    s_vals = np.asarray(blk.s_vals)
    live = s_vals > 0
    r_live = int(live.sum(axis=-1).max()) if live.size else 0
    if r_live == 0:
        return None, None
    order = np.argsort(-s_vals, axis=-1)[..., :r_live]
    p = np.take_along_axis(np.asarray(blk.p), order[..., None, :], axis=-1)
    vt = np.take_along_axis(np.asarray(blk.vt), order[..., :, None], axis=-2)
    return jnp.asarray(p), jnp.asarray(vt)


def build_slr_linears(
    state: SLRState,
    blocks: list[BlockInfo],
    fmt: str = "factored",
    bsr_block: int = 128,
) -> dict[str, Any]:
    """Per-block deployed representation. Stacked blocks are kept stacked for
    'factored'; 'bsr' unstacks (the Pallas kernel is per-matrix)."""
    out = {}
    for info in blocks:
        blk = state[info.name]
        p, vt = _live_rank_slice(blk, info)
        # an HPA budget that removed every sparse entry (e.g. kappa -> 0, the
        # pure-low-rank end of the spectrum) must not keep paying the dense
        # COO scatter at every apply — drop the empty S at build time
        s_coo = blk.s_coo
        if int(np.sum(np.asarray(s_coo.idx) >= 0)) == 0:
            s_coo = None
        if fmt == "bsr" and not info.stack_dims:
            s_bsr = coo_to_bsr(blk.s_coo, bsr_block) if s_coo is not None else None
            # keep the COO view too: apply(kernel=False) is the XLA/GSPMD
            # fallback and must include the sparse part
            out[info.name] = SLRLinear(
                p=p, vt=vt, s_coo=s_coo, s_bsr=s_bsr, shape=(info.n, info.m)
            )
        else:
            out[info.name] = SLRLinear(
                p=p, vt=vt, s_coo=s_coo, s_bsr=None, shape=(info.n, info.m)
            )
    return out


def deploy_params(params: Any, state: SLRState, blocks: list[BlockInfo], fmt: str = "dense"):
    """For fmt='dense': params with X replaced by X_hat = L + S (architecture-
    preserving — the model code runs unchanged, paper §4.3)."""
    assert fmt == "dense"
    return surrogate_params(params, state, blocks)


def deployment_report(params: Any, state: SLRState, blocks: list[BlockInfo]) -> dict:
    """Bytes by format vs the dense original (per block + totals)."""
    report: dict[str, Any] = {"blocks": {}}
    dense_total = 0
    slr_total = 0
    for info in blocks:
        blk = state[info.name]
        dense_b = int(np.prod(info.shape)) * 2  # bf16 deploy baseline
        nnz = int(np.sum(np.asarray(blk.s_coo.idx) >= 0))
        live = int(np.sum(np.asarray(blk.s_vals) > 0))
        slr_b = live * (info.n + info.m) * 2 + nnz * (2 + 4)
        report["blocks"][info.name] = {
            "dense_bytes": dense_b, "slr_bytes": slr_b,
            "rank_live": live, "nnz": nnz,
        }
        dense_total += dense_b
        slr_total += slr_b
    unselected = 0
    sel = {info.name for info in blocks}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        from ..core.selection import path_str

        if path_str(path) not in sel:
            unselected += int(np.prod(leaf.shape)) * 2
    report["dense_total_bytes"] = dense_total + unselected
    report["slr_total_bytes"] = slr_total + unselected
    report["compression"] = (
        (dense_total + unselected) / max(slr_total + unselected, 1)
    )
    return report

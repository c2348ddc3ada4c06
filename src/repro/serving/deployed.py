"""DeployedModel: run the transformer forward on SLR (L + S) weights directly.

The paper's headline claim is that one SALAAD run yields a *spectrum* of
deployable capacities — but that only pays off if inference consumes the
deployed representation instead of re-materializing dense weights. This
module builds a model parameter tree in which every SALAAD-selected matmul
weight is replaced by a :class:`~repro.serving.slr_params.SLRLinear` (a
registered pytree), so the unchanged model code — via
``models.layers.apply_weight`` — runs ``x @ P @ Vt + x @ S`` at every linear
site. Three formats, increasing TPU specialization:

  * ``dense``    — X_hat = L + S materialized (parity baseline; scan path)
  * ``factored`` — (p, vt) + COO S as pytree leaves; XLA path, scan-stacked,
                   shards under GSPMD exactly like dense weights
  * ``bsr``      — factored L + block-CSR S through the Pallas kernels; the
                   per-matrix kernels cannot ride a scan, so the layer stack
                   is *unrolled* into per-layer param dicts
                   (``models.transformer._forward_unrolled``)
  * ``fused``    — ONE fused Pallas pass per linear site
                   (``kernels/slr_matmul.py``: x @ P @ Vt + x @ S into a
                   shared accumulator, x read once / y written once) with
                   layer-STACKED block-CSC tables. The layer stack stays
                   scan-stacked: the forward scans layer *indices* and the
                   kernel selects the layer in its scalar-prefetched DMA
                   index maps (``SLRLinear.scan_by_index``), so trace and
                   compile time stay depth-independent

Only matmul-applied sites are structured: attention q/k/v/o, MLP gate/up/down
and (if selected) the LM head. Embedding tables are gather sites and MoE
experts are einsum-dispatched, so those blocks are served dense-materialized;
``param_bytes`` accounts for both honestly.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.admm import SLRState, materialize_block, surrogate_params
from ..core.selection import BlockInfo, path_str
from ..models import model as model_lib
from .slr_params import SLRLinear, build_slr_linears, coo_to_bsr, coo_to_bsr_stack

__all__ = ["DeployedModel", "is_linear_site"]

# Param-dict keys that are consumed via apply_weight (plain x @ w sites).
_LINEAR_KEYS = frozenset({"q", "k", "v", "o", "gate", "up", "down", "w"})


def is_linear_site(info: BlockInfo) -> bool:
    """Can this block be served structured (its use site is a plain matmul)?"""
    last = info.name.split("/")[-1]
    return last in _LINEAR_KEYS and "moe" not in info.name and not info.is_embedding


def _coo_slice_to_bsr(lin: SLRLinear, bsr_block: int) -> SLRLinear:
    """Convert one unstacked SLRLinear's COO part to block-CSR (eager)."""
    if lin.s_coo is None:
        return lin
    s_bsr = coo_to_bsr(lin.s_coo, bsr_block)
    return SLRLinear(
        p=lin.p, vt=lin.vt, s_coo=None, s_bsr=s_bsr, shape=lin.shape,
        use_kernel=True,
    )


def _fuse_linear(lin: SLRLinear, bsr_block: int) -> SLRLinear:
    """One SLRLinear → fused format: stacked slices keep the layer axis as a
    ``BsrStack`` (scan-by-index), unstacked ones get a per-matrix block-CSC.
    Empty-S sites (s_coo already dropped at build) carry no sparse table at
    all — ``ops.slr_matmul`` statically skips the sparse epilogue."""
    if lin.ndim == 3:
        s_stack = (
            coo_to_bsr_stack(lin.s_coo, bsr_block) if lin.s_coo is not None else None
        )
        return SLRLinear(
            p=lin.p, vt=lin.vt, s_coo=None, s_bsr=None, s_stack=s_stack,
            shape=lin.shape, use_kernel=True, fuse=True,
        )
    s_bsr = coo_to_bsr(lin.s_coo, bsr_block) if lin.s_coo is not None else None
    return SLRLinear(
        p=lin.p, vt=lin.vt, s_coo=None, s_bsr=s_bsr, shape=lin.shape,
        use_kernel=True, fuse=True,
    )


class DeployedModel:
    """A servable model: arch config + a param tree in a deployment format.

    ``params`` is consumed by the ordinary ``models.model`` API (loss_fn /
    prefill / decode_step) — the format is invisible to model code.
    """

    def __init__(self, cfg, params: Any, fmt: str = "dense"):
        self.cfg = cfg
        self.params = params
        self.fmt = fmt

    # ------------------------------------------------------------- build ---

    @classmethod
    def build(
        cls,
        cfg,
        params: Any,
        state: SLRState,
        blocks: list[BlockInfo],
        fmt: str = "factored",
        bsr_block: int = 128,
    ) -> "DeployedModel":
        """Deploy (params, SLR state) at format ``fmt``."""
        if fmt == "dense":
            return cls(cfg, surrogate_params(params, state, blocks), fmt)
        if fmt not in ("factored", "bsr", "fused"):
            raise ValueError(f"unknown deployment format {fmt!r}")

        by_name = {info.name: info for info in blocks}
        # factored build keeps stacked blocks stacked — scan-compatible; the
        # COO part rides along for the XLA fallback and for bsr conversion
        linears = build_slr_linears(state, blocks, fmt="factored")

        def replace_leaf(path, leaf):
            name = path_str(path)
            info = by_name.get(name)
            if info is None or name not in state:
                return leaf
            if is_linear_site(info):
                return linears[name]
            return materialize_block(state[name], leaf.dtype)

        serving = jax.tree_util.tree_map_with_path(replace_leaf, params)

        if fmt == "bsr":
            serving = cls._unroll_layers(cfg, serving, bsr_block)
            # unstacked blocks outside the layer stack also get the kernel path
            serving = jax.tree_util.tree_map(
                lambda x: _coo_slice_to_bsr(x, bsr_block)
                if isinstance(x, SLRLinear) and x.ndim == 2 else x,
                serving,
                is_leaf=lambda x: isinstance(x, SLRLinear),
            )
        elif fmt == "fused":
            # layer stack STAYS stacked — stacked sites become scan-by-index
            # fused weights, unstacked ones per-matrix fused weights
            serving = jax.tree_util.tree_map(
                lambda x: _fuse_linear(x, bsr_block) if isinstance(x, SLRLinear) else x,
                serving,
                is_leaf=lambda x: isinstance(x, SLRLinear),
            )
        return cls(cfg, serving, fmt)

    @staticmethod
    def _unroll_layers(cfg, serving: Any, bsr_block: int) -> Any:
        """Split the scan-stacked layer tree into a per-layer list and convert
        each layer's SLR weights to block-CSR (Pallas kernels are per-matrix)."""
        layers = serving.get("layers") if isinstance(serving, dict) else None
        if layers is None:
            return serving
        unrolled = []
        for l in range(cfg.num_layers):
            is_slr = lambda x: isinstance(x, SLRLinear)  # noqa: E731
            layer = jax.tree_util.tree_map(lambda a: a[l], layers)
            layer = jax.tree_util.tree_map(
                lambda x: _coo_slice_to_bsr(x, bsr_block) if isinstance(x, SLRLinear) else x,
                layer, is_leaf=is_slr,
            )
            unrolled.append(layer)
        out = dict(serving)
        out["layers"] = unrolled
        return out

    # ----------------------------------------------------------- forward ---

    def forward(self, tokens: jax.Array) -> jax.Array:
        """Full no-cache forward → logits (parity checks / eval)."""
        logits, _, _ = model_lib._forward(self.params, {"tokens": tokens}, self.cfg)
        return logits

    def loss(self, batch: dict) -> float:
        loss, _ = model_lib.loss_fn(self.params, batch, self.cfg)
        return float(loss)

    # -------------------------------------------------------- accounting ---

    def param_bytes(self) -> dict:
        """Served memory by leaf kind (structured vs dense), in bytes."""
        structured = 0
        dense = 0
        is_slr = lambda x: isinstance(x, SLRLinear)  # noqa: E731
        for leaf in jax.tree_util.tree_leaves(self.params, is_leaf=is_slr):
            if isinstance(leaf, SLRLinear):
                structured += leaf.param_bytes
            else:
                structured_or_dense = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                dense += structured_or_dense
        return {
            "structured_bytes": structured,
            "dense_bytes": dense,
            "total_bytes": structured + dense,
            "format": self.fmt,
        }

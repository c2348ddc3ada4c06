"""Homomorphic Parameter Allocation as the paper states it (SALAAD, Sec. 4.3
and App. D), written from the paper for the yardstick and the reference.

For a keep ratio, the removal budget ``C = round((1 - keep) * (C_L + C_S))``
is split ``kappa * C`` to the low-rank part and the rest to the sparse part,
with a component's surplus over what it holds reassigned to the other
(footnote 3). Every slice then keeps the ``ceil((1 - phi_L) * live)``
largest singular values and the ``floor((1 - phi_S) * live)`` largest-|.|
sparse entries.
"""
from __future__ import annotations

from dataclasses import dataclass
import numpy as np


@dataclass
class BlockHost:
    """What HPA reads of one block, on the host."""

    name: str
    n: int
    m: int
    s_vals: np.ndarray                 # (..., r)
    nnz: np.ndarray                    # (...,) live sparse entries per slice


@dataclass
class BlockKeep:
    rank: np.ndarray                   # (..., r) bool — singular values kept
    sparse: str                        # "all", "none" or "top"
    kept_nnz: np.ndarray               # (...,) sparse entries kept per slice

    @property
    def kept_rank(self) -> np.ndarray:   # (...,) per slice
        return self.rank.sum(axis=-1)

    def sparse_mask(self, values: np.ndarray, idx: np.ndarray) -> np.ndarray:
        """(..., cap) bool: the entries kept, from the block's COO lists."""
        live = idx >= 0
        if self.sparse == "all":
            return live
        if self.sparse == "none":
            return np.zeros_like(live)
        mag = np.where(live, np.abs(values.astype(np.float64)), -np.inf)
        return _keep_top(mag, live, self.kept_nnz)


def split(c: int, kappa: float, c_l: int, c_s: int) -> tuple[float, float]:
    if c > c_l + c_s:
        raise ValueError(f"budget {c} exceeds the {c_l + c_s} removable")
    bl, bs = kappa * c, (1.0 - kappa) * c
    if c_l > 0 and bl > c_l:
        bs, bl = bs + bl - c_l, c_l
    if c_s > 0 and bs > c_s:
        bl, bs = min(bl + bs - c_s, c_l), c_s
    phi_l = bl / c_l if c_l > 0 else 0.0
    phi_s = bs / c_s if c_s > 0 else 0.0
    return min(phi_l, 1.0), min(phi_s, 1.0)


def _keep_top(score: np.ndarray, live: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """The ``keep`` highest-scoring live entries per slice. Equal scores go
    in ``np.argsort``'s order of the float64 scores, with empty entries at
    -inf: given the same values, the same entries as any cut that sorts them
    so."""
    order = np.argsort(-np.where(live, score, -np.inf), axis=-1)
    ranks = np.empty_like(order)
    np.put_along_axis(ranks, order,
                      np.broadcast_to(np.arange(score.shape[-1]), order.shape),
                      axis=-1)
    return (ranks < keep[..., None]) & live


def keep_masks(blocks: list[BlockHost], keep: float, kappa: float):
    """({block name: BlockKeep}, (phi_L, phi_S)) for one tier."""
    c_l = sum(int((b.s_vals > 0).sum()) * (b.n + b.m) for b in blocks)
    c_s = sum(int(b.nnz.sum()) for b in blocks)
    budget = int(round((1.0 - keep) * (c_l + c_s)))
    phi_l, phi_s = split(budget, kappa, c_l, c_s)
    out = {}
    for b in blocks:
        live_l = b.s_vals > 0
        keep_l = np.ceil((1.0 - phi_l) * live_l.sum(axis=-1)).astype(np.int64)
        keep_s = np.floor((1.0 - phi_s) * b.nnz).astype(np.int64)
        mode = "all" if phi_s == 0.0 else "none" if phi_s == 1.0 else "top"
        out[b.name] = BlockKeep(
            _keep_top(b.s_vals.astype(np.float64), live_l, keep_l), mode,
            b.nnz if mode == "all" else keep_s)
    return out, (phi_l, phi_s)


def host_blocks(state, blocks) -> list[BlockHost]:
    """HPA's inputs of a device state: singular values and live counts."""
    import jax.numpy as jnp

    return [BlockHost(info.name, info.n, info.m,
                      np.asarray(state[info.name].s_vals, np.float32),
                      np.asarray(jnp.sum(state[info.name].s_coo.idx >= 0, axis=-1)))
            for info in blocks]

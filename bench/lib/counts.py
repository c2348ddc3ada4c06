"""Operations and bytes of the served mathematics, from shapes.

Counts follow what a tier computes, not how it is stored: a linear site of
layer ``l`` costs ``2 (n r + r m)`` FLOPs per token at the rank ``r`` that HPA
kept and ``2 nnz`` for the sparse entries it kept; padding of ranks to the
largest layer, densified block tables and padded rows do not count, so they
show as a lower share of the peak. Attention counts the scores and the
weighted sum over the keys a query sees; the output head counts once per
token that a request receives.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

BF16 = 2
INT32 = 4


@dataclass
class Site:
    """One linear site of the layer stack under one tier."""

    name: str
    n: int
    m: int
    rank: np.ndarray          # (L,) kept rank per layer
    nnz: np.ndarray           # (L,) kept sparse entries per layer

    @property
    def fused_sparse(self) -> bool:
        """The tier serves this site through the stacked fused kernel (it
        keeps sparse entries in some layer)."""
        return bool(self.nnz.max() > 0)


@dataclass
class TierMath:
    keep: float
    sites: list[Site] = field(default_factory=list)
    num_layers: int = 0
    d_model: int = 0
    vocab: int = 0
    q_heads: int = 0
    head_dim: int = 0

    def linear_flops_per_token(self) -> float:
        return float(sum(
            (2 * (s.n * s.rank + s.rank * s.m) + 2 * s.nnz).sum()
            for s in self.sites))

    def attention_flops(self, keys: float) -> float:
        """Scores and weighted sum of one query over ``keys`` keys, all layers."""
        return 4.0 * self.num_layers * self.q_heads * self.head_dim * keys

    def head_flops(self) -> float:
        return 2.0 * self.d_model * self.vocab

    def prefill_flops(self, prompt: int) -> float:
        keys = prompt * (prompt + 1) / 2.0
        return (prompt * self.linear_flops_per_token()
                + self.attention_flops(keys) + self.head_flops())

    def decode_flops(self, context: int) -> float:
        """One decode token whose query sees ``context`` keys."""
        return (self.linear_flops_per_token() + self.attention_flops(context)
                + self.head_flops())

    def stacked_kernel_least_s(self, rows: int, peak_flops: float,
                               peak_bw: float) -> float:
        """Least time of the stacked fused SLR kernel calls of one program
        execution over ``rows`` token rows: per call, the larger of its FLOPs
        over peak and its bytes (x, P, Vt, kept S values with one int32
        index each, y) over bandwidth."""
        total = 0.0
        for s in self.sites:
            if not s.fused_sparse:
                continue
            for r, nnz in zip(s.rank.tolist(), s.nnz.tolist()):
                flops = 2.0 * rows * (s.n * r + r * s.m) + 2.0 * rows * nnz
                nbytes = (rows * s.n * BF16 + s.n * r * BF16 + r * s.m * BF16
                          + nnz * (BF16 + INT32) + rows * s.m * BF16)
                total += max(flops / peak_flops, nbytes / peak_bw)
        return total


def tier_math(keep: float, keeps_by_block: dict, blocks, arch) -> TierMath:
    """The tier's sites from HPA's kept ranks and entries (``lib.hpa``)."""
    sites = []
    for info in blocks:
        if not info.stack_dims or info.is_embedding:
            continue
        k = keeps_by_block[info.name]
        sites.append(Site(info.name, info.n, info.m,
                          np.asarray(k.kept_rank).reshape(-1),
                          np.asarray(k.kept_nnz).reshape(-1)))
    return TierMath(keep=keep, sites=sites, num_layers=arch.num_layers,
                    d_model=arch.d_model, vocab=arch.vocab_size,
                    q_heads=arch.num_heads, head_dim=arch.head_dim)

"""Two-stage ADMM for SLR induction — Algorithm 1 of the paper.

Stage 1 (guided learning) lives in the training loop: ``K`` ordinary optimizer
steps on the coupled loss

    l_c(X) = l(X) + sum_i rho_i/2 * || X_i - (L_i + S_i - Y_i/rho_i) ||_F^2

This module provides ``penalty`` for that term (with the surrogate target
``Z = L + S - Y/rho`` stop-gradiented: it is a constant during stage 1) and
``admm_update`` for stage 2 — the closed-form proximal sweep

    L <- SVT_{alpha/rho}(X - S + Y/rho)
    S <- shrink_{beta/rho}(X - L + Y/rho)
    Y <- Y + rho (X - L - S)

followed by the I-controller update of (alpha, beta).

Memory layout (beyond-paper, see DESIGN.md §2):
  * L is stored factored as ``p = U diag(s_thr)`` (n, r) and ``vt`` (r, m)
    with r the randomized-SVD rank cap — never dense;
  * S is a fixed-capacity COO list (``core.sparse``);
  * only Y is dense.
Surrogate tensors inherit the sharding of their weight (the launcher pins
them with the same NamedSharding), so the update is fully SPMD — this is the
TPU analogue of the paper's per-GPU block placement (App. C).

Stacked leaves (scan-stacked layers ``(Lyr, n, m)``, stacked experts
``(Lyr, E, n, m)``) are handled by flattening the leading dims and vmapping
the per-block update, so every slice keeps its own (alpha, beta) — exactly
the paper's block-wise controller.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import sparse
from .controller import ControllerConfig, controller_update
from .prox import effective_rank_ratio_from_singular_values, soft_threshold
from .rsvd import randomized_svd, rank_cap
from .scaling import PAPER_RHO_CONSTANT, rho_for_block
from .selection import BlockInfo, SelectionConfig, select_blocks, total_logical_blocks

__all__ = [
    "SalaadConfig",
    "BlockSLR",
    "SLRState",
    "init_slr_state",
    "penalty",
    "admm_update",
    "surrogate_params",
    "slr_param_count",
]


@dataclass(frozen=True)
class SalaadConfig:
    """Everything that parameterizes Algorithm 1."""

    rho_constant: float = PAPER_RHO_CONSTANT  # proportionality in Eq. (7)
    controller: ControllerConfig = field(default_factory=ControllerConfig)
    selection: SelectionConfig = field(default_factory=SelectionConfig)
    rank_cap_ratio: float = 0.25     # randomized-SVD sketch cap (vs min(n,m))
    coo_cap_density: float = 0.15    # S capacity (vs n*m); 3x the 0.05 target
    rsvd_iters: int = 2              # power iterations in the range finder
    admm_inner_steps: int = 1        # J in Algorithm 1 (paper default: 1)
    update_every: int = 40           # K in Algorithm 1 (paper App. C: K=40)
    surrogate_dtype: Any = jnp.float32  # dtype of (p, vt, S, Y); bf16 at scale
    exact_svd: bool = False          # tests: use jnp.linalg.svd instead of rsvd


@dataclass(frozen=True)
class BlockSLR:
    """Per-leaf surrogate state; leading dims mirror the weight's stack dims."""

    p: jax.Array          # (..., n, r)   U diag(s_thr)  — L = p @ vt
    vt: jax.Array         # (..., r, m)
    s_vals: jax.Array     # (..., r)      thresholded singular values
    s_coo: sparse.CooMatrix  # sparse S
    y: jax.Array          # (..., n, m)   dual
    z: jax.Array          # (..., n, m)   cached penalty target L + S - Y/rho
    alpha: jax.Array      # (...,)        per-slice nuclear-norm weight
    beta: jax.Array       # (...,)        per-slice l1 weight
    rho: float            # static — Eq. (7) value for this block shape


jax.tree_util.register_dataclass(
    BlockSLR,
    data_fields=["p", "vt", "s_vals", "s_coo", "y", "z", "alpha", "beta"],
    meta_fields=["rho"],
)

# An SLRState is a dict: block name -> BlockSLR (a plain pytree).
SLRState = dict


def _leaf_by_path(params: Any, path: tuple) -> jax.Array:
    leaf = params
    for p in path:
        if hasattr(p, "key"):
            leaf = leaf[p.key]
        elif hasattr(p, "idx"):
            leaf = leaf[p.idx]
        elif hasattr(p, "name"):
            leaf = getattr(leaf, p.name)
        else:
            leaf = leaf[p]
    return leaf


def init_slr_state(
    params: Any, cfg: SalaadConfig = SalaadConfig()
) -> tuple[SLRState, list[BlockInfo]]:
    """Zero-initialized surrogate state for every selected block.

    With (L, S, Y) = 0 the coupled penalty starts as plain weight decay toward
    the SLR manifold through Z=0 scaled by the (tiny) rho — matching the
    paper's observation that stage 1 "does not interfere with the behavior of
    the underlying optimizer".
    """
    blocks = select_blocks(params, cfg.selection)
    n_logical = max(1, total_logical_blocks(blocks))
    state: SLRState = {}
    for info in blocks:
        x = _leaf_by_path(params, info.path)
        n, m = info.n, info.m
        r = rank_cap(n, m, cfg.rank_cap_ratio)
        cap = sparse.coo_cap(n, m, cfg.coo_cap_density)
        stack = info.stack_dims
        dt = cfg.surrogate_dtype
        state[info.name] = BlockSLR(
            p=jnp.zeros((*stack, n, r), dt),
            vt=jnp.zeros((*stack, r, m), dt),
            s_vals=jnp.zeros((*stack, r), dt),
            s_coo=sparse.CooMatrix(
                values=jnp.zeros((*stack, cap), dt),
                idx=jnp.full((*stack, cap), -1, jnp.int32),
                shape=(n, m),
            ),
            y=jnp.zeros((*stack, n, m), dt),
            z=jnp.zeros((*stack, n, m), dt),
            alpha=jnp.zeros(stack, jnp.float32),
            beta=jnp.zeros(stack, jnp.float32),
            rho=rho_for_block(n, m, n_logical, cfg.rho_constant),
        )
    return state, blocks


def _z_target(blk: BlockSLR) -> jax.Array:
    """Z = L + S - Y/rho, reconstructed from the compact storage."""
    l_dense = blk.p @ blk.vt
    s_dense = sparse.to_dense(blk.s_coo).astype(l_dense.dtype)
    return l_dense + s_dense - blk.y / blk.rho


def penalty(params: Any, state: SLRState, blocks: list[BlockInfo]) -> jax.Array:
    """Stage-1 coupled-loss term  sum_i rho_i/2 ||X_i - Z_i||_F^2.

    Uses the CACHED dense target Z (refreshed by every admm_update): Z is a
    constant within a guided-learning phase, so deriving it from (L, S, Y)
    every microstep would only add a scatter + matmul per block per step to
    the hot path. Computed in f32 for a well-scaled scalar.
    """
    total = jnp.zeros((), jnp.float32)
    for info in blocks:
        blk = state[info.name]
        x = _leaf_by_path(params, info.path).astype(jnp.float32)
        z = jax.lax.stop_gradient(blk.z).astype(jnp.float32)
        total = total + 0.5 * blk.rho * jnp.sum((x - z) ** 2)
    return total


# ---------------------------------------------------------------- stage 2 ---


def _admm_update_single(
    x: jax.Array,
    p: jax.Array,
    vt: jax.Array,
    s_vals: jax.Array,
    s_coo_values: jax.Array,
    s_coo_idx: jax.Array,
    y: jax.Array,
    alpha: jax.Array,
    beta: jax.Array,
    key: jax.Array,
    *,
    rho: float,
    shape: tuple[int, int],
    rank: int,
    cap: int,
    cfg: SalaadConfig,
) -> tuple[tuple, dict]:
    """One J-sweep of proximal updates for a single (n, m) block."""
    n, m = shape
    dt = p.dtype
    x32 = x.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    s_dense = sparse.to_dense(
        sparse.CooMatrix(s_coo_values, s_coo_idx, shape)
    ).astype(jnp.float32)

    def sweep(j, carry):
        s_dense, y32, p_, vt_, s_vals_ = carry
        mmat = x32 - s_dense + y32 / rho
        if cfg.exact_svd:
            u, s, v = jnp.linalg.svd(mmat, full_matrices=False)
            u, s, v = u[:, :rank], s[:rank], v[:rank, :]
        else:
            u, s, v = randomized_svd(mmat, jax.random.fold_in(key, j), rank, cfg.rsvd_iters)
        s_thr = jnp.maximum(s - alpha / rho, 0.0)
        p_new = u * s_thr[None, :]
        l_dense = p_new @ v
        s_new = soft_threshold(x32 - l_dense + y32 / rho, beta / rho)
        y_new = y32 + rho * (x32 - l_dense - s_new)
        return (s_new, y_new, p_new, v, s_thr)

    s_dense, y32, p_new, vt_new, s_thr = jax.lax.fori_loop(
        0,
        cfg.admm_inner_steps,
        sweep,
        (s_dense, y32, jnp.zeros_like(p, jnp.float32), jnp.zeros_like(vt, jnp.float32), jnp.zeros_like(s_vals, jnp.float32)),
    )

    coo = sparse.from_dense(s_dense, cap)
    s_back = sparse.to_dense(coo)
    rank_ratio = effective_rank_ratio_from_singular_values(
        s_thr, cfg.controller.gamma, denom=min(n, m)
    )
    dens = sparse.nnz(coo).astype(jnp.float32) / (n * m)
    alpha_new, beta_new = controller_update(
        alpha, beta, rank_ratio, dens, rho, cfg.controller
    )
    l_dense = p_new @ vt_new
    recon_err = jnp.linalg.norm(x32 - l_dense - s_back)
    z_new = l_dense + s_back - y32 / rho
    stats = {
        "rank_ratio": rank_ratio,
        "density": dens,
        "recon_err": recon_err,
        "alpha": alpha_new,
        "beta": beta_new,
    }
    new = (
        p_new.astype(dt),
        vt_new.astype(dt),
        s_thr.astype(dt),
        coo.values.astype(dt),
        coo.idx,
        y32.astype(dt),
        z_new.astype(dt),
        alpha_new,
        beta_new,
    )
    return new, stats


def _update_leaf(x: jax.Array, blk: BlockSLR, info: BlockInfo, key: jax.Array, cfg: SalaadConfig):
    n, m = info.n, info.m
    r = blk.p.shape[-1]
    cap = blk.s_coo.values.shape[-1]
    fn = partial(
        _admm_update_single,
        rho=blk.rho,
        shape=(n, m),
        rank=r,
        cap=cap,
        cfg=cfg,
    )
    stack = info.stack_dims
    if stack:
        nb = int(np.prod(stack))
        flat = lambda a, tail: a.reshape(nb, *tail)  # noqa: E731
        keys = jax.random.split(key, nb)
        new, stats = jax.vmap(fn)(
            flat(x, (n, m)),
            flat(blk.p, (n, r)),
            flat(blk.vt, (r, m)),
            flat(blk.s_vals, (r,)),
            flat(blk.s_coo.values, (cap,)),
            flat(blk.s_coo.idx, (cap,)),
            flat(blk.y, (n, m)),
            blk.alpha.reshape(nb),
            blk.beta.reshape(nb),
            keys,
        )
        unflat = lambda a: a.reshape(*stack, *a.shape[1:])  # noqa: E731
        new = tuple(unflat(a) for a in new)
        stats = {k: unflat(v) for k, v in stats.items()}
    else:
        new, stats = fn(
            x, blk.p, blk.vt, blk.s_vals, blk.s_coo.values, blk.s_coo.idx,
            blk.y, blk.alpha, blk.beta, key,
        )
    p, vt, s_vals, coo_v, coo_i, y, z, alpha, beta = new
    blk_new = BlockSLR(
        p=p, vt=vt, s_vals=s_vals,
        s_coo=sparse.CooMatrix(coo_v, coo_i, (n, m)),
        y=y, z=z, alpha=alpha, beta=beta, rho=blk.rho,
    )
    return blk_new, stats


def admm_update(
    params: Any,
    state: SLRState,
    blocks: list[BlockInfo],
    cfg: SalaadConfig,
    step: jax.Array | int,
) -> tuple[SLRState, dict]:
    """Stage 2 + I-controller for every block. Deterministic in ``step``
    (rSVD keys are folded from it) so checkpoint/restart replays identically.
    """
    base_key = jax.random.PRNGKey(0)
    new_state: SLRState = {}
    all_stats: dict = {}
    for i, info in enumerate(blocks):
        x = _leaf_by_path(params, info.path)
        key = jax.random.fold_in(jax.random.fold_in(base_key, jnp.asarray(step, jnp.int32)), i)
        blk_new, stats = _update_leaf(x.astype(jnp.float32), state[info.name], info, key, cfg)
        new_state[info.name] = blk_new
        all_stats[info.name] = stats
    # aggregates (paper's delta-bar: mean reconstruction error over blocks)
    recon = [jnp.mean(s["recon_err"]) for s in all_stats.values()]
    all_stats["_mean_recon_err"] = jnp.mean(jnp.stack(recon)) if recon else jnp.zeros(())
    return new_state, all_stats


# --------------------------------------------------------------- deploy ----


def materialize_block(blk: BlockSLR, dtype) -> jax.Array:
    """X_hat = L + S of one block. L = P @ Vt is taken at full precision: a
    TPU's default runs a float32 matmul as one bfloat16 pass, which would
    round the deployed weights themselves."""
    low_rank = jnp.matmul(blk.p, blk.vt, precision=jax.lax.Precision.HIGHEST)
    return (low_rank + sparse.to_dense(blk.s_coo).astype(blk.p.dtype)).astype(dtype)


def surrogate_params(params: Any, state: SLRState, blocks: list[BlockInfo]) -> Any:
    """X_hat = L + S for selected blocks; other leaves pass through.

    This is the paper's structured surrogate model used at deployment.
    """
    by_name = {info.name: info for info in blocks}

    def replace_leaf(path, leaf):
        from .selection import path_str

        name = path_str(path)
        if name in by_name and name in state:
            return materialize_block(state[name], leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(replace_leaf, params)


def slr_param_count(state: SLRState, blocks: list[BlockInfo]) -> dict:
    """Deployment parameter accounting (factored L + COO S), per block + total."""
    out = {}
    total = 0
    for info in blocks:
        blk = state[info.name]
        rank_live = np.asarray(jnp.sum((blk.s_vals > 0).astype(jnp.int32), axis=-1))
        nnz_live = np.asarray(sparse.nnz(blk.s_coo))
        l_params = int(np.sum(rank_live) * (info.n + info.m))
        s_params = int(np.sum(nnz_live))
        out[info.name] = {"L": l_params, "S": s_params}
        total += l_params + s_params
    out["_total"] = total
    return out

"""Public jit'd entry points for the Pallas kernels.

``interpret`` resolves, in order: the argument; the mode named by an
enclosing :func:`interpret_mode` block; else Pallas interpret mode when the
default backend is not a TPU, compiled Mosaic on one. Models and the serving
engine call these through the ``kernel_impl`` config switch; everything falls
back to the pure-jnp reference implementations under ``kernel_impl='xla'`` so
pjit / GSPMD sharding is never blocked by a kernel.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp

from . import ref
from .bsr_matmul import BsrMatrix, bsr_from_dense, bsr_matmul_pallas, bsr_to_dense
from .flash_attention import flash_attention_pallas
from .lowrank_matmul import lowrank_matmul_pallas
from .page_copy import page_copy_pallas
from .paged_attention import paged_attention_kquery_pallas, paged_attention_pallas
from .slr_matmul import (
    BsrStack,
    slr_matmul_multi_pallas,
    slr_matmul_pallas,
    slr_matmul_stacked_pallas,
    stack_bsr,
)
from .soft_threshold import soft_threshold_pallas

__all__ = [
    "interpret_mode",
    "BsrMatrix",
    "BsrStack",
    "bsr_from_dense",
    "bsr_to_dense",
    "stack_bsr",
    "soft_threshold",
    "lowrank_matmul",
    "bsr_matmul",
    "slr_matmul",
    "slr_matmul_stacked",
    "slr_matmul_multi",
    "flash_attention",
    "paged_attention",
    "paged_attention_kquery",
    "page_copy",
    "bsr_occupancy",
]


_INTERPRET: contextvars.ContextVar[bool | None] = contextvars.ContextVar(
    "pallas_interpret", default=None
)


@contextlib.contextmanager
def interpret_mode(interpret: bool):
    """Kernels traced inside the block run in Pallas interpret mode (True) or
    compiled for the TPU (False), whatever the default backend is."""
    token = _INTERPRET.set(interpret)
    try:
        yield
    finally:
        _INTERPRET.reset(token)


def _auto_interpret() -> bool:
    chosen = _INTERPRET.get()
    if chosen is not None:
        return chosen
    return jax.default_backend() != "tpu"


def soft_threshold(x, tau, interpret: bool | None = None):
    return soft_threshold_pallas(
        x, tau, interpret=_auto_interpret() if interpret is None else interpret
    )


def lowrank_matmul(x, p, vt, interpret: bool | None = None, **kw):
    return lowrank_matmul_pallas(
        x, p, vt, interpret=_auto_interpret() if interpret is None else interpret, **kw
    )


def bsr_matmul(x, bsr: BsrMatrix, interpret: bool | None = None, **kw):
    # Empty-S fast path: `empty` is static deploy-time metadata, so jitted
    # callers skip the kernel (MAXB is padded to >= 1 even for an all-zero
    # matrix — one dead DMA+matmul per column block per call otherwise).
    if getattr(bsr, "empty", False):
        return jnp.zeros((x.shape[0], bsr.shape[1]), x.dtype)
    return bsr_matmul_pallas(
        x, bsr, interpret=_auto_interpret() if interpret is None else interpret, **kw
    )


def slr_matmul(x, p, vt, bsr: BsrMatrix | None, interpret: bool | None = None, **kw):
    """Fused y = x @ P @ Vt + x @ S in one Pallas pass over x row-tiles.

    Degenerate corners dispatch to the cheaper single-phase kernels: empty S
    (static ``bsr.empty``) skips the sparse epilogue via ``lowrank_matmul``,
    r == 0 / missing factors skip the low-rank phases via ``bsr_matmul``.
    """
    interp = _auto_interpret() if interpret is None else interpret
    r = 0 if p is None else p.shape[-1]
    empty_s = bsr is None or getattr(bsr, "empty", False)
    if empty_s and r == 0:
        m = vt.shape[-1] if vt is not None else bsr.shape[1]
        return jnp.zeros((x.shape[0], m), x.dtype)
    if empty_s:
        from .slr_matmul import row_tile

        bm = row_tile(x.shape[0], x.dtype, cap=kw.pop("bt", 128))
        return lowrank_matmul_pallas(x, p, vt, bm=bm, interpret=interp)
    if r == 0:
        return bsr_matmul(x, bsr, interpret=interp, **kw)
    return slr_matmul_pallas(x, p, vt, bsr, interpret=interp, **kw)


def slr_matmul_stacked(x, p, vt, stack: BsrStack | None, layer,
                       interpret: bool | None = None, **kw):
    """Layer-scannable fused SLR matmul: per-layer tables selected inside the
    kernel's DMA index maps via the scalar-prefetched ``layer`` id.

    Same degenerate-corner dispatch as ``slr_matmul``; the r == 0 /
    non-empty-S corner (rare: a site that kept sparse support but no live
    rank) rides the fused kernel with dummy rank-1 zero factors rather than
    growing a third stacked kernel.
    """
    interp = _auto_interpret() if interpret is None else interpret
    r = 0 if p is None else p.shape[-1]
    empty_s = stack is None or getattr(stack, "empty", False)
    if empty_s and r == 0:
        m = vt.shape[-1] if vt is not None else stack.shape[1]
        return jnp.zeros((x.shape[0], m), x.dtype)
    if empty_s:
        from .slr_matmul import row_tile

        p_l = jax.lax.dynamic_index_in_dim(p, layer, keepdims=False)
        vt_l = jax.lax.dynamic_index_in_dim(vt, layer, keepdims=False)
        bm = row_tile(x.shape[0], x.dtype, cap=kw.pop("bt", 128))
        return lowrank_matmul_pallas(x, p_l, vt_l, bm=bm, interpret=interp)
    if r == 0:
        num_l = stack.counts.shape[0]
        p = jnp.zeros((num_l, x.shape[1], 1), x.dtype)
        vt = jnp.zeros((num_l, 1, stack.shape[1]), x.dtype)
    return slr_matmul_stacked_pallas(x, p, vt, stack, layer, interpret=interp, **kw)


def slr_matmul_multi(x, p, vt, stack: BsrStack | None, ids,
                     interpret: bool | None = None, **kw):
    """Batched heterogeneous-adapter fused SLR matmul: slot ``b`` of the
    (B, T, K) activation batch runs adapter ``ids[b]``'s (P, Vt, S) tables,
    selected per slot inside the kernel's DMA index maps.

    Degenerate corners mirror ``slr_matmul_stacked`` OP FOR OP — the serving
    parity guarantee (AdapterBank vs ModelBank bitwise-identical streams)
    depends on each corner running the exact same kernel per slot, so the
    empty-S corner maps ``lowrank_matmul_pallas`` over slots rather than
    batching into a differently-tiled einsum.
    """
    interp = _auto_interpret() if interpret is None else interpret
    r = 0 if p is None else p.shape[-1]
    empty_s = stack is None or getattr(stack, "empty", False)
    if empty_s and r == 0:
        m = vt.shape[-1] if vt is not None else stack.shape[1]
        return jnp.zeros((*x.shape[:2], m), x.dtype)
    if empty_s:
        from .slr_matmul import row_tile

        bm = row_tile(x.shape[1], x.dtype, cap=kw.pop("bt", 128))
        ids = jnp.asarray(ids, jnp.int32)

        def one_slot(args):
            xb, i = args
            p_i = jax.lax.dynamic_index_in_dim(p, i, keepdims=False)
            vt_i = jax.lax.dynamic_index_in_dim(vt, i, keepdims=False)
            return lowrank_matmul_pallas(xb, p_i, vt_i, bm=bm, interpret=interp)

        return jax.lax.map(one_slot, (x, ids))
    if r == 0:
        num_n = stack.counts.shape[0]
        p = jnp.zeros((num_n, x.shape[2], 1), x.dtype)
        vt = jnp.zeros((num_n, 1, stack.shape[1]), x.dtype)
    if interpret is None and interp:
        # Off-TPU the grid emulation is pathological for THIS op: every
        # pallas_call charges for the full (A*L, ...) pooled operands and
        # the N*JB*MAXB scalar table, where on hardware the DMA index maps
        # move only the B slots' blocks — cost grows with pool capacity,
        # not with the batch. The jnp oracle performs the same per-slot
        # gather + matmul in one vectorized pass, so it IS the correct
        # non-TPU lowering; pass ``interpret=True`` explicitly to exercise
        # the emulated kernel itself (kernel tests do). The degenerate
        # corners above stay on the single-tenant kernels in either case —
        # the bitwise-parity guarantee needs each corner to run the exact
        # per-slot op the plain tier path runs.
        return ref.slr_matmul_multi_ref(x, p, vt, stack, ids)
    return slr_matmul_multi_pallas(x, p, vt, stack, ids, interpret=interp, **kw)


def flash_attention(q, k, v, causal=True, interpret: bool | None = None, **kw):
    fn = functools.partial(
        flash_attention_pallas, causal=causal,
        interpret=_auto_interpret() if interpret is None else interpret, **kw
    )
    mesh = _head_shard_mesh(q.shape[1], k.shape[1])
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        heads = P(None, "model", None, None)   # (B, H, T, D) heads sharded
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(heads, heads, heads),
                           out_specs=heads, check_vma=False)
    return fn(q, k, v)


def _head_shard_mesh(num_q_heads: int, num_kv_heads: int):
    """The active mesh, iff an attention kernel should shard_map over heads.

    GSPMD cannot partition a Mosaic kernel (the TPU compiler refuses one with
    sharded operands), so under an active mesh with model > 1 the attention
    dispatchers wrap their kernels in ``shard_map`` over the KV-head axis.
    Per-(batch, head) attention is independent, and GQA groups stay
    co-located (hq/m q heads + hkv/m kv heads per rank), so the body needs NO
    collective; the psum happens later at the row-parallel o-projection,
    exactly as for dense TP.
    """
    from ..parallel.sharding import current_mesh

    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    m = int(mesh.shape["model"])
    if m <= 1 or num_q_heads % m or num_kv_heads % m:
        return None
    return mesh


def paged_attention(q, k_pages, v_pages, block_table, lengths,
                    interpret: bool | None = None):
    interp = _auto_interpret() if interpret is None else interpret
    mesh = _head_shard_mesh(q.shape[1], k_pages.shape[1])
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            functools.partial(paged_attention_pallas, interpret=interp),
            mesh=mesh,
            in_specs=(
                P(None, "model", None),        # q: (B, Hq, D) heads sharded
                P(None, "model", None, None),  # k pool: (pages, Hkv, bs, D)
                P(None, "model", None, None),  # v pool
                P(),                           # block table: host bookkeeping
                P(),                           # lengths
            ),
            out_specs=P(None, "model", None),
            check_vma=False,
        )
        return fn(q, k_pages, v_pages, block_table, lengths)
    return paged_attention_pallas(
        q, k_pages, v_pages, block_table, lengths, interpret=interp,
    )


def paged_attention_kquery(q, k_pages, v_pages, block_table, lengths,
                           interpret: bool | None = None,
                           q_tile: int | None = None):
    """Multi-query paged attention: the speculative-verify window (kq = draft
    k) and chunked-prefill chunks (kq = prefill_chunk) share this kernel —
    wide windows tile the query axis across the grid (``q_tile``)."""
    interp = _auto_interpret() if interpret is None else interpret
    mesh = _head_shard_mesh(q.shape[1], k_pages.shape[1])
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            functools.partial(
                paged_attention_kquery_pallas, interpret=interp, q_tile=q_tile
            ),
            mesh=mesh,
            in_specs=(
                P(None, "model", None, None),  # q: (B, Hq, kq, D)
                P(None, "model", None, None),
                P(None, "model", None, None),
                P(),
                P(),
            ),
            out_specs=P(None, "model", None, None),
            check_vma=False,
        )
        return fn(q, k_pages, v_pages, block_table, lengths)
    return paged_attention_kquery_pallas(
        q, k_pages, v_pages, block_table, lengths, interpret=interp, q_tile=q_tile,
    )


def page_copy(pool, src, dst, interpret: bool | None = None):
    """Batched whole-page copy ``out[:, dst[i]] = pool[:, src[i]]`` — the
    device half of copy-on-write prefix sharing. One kernel serves float
    payload, int8 payload, and f32 scale pools alike."""
    return page_copy_pallas(
        pool, src, dst,
        interpret=_auto_interpret() if interpret is None else interpret,
    )


def bsr_occupancy(bsr: BsrMatrix) -> float:
    return bsr.occupancy

"""Gradient compression for the data-parallel all-reduce.

int8 quantized all-reduce with per-tensor scales, shard_map-based: each data
shard quantizes its local gradient, all-reduces the int32-accumulated values
(psum of int8 payloads upcast to int32 — exact), and dequantizes with the
psum'd max-scale. Wire bytes drop 4x (f32) / 2x (bf16) on the slowest link
(cross-pod DCN), at a quantization error bounded by scale/127 per element.

Off by default; ``make_compressed_grad_fn`` wraps a per-example loss into a
grad function with the compressed DP reduction, and the error-feedback
variant keeps a residual so the bias does not accumulate across steps
(Seide et al. 2014; tested for convergence-neutrality in
tests/test_runtime.py::TestGradCompression).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantize_int8(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_tree(grads: Any, axis_name: str, residuals: Any = None):
    """int8-quantized psum over ``axis_name`` (call inside shard_map).

    With ``residuals`` (same pytree as grads), applies error feedback: each
    worker adds its previous quantization error before quantizing and carries
    the new error forward, so compression bias does not accumulate.
    Returns (mean_grads, new_residuals) when residuals is not None.
    """

    def one(g, r=None):
        g32 = g.astype(jnp.float32)
        if r is not None:
            g32 = g32 + r
        q, scale = quantize_int8(g32)
        # exact integer accumulation; scales reduced with max (conservative)
        acc = jax.lax.psum(q.astype(jnp.int32), axis_name)
        scale_max = jax.lax.pmax(scale, axis_name)
        n = jax.lax.psum(jnp.ones(()), axis_name)
        out = (acc.astype(jnp.float32) * scale_max / n).astype(g.dtype)
        new_r = g32 - dequantize_int8(q, scale) if r is not None else None
        return out, new_r

    if residuals is None:
        return jax.tree.map(lambda g: one(g)[0], grads)
    pairs = jax.tree.map(one, grads, residuals)
    is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
    return (
        jax.tree.map(lambda t: t[0], pairs, is_leaf=is_pair),
        jax.tree.map(lambda t: t[1], pairs, is_leaf=is_pair),
    )


def make_compressed_grad_fn(loss_fn, mesh: Mesh, data_axis: str = "data"):
    """grads(params, batch) with an int8 DP all-reduce via shard_map.

    ``loss_fn(params, local_batch) -> scalar`` is evaluated per data shard on
    its batch slice; local grads are quantize-psum'd across the data axis.
    """

    def local_grads(params, batch):
        g = jax.grad(loss_fn)(params, batch)
        return compressed_psum_tree(g, data_axis)

    def grad_fn(params, batch):
        batch_specs = jax.tree.map(lambda _: P(data_axis), batch)
        param_specs = jax.tree.map(lambda _: P(), params)
        fn = jax.shard_map(
            local_grads,
            mesh=mesh,
            in_specs=(param_specs, batch_specs),
            out_specs=param_specs,
            check_vma=False,
        )
        return fn(params, batch)

    return grad_fn

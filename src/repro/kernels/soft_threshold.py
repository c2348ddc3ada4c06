"""Pallas TPU kernel: element-wise soft thresholding (l1 prox).

The shrinkage op runs over every selected block every ADMM phase — on dense
residuals the size of the weight matrix — so it is bandwidth-bound. One VMEM
tile in, one out, fully vectorized on the VPU: the roofline is HBM bandwidth
and this kernel hits it by construction (1 load + 1 store per element).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (256, 256)


def _kernel(x_ref, tau_ref, o_ref):
    x = x_ref[...]
    tau = tau_ref[0]
    o_ref[...] = jnp.sign(x) * jnp.maximum(jnp.abs(x) - tau, 0.0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def soft_threshold_pallas(
    x: jax.Array,
    tau: jax.Array | float,
    block: tuple[int, int] = DEFAULT_BLOCK,
    *,
    interpret: bool,
) -> jax.Array:
    """Shrinkage of a 2-D array, tiled (block[0], block[1]) in VMEM."""
    n, m = x.shape
    bn = min(block[0], n)
    bm = min(block[1], m)
    tau_arr = jnp.asarray(tau, x.dtype).reshape(1)
    grid = (pl.cdiv(n, bn), pl.cdiv(m, bm))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
            pl.BlockSpec((1,), lambda i, j: (0,)),  # replicated scalar threshold
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        interpret=interpret,
    )(x, tau_arr)

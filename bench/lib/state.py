"""Weights and SALAAD (L + S) state made on the device from the seed.

The shapes come from the program's own ``init_slr_state`` (traced, never
allocated: its dense dual and target tensors would take twice the model).
Per selected block, with ``v`` the entry variance of a plainly initialised
weight (``1/n`` for an ``(n, m)`` linear map, ``std**2`` for the embedding):

- ``L = P @ Vt`` at the program's rank cap: ``P = U diag(s)``, ``U`` and
  ``Vt`` Gaussian with unit columns and rows, and ``s`` strictly decreasing
  over ``octaves`` octaves (distinct, so HPA's rank cut is unambiguous),
  scaled so that ``sum(s**2) = low_share * n * m * v``;
- ``S`` at ``s_density``, placed uniformly (each entry on its own with that
  chance, as stage 2 leaves it on weights without structure), magnitudes
  log-normal, scaled so that S holds the rest of the energy.

Leaves the program does not select (norm scales, an untied output head) are
drawn here too. Everything is drawn in one jitted call per block family and
kept in the dtypes the program serves.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class SlrSpec:
    rank_cap_ratio: float
    coo_cap_density: float
    s_density: float
    kappa: float
    low_share: float
    octaves: float
    s_log_sigma: float
    embed_std: float

    @classmethod
    def from_config(cls, cfg: dict) -> "SlrSpec":
        s = cfg["slr"]
        return cls(rank_cap_ratio=s["rank_cap_ratio"],
                   coo_cap_density=s["coo_cap_density"],
                   s_density=s["s_density"],
                   kappa=s["kappa"], low_share=s["low_share"],
                   octaves=s["octaves"], s_log_sigma=s["s_log_sigma"],
                   embed_std=s["embed_std"])


def salaad_config(spec: SlrSpec, dtype):
    from repro.core.admm import SalaadConfig

    return SalaadConfig(rank_cap_ratio=spec.rank_cap_ratio,
                        coo_cap_density=spec.coo_cap_density,
                        surrogate_dtype=dtype)


def layout(arch, spec: SlrSpec):
    """(abstract params, selected blocks, abstract SLR state)."""
    from repro.core.admm import init_slr_state
    from repro.core.selection import select_blocks
    from repro.models import model as model_lib

    scfg = salaad_config(spec, arch.param_dtype)
    abstract = jax.eval_shape(
        lambda k: model_lib.init_params(arch, k), jax.random.PRNGKey(0))
    blocks = select_blocks(abstract, scfg.selection)
    shapes = jax.eval_shape(lambda p: init_slr_state(p, scfg)[0], abstract)
    return abstract, blocks, shapes


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, 32 bits or more."""
    words = np.random.default_rng(seed).integers(0, 2**31, size=2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])), int(words[1]))


def singular_values(r: int, octaves: float, energy: float) -> np.ndarray:
    s = 2.0 ** (-octaves * np.arange(r) / r)
    return (s * np.sqrt(energy / np.sum(s * s))).astype(np.float32)


@partial(jax.jit, static_argnames=("n", "m", "r", "cap", "nstack", "dtype",
                                   "density", "s_scale", "s_log_sigma"))
def _draw_block(key, s, *, n, m, r, cap, nstack, dtype, density, s_scale,
                s_log_sigma):
    """P, Vt, COO values and indices for ``nstack`` slices of one block."""
    thresh = np.uint32(min(int(density * 2.0**32), 2**32 - 1))

    def one(k):
        kp, kv, km, kmag, ksg = jax.random.split(k, 5)
        p = jax.random.normal(kp, (n, r), jnp.float32) / np.sqrt(n) * s
        vt = jax.random.normal(kv, (r, m), jnp.float32) / np.sqrt(m)
        live = jax.random.bits(km, (n * m,), jnp.uint32) < thresh
        idx = jnp.nonzero(live, size=cap, fill_value=-1)[0].astype(jnp.int32)
        mag = s_scale * jnp.exp(s_log_sigma * jax.random.normal(kmag, (cap,)))
        sign = jnp.where(jax.random.bernoulli(ksg, 0.5, (cap,)), 1.0, -1.0)
        vals = jnp.where(idx >= 0, sign * mag, 0.0)
        return p.astype(dtype), vt.astype(dtype), vals.astype(dtype), idx

    return jax.lax.map(one, jax.random.split(key, nstack))


def plain_layout(abstract, blocks, shapes) -> dict:
    """The program's layout (``layout``) as plain data: each selected block's
    stack, sizes, rank cap and sparse capacity, and every other leaf's shape
    and dtype."""
    from repro.core.selection import path_str

    names = {info.name for info in blocks}
    return {
        "blocks": [
            {"name": info.name, "stack": list(info.stack_dims), "n": info.n,
             "m": info.m, "r": int(shapes[info.name].p.shape[-1]),
             "cap": int(shapes[info.name].s_coo.values.shape[-1]),
             "embedding": bool(info.is_embedding)}
            for info in blocks
        ],
        "others": [
            {"name": path_str(path), "shape": list(sds.shape),
             "dtype": str(sds.dtype)}
            for path, sds in jax.tree_util.tree_leaves_with_path(abstract)
            if path_str(path) not in names
        ],
    }


def draw(plain: dict, spec: SlrSpec, seed: int, dtype) -> tuple[dict, dict]:
    """Every array of the model for ``seed``: per block
    ``(p, vt, s_vals, values, idx)`` with the stack axes kept, and the other
    leaves by name."""
    key = jax_key(seed)
    blocks = {}
    for bi, b in enumerate(plain["blocks"]):
        n, m, r, cap, stack = b["n"], b["m"], b["r"], b["cap"], tuple(b["stack"])
        v = spec.embed_std ** 2 if b["embedding"] else 1.0 / n
        nstack = int(np.prod(stack)) if stack else 1
        s = singular_values(r, spec.octaves, spec.low_share * n * m * v)
        # E|value|^2 = s_scale^2 exp(2 sigma^2): S holds the rest of the energy
        s_scale = float(np.sqrt((1 - spec.low_share) * v / spec.s_density)
                        * np.exp(-spec.s_log_sigma ** 2))
        p, vt, vals, idx = _draw_block(
            jax.random.fold_in(key, bi), jnp.asarray(s), n=n, m=m, r=r,
            cap=cap, nstack=nstack, dtype=jnp.dtype(dtype),
            density=spec.s_density, s_scale=s_scale,
            s_log_sigma=spec.s_log_sigma)
        blocks[b["name"]] = (
            p.reshape(*stack, n, r), vt.reshape(*stack, r, m),
            jnp.broadcast_to(jnp.asarray(s), (*stack, r)),
            vals.reshape(*stack, cap), idx.reshape(*stack, cap))
    others = {}
    okey = jax.random.fold_in(key, 10**6)
    for i, o in enumerate(plain["others"]):
        k, shape, name = jax.random.fold_in(okey, i), tuple(o["shape"]), o["name"]
        if name.endswith("norm_scale"):
            x = 0.05 * jax.random.normal(k, shape, jnp.float32)
        elif name == "lm_head/w":
            x = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        else:
            raise ValueError(f"no rule draws the leaf {name!r} {shape}")
        others[name] = x.astype(o["dtype"])
    return blocks, others


def make_state(arch, spec: SlrSpec, seed: int):
    """(params for the program, SLR state, blocks, plain layout) for ``seed``.

    In the params, every selected leaf is a ``ShapeDtypeStruct``: the program
    replaces it from the state when it deploys, and no dense weight of a
    selected block is ever allocated."""
    from repro.core import sparse
    from repro.core.admm import BlockSLR
    from repro.core.selection import path_str

    abstract, blocks, shapes = layout(arch, spec)
    plain = plain_layout(abstract, blocks, shapes)
    raw, others = draw(plain, spec, seed, arch.param_dtype)
    dtype = jnp.dtype(arch.param_dtype)
    state = {}
    for info in blocks:
        p, vt, s_vals, vals, idx = raw.pop(info.name)
        stack = tuple(info.stack_dims)
        state[info.name] = BlockSLR(
            p=p, vt=vt, s_vals=s_vals,
            s_coo=sparse.CooMatrix(vals, idx, (info.n, info.m)),
            # the dual and its target are training state; serving never
            # reads them
            y=jnp.zeros((0,), dtype), z=jnp.zeros((0,), dtype),
            alpha=jnp.zeros(stack, jnp.float32),
            beta=jnp.zeros(stack, jnp.float32), rho=shapes[info.name].rho,
        )
    selected = {info.name for info in blocks}

    def leaf(path, sds):
        name = path_str(path)
        return sds if name in selected else others[name]

    params = jax.tree_util.tree_map_with_path(leaf, abstract)
    return params, state, blocks, plain

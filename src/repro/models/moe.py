"""Mixture-of-Experts FFN with explicit expert parallelism.

Two execution paths:

* **shard_map EP** (under a mesh with a 'model' axis): activations at the MoE
  boundary are replicated across 'model' (they're P(data, None, None) — the
  same layout the dense TP blocks use), so each model-rank owns E/TP experts
  and simply processes ALL of its data-shard's tokens for ITS experts; the
  partial outputs are psum'd over 'model' — the exact collective pattern of a
  row-parallel dense FFN. No all_to_all, no GSPMD guesswork. Leaving dispatch
  to GSPMD instead was measured to all-gather ~1 TB/device/step on
  dbrx-132b train_4k (see EXPERIMENTS.md §Perf iteration 1).

* **single-device** path (tests, CPU): same math, one "rank" owning all
  experts.

Dispatch inside a rank is scatter-based (argsort + capacity), NOT one-hot
einsum: a (tokens, E, capacity) one-hot for 1M tokens x 128 experts costs
40-80 GB and ~1e17 counted multiply-by-zero FLOPs which would falsify the
roofline's compute term (DESIGN.md §4).

Capacity semantics: capacity is per (data-shard, expert): C = ceil(T_local *
top_k / E * capacity_factor); overflow tokens are dropped in router-score
order (Switch/GShard convention).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import current_mesh, logical_to_mesh


def init_moe(key, d: int, d_ff: int, num_experts: int, dtype) -> dict:
    kr, k1, k2, k3 = jax.random.split(key, 4)
    si, so = 1.0 / np.sqrt(d), 1.0 / np.sqrt(d_ff)
    return {
        "router": (jax.random.normal(kr, (d, num_experts)) * si).astype(jnp.float32),
        "experts": {
            "gate": (jax.random.normal(k1, (num_experts, d, d_ff)) * si).astype(dtype),
            "up": (jax.random.normal(k2, (num_experts, d, d_ff)) * si).astype(dtype),
            "down": (jax.random.normal(k3, (num_experts, d_ff, d)) * so).astype(dtype),
        },
    }


def _expert_compute(buf, w):
    """buf: (E_loc, C, d); w: experts dict with (E_loc, d, f) leaves."""
    h = jnp.einsum("ecd,edf->ecf", buf, w["gate"].astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w["up"].astype(buf.dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, w["down"].astype(buf.dtype))


def _rank_moe(
    x_flat,        # (T_loc, d) tokens this rank must serve
    gate_vals,     # (T_loc, k) normalized router weights
    expert_ids,    # (T_loc, k) global expert ids
    experts,       # dict of (E_loc, d, f) local expert weights
    e_offset,      # global id of this rank's first expert
    num_local: int,
    cap: int,
):
    """Dispatch/compute/combine for the experts owned by this rank."""
    t_loc, d = x_flat.shape
    k = expert_ids.shape[-1]
    flat_e = expert_ids.reshape(-1) - e_offset          # local expert ids
    flat_g = gate_vals.reshape(-1)
    tok_of = jnp.repeat(jnp.arange(t_loc), k)
    mine = (flat_e >= 0) & (flat_e < num_local)
    e_for_sort = jnp.where(mine, flat_e, num_local)     # park foreign slots at E
    order = jnp.argsort(e_for_sort)                     # stable by expert
    e_sorted = e_for_sort[order]
    counts = jnp.bincount(e_sorted, length=num_local + 1)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(t_loc * k) - starts[e_sorted]
    keep = (pos_sorted < cap) & (e_sorted < num_local)

    buf = jnp.zeros((num_local, cap, d), x_flat.dtype)
    src = x_flat[tok_of[order]]
    buf = buf.at[
        jnp.where(keep, e_sorted, 0), jnp.where(keep, pos_sorted, 0)
    ].add(jnp.where(keep[:, None], src, 0), mode="drop")

    y_buf = _expert_compute(buf, experts)

    vals = y_buf[jnp.where(keep, e_sorted, 0), jnp.where(keep, pos_sorted, 0)]
    vals = jnp.where(keep[:, None], vals, 0)
    out = jnp.zeros((t_loc, d), y_buf.dtype)
    out = out.at[tok_of[order]].add(vals * flat_g[order][:, None])
    return out


def _route(x_flat, router_w, num_experts, top_k):
    logits = x_flat.astype(jnp.float32) @ router_w      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e fraction_routed_e * mean_prob_e
    me = jnp.mean(probs, axis=0)
    fe = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], num_experts), axis=0)
    aux = num_experts * jnp.sum(fe * me)
    return gate_vals, expert_ids, aux


def moe_ffn(
    params: dict,
    x: jax.Array,              # (B, T, d)
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    num_groups: int | None = None,  # kept for config compat; unused
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B, T, d), aux_loss ())."""
    b, t, d = x.shape
    mesh = current_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1

    if mesh is not None and tp > 1 and num_experts % tp == 0:
        dp_ax = logical_to_mesh("data", mesh)
        dp = int(np.prod([mesh.shape[a] for a in (dp_ax if isinstance(dp_ax, tuple) else (dp_ax,)) if a]))
        e_loc = num_experts // tp
        t_loc = (b // max(dp, 1)) * t if b % max(dp, 1) == 0 else b * t
        cap = max(int(np.ceil(t_loc * top_k / num_experts * capacity_factor)), top_k)

        def local_fn(xl, router_w, experts):
            # xl: (b_loc, t, d) — replicated over 'model', sharded over data
            bl = xl.shape[0]
            x_flat = xl.reshape(bl * t, d)
            gate_vals, expert_ids, aux = _route(x_flat, router_w, num_experts, top_k)
            m_idx = jax.lax.axis_index("model")
            out = _rank_moe(
                x_flat, gate_vals, expert_ids, experts,
                e_offset=m_idx * e_loc, num_local=e_loc, cap=cap,
            )
            out = jax.lax.psum(out, "model")            # sum expert contributions
            aux = jax.lax.pmean(aux, dp_ax) if dp_ax else aux
            return out.reshape(bl, t, d), aux[None]

        batch_ax = dp_ax if b % max(dp, 1) == 0 else None
        fn = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(
                P(batch_ax, None, None),
                P(None, None),           # router replicated (tiny)
                jax.tree.map(lambda _: P("model", None, None), params["experts"]),
            ),
            out_specs=(P(batch_ax, None, None), P(None)),
            check_vma=False,
        )
        out, aux = fn(x, params["router"], params["experts"])
        return out.astype(x.dtype), jnp.mean(aux)

    # ---------------- single-rank fallback (tests / CPU / no model axis) ----
    x_flat = x.reshape(b * t, d)
    cap = max(int(np.ceil(b * t * top_k / num_experts * capacity_factor)), top_k)
    gate_vals, expert_ids, aux = _route(x_flat, params["router"], num_experts, top_k)
    out = _rank_moe(
        x_flat, gate_vals, expert_ids, params["experts"],
        e_offset=0, num_local=num_experts, cap=cap,
    )
    return out.reshape(b, t, d).astype(x.dtype), aux

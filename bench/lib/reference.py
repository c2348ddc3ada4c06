"""The plain reference: a float32 ``jax.numpy`` forward of a dense decoder
LM at a tier's L + S weights, as the configuration's source describes it.

It reads the weights the benchmark draws from the seed (``lib.state.draw``),
truncates them as HPA does (``lib.hpa``), and imports nothing of the program.
Departures from the published models, both the program's conventions:

- the token embedding is multiplied by ``sqrt(hidden_size)`` before the
  first layer;
- an RMSNorm weight is stored as its offset from 1 (``y * (1 + w)``).

Every matmul runs at ``HIGHEST`` precision, every op in float32, one layer at
a time so that the dense weights of a single layer are all it holds.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SITES = ("q", "k", "v", "o", "gate", "up", "down")


@partial(jax.jit, static_argnames=("n", "m"))
def dense_weight(p, vt, rank_keep, vals, idx, s_keep, *, n, m):
    """L + S of one slice in float32, with HPA's cuts applied."""
    low = jnp.matmul(p.astype(jnp.float32) * rank_keep, vt.astype(jnp.float32),
                     precision=HIGHEST)
    v = jnp.where((idx >= 0) & s_keep, vals.astype(jnp.float32), 0.0)
    s = jnp.zeros((n * m,), jnp.float32).at[jnp.maximum(idx, 0)].add(v)
    return low + s.reshape(n, m)


def round_e4m3(x):
    """``x`` (float32, |x| <= 448) rounded to the nearest float8 e4m3 value,
    in arithmetic: a chip without float8 may drop a round trip through the
    dtype, and the control must not."""
    m, e = jnp.frexp(x)                       # x = m 2^e, 0.5 <= |m| < 1
    normal = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)   # 3 mantissa bits
    subnormal = jnp.round(x * 2.0**9) / 2.0**9          # below 2^-6
    return jnp.where(jnp.abs(x) < 2.0**-6, subnormal, normal)


def fp8(x, axis):
    """``x`` rounded to float8 (e4m3), one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return round_e4m3(x / scale) * scale


@jax.jit
def fp8_weight(w):
    """A weight in the control's precision: float8 with one scale per output
    column, the step below the bfloat16 the models state."""
    return fp8(w, axis=0)


def _norm(x, w, kind: str, eps: float):
    if kind == "nonparametric_layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps)
    if kind == "rmsnorm":
        y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return y * (1.0 + w)
    raise ValueError(kind)


def _rope(x, theta: float):
    """Rotary embedding, halves rotated (x: (T, heads, head_dim))."""
    t, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@partial(jax.jit, static_argnames=("hq", "hkv", "hd", "norm", "eps", "theta",
                                   "control"))
def layer(x, w, n1, n2, *, hq, hkv, hd, norm, eps, theta, control=False):
    """One pre-norm decoder layer over one sequence ``x`` (T, d). With
    ``control`` every linear map takes its input in float8 too (one scale
    per token), as a float8 matmul unit would, accumulating in float32."""
    def mm(a, b):
        if control:
            a = fp8(a, axis=-1)
        return jnp.matmul(a, b, precision=HIGHEST)

    t = x.shape[0]
    h = _norm(x, n1, norm, eps)
    q = _rope(mm(h, w["q"]).reshape(t, hq, hd), theta)
    k = _rope(mm(h, w["k"]).reshape(t, hkv, hd), theta)
    v = mm(h, w["v"]).reshape(t, hkv, hd)
    g = hq // hkv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HIGHEST).reshape(t, hq * hd)
    x = x + mm(a, w["o"])
    h = _norm(x, n2, norm, eps)
    return x + mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])


@partial(jax.jit, static_argnames=("norm", "eps", "control"))
def head_logits(x, rows, nf, head, *, norm, eps, control=False):
    """Logits (len(rows), vocab) at the positions ``rows`` of ``x``."""
    h = _norm(x[rows], nf, norm, eps)
    if control:
        h = fp8(h, axis=-1)
    return jnp.matmul(h, head, precision=HIGHEST)


def sparse_masks(raw: dict, keep: dict) -> dict:
    """Per block, the (..., cap) mask of the sparse entries a tier keeps."""
    out = {}
    for name, (_, _, _, vals, idx) in raw.items():
        k = keep[name]
        if k.sparse == "top":
            out[name] = jnp.asarray(k.sparse_mask(np.asarray(vals), np.asarray(idx)))
        else:
            out[name] = jnp.full(idx.shape, k.sparse == "all")
    return out


def _block_weight(raw, keep, smask, name: str, layer_idx, n: int, m: int):
    p, vt, _, vals, idx = raw[name]
    sl = (lambda a: a) if layer_idx is None else (lambda a: a[layer_idx])
    rank_keep = jnp.asarray(sl(keep[name].rank), jnp.float32)
    return dense_weight(sl(p), sl(vt), rank_keep, sl(vals), sl(idx),
                        sl(smask[name]), n=n, m=m)


def logits(cfg: dict, plain: dict, raw: dict, others: dict, keep: dict,
           seqs: list[list[int]], rows: list[list[int]],
           control: bool = False) -> list[np.ndarray]:
    """Per sequence, the float32 logits at ``rows`` (positions whose next
    token was served); with ``control`` the same forward with every matmul's
    weights and inputs in float8 (the control).
    Sequences are zero-padded to one length, which causality keeps apart."""
    q8 = fp8_weight if control else (lambda w: w)
    sizes = {b["name"]: (b["n"], b["m"]) for b in plain["blocks"]}
    d, nl = cfg["hidden_size"], cfg["num_hidden_layers"]
    norm, eps, theta = cfg["norm"], float(cfg["norm_eps"]), float(cfg["rope_theta"])
    tpad = -(-max(len(s) for s in seqs) // 128) * 128
    smask = sparse_masks(raw, keep)
    emb = q8(_block_weight(raw, keep, smask, "embed/embedding", None,
                           *sizes["embed/embedding"]))
    xs = []
    for s in seqs:
        tok = np.zeros((tpad,), np.int32)
        tok[: len(s)] = s
        # the program's convention: embeddings scaled by sqrt(hidden_size)
        xs.append(emb[jnp.asarray(tok)] * np.float32(np.sqrt(d)))
    zeros = jnp.zeros((d,), jnp.float32)

    def norm_w(name, li=None):
        w = others.get(name)
        if w is None:
            return zeros
        return jnp.asarray(w if li is None else w[li], jnp.float32)

    for li in range(nl):
        w = {s: q8(_block_weight(raw, keep, smask, f"layers/{s}", li,
                                 *sizes[f"layers/{s}"])) for s in SITES}
        n1 = norm_w("layers/pre_attn/norm_scale", li)
        n2 = norm_w("layers/pre_mlp/norm_scale", li)
        xs = [layer(x, w, n1, n2, hq=cfg["num_attention_heads"],
                    hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                    norm=norm, eps=eps, theta=theta, control=control)
              for x in xs]
        del w
    if cfg["tie_word_embeddings"]:
        head = emb.T
    else:
        head = q8(jnp.asarray(others["lm_head/w"], jnp.float32))
    nf = norm_w("final_norm/norm_scale")
    return [np.asarray(head_logits(x, jnp.asarray(r, jnp.int32), nf, head,
                                   norm=norm, eps=eps, control=control))
            for x, r in zip(xs, rows)]

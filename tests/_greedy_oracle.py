"""Greedy decoding through the plain model API: the batched engine's oracle.

One batch-1 ``model.prefill`` into a private contiguous float32 cache, then
one ``model.decode_step`` per token. Nothing here touches the engine's page
pool, block tables, scheduler or sampling code, so a paged stream that
matches it is right on its own terms, not merely equal to another engine.
"""
import functools

import jax
import jax.numpy as jnp

from repro.models import model as model_lib


@functools.partial(jax.jit, static_argnames=("cfg", "max_len"))
def _prefill(params, tokens, cfg, max_len):
    logits, cache = model_lib.prefill(params, {"tokens": tokens}, cfg,
                                      max_len=max_len,
                                      cache_dtype=jnp.float32)
    return jnp.argmax(logits[0, -1]), cache


@functools.partial(jax.jit, static_argnames=("cfg",))
def _decode(params, token, cache, cfg):
    logits, cache = model_lib.decode_step(params, token, cache, cfg)
    return jnp.argmax(logits[0, -1]), cache


def greedy_tokens(cfg, params, prompt, max_new, max_len):
    """The first ``max_new`` greedy tokens after ``prompt``."""
    tok, cache = _prefill(params, jnp.asarray([prompt], jnp.int32), cfg,
                          max_len)
    out = [int(tok)]
    while len(out) < max_new:
        tok, cache = _decode(params, jnp.asarray([[out[-1]]], jnp.int32),
                             cache, cfg)
        out.append(int(tok))
    return out


def greedy_streams(cfg, params, prompts, max_new, max_len):
    """``{uid: tokens}`` as an engine reports it when ``prompts`` are
    submitted in order (uids count from 1)."""
    return {uid: greedy_tokens(cfg, params, p, max_new, max_len)
            for uid, p in enumerate(prompts, start=1)}

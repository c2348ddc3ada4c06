"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode accepts block shapes that Mosaic refuses on the chip, so each
kernel the serving path runs is compiled here at ``salaad_llama_60m`` widths
(d_model 512, d_ff 1376, 8 layers, 8 heads of 64) for a described ``v5e:2x2``
chip: no TPU needs to be attached, and nothing runs. The topology is
described inside a fixture, never while a module is imported, so every test
worker collects the same tests and only the worker that runs this file loads
the TPU compiler.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels import ops
from repro.kernels.bsr_matmul import BsrMatrix, bsr_matmul_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lowrank_matmul import lowrank_matmul_pallas
from repro.kernels.page_copy import page_copy_pallas
from repro.kernels.paged_attention import (
    paged_attention_kquery_pallas,
    paged_attention_pallas,
)
from repro.kernels.slr_matmul import (
    BsrStack,
    slr_matmul_multi_pallas,
    slr_matmul_stacked_pallas,
)
from repro.serving.slr_params import _fit_block

CFG = get_arch("salaad_llama_60m")
D, FF, L = CFG.d_model, CFG.d_ff, CFG.num_layers
H, HD = CFG.num_heads, CFG.head_dim
RANK = D // 4          # SalaadConfig.rank_cap_ratio of the 512-wide sites
MAXB = 4               # live S blocks per column window
SLOTS = 8              # decode batch
PAGE = 16              # KV page size in tokens
PAGES_PER_SLOT = 16    # 256 positions per slot
SITES = {"attn": (D, D), "mlp_up": (D, FF), "mlp_down": (FF, D)}
WIDTHS = {"decode": SLOTS, "prefill": 512}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# OLMo-1B's sites as the benchmark deploys them: every S block live (MAXB =
# K blocks), bf16 activations and factors, float32 block tables, at the
# ranks HPA keeps at budgets 1.0 and 0.6
OLMO_SITES = {"olmo_attn": (2048, 2048), "olmo_up": (2048, 8192),
              "olmo_down": (8192, 2048)}
OLMO_RANKS = (512, 349)
# Phi-3-medium-4k's sites at the rank HPA keeps at budget 0.6 (4 layers, as
# the benchmark's one-chip stage deploys them), every S block live, at
# decode (2 slots) and one-shot prefill (2 rows of 2048) widths
PHI3_SITES = {"phi3_qo": (5120, 5120, 870), "phi3_kv": (5120, 1280, 261),
              "phi3_up": (5120, 17920, 870), "phi3_down": (17920, 5120, 870)}
PHI3_ROWS = (2, 2 * 2048)
PHI3_H, PHI3_KV, PHI3_SLOTS, PHI3_PAGE = 40, 10, 2, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache but
    cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def stack_spec(sharding, lead: int, n: int, m: int, dtype,
               maxb: int = MAXB) -> BsrStack:
    """Block-CSC tables of an (n, m) site stacked over ``lead`` layers or
    adapters, tiled with the block size the deployment build picks."""
    bs = _fit_block(n, m, 128)
    jb = -(-m // bs)
    return BsrStack(
        spec(sharding, (lead, jb), jnp.int32),
        spec(sharding, (lead, jb, maxb), jnp.int32),
        spec(sharding, (lead, jb, maxb, bs, bs), dtype),
        (n, m), bs,
    )


def stacked_cases():
    """(site, width, dtype) at 60m widths, then OLMo-1B's sites at ranks 512
    and 349 and row widths 8 and 128, and Phi-3-medium's at rank 870 (261 at
    k and v) and row widths 2 and 4096."""
    cases = [pytest.param(site, width, dtype, id=f"{site}-{width}-{dtype}")
             for site in sorted(SITES) for width in sorted(WIDTHS)
             for dtype in sorted(DTYPES)]
    cases += [pytest.param(site, rows, r, id=f"{site}-r{r}-rows{rows}")
              for site in sorted(OLMO_SITES) for r in OLMO_RANKS
              for rows in (8, 128)]
    cases += [pytest.param(site, rows, PHI3_SITES[site][2],
                           id=f"{site}-rows{rows}")
              for site in sorted(PHI3_SITES) for rows in PHI3_ROWS]
    return cases


def compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("site,width,dtype", stacked_cases())
def test_slr_matmul_stacked(one_chip, site, width, dtype):
    """The fused-format hot path at every linear site of the model. The MLP
    sites are the regression for block sizes that divide 1376 (32 and below),
    whose x blocks Mosaic refuses. At OLMo-1B's and Phi-3-medium's sites
    ``width`` is the row count and ``dtype`` the rank: a VMEM refusal at the
    benchmark's sizes shows here."""
    if site in OLMO_SITES:
        (n, m), rows, r = OLMO_SITES[site], width, dtype
        layers, x_dt, s_dt, maxb = 16, jnp.bfloat16, jnp.float32, n // 128
    elif site in PHI3_SITES:
        (n, m, _), rows, r = PHI3_SITES[site], width, dtype
        layers, x_dt, s_dt, maxb = 4, jnp.bfloat16, jnp.float32, n // 128
    else:
        (n, m), rows, r = SITES[site], WIDTHS[width], RANK
        layers, x_dt, s_dt, maxb = L, DTYPES[dtype], DTYPES[dtype], MAXB
    compiled_text(
        functools.partial(slr_matmul_stacked_pallas, interpret=False),
        spec(one_chip, (rows, n), x_dt),
        spec(one_chip, (layers, n, r), x_dt),
        spec(one_chip, (layers, r, m), x_dt),
        stack_spec(one_chip, layers, n, m, s_dt, maxb),
        spec(one_chip, (), jnp.int32),
    )


def multi_cases():
    cases = [pytest.param(site, 1, RANK, id=site) for site in sorted(SITES)]
    cases += [pytest.param(site, t, r, id=f"{site}-r{r}-rows{SLOTS * t}")
              for site in sorted(OLMO_SITES) for r in OLMO_RANKS
              for t in (1, 16)]
    return cases


@pytest.mark.parametrize("site,t,r", multi_cases())
def test_slr_matmul_multi(one_chip, site, t, r):
    """Multi-adapter decode: each of 8 slots runs one of 4 pooled adapters
    (``t`` rows each). At OLMo-1B's sites: bf16 activations and factors,
    float32 tables, every block live."""
    adapters = 4
    if site in OLMO_SITES:
        (n, m), x_dt, maxb = OLMO_SITES[site], jnp.bfloat16, OLMO_SITES[site][0] // 128
    else:
        (n, m), x_dt, maxb = SITES[site], jnp.float32, MAXB
    compiled_text(
        functools.partial(slr_matmul_multi_pallas, interpret=False),
        spec(one_chip, (SLOTS, t, n), x_dt),
        spec(one_chip, (adapters, n, r), x_dt),
        spec(one_chip, (adapters, r, m), x_dt),
        stack_spec(one_chip, adapters, n, m, jnp.float32, maxb),
        spec(one_chip, (SLOTS,), jnp.int32),
    )


@pytest.mark.parametrize("site", sorted(SITES))
def test_lowrank_and_bsr_matmul(one_chip, site):
    """The fused dispatcher's degenerate corners: empty S and zero rank."""
    n, m = SITES[site]
    x = spec(one_chip, (SLOTS, n), jnp.float32)
    compiled_text(
        functools.partial(lowrank_matmul_pallas, bm=SLOTS, interpret=False),
        x,
        spec(one_chip, (n, RANK), jnp.float32),
        spec(one_chip, (RANK, m), jnp.float32),
    )
    one = stack_spec(one_chip, 1, n, m, jnp.float32)
    bsr = BsrMatrix(
        spec(one_chip, one.counts.shape[1:], jnp.int32),
        spec(one_chip, one.rows.shape[1:], jnp.int32),
        spec(one_chip, one.vals.shape[1:], jnp.float32),
        (n, m), one.block_size,
    )
    compiled_text(functools.partial(bsr_matmul_pallas, interpret=False), x, bsr)


def paged_pool(pool_sharding, table_sharding, dtype, slots=SLOTS, heads=H,
               page=PAGE, per_slot=PAGES_PER_SLOT, head_dim=HD, layers=L):
    """K and V stacked page pools, block table, lengths and layer index."""
    pages = slots * per_slot
    pool = (layers, pages, heads, page, head_dim)
    return (
        spec(pool_sharding, pool, dtype),
        spec(pool_sharding, pool, dtype),
        spec(table_sharding, (slots, per_slot), jnp.int32),
        spec(table_sharding, (slots,), jnp.int32),
        spec(table_sharding, (), jnp.int32),
    )


@pytest.mark.parametrize("dtype", sorted(DTYPES) + ["phi3"])
def test_paged_attention_decode(one_chip, dtype):
    """At 60m widths in each dtype; "phi3" is Phi-3-medium's decode: 40
    query heads over 10 KV heads of 128 (GQA group 4), 2 slots of 2048
    positions in pages of 128, bfloat16."""
    if dtype == "phi3":
        dt, slots, heads = jnp.bfloat16, PHI3_SLOTS, PHI3_H
        pool = paged_pool(one_chip, one_chip, dt, PHI3_SLOTS, PHI3_KV,
                          PHI3_PAGE, 2048 // PHI3_PAGE, 128, layers=4)
    else:
        dt, slots, heads = DTYPES[dtype], SLOTS, H
        pool = paged_pool(one_chip, one_chip, dt)
    compiled_text(
        functools.partial(paged_attention_pallas, interpret=False),
        spec(one_chip, (slots, heads, pool[0].shape[-1]), dt), *pool,
    )


@pytest.mark.parametrize("kq", [4, 64])     # speculative verify / prefill chunk
def test_paged_attention_kquery(one_chip, kq):
    compiled_text(
        functools.partial(paged_attention_kquery_pallas, interpret=False),
        spec(one_chip, (SLOTS, H, kq, HD), jnp.float32),
        *paged_pool(one_chip, one_chip, jnp.float32),
    )


@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_page_copy(one_chip, dtype):
    """Copy-on-write of prefix pages, over the float and the int8 pools."""
    dt = {"f32": jnp.float32, "int8": jnp.int8}[dtype]
    pages = SLOTS * PAGES_PER_SLOT
    compiled_text(
        functools.partial(page_copy_pallas, interpret=False),
        spec(one_chip, (L, pages, H, PAGE, HD), dt),
        spec(one_chip, (SLOTS,), jnp.int32),
        spec(one_chip, (SLOTS,), jnp.int32),
    )


@pytest.mark.parametrize("seq", [128, 256, "phi3-2048"])
def test_flash_attention(one_chip, seq):
    """One-shot prefill attention at the serving prompt buckets; "phi3-2048"
    is Phi-3-medium's longest bucket: 2 rows of 2048 positions, 40 query
    heads over 10 KV heads of 128, bfloat16."""
    if seq == "phi3-2048":
        q = spec(one_chip, (PHI3_SLOTS, PHI3_H, 2048, 128), jnp.bfloat16)
        kv = spec(one_chip, (PHI3_SLOTS, PHI3_KV, 2048, 128), jnp.bfloat16)
    else:
        q = kv = spec(one_chip, (SLOTS, H, seq, HD), jnp.float32)
    compiled_text(
        functools.partial(flash_attention_pallas, causal=True, interpret=False),
        q, kv, kv,
    )


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))


@pytest.mark.parametrize("kernel", ["flash", "paged_decode"])
def test_attention_head_sharded(four_chips, kernel):
    """Tensor-parallel serving (mesh model=4): the TPU compiler refuses a
    Mosaic kernel with sharded operands, so the attention dispatchers must
    shard_map their kernels over the head axis."""
    heads4 = NamedSharding(four_chips, P(None, "model", None, None))
    pool4 = NamedSharding(four_chips, P(None, None, "model", None, None))
    if kernel == "flash":
        q = spec(heads4, (SLOTS, H, 256, HD), jnp.float32)
        fn, args = ops.flash_attention, (q, q, q)
    else:
        q = spec(NamedSharding(four_chips, P(None, "model", None)),
                 (SLOTS, H, HD), jnp.float32)
        whole = NamedSharding(four_chips, P())
        fn = ops.paged_attention
        args = (q, *paged_pool(pool4, whole, jnp.float32))
    with jax.set_mesh(four_chips):
        compiled_text(functools.partial(fn, interpret=False), *args)


# the per-layer pool's (pages, Hkv, bs, D) as the trailing dims of an HLO
# result that only moves bytes
_POOL_MOVE = re.compile(
    r"= \w+\[([\d,]+)\]\S* (copy|dynamic-slice|dynamic-update-slice)\("
)


@pytest.mark.parametrize("arch,slots,pages", [("olmo_1b", 8, 96),
                                              ("phi3_medium_14b", 2, 32)])
def test_paged_decode_writes_pool_in_place(one_chip, arch, slots, pages):
    """The whole paged decode step at the benchmark's pool sizes (2 of its
    layers, pages of 128, bfloat16, Pallas attention): no op copies or
    slices a layer's pool, and the program's scratch stays below one
    layer's pool. A scatter whose update window spans the head axis makes
    the compiler transpose and copy the whole stacked pool instead."""
    from repro.models import model as model_lib

    cfg = dataclasses.replace(get_arch(arch), num_layers=2, kernel_impl="pallas",
                              param_dtype=jnp.bfloat16)
    bs = 128
    cache = jax.eval_shape(lambda: model_lib.init_paged_cache(
        cfg, slots, pages, bs, pages // slots))
    params = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    on_chip = lambda t: jax.tree.map(
        lambda a: spec(one_chip, a.shape, a.dtype), t)
    step = jax.jit(lambda p, t, c: model_lib.decode_step(p, t, c, cfg),
                   donate_argnums=(2,))
    with ops.interpret_mode(False):
        compiled = step.lower(
            on_chip(params), spec(one_chip, (slots, 1), jnp.int32),
            on_chip(cache),
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    pool = (pages, cfg.num_kv_heads, bs, cfg.head_dim)
    moves = [m.group(0) for m in _POOL_MOVE.finditer(text)
             if tuple(map(int, m.group(1).split(",")))[-4:] == pool]
    assert not moves, moves
    layer_pool_bytes = np.prod(pool) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool_bytes

"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode accepts block shapes that Mosaic refuses on the chip, so each
kernel the serving path runs is compiled here at ``salaad_llama_60m`` widths
(d_model 512, d_ff 1376, 8 layers, 8 heads of 64) for a described ``v5e:2x2``
chip: no TPU needs to be attached, and nothing runs. The topology is
described inside a fixture, never while a module is imported, so every test
worker collects the same tests and only the worker that runs this file loads
the TPU compiler.
"""
import functools

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


def stack_spec(sharding, lead: int, n: int, m: int, dtype) -> BsrStack:
    """Block-CSC tables of an (n, m) site stacked over ``lead`` layers or
    adapters, tiled with the block size the deployment build picks."""
    bs = _fit_block(n, m, 128)
    jb = -(-m // bs)
    return BsrStack(
        spec(sharding, (lead, jb), jnp.int32),
        spec(sharding, (lead, jb, MAXB), jnp.int32),
        spec(sharding, (lead, jb, MAXB, bs, bs), dtype),
        (n, m), bs,
    )


def compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("site", sorted(SITES))
def test_slr_matmul_stacked(one_chip, site, width, dtype):
    """The fused-format hot path at every linear site of the model. The MLP
    sites are the regression for block sizes that divide 1376 (32 and below),
    whose x blocks Mosaic refuses."""
    n, m = SITES[site]
    dt = DTYPES[dtype]
    compiled_text(
        functools.partial(slr_matmul_stacked_pallas, interpret=False),
        spec(one_chip, (WIDTHS[width], n), dt),
        spec(one_chip, (L, n, RANK), dt),
        spec(one_chip, (L, RANK, m), dt),
        stack_spec(one_chip, L, n, m, dt),
        spec(one_chip, (), jnp.int32),
    )


@pytest.mark.parametrize("site", sorted(SITES))
def test_slr_matmul_multi(one_chip, site):
    """Multi-adapter decode: each of 8 slots runs one of 4 pooled adapters."""
    n, m = SITES[site]
    adapters = 4
    compiled_text(
        functools.partial(slr_matmul_multi_pallas, interpret=False),
        spec(one_chip, (SLOTS, 1, n), jnp.float32),
        spec(one_chip, (adapters, n, RANK), jnp.float32),
        spec(one_chip, (adapters, RANK, m), jnp.float32),
        stack_spec(one_chip, adapters, n, m, jnp.float32),
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


def paged_pool(pool_sharding, table_sharding, dtype):
    """K and V page pools, block table and lengths."""
    pages = SLOTS * PAGES_PER_SLOT
    return (
        spec(pool_sharding, (pages, H, PAGE, HD), dtype),
        spec(pool_sharding, (pages, H, PAGE, HD), dtype),
        spec(table_sharding, (SLOTS, PAGES_PER_SLOT), jnp.int32),
        spec(table_sharding, (SLOTS,), jnp.int32),
    )


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_paged_attention_decode(one_chip, dtype):
    dt = DTYPES[dtype]
    compiled_text(
        functools.partial(paged_attention_pallas, interpret=False),
        spec(one_chip, (SLOTS, H, HD), dt),
        *paged_pool(one_chip, one_chip, dt),
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


@pytest.mark.parametrize("seq", [128, 256])
def test_flash_attention(one_chip, seq):
    """One-shot prefill attention at the serving prompt buckets."""
    q = spec(one_chip, (SLOTS, H, seq, HD), jnp.float32)
    compiled_text(
        functools.partial(flash_attention_pallas, causal=True, interpret=False),
        q, q, q,
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
    if kernel == "flash":
        q = spec(heads4, (SLOTS, H, 256, HD), jnp.float32)
        fn, args = ops.flash_attention, (q, q, q)
    else:
        q = spec(NamedSharding(four_chips, P(None, "model", None)),
                 (SLOTS, H, HD), jnp.float32)
        whole = NamedSharding(four_chips, P())
        fn = ops.paged_attention
        args = (q, *paged_pool(heads4, whole, jnp.float32))
    with jax.set_mesh(four_chips):
        compiled_text(functools.partial(fn, interpret=False), *args)

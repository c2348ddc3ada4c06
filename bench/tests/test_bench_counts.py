"""FLOP and byte counts against hand arithmetic, and the peaks table."""
import _paths  # noqa: F401
import numpy as np
import pytest

from bench.lib.counts import Site, TierMath
from bench.lib.peaks import peaks_for


def tier(nnz=(10, 0)):
    # two layers of one (8, 16) site, ranks 2 and 1
    site = Site("layers/q", 8, 16, np.array([2, 1]), np.array(nnz))
    return TierMath(keep=1.0, sites=[site], num_layers=2, d_model=8,
                    vocab=32, q_heads=2, head_dim=4)


def test_linear_flops_count_kept_rank_and_entries():
    # layer 0: 2*(8*2 + 2*16) + 2*10 = 116; layer 1: 2*(8 + 16) + 0 = 48
    assert tier().linear_flops_per_token() == 164


def test_prefill_and_decode_flops():
    t = tier()
    # attention: 4 * layers * heads * head_dim * keys = 64 * keys
    assert t.attention_flops(3) == 192
    assert t.head_flops() == 2 * 8 * 32
    # a prompt of 3 tokens: 3 linear tokens, 1+2+3 keys, one head row
    assert t.prefill_flops(3) == 3 * 164 + 64 * 6 + 512
    assert t.decode_flops(5) == 164 + 64 * 5 + 512


def test_stacked_kernel_least_time_per_call():
    t = tier()
    # layer 0, 4 rows: flops 2*4*(16 + 32) + 2*4*10 = 464;
    # bytes x 4*8*2 + P 8*2*2 + Vt 2*16*2 + S 10*6 + y 4*16*2 = 348
    # layer 1: flops 2*4*24 = 192; bytes 64 + 16 + 32 + 0 + 128 = 240
    got = t.stacked_kernel_least_s(4, peak_flops=1.0, peak_bw=1.0)
    assert got == 464 + 240
    got = t.stacked_kernel_least_s(4, peak_flops=1000.0, peak_bw=1.0)
    assert got == pytest.approx(348 + 240)


def test_sites_without_entries_use_no_stacked_kernel():
    t = tier(nnz=(0, 0))
    assert not t.sites[0].fused_sparse
    assert t.stacked_kernel_least_s(4, 1.0, 1.0) == 0.0


def test_peaks_table_knows_v5e_and_refuses_others():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks_for("TPU v4")


def test_float8_rounding_matches_the_dtype():
    import jax.numpy as jnp

    from bench.lib.reference import round_e4m3

    x = np.random.default_rng(0).standard_normal(20000).astype(np.float32)
    x = np.concatenate([x * 100.0, x * 1e-3, [0.0, 448.0, -448.0, 2.0**-9]])
    x = np.clip(x, -448.0, 448.0).astype(np.float32)
    want = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(round_e4m3(jnp.asarray(x))), want)

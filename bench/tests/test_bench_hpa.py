"""The yardstick's HPA, written from the paper, keeps what the program's HPA
keeps: ranks and sparse entries per slice, at cuts that keep all of S, none
of it and part of it."""
import _paths  # noqa: F401
import numpy as np
import pytest

from bench.lib import hpa, state
from bench.lib.config import arch_config, load


@pytest.fixture(scope="module")
def drawn():
    cfg = load("olmo_1b")
    cfg.update(hidden_size=128, num_hidden_layers=2, num_attention_heads=2,
               num_key_value_heads=1, head_dim=64, intermediate_size=256,
               vocab_size=256)
    arch = arch_config(cfg)
    spec = state.SlrSpec.from_config(cfg)
    _, st, blocks, _ = state.make_state(arch, spec, 2**31 + 5)
    return st, blocks


@pytest.mark.parametrize("keep,kappa", [(1.0, 0.7), (0.6, 0.7), (0.3, 0.7),
                                        (0.6, 0.97)])
def test_kept_ranks_and_entries_match_the_program(drawn, keep, kappa):
    from repro.core.hpa import hpa_keep_ratio

    st, blocks = drawn
    ours, (phi_l, phi_s) = hpa.keep_masks(hpa.host_blocks(st, blocks), keep, kappa)
    theirs, rep = hpa_keep_ratio(st, blocks, keep, kappa)
    assert rep["phi_L"] == pytest.approx(phi_l) and rep["phi_S"] == pytest.approx(phi_s)
    for info in blocks:
        blk = theirs[info.name]
        rank = (np.asarray(blk.s_vals) > 0).sum(axis=-1)
        nnz = (np.asarray(blk.s_coo.idx) >= 0).sum(axis=-1)
        np.testing.assert_array_equal(ours[info.name].kept_rank, rank)
        np.testing.assert_array_equal(ours[info.name].kept_nnz, nnz)
        # the very entries the program kept, ties at the cut included
        mine = ours[info.name].sparse_mask(np.asarray(st[info.name].s_coo.values),
                                           np.asarray(st[info.name].s_coo.idx))
        np.testing.assert_array_equal(mine, np.asarray(blk.s_coo.idx) >= 0)
    if kappa == 0.97:
        assert 0.0 < phi_s < 1.0

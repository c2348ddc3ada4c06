"""CPU rehearsal of ``chip_smoke.py``: its train and serve phases at the
``reduced()`` size with the Pallas kernels in interpret mode, and its refusal
to run anywhere but on a TPU."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.configs.base import get_arch  # noqa: E402


@pytest.fixture(scope="module")
def trained():
    cfg = get_arch(chip_smoke.ARCH).reduced()
    trainer, state = chip_smoke.train_phase(cfg, seed=0, steps=4, seq_len=32,
                                            batch=4)
    return cfg, trainer, state


def test_train_phase_leaves_live_slr_sites(trained):
    cfg, trainer, state = trained
    stats = chip_smoke.served_site_stats(state.slr, trainer.blocks)
    assert stats["sites"] > 0
    assert stats["min_rank"] > 0 and stats["min_nnz"] > 0
    assert sum("admm_recon_err" in m for m in trainer.metrics_log) == 2


def test_serve_phase_matches_reference_in_interpret_mode(trained):
    cfg, trainer, state = trained
    report = chip_smoke.serve_phase(
        cfg, state.params, state.slr, trainer.blocks, interpret=True, seed=0,
        prompt_lens=(20, 5, 9, 14), max_new=4, max_len=32,
    )
    assert report["completed"] == report["requests"] == 4
    assert report["jit_retraces_timed"] == 0
    assert report["streams_repeat"]
    assert report["logits_err_served_f32"] <= report["logits_tol_f32"]
    assert report["logits_err_served"] <= report["logits_tol_default"]
    assert report["greedy_disagree"] == 0 and report["greedy_exact"] > 0


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out

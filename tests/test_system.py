"""End-to-end behaviour tests for the full SALAAD system:
train (Alg. 1) -> checkpoint -> restore -> HPA compress -> deploy -> serve,
and the paper's headline qualitative claims at smoke scale.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig, slr_param_count, surrogate_params
from repro.core.hpa import hpa_keep_ratio
from repro.core.selection import SelectionConfig
from repro.data.synthetic import DataConfig, SyntheticC4
from repro.models import model as model_lib
from repro.optim.adam import AdamConfig
from repro.serving.elastic import ModelBank
from repro.serving.engine import EngineConfig, PagedServingEngine
from repro.train import checkpoint
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def pipeline(tmp_path_factory):
    """One full training run shared by the system tests."""
    cfg = get_arch("salaad_llama_60m").reduced()
    salaad = SalaadConfig(
        selection=SelectionConfig(min_dim=16), rho_constant=0.5,
        update_every=5, exact_svd=True,
    )
    ckpt_dir = str(tmp_path_factory.mktemp("ckpt"))
    tcfg = TrainerConfig(
        total_steps=30, salaad=salaad, adam=AdamConfig(lr=1e-3),
        ckpt_dir=ckpt_dir, ckpt_every=10, log_every=5,
    )
    trainer = Trainer(cfg, tcfg)
    state = trainer.init(jax.random.PRNGKey(0))
    data = SyntheticC4(DataConfig(cfg.vocab_size, 32, 8))
    state = trainer.fit(state, data)
    return cfg, trainer, state, data, ckpt_dir


def eval_loss(params, cfg, data):
    return float(model_lib.loss_fn(params, data.batch(9999), cfg)[0])


class TestEndToEnd:
    def test_training_reduces_loss(self, pipeline):
        cfg, trainer, state, data, _ = pipeline
        losses = [m["loss"] for m in trainer.metrics_log if "loss" in m]
        assert losses[-1] < losses[0]

    def test_admm_reconstruction_bounded_and_shrinking(self, pipeline):
        """Paper App. F: ||X - L - S||_F stays bounded and decreases."""
        cfg, trainer, state, data, _ = pipeline
        recon = [m["admm_recon_err"] for m in trainer.metrics_log if "admm_recon_err" in m]
        assert len(recon) >= 3
        assert recon[-1] <= recon[0]
        assert all(np.isfinite(r) for r in recon)

    def test_surrogate_quality_close_to_dense(self, pipeline):
        """Paper Table 1: L+S within a reasonable margin of X."""
        cfg, trainer, state, data, _ = pipeline
        lx = eval_loss(state.params, cfg, data)
        ls = eval_loss(trainer.surrogate(state), cfg, data)
        assert ls < lx + 0.5

    def test_elastic_budgets_degrade_smoothly(self, pipeline):
        """Paper Fig. 3: loss is monotone-ish (no collapse) across budgets."""
        cfg, trainer, state, data, _ = pipeline
        losses = []
        for keep in (1.0, 0.7, 0.4):
            slr_c, _ = hpa_keep_ratio(state.slr, trainer.blocks, keep, kappa=0.7)
            params_c = surrogate_params(state.params, slr_c, trainer.blocks)
            losses.append(eval_loss(params_c, cfg, data))
        assert losses[2] < losses[0] + 2.0  # graceful, not collapsed
        assert all(np.isfinite(l) for l in losses)

    def test_checkpoint_restore_and_continue(self, pipeline):
        cfg, trainer, state, data, ckpt_dir = pipeline
        assert checkpoint.latest_step(ckpt_dir) == 30
        restored = checkpoint.restore(ckpt_dir, state)
        assert int(restored.step) == 30
        state2 = trainer.fit(restored, data, steps=32)  # two more steps
        assert int(state2.step) == 32

    def test_compressed_model_serves(self, pipeline):
        cfg, trainer, state, data, _ = pipeline
        slr_c, _ = hpa_keep_ratio(state.slr, trainer.blocks, 0.6, kappa=0.7)
        deploy = surrogate_params(state.params, slr_c, trainer.blocks)
        engine = PagedServingEngine(ModelBank.single(cfg, deploy),
                                    EngineConfig(max_slots=2, max_len=48))
        engine.submit([1, 2, 3], max_new_tokens=4)
        engine.submit([4, 5], max_new_tokens=4)
        done = engine.run()
        assert len(done) == 2 and all(len(r.out_tokens) == 4 for r in done)

    def test_param_accounting_consistent(self, pipeline):
        cfg, trainer, state, data, _ = pipeline
        counts = slr_param_count(state.slr, trainer.blocks)
        assert counts["_total"] > 0
        slr_c, rep = hpa_keep_ratio(state.slr, trainer.blocks, 0.5, kappa=0.7)
        counts_c = slr_param_count(slr_c, trainer.blocks)
        assert counts_c["_total"] == rep["params_after"]

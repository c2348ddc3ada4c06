"""Per-architecture smoke tests: one reduced-config train step + decode
consistency + SALAAD applicability, for all 10 assigned archs (+ paper family).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_arch
from repro.core.admm import SalaadConfig, admm_update, init_slr_state, penalty
from repro.core.selection import SelectionConfig
from repro.models import model

ASSIGNED = ARCH_IDS[:10]
PAPER = ARCH_IDS[10:]


def make_batch(cfg, key, b=2, t=16):
    ks = jax.random.split(key, 4)
    batch = {
        "tokens": jax.random.randint(ks[0], (b, t), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (b, t), 0, cfg.vocab_size),
    }
    if cfg.family == "encdec":
        batch["frames"] = (
            jax.random.normal(ks[2], (b, cfg.encoder_seq, cfg.d_model)) * 0.1
        ).astype(cfg.param_dtype)
    if cfg.family == "vlm":
        batch["patches"] = (
            jax.random.normal(ks[3], (b, cfg.num_patches, cfg.d_model)) * 0.1
        ).astype(cfg.param_dtype)
    return batch


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch_id", ASSIGNED + PAPER)
class TestArchSmoke:
    def test_forward_and_train_step(self, arch_id, rng):
        cfg = get_arch(arch_id).reduced()
        params = model.init_params(cfg, rng)
        batch = make_batch(cfg, jax.random.fold_in(rng, 1))

        logits, _, aux = model._forward(params, batch, cfg)
        exp_t = batch["tokens"].shape[1] + (
            cfg.num_patches if cfg.family == "vlm" else 0
        )
        assert logits.shape == (2, exp_t, cfg.vocab_size)
        assert not bool(jnp.any(jnp.isnan(logits)))

        # one SGD step on the task loss must not produce NaNs
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, batch, cfg), has_aux=True
        )(params)
        assert np.isfinite(float(loss))
        new_params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype), params, grads)
        loss2, _ = model.loss_fn(new_params, batch, cfg)
        assert np.isfinite(float(loss2))

    def test_decode_consistency(self, arch_id, rng):
        """prefill(T-1) + decode(1) == full forward on the last token, with an
        fp32 cache (removes the bf16 cache quantization from the comparison)."""
        cfg = get_arch(arch_id).reduced()
        params = model.init_params(cfg, rng)
        b, t = 2, 16
        batch = make_batch(cfg, jax.random.fold_in(rng, 2), b, t)
        logits_full, _, _ = model._forward(params, batch, cfg)

        batch_p = dict(batch)
        batch_p["tokens"] = batch["tokens"][:, : t - 1]
        _, cache = model.prefill(params, batch_p, cfg, max_len=32)
        # fp32-ify the cache for an exactness check
        cache = jax.tree.map(
            lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, cache
        )
        lg_d, _ = model.decode_step(params, batch["tokens"][:, t - 1 :], cache, cfg)
        ref = logits_full[:, -1]
        np.testing.assert_allclose(
            np.asarray(lg_d[:, 0], np.float32),
            np.asarray(ref, np.float32),
            atol=5e-2, rtol=5e-2,
        )

    def test_salaad_plug_and_play(self, arch_id, rng):
        """The paper's central claim: SALAAD attaches to ANY architecture's
        param pytree without model changes."""
        cfg = get_arch(arch_id).reduced()
        params = model.init_params(cfg, rng)
        scfg = SalaadConfig(selection=SelectionConfig(min_dim=16), rho_constant=1.0)
        state, blocks = init_slr_state(params, scfg)
        assert len(blocks) >= 2, f"no blocks selected for {arch_id}"
        assert any(b.is_embedding for b in blocks)  # §5.1: embedding included
        assert all("lm_head" not in b.name for b in blocks)  # App. H
        pen = penalty(params, state, blocks)
        assert np.isfinite(float(pen)) and float(pen) > 0
        new_state, stats = admm_update(params, state, blocks, scfg, 0)
        assert np.isfinite(float(stats["_mean_recon_err"]))

    def test_full_config_matches_assignment(self, arch_id, rng):
        """The FULL (non-reduced) config carries the assigned dimensions."""
        cfg = get_arch(arch_id)
        assert cfg.num_layers >= 8 or cfg.family == "encdec"
        assert cfg.d_model >= 512
        if cfg.family == "moe":
            assert cfg.num_experts in (16, 128)
        if cfg.family in ("ssm", "hybrid"):
            assert cfg.ssm_state in (64, 128)


EXPECTED_DIMS = {
    "zamba2_2p7b": dict(num_layers=54, d_model=2560, d_ff=10240, vocab_size=32000, ssm_state=64),
    "dbrx_132b": dict(num_layers=40, d_model=6144, num_heads=48, num_kv_heads=8, d_ff=10752, vocab_size=100352, num_experts=16, top_k=4),
    "qwen3_moe_30b_a3b": dict(num_layers=48, d_model=2048, num_heads=32, num_kv_heads=4, d_ff=768, vocab_size=151936, num_experts=128, top_k=8),
    "whisper_small": dict(num_layers=12, d_model=768, num_heads=12, d_ff=3072, vocab_size=51865),
    "olmo_1b": dict(num_layers=16, d_model=2048, num_heads=16, d_ff=8192, vocab_size=50304),
    "phi3_medium_14b": dict(num_layers=40, d_model=5120, num_heads=40, num_kv_heads=10, d_ff=17920, vocab_size=32064, tie_embeddings=False, norm_type="rmsnorm"),
    "gemma_7b": dict(num_layers=28, d_model=3072, num_heads=16, d_ff=24576, vocab_size=256000, head_dim=256),
    "qwen1p5_4b": dict(num_layers=40, d_model=2560, num_heads=20, d_ff=6912, vocab_size=151936, qkv_bias=True),
    "internvl2_76b": dict(num_layers=80, d_model=8192, num_heads=64, num_kv_heads=8, d_ff=28672, vocab_size=128256),
    "mamba2_370m": dict(num_layers=48, d_model=1024, vocab_size=50280, ssm_state=128),
}


@pytest.mark.parametrize("arch_id", list(EXPECTED_DIMS))
def test_exact_assigned_dims(arch_id):
    cfg = get_arch(arch_id)
    for k, v in EXPECTED_DIMS[arch_id].items():
        assert getattr(cfg, k) == v, f"{arch_id}.{k}: {getattr(cfg, k)} != {v}"

"""Deployment-path tests: SLR parameter formats, kernels vs XLA fallback,
deployment accounting, and the surrogate-equals-deployed invariant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig, admm_update, init_slr_state, surrogate_params
from repro.core.hpa import hpa_keep_ratio
from repro.core.selection import SelectionConfig
from repro.models import model as model_lib
from repro.serving.deployed import DeployedModel
from repro.serving.elastic import ModelBank
from repro.serving.engine import EngineConfig, PagedServingEngine
from repro.serving.slr_params import build_slr_linears, deployment_report


@pytest.fixture(scope="module")
def trained():
    cfg = get_arch("olmo_1b").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    scfg = SalaadConfig(
        selection=SelectionConfig(min_dim=16), rho_constant=5.0, exact_svd=True
    )
    state, blocks = init_slr_state(params, scfg)
    for step in range(4):
        state, _ = admm_update(params, state, blocks, scfg, step)
    return cfg, params, state, blocks


class TestSLRLinears:
    def test_factored_apply_matches_surrogate(self, trained):
        cfg, params, state, blocks = trained
        linears = build_slr_linears(state, blocks, fmt="factored")
        surr = surrogate_params(params, state, blocks)
        info = next(b for b in blocks if not b.stack_dims)
        lin = linears[info.name]
        w_surr = surr
        for p in info.path:
            w_surr = w_surr[getattr(p, "key", getattr(p, "idx", None))]
        x = jax.random.normal(jax.random.PRNGKey(1), (8, info.n))
        np.testing.assert_allclose(
            lin.apply(x), x @ w_surr, atol=1e-3, rtol=1e-3
        )

    def test_bsr_kernel_matches_xla(self, trained):
        cfg, params, state, blocks = trained
        linears = build_slr_linears(state, blocks, fmt="bsr", bsr_block=32)
        checked = 0
        for info in blocks:
            lin = linears[info.name]
            if lin.p is None or lin.p.ndim != 2:
                continue
            x = jax.random.normal(jax.random.PRNGKey(2), (8, info.n))
            np.testing.assert_allclose(
                lin.apply(x, kernel=True), lin.apply(x, kernel=False),
                atol=2e-3, rtol=2e-3,
            )
            checked += 1
        assert checked >= 1

    def test_param_bytes_drop_after_hpa(self, trained):
        cfg, params, state, blocks = trained
        before = deployment_report(params, state, blocks)
        comp, _ = hpa_keep_ratio(state, blocks, keep_ratio=0.4, kappa=0.7)
        after = deployment_report(params, comp, blocks)
        assert after["slr_total_bytes"] < before["slr_total_bytes"]
        assert after["compression"] > before["compression"]

    def test_deployed_model_runs(self, trained):
        """HPA-compressed surrogate params drive the unchanged model code."""
        cfg, params, state, blocks = trained
        comp, _ = hpa_keep_ratio(state, blocks, keep_ratio=0.5, kappa=0.7)
        deploy = surrogate_params(params, comp, blocks)
        batch = {
            "tokens": jnp.ones((2, 8), jnp.int32),
            "labels": jnp.ones((2, 8), jnp.int32),
        }
        loss, _ = model_lib.loss_fn(deploy, batch, cfg)
        assert np.isfinite(float(loss))


class TestDeployedModel:
    """The serving-format forward must match the dense-materialized forward."""

    def test_factored_and_bsr_match_dense_forward(self, trained):
        cfg, params, state, blocks = trained
        toks = jax.random.randint(jax.random.PRNGKey(3), (2, 10), 0, cfg.vocab_size)
        dense = DeployedModel.build(cfg, params, state, blocks, fmt="dense")
        ref = dense.forward(toks)
        for fmt in ("factored", "bsr"):
            dm = DeployedModel.build(cfg, params, state, blocks, fmt=fmt, bsr_block=32)
            np.testing.assert_allclose(
                np.asarray(dm.forward(toks)), np.asarray(ref), atol=1e-3, rtol=1e-3,
            )

    def test_formats_work_under_jit(self, trained):
        cfg, params, state, blocks = trained
        toks = jax.random.randint(jax.random.PRNGKey(4), (1, 6), 0, cfg.vocab_size)
        dm = DeployedModel.build(cfg, params, state, blocks, fmt="factored")
        f = jax.jit(lambda p, t: model_lib._forward(p, {"tokens": t}, cfg)[0])
        np.testing.assert_allclose(
            np.asarray(f(dm.params, toks)), np.asarray(dm.forward(toks)),
            atol=1e-5, rtol=1e-5,
        )

    def test_served_bytes_shrink_with_budget(self, trained):
        cfg, params, state, blocks = trained
        full = DeployedModel.build(cfg, params, state, blocks, fmt="factored")
        comp, _ = hpa_keep_ratio(state, blocks, keep_ratio=0.4, kappa=0.7)
        small = DeployedModel.build(cfg, params, comp, blocks, fmt="factored")
        assert small.param_bytes()["total_bytes"] < full.param_bytes()["total_bytes"]


class TestBatchedEngine:
    """The tentpole invariants: one jitted decode step per engine tick for ALL
    active slots, and exact parity with the plain full-forward greedy rollout."""

    def _full_forward_greedy(self, cfg, params, prompt, n_new):
        toks = list(prompt)
        for _ in range(n_new):
            logits, _, _ = model_lib._forward(
                params, {"tokens": jnp.asarray([toks], jnp.int32)}, cfg
            )
            toks.append(int(jnp.argmax(logits[0, -1])))
        return toks[len(prompt):]

    def test_one_device_call_per_decode_step(self, trained):
        cfg, params, state, blocks = trained
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32))
        for i in range(5):
            eng.submit([1 + i, 2, 3], max_new_tokens=4)
        done = eng.run()
        assert len(done) == 5 and all(len(r.out_tokens) == 4 for r in done)
        total_tokens = sum(len(r.out_tokens) for r in done)
        # one jitted decode program, traced exactly once, one device call per
        # step — NOT one per slot per token (the seed reference behavior)
        assert eng.decode_traces == 1
        assert eng.decode_calls < total_tokens
        # prefill went through the batched program: one trace per bucket,
        # never one call per token
        assert eng.prefill_traces <= 2
        assert eng.prefill_calls <= 5

    def test_batched_decode_matches_full_forward(self, trained):
        """Per-slot lengths + batched sampling == independent greedy rollouts."""
        cfg, params, state, blocks = trained
        prompts = [[5, 7, 11], [3, 1], [2, 9, 4, 6]]
        eng = PagedServingEngine(ModelBank.single(cfg, params), EngineConfig(max_slots=2, max_len=32))
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        by_uid = {r.uid: r.out_tokens for r in eng.run()}
        for uid, prompt in enumerate(prompts, start=1):
            ref = self._full_forward_greedy(cfg, params, prompt, 4)
            assert by_uid[uid] == ref, (uid, by_uid[uid], ref)

    def test_engine_serves_slr_formats_identically(self, trained):
        cfg, params, state, blocks = trained
        comp, _ = hpa_keep_ratio(state, blocks, keep_ratio=0.6, kappa=0.7)
        outs = {}
        for fmt in ("dense", "factored"):
            dm = DeployedModel.build(cfg, params, comp, blocks, fmt=fmt)
            eng = PagedServingEngine(dm, EngineConfig(max_slots=2, max_len=32))
            eng.submit([4, 8, 15], max_new_tokens=4)
            eng.submit([16, 23], max_new_tokens=4)
            outs[fmt] = [r.out_tokens for r in sorted(eng.run(), key=lambda r: r.uid)]
        assert outs["dense"] == outs["factored"]


class TestBenchmarkModules:
    """Smoke the benchmark harness entry points at minimal sizes."""

    def test_fig2_overhead(self):
        from benchmarks import fig2_overhead

        r = fig2_overhead.run(steps=2)
        assert r["train_step_s"] > 0 and r["admm_step_s"] > 0

    def test_table10_freq_trend(self):
        from benchmarks import table10_freq

        rows = table10_freq.run(steps=8, ks=(2, 8))
        by_k = {r["K"]: r for r in rows}
        # more frequent ADMM (smaller K) tracks better: lower recon error
        assert by_k[2]["final_recon"] <= by_k[8]["final_recon"] * 1.5

    def test_roofline_loader(self):
        from benchmarks import roofline

        recs = roofline.load_records()  # may be empty before the sweep
        assert isinstance(recs, list)

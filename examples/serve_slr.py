"""Serve an HPA-compressed SLR model with the batched engine, and exercise
the TPU-targeted SLR kernels (fused low-rank matmul + block-CSR sparse
matmul, interpret mode on CPU) on a deployed block.

    PYTHONPATH=src python examples/serve_slr.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig
from repro.core.hpa import hpa_keep_ratio
from repro.core.selection import SelectionConfig
from repro.data.synthetic import DataConfig, SyntheticC4
from repro.optim.adam import AdamConfig
from repro.serving.engine import EngineConfig, PagedServingEngine
from repro.serving.slr_params import build_slr_linears
from repro.train.trainer import Trainer, TrainerConfig


def main():
    cfg = get_arch("salaad_llama_60m").reduced()
    salaad = SalaadConfig(
        selection=SelectionConfig(min_dim=16), rho_constant=0.5,
        update_every=5, exact_svd=True,
    )
    trainer = Trainer(cfg, TrainerConfig(total_steps=120, salaad=salaad, adam=AdamConfig(lr=1e-3)))
    state = trainer.init(jax.random.PRNGKey(0))
    data = SyntheticC4(DataConfig(cfg.vocab_size, 32, 8))
    state = trainer.fit(state, data)

    # compress + deploy WITHOUT dense materialization: the engine consumes
    # the factored (p, vt) + COO S representation directly
    from repro.serving.deployed import DeployedModel

    slr_c, rep = hpa_keep_ratio(state.slr, trainer.blocks, keep_ratio=0.7, kappa=0.7)
    deployed = DeployedModel.build(cfg, state.params, slr_c, trainer.blocks, fmt="factored")
    print(
        f"deployed at keep=0.7: slr_params={rep['params_after']} "
        f"served_bytes={deployed.param_bytes()['total_bytes']}"
    )

    # batched serving straight off the SLR weights (single-tier bank)
    from repro.serving.elastic import ModelBank

    engine = PagedServingEngine(ModelBank.single(cfg, deployed),
                                EngineConfig(max_slots=2, max_len=48))
    for i in range(4):
        engine.submit([1 + i, 2, 3], max_new_tokens=6)
    t0 = time.time()
    done = engine.run()
    toks = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {toks} tokens, {toks/(time.time()-t0):.1f} tok/s")
    print("sample:", done[0].out_tokens)

    # elastic self-speculation: the SAME SLR state at an aggressive budget
    # drafts for the full-budget target (one jitted k-wide verify per tick)
    from repro.serving.speculative import SpeculativeEngine

    slr_d, _ = hpa_keep_ratio(state.slr, trainer.blocks, keep_ratio=0.4, kappa=0.7)
    draft = DeployedModel.build(cfg, state.params, slr_d, trainer.blocks, fmt="dense")
    target = DeployedModel.build(cfg, state.params, slr_c, trainer.blocks, fmt="dense")
    # the draft/target pair is two tiers of one bank: tier 0 verifies,
    # the cheapest tier (spec_draft_tier=-1, the default) drafts
    spec = SpeculativeEngine(
        ModelBank(cfg, [target, draft], keeps=[0.7, 0.4]),
        EngineConfig(
            max_slots=2, max_len=48, block_size=8, spec_k=4,
            spec_draft_mode="sequential",   # short demo: no lookahead warmup
        ))
    for i in range(4):
        spec.submit([1 + i, 2, 3], max_new_tokens=6)
    done = spec.run()
    print(
        f"speculative: {sum(len(r.out_tokens) for r in done)} tokens in "
        f"{spec.decode_calls} verify steps, acceptance {spec.acceptance_rate:.2f}"
    )

    # TPU-kernel path on one deployed block (interpret mode on CPU)
    linears = build_slr_linears(slr_c, trainer.blocks, fmt="bsr", bsr_block=32)
    name, lin = next((k, v) for k, v in linears.items() if v.p is not None and v.p.ndim == 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, lin.shape[0]))
    y_kernel = lin.apply(x, kernel=True)
    y_ref = lin.apply(x, kernel=False)
    err = float(jnp.abs(y_kernel - y_ref).max())
    occ = lin.s_bsr.occupancy if lin.s_bsr is not None else float("nan")
    print(f"kernel path on '{name}': max|Δ| vs XLA path = {err:.2e}, BSR occupancy {occ:.2f}")


if __name__ == "__main__":
    main()

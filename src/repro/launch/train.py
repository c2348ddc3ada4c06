"""Production training launcher.

Single-host (CPU/tests): ``python -m repro.launch.train --arch salaad_llama_60m
--steps 100 --reduced``. On a real TPU pod, jax.distributed.initialize() picks
up the cluster env and the same script runs SPMD; the XLA flags below enable
the latency-hiding scheduler so GSPMD's weight all-gathers / grad
reduce-scatters overlap with compute (the comm/compute-overlap knob of
EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import os

_PERF_FLAGS = (
    " --xla_tpu_enable_latency_hiding_scheduler=true"
    " --xla_tpu_enable_async_collective_fusion=true"
    " --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"
    " --xla_tpu_overlap_compute_collective_tc=true"
)
if os.environ.get("REPRO_TPU_PERF_FLAGS", "0") == "1":
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + _PERF_FLAGS

import argparse  # noqa: E402
import json      # noqa: E402

import jax       # noqa: E402

from repro.configs.base import get_arch                       # noqa: E402
from repro.core.admm import SalaadConfig                      # noqa: E402
from repro.core.selection import SelectionConfig              # noqa: E402
from repro.data.synthetic import DataConfig, SyntheticC4      # noqa: E402
from repro.launch.compile_cache import use_compile_cache      # noqa: E402
from repro.optim.adam import AdamConfig                       # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig        # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument("--no-salaad", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--update-every", type=int, default=20, help="K (Alg. 1)")
    ap.add_argument("--rho-constant", type=float, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distributed", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    if args.distributed:
        jax.distributed.initialize()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    salaad = None
    if not args.no_salaad:
        kw = dict(selection=SelectionConfig(min_dim=16), update_every=args.update_every)
        if args.rho_constant is not None:
            kw["rho_constant"] = args.rho_constant
        salaad = SalaadConfig(**kw)

    tcfg = TrainerConfig(
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        salaad=salaad,
        adam=AdamConfig(lr=args.lr),
    )
    trainer = Trainer(cfg, tcfg)
    state = trainer.init(jax.random.PRNGKey(args.seed))
    state = trainer.maybe_restore(state)

    data = SyntheticC4(
        DataConfig(cfg.vocab_size, args.seq_len, args.batch, seed=args.seed)
    )
    state = trainer.fit(state, data)
    print(json.dumps({"metrics": trainer.metrics_log[-5:], "events": trainer.events}, indent=1))


if __name__ == "__main__":
    main()

"""Serving throughput: seed-style per-slot reference engine vs the batched
SLR-native engine, across HPA keep-ratios.

The paper's deployment story only matters if the serving path is fast:
this benchmark drives BOTH engines over the same request trace at several
served capacities and emits ``BENCH_serve.json`` with tokens/sec (steady
state — a warmup pass absorbs compilation, which the per-slot engine pays
per shape anyway). The batched engine must clear >= 5x on the reduced
config; on real hardware the gap grows with slot count.

  PYTHONPATH=src python -m benchmarks.serve_throughput --quick
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax

from repro.core.hpa import hpa_keep_ratio
from repro.serving.deployed import DeployedModel
from repro.serving.elastic import ModelBank
from repro.serving.engine import EngineConfig, PagedServingEngine, ReferenceEngine

from .common import bench_arch, emit, salaad_cfg, train_salaad

KEEP_RATIOS = (1.0, 0.6, 0.3)


def _drive(engine, requests: int, max_new: int) -> float:
    """Submit a fixed trace, run to completion, return tokens/sec."""
    for i in range(requests):
        engine.submit([1 + (i % 7), 2, 3, 4], max_new_tokens=max_new)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    tokens = sum(len(r.out_tokens) for r in done)
    assert len(done) == requests, (len(done), requests)
    return tokens / max(dt, 1e-9)


def run(
    steps: int = 30,
    requests: int = 8,
    max_new: int = 16,
    max_slots: int = 4,
    fmt: str = "factored",
    keep_ratios=KEEP_RATIOS,
) -> list[dict]:
    cfg = bench_arch()
    tr, state = train_salaad(cfg, steps=steps, scfg=salaad_cfg())
    ecfg = EngineConfig(max_slots=max_slots, max_len=64)

    rows = []
    for keep in keep_ratios:
        slr_c, rep = hpa_keep_ratio(state.slr, tr.blocks, keep, kappa=0.7)
        deployed = DeployedModel.build(cfg, state.params, slr_c, tr.blocks, fmt=fmt)
        dense = DeployedModel.build(cfg, state.params, slr_c, tr.blocks, fmt="dense")

        engines = {
            "reference_per_slot": ReferenceEngine(ModelBank.single(cfg, dense), ecfg),
            "batched_dense": PagedServingEngine(ModelBank.single(cfg, dense),
                                                ecfg),
        }
        if fmt != "dense":  # avoid key collision with the dense baseline
            engines[f"batched_{fmt}"] = PagedServingEngine(
                ModelBank.single(cfg, deployed), ecfg
            )
        row = {"keep": keep, "slr_params": rep["params_after"],
               "served_bytes": deployed.param_bytes()["total_bytes"]}
        for name, eng in engines.items():
            _drive(eng, max(requests // 2, 2), max_new)   # warmup: compile
            row[f"tok_per_s_{name}"] = round(_drive(eng, requests, max_new), 1)
        row["speedup_batched_vs_reference"] = round(
            row["tok_per_s_batched_dense"] / max(row["tok_per_s_reference_per_slot"], 1e-9), 2
        )
        rows.append(row)
    return rows


def main(steps: int = 30, out: str = "BENCH_serve.json", **kw):
    rows = run(steps=steps, **kw)
    Path(out).write_text(json.dumps(rows, indent=2))
    for r in rows:
        emit(
            f"serve/keep={r['keep']}", 0.0,
            f"ref={r['tok_per_s_reference_per_slot']};batched={r['tok_per_s_batched_dense']};"
            f"speedup={r['speedup_batched_vs_reference']}x",
        )
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--fmt", default="factored",
                    choices=("dense", "factored", "bsr", "fused"))
    ap.add_argument("--out", default="BENCH_serve.json")
    a = ap.parse_args()
    main(steps=10 if a.quick else 30, out=a.out, fmt=a.fmt,
         requests=4 if a.quick else 8, max_new=8 if a.quick else 16)

"""A cell cut to a size the CPU runs in seconds (Pallas in interpret mode):
the same harness, mix shape, tiers and check, at tiny widths."""
import json
import time

import _paths  # noqa: F401

from bench import run
from bench.lib import peaks
from bench.lib.clock import CompileClock


def tiny_cell(workload: str) -> run.Cell:
    bm = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.Cell(bm, workload)
    gqa = cell.cfg["num_key_value_heads"] != cell.cfg["num_attention_heads"]
    # embeddings scaled by sqrt(width) so that the tied head's logits, and
    # so the gaps the check compares, keep the full size's scale
    slr = dict(cell.cfg["slr"])
    slr["embed_std"] *= (cell.cfg["hidden_size"] / 256) ** 0.5
    cell.cfg.update(hidden_size=256, num_hidden_layers=2, num_attention_heads=2,
                    num_key_value_heads=1 if gqa else 2, head_dim=128,
                    intermediate_size=512, vocab_size=512, slr=slr)
    cell.mix["prompt"] = {"dist": "uniform", "min": 17, "max": 32}
    cell.mix["output"] = {"dist": "uniform", "min": 3, "max": 6}
    cell.wl["engine"].update(max_slots=4, max_len=64)
    cell.wl["rate_per_s"] = 3.0
    cell.wl["check"] = dict(cell.wl["check"], min_tokens=8, tokens_per_tier=6)
    return cell


def run_tiny(cell: run.Cell, seed: int, tmp_path, **kw) -> dict:
    import jax

    peaks.PEAKS.setdefault("cpu", peaks.PEAKS["TPU v5 lite"])
    return run.run(cell, seed, 2.0, 0, jax.devices(), CompileClock().install(),
                   time.perf_counter(), out_dir=tmp_path, **kw)

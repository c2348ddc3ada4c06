"""Phi-3-medium-4k's cell (``phi3m.long_prompt``) cut to a tiny size on the
CPU, against the plain reference (Pallas in interpret mode): GQA with a
group of 4 (4 query heads, 1 KV head of 128), a ragged vocabulary of 500
(not a multiple of 128), an untied head and drawn RMSNorm scales. A sound
run is correct, the float8 control fails at the committed limit, and a
token altered where the engine produces it turns ``correct`` false. Also:
the configuration's widths are the program's, and the cell's new readers
on hand-built and recorded traces."""
import dataclasses
import json
import time
import types

import _paths  # noqa: F401
import numpy as np
import pytest
from _tiny import tiny_cell

from bench import run
from bench.lib import config
from bench.lib import spans as sp
from bench.lib.counts import Site, TierMath

BENCHMARK = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELL = "phi3m.long_prompt"
SEED = 2**31 + 37
HIDDEN = 512
WINDOW_S = 8.0


def phi3_tiny() -> run.Cell:
    cell = tiny_cell(CELL)
    full = config.load(cell.entry["config"])
    # the scaled embeddings keep the full size's RMS, as in ``tiny_cell``
    slr = dict(full["slr"])
    slr["embed_std"] *= (full["hidden_size"] / HIDDEN) ** 0.5
    # the published MLP ratio (17920 / 5120), at which tier 0.6 keeps some S
    # and so serves through the fused kernel, as at full size
    ff = HIDDEN * full["intermediate_size"] // full["hidden_size"]
    cell.cfg.update(hidden_size=HIDDEN, num_attention_heads=4,
                    num_key_value_heads=1, head_dim=128, intermediate_size=ff,
                    vocab_size=500, slr=slr)
    # one tier, as in the cell: its sample has to hold all of min_tokens
    check = cell.wl["check"]
    check["tokens_per_tier"] = check["min_tokens"]
    # 12 arrivals in the window, which the tiny engine finishes inside it
    cell.wl["rate_per_s"] = 1.5
    return cell


def run_phi3(cell: run.Cell, seed: int, tmp_path, **kw) -> dict:
    """``run_tiny`` over a longer window: at the published MLP ratio a tiny
    step takes about twice as long in interpret mode, and the check needs
    enough finished tokens however loaded the CPU is."""
    import jax

    from bench.lib import peaks
    from bench.lib.clock import CompileClock

    peaks.PEAKS.setdefault("cpu", peaks.PEAKS["TPU v5 lite"])
    return run.run(cell, seed, WINDOW_S, kw.pop("trace", 0), jax.devices(),
                   CompileClock().install(), time.perf_counter(),
                   out_dir=tmp_path, **kw)


def test_tiny_cell_keeps_what_phi3_forces():
    from bench.lib import hpa, state

    cell = phi3_tiny()
    arch = config.arch_config(cell.cfg)
    assert arch.num_heads // arch.num_kv_heads == 4
    assert arch.vocab_size % 128 and not arch.tie_embeddings
    assert arch.norm_type == "rmsnorm"
    spec = state.SlrSpec.from_config(cell.cfg)
    params, st, blocks, plain = state.make_state(arch, spec, SEED)
    others = {o["name"] for o in plain["others"]}
    assert {"lm_head/w", "layers/pre_attn/norm_scale",
            "layers/pre_mlp/norm_scale", "final_norm/norm_scale"} <= others
    for scale in (params["layers"]["pre_attn"]["norm_scale"],
                  params["layers"]["pre_mlp"]["norm_scale"],
                  params["final_norm"]["norm_scale"]):
        assert np.abs(np.asarray(scale, np.float32)).max() > 0
    # tier 0.6 keeps part of S: the fused SLR kernel serves every site
    _, (_, phi_s) = hpa.keep_masks(hpa.host_blocks(st, blocks),
                                   cell.mix["tiers"]["keeps"][0], spec.kappa)
    assert 0.0 < phi_s < 1.0


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return run_phi3(phi3_tiny(), SEED, tmp_path_factory.mktemp("sound"))


@pytest.fixture(scope="module")
def control(tmp_path_factory):
    return run_phi3(phi3_tiny(), SEED, tmp_path_factory.mktemp("control"),
                    control=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["check"]["tiers_checked"]["value"] == 1
    assert sound["check"]["tokens_compared"]["value"] >= 8
    assert sound["attempted"] > 0
    assert set(sound["metrics"]) == {"output_tok_s", "ttft_p50_ms",
                                     "itl_p95_ms", "setup_s"}


def test_control_run_is_not_correct_at_the_committed_limit(control):
    limit = run.load_json("workloads", CELL)["check"]["max_logit_gap"]
    check = control["check"]
    assert check["max_logit_gap"]["limit"] == limit
    assert check["max_logit_gap"]["value"] > limit
    assert check["program_gap"]["value"] <= limit
    assert check["max_logit_gap"]["value"] > 3 * check["program_gap"]["value"]
    assert control["correct"] is False


def test_token_altered_where_produced_fails(tmp_path, monkeypatch):
    from repro.serving.engine import ServingEngine

    record = ServingEngine._record

    def altered(self, slot, req, tok, free, done):
        if len(req.out_tokens) == 1:          # the second token of each request
            tok = (tok + 1) % self.cfg.vocab_size
        return record(self, slot, req, tok, free, done)

    monkeypatch.setattr(ServingEngine, "_record", altered)
    line = run_phi3(phi3_tiny(), SEED, tmp_path)
    assert line["correct"] is False
    assert line["check"]["max_logit_gap"]["value"] > \
        line["check"]["max_logit_gap"]["limit"]


def test_traced_run_reads_the_prefill_padding(tmp_path, monkeypatch):
    """A ``--trace 1`` run: the padding reader finds the engine's prefill
    spans; the device readers find no device on the CPU and read nothing."""
    monkeypatch.setattr(sp, "ROOT", tmp_path)
    line = run_phi3(phi3_tiny(), SEED, tmp_path / "bench_out" / CELL, trace=1)
    assert line["correct"] is True
    pad = line["metrics"]["sched.prefill_pad_share"]
    assert pad["unit"] == "%" and 0.0 < pad["value"] < 100.0
    for name in ("step.prefill_ms", "mfu.prefill",
                 "slr_matmul_roofline.prefill"):
        assert name not in line["metrics"]


def test_widths_are_the_programs_but_depth():
    from repro.configs.phi3_medium_14b import CONFIG

    cfg = config.load("phi3_medium_4k")
    assert cfg["reduced"] == ["num_hidden_layers"]
    entry = {c["name"]: c for c in BENCHMARK["configs"]}["phi3_medium_4k"]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    arch = config.arch_config(cfg)
    assert (arch.num_layers, CONFIG.num_layers) == (4, 40)
    fields = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
              "vocab_size", "mlp_type", "norm_type", "rope_theta",
              "tie_embeddings", "family")
    want = dataclasses.replace(CONFIG, num_layers=4)
    for f in fields:
        assert getattr(arch, f) == getattr(want, f), f
    assert cfg["vocab_size"] == 32064 and cfg["intermediate_size"] == 17920
    assert cfg["source"] in CONFIG.source


def test_cell_keeps_every_sequence_inside_the_sliding_window():
    cell = run.Cell(BENCHMARK, CELL)
    longest = cell.mix["prompt"]["max"] + cell.mix["output"]["max"] - 1
    assert longest <= cell.cfg["sliding_window"]
    assert longest < cell.wl["engine"]["max_len"] <= \
        cell.cfg["max_position_embeddings"]
    assert cell.mix["tiers"] == {"keeps": [0.6], "shares": [1.0]}
    assert cell.wl["check"]["tokens_per_tier"] >= 200
    assert cell.wl["rate_per_s"] == pytest.approx(
        0.6 * cell.wl["knee_per_s"], rel=0.02)


# ------------------------------------------------- readers on hand-built ---

def _tier():
    # two layers of one (8, 16) site that keeps sparse entries
    site = Site("layers/q", 8, 16, np.array([2, 1]), np.array([10, 4]))
    return TierMath(keep=0.6, sites=[site], num_layers=2, d_model=8,
                    vocab=32, q_heads=2, head_dim=4)


def _ctx(steps):
    from bench.lib.layers import LayerContext
    from bench.lib.serve import StepWork

    work = [StepWork(0.0, 1.0, prefill={0: [3, 5]}, decode={0: [9]}),
            StepWork(1.0, 2.0, decode={0: [4, 10]})]
    modules = [("jit__prefill_fn(3)", 0.0, 4e6), ("jit__decode_fn(4)", 5e6, 6e6),
               ("jit__decode_fn(4)", 10e6, 11e6)]
    ops = [("slr_matmul_stacked_pallas.7", 1e6, 3e6),
           ("fusion.2", 3e6, 4e6),
           ("slr_matmul_stacked_pallas.8", 5e6, 6e6)]
    return LayerContext(ops, modules, steps, work, 0.0, 12e6, None,
                        [_tier()], {"bf16_flop_per_s": 1e9,
                                    "hbm_bytes_per_s": 1e9})


STEPS = [("engine.step", 0.0, 8e6), ("engine.step", 9e6, 12e6)]


def test_prefill_step_and_mfu_readers():
    ctx = _ctx(STEPS)
    read = lambda n: run.load_module("metrics", n).read(ctx)  # noqa: E731
    assert read("step.prefill_ms") == pytest.approx(4.0)
    flops = _tier().prefill_flops(3) + _tier().prefill_flops(5)
    assert read("mfu.prefill") == pytest.approx(100 * flops / (4e-3 * 1e9))
    # the kernel's 2 ms inside the prefill program, at 8 prompt rows
    least = _tier().stacked_kernel_least_s(8, 1e9, 1e9)
    assert read("slr_matmul_roofline.prefill") == pytest.approx(
        100 * least / 2e-3)
    unaligned = dataclasses.replace(ctx, steps=None)
    for n in ("mfu.prefill", "slr_matmul_roofline.prefill"):
        assert run.load_module("metrics", n).read(unaligned) is None
    nothing = dataclasses.replace(ctx, modules=[m for m in ctx.modules
                                                if "prefill" not in m[0]])
    assert run.load_module("metrics", "step.prefill_ms").read(nothing) is None
    assert run.load_module("metrics", "mfu.prefill").read(nothing) is None


def test_pad_share_reads_prefill_spans_inside_the_steps(monkeypatch):
    found = [
        ("serve.program", 1e6, 4e6, {"program": "prefill[256]", "tier": 0,
                                     "rows": 2, "tokens": 300,
                                     "positions": 1024}),
        ("serve.program", 5e6, 6e6, {"program": "decode", "tier": 0,
                                     "rows": 2}),
        ("serve.program", 9.5e6, 10e6, {"program": "chunk[64]", "tier": 0,
                                        "rows": 1, "tokens": 12,
                                        "positions": 128}),
        # outside every step: not of the window
        ("serve.program", 20e6, 21e6, {"program": "prefill[128]", "tier": 0,
                                       "rows": 1, "tokens": 1,
                                       "positions": 512}),
    ]
    ctx = types.SimpleNamespace(step_spans=STEPS)
    monkeypatch.setattr(sp, "for_context", lambda c: found)
    read = run.load_module("metrics", "sched.prefill_pad_share").read
    assert read(ctx) == pytest.approx(100 * (1 - 312 / 1152))
    # spans without the arguments, as a program older than them leaves
    bare = [(n, s, e, {k: v for k, v in st.items()
                       if k not in ("tokens", "positions")})
            for n, s, e, st in found]
    monkeypatch.setattr(sp, "for_context", lambda c: bare)
    assert read(ctx) is None
    monkeypatch.setattr(sp, "for_context", lambda c: None)
    assert read(ctx) is None

"""The configurations' widths against their published values, and every
name in BENCHMARK.json resolving to its file; a new configuration, mix or
per-layer metric needs only new files and entries."""
import json
import shutil

import _paths  # noqa: F401
import pytest

from bench import run
from bench.lib import config

BENCHMARK = json.loads((run.ROOT / "BENCHMARK.json").read_text())

PUBLISHED = {
    "olmo_1b": dict(hidden_size=2048, num_hidden_layers=16,
                    num_attention_heads=16, num_key_value_heads=16,
                    head_dim=128, intermediate_size=8192, vocab_size=50304,
                    rope_theta=10000.0, max_position_embeddings=2048,
                    tie_word_embeddings=True, norm="nonparametric_layernorm"),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_widths_are_published(name):
    cfg = config.load(name)
    for key, value in PUBLISHED[name].items():
        assert cfg[key] == value, key
    entry = {c["name"]: c for c in BENCHMARK["configs"]}.get(name)
    if entry is not None:       # a configuration the benchmark runs now
        assert entry["file"] == f"bench/configs/{name}.json"
        assert entry["source"] == cfg["source"]
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"])


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_arch_config_serves_bf16_through_pallas(name):
    import jax.numpy as jnp

    arch = config.arch_config(config.load(name))
    assert arch.param_dtype == jnp.bfloat16 and arch.kernel_impl == "pallas"
    assert arch.d_model == PUBLISHED[name]["hidden_size"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_name_resolves_to_its_file(cell):
    c = run.Cell(BENCHMARK, cell)
    assert c.wl["config"] == c.entry["config"]
    assert c.wl["traffic"] == c.entry["traffic"]
    assert hasattr(c.generator, "generate")
    names = run.per_layer_names(BENCHMARK, c.entry)
    assert names
    for name in names:
        assert hasattr(run.load_module("metrics", name), "read")
    assert "setup_s" in run.end_to_end_names(BENCHMARK, c.entry)


def test_paths_hold_every_file_the_benchmark_names():
    assert BENCHMARK["paths"] == ["bench"]
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    for c in BENCHMARK["configs"]:
        assert (run.ROOT / c["file"]).is_file()


def test_a_new_config_mix_and_metric_need_only_new_files(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(run.BENCH, bench, ignore=shutil.ignore_patterns("tests"))
    cfg = config.load("olmo_1b")
    cfg["name"] = "new_model"
    (bench / "configs" / "new_model.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps(
        {"generator": "open_loop_serve",
         "prompt": {"dist": "uniform", "min": 8, "max": 16},
         "output": {"dist": "uniform", "min": 2, "max": 4},
         "tiers": {"keeps": [1.0], "shares": [1.0]}, "schedule_seed": 1}))
    (bench / "workloads" / "new.cell.json").write_text(json.dumps(
        {"config": "new_model", "traffic": "new_mix", "chips": 1,
         "rate_per_s": 1.0}))
    (bench / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    bm = json.loads(json.dumps(BENCHMARK))
    bm["configs"].append({"name": "new_model", "source": cfg["source"],
                          "file": "bench/configs/new_model.json",
                          "reduced": []})
    bm["workloads"].append({"name": "new.cell", "config": "new_model",
                            "traffic": "new_mix", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "new.metric", "unit": "%",
                            "better": "higher", "source": "device_trace",
                            "layer": "device", "moves": "output_tok_s"})
    monkeypatch.setattr(run, "BENCH", bench)
    c = run.Cell(bm, "new.cell")
    assert c.cfg["name"] == "new_model"
    assert len(c.generator.generate(c.mix, rate_per_s=1.0, seconds=4.0,
                                    seed=1, vocab=100)) == 4
    names = run.per_layer_names(bm, c.entry)
    assert "new.metric" in names
    assert run.load_module("metrics", "new.metric").read(None) == 1.0
    # a metric without a cell list follows its end-to-end metric everywhere
    old = run.Cell(bm, "olmo1b.chat_tiers")
    assert "new.metric" in run.per_layer_names(bm, old.entry)


def test_an_end_to_end_metric_follows_its_cell_list():
    bm = json.loads(json.dumps(BENCHMARK))
    bm["end_to_end"].append({"name": "train_tok_s", "unit": "tokens/s",
                             "better": "higher", "bound": 0.05,
                             "source": "host_clock",
                             "workloads": ["olmo1b.train"]})
    serve = run.cell_entry(bm, "olmo1b.chat_tiers")
    assert "train_tok_s" not in run.end_to_end_names(bm, serve)
    assert "train_tok_s" in run.end_to_end_names(
        bm, {"name": "olmo1b.train", "config": "olmo_1b"})

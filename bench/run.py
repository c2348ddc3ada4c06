"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``bench/workloads/<cell>.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``, read by the generator it names). Weights and
the SALAAD state are drawn on the chip from ``--seed``, HPA tiers are built
through ``ModelBank.build`` in the ``fused`` format with Pallas kernels, and
every prefill bucket and tier the mix uses is compiled before the window
opens. The window drives ``PagedServingEngine.submit``/``step`` in open loop
for ``--seconds``. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` profiles the middle of the window and reports its per-layer
metrics (``bench/metrics/<name>.py``). Either way, once the window has
closed and the program's state is freed, a sample of the finished requests
is run through the float32 reference (``bench/lib/reference.py``) and the
widest gap by which a served token's logit lies below the reference's best
decides ``correct``.

Off a TPU it exits 1 and prints no result. ``--sweep r1,r2,..`` serves one
window per rate after one set-up and prints the backlog of each (requests due
and not finished, over the window), to find the rate a cell can sustain.
``--control`` puts the float8 control in the program's place for the check:
``correct`` is then decided from the control's first choices, and has to
read false.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

TRACE_SECONDS = 4.0     # length of the profiled slice of a --trace 1 window


def log(**fields):
    print(json.dumps(fields), file=sys.stderr, flush=True)


def load_json(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_entry(benchmark: dict, workload: str) -> dict:
    for w in benchmark["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def per_layer_names(benchmark: dict, entry: dict) -> list[str]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric it reports."""
    e2e = end_to_end_names(benchmark, entry)
    out = []
    for m in benchmark["per_layer"]:
        cells = m.get("workloads")
        if (cells is not None and entry["name"] in cells) or (
                cells is None and m["moves"] in e2e):
            out.append(m["name"])
    return out


def end_to_end_names(benchmark: dict, entry: dict) -> list[str]:
    return [m["name"] for m in benchmark["end_to_end"]
            if entry["name"] in m.get("workloads", [entry["name"]])]


class Cell:
    """Everything a run reads by name: the cell, its configuration, its mix."""

    def __init__(self, benchmark: dict, workload: str):
        self.benchmark = benchmark
        self.entry = cell_entry(benchmark, workload)
        self.wl = load_json("workloads", workload)
        self.cfg = load_json("configs", self.entry["config"])
        self.mix = load_json("traffic", self.entry["traffic"])
        self.generator = load_module("traffic", self.mix["generator"])
        self.units = {m["name"]: m["unit"] for m in
                      benchmark["end_to_end"] + benchmark["per_layer"]}


def build(cell: Cell, seed: int, arch):
    """Draw the model, build the tiers and the engine. Returns (engine,
    tier mathematics, plain layout, keep masks per tier)."""
    from bench.lib import counts, hpa, state
    from repro.serving.elastic import ModelBank
    from repro.serving.engine import EngineConfig, PagedServingEngine

    spec = state.SlrSpec.from_config(cell.cfg)
    keeps = cell.mix["tiers"]["keeps"]
    t0 = time.perf_counter()
    params, st, blocks, plain = state.make_state(arch, spec, seed)
    hb = hpa.host_blocks(st, blocks)
    t1 = time.perf_counter()
    masks, tiers = [], []
    for keep in keeps:
        m, (phi_l, phi_s) = hpa.keep_masks(hb, keep, spec.kappa)
        t = counts.tier_math(keep, m, blocks, arch)
        masks.append(m)
        tiers.append(t)
        log(phase="tier", keep=keep, phi_L=phi_l, phi_S=phi_s,
            rank={s.name: [int(s.rank.min()), int(s.rank.max())] for s in t.sites},
            nnz={s.name: int(s.nnz.sum()) for s in t.sites},
            embedding_rank=int(m["embed/embedding"].kept_rank),
            embedding_nnz=int(m["embed/embedding"].kept_nnz),
            fused_sparse_sites=[s.name for s in t.sites if s.fused_sparse],
            linear_gflop_per_token=t.linear_flops_per_token() / 1e9)
    del hb
    t2 = time.perf_counter()
    bank = ModelBank.build(arch, params, st, blocks, budgets=keeps,
                           kappa=spec.kappa, fmt="fused")
    del params, st
    log(phase="bank", draw_s=t1 - t0, yardstick_s=t2 - t1,
        bank_build_s=time.perf_counter() - t2,
        tiers=[{"keep": t.keep, "param_bytes": t.param_bytes} for t in bank])
    e = cell.wl["engine"]
    engine = PagedServingEngine(bank, EngineConfig(
        max_slots=e["max_slots"], max_len=e["max_len"],
        block_size=e["block_size"], kv_dtype=cell.cfg["kv_dtype"],
        greedy=True))
    return engine, tiers, plain, masks


def warm_up(engine, cell: Cell, seed: int):
    """Compile the prefill bucket of every prompt length the mix can send,
    at every tier, and each tier's decode program."""
    import numpy as np
    from jax.profiler import TraceAnnotation

    p = cell.mix["prompt"]
    by_bucket: dict[int, int] = {}
    for n in range(int(p["min"]), int(p["max"]) + 1):
        by_bucket[engine._bucket(n)] = n
    rng = np.random.default_rng(seed)
    vocab = engine.cfg.vocab_size
    with TraceAnnotation("bench.warmup"):
        for tier in range(len(cell.mix["tiers"]["keeps"])):
            for n in by_bucket.values():
                engine.submit(rng.integers(1, vocab, size=n).tolist(),
                              max_new_tokens=2, tier=tier)
                engine.run()
    return sorted(by_bucket)


def sample_finished(window, rng, per_tier_tokens: int, tiers: int) -> list:
    """Finished requests to check: the longest, then per tier a random draw
    until it holds ``per_tier_tokens`` served tokens."""
    done = [r for r in window.requests.values()
            if r.done and r.finished_at <= window.close]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.out_tokens))
    chosen = {longest.uid: longest}
    for tier in range(tiers):
        pool = [r for r in done if r.tier == tier]
        got = sum(len(r.out_tokens) for r in chosen.values() if r.tier == tier)
        for j in rng.permutation(len(pool)):
            if got >= per_tier_tokens:
                break
            r = pool[int(j)]
            if r.uid not in chosen:
                chosen[r.uid] = r
                got += len(r.out_tokens)
    return sorted(chosen.values(), key=lambda r: r.uid)


def check(cell: Cell, seed: int, arch, plain, masks, sample, control: bool):
    """Widest logit gap of the served tokens under the float32 reference
    (and, with ``control``, of the float8 control's first choices)."""
    import numpy as np

    from bench.lib import reference, state

    spec = state.SlrSpec.from_config(cell.cfg)
    raw, others = state.draw(plain, spec, seed, arch.param_dtype)
    out = {"gap": 0.0, "tokens": 0, "per_tier": {}, "control_gap": None}
    cgaps = []
    for tier in sorted({r.tier for r in sample}):
        reqs = [r for r in sample if r.tier == tier]
        seqs = [r.prompt + r.out_tokens[:-1] for r in reqs]
        rows = [list(range(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.out_tokens)))
                for r in reqs]
        ref = reference.logits(cell.cfg, plain, raw, others, masks[tier], seqs, rows)
        gaps = []
        for r, lg in zip(reqs, ref):
            served = lg[np.arange(len(r.out_tokens)), np.asarray(r.out_tokens)]
            gaps.append(lg.max(axis=1) - served)
        g = np.concatenate(gaps)
        out["per_tier"][tier] = {"gap": float(g.max()), "tokens": int(g.size)}
        out["gap"] = max(out["gap"], float(g.max()))
        out["tokens"] += int(g.size)
        if control:
            ctl = reference.logits(cell.cfg, plain, raw, others, masks[tier],
                                   seqs, rows, control=True)
            for lg, lc in zip(ref, ctl):
                pick = lc.argmax(axis=1)
                cgaps.append(lg.max(axis=1) - lg[np.arange(len(pick)), pick])
    if cgaps:
        out["control_gap"] = float(np.concatenate(cgaps).max())
    return out


def run(cell: Cell, seed: int, seconds: float, trace: int, devices, clock,
        t_start: float, *, control: bool = False,
        out_dir: Path | None = None) -> dict:
    """One run of the cell; returns the result line."""
    import numpy as np

    from bench.lib import device as dev
    from bench.lib import layers, roofline, serve
    from bench.lib import trace as tr
    from bench.lib.config import arch_config
    from bench.lib.peaks import peaks_for

    arch = arch_config(cell.cfg)
    engine, tiers, plain, masks = build(cell, seed, arch)
    t0 = time.perf_counter()
    buckets = warm_up(engine, cell, seed)
    # set-up's objects leave the collector's generations, so that a full
    # collection inside the window does not walk them
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(phase="setup", setup_s=setup_s, warm_up_s=time.perf_counter() - t0,
        buckets=buckets, **clock.snapshot())

    rate = float(cell.wl["rate_per_s"])
    arrivals = cell.generator.generate(cell.mix, rate_per_s=rate, seconds=seconds,
                                       seed=seed, vocab=arch.vocab_size)
    out_dir = out_dir or ROOT / "bench_out" / cell.entry["name"]
    trace_dir = str(out_dir / "trace") if trace else None
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    mid = max(0.0, seconds / 2 - TRACE_SECONDS / 2)
    full_gc = gc.get_stats()[2]["collections"]
    window = serve.run_window(engine, arrivals, seconds, clock,
                              trace_dir=trace_dir,
                              trace_span=(mid, mid + TRACE_SECONDS))
    full_gc = gc.get_stats()[2]["collections"] - full_gc
    memory_peak = dev.memory_peak_bytes(devices)
    stats = engine.stats_snapshot()
    lag = np.asarray(window.lag)
    log(phase="window", requests_due=len(window.due), steps=len(window.steps),
        compiles_in_window=window.compiles, evictions=engine.evictions,
        generator_lag_p50_ms=float(np.median(lag)) * 1e3 if lag.size else None,
        generator_lag_max_ms=float(lag.max()) * 1e3 if lag.size else None,
        backlog=window.backlog, jit_retraces=stats["jit_retraces"],
        longest_step_s_at=window.longest_step,
        longest_outside_steps_s_at=window.longest_other,
        full_collections_in_window=full_gc)

    device = dev.describe(devices)
    device["memory_peak_bytes"] = memory_peak
    metrics, breakdown = {}, None
    if trace:
        names = per_layer_names(cell.benchmark, cell.entry)
        t = tr.load(trace_dir, {"engine.step", "engine.submit", "bench.idle"})
        ctx = layers.context(t, window, tiers, peaks_for(device["kind"]))
        for name in names:
            value = load_module("metrics", name).read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": cell.units[name]}
        busy = [tr.busy_ns(ops, ctx.lo, ctx.hi) for ops in
                (tr.clip(t.ops.get(i, []), ctx.lo, ctx.hi)
                 for i in range(len(devices)))]
        device["busy_s"] = float(np.mean(busy)) / 1e9
        device["window_s"] = ctx.seconds
        breakdown = {
            "device_ops": [[n, s] for n, s in tr.top_ops(ctx.ops)],
            "idle_gaps": [[n, s] for n, s in tr.label_gaps(
                tr.gaps(ctx.ops, ctx.lo, ctx.hi), t.spans)],
        }
        log(phase="trace", steps_aligned=ctx.steps is not None,
            trace_steps=len(ctx.step_spans), stacked_kernel_events=tr.time_by_name(
                ctx.ops, roofline.KERNEL)[1])
    else:
        e2e = serve.end_to_end(window, seconds)
        log(phase="end_to_end", samples=e2e.pop("_samples"))
        e2e["setup_s"] = setup_s
        for name in end_to_end_names(cell.benchmark, cell.entry):
            metrics[name] = {"value": e2e[name], "unit": cell.units[name]}

    # the check: after the window, with the program's state freed
    lim = cell.wl["check"]
    rng = np.random.default_rng([seed, 1])
    ntiers = len(cell.mix["tiers"]["keeps"])
    sample = sample_finished(window, rng, lim["tokens_per_tier"], ntiers)
    del engine
    gc.unfreeze()
    gc.collect()
    t0 = time.perf_counter()
    result = check(cell, seed, arch, plain, masks, sample, control)
    tiers_seen = len(result["per_tier"])
    log(phase="check", seconds=time.perf_counter() - t0,
        requests=len(sample), per_tier=result["per_tier"],
        control_gap=result["control_gap"])
    # under --control the control stands where the program stood
    gap = result["control_gap"] if control else result["gap"]
    compared = {
        "max_logit_gap": {"value": gap, "limit": lim["max_logit_gap"]},
        "tokens_compared": {"value": result["tokens"],
                            "limit": lim["min_tokens"]},
        "tiers_checked": {"value": tiers_seen, "limit": ntiers},
    }
    if control:
        compared["program_gap"] = {"value": result["gap"],
                                   "limit": lim["max_logit_gap"]}
    correct = (gap is not None and gap <= lim["max_logit_gap"]
               and result["tokens"] >= lim["min_tokens"]
               and tiers_seen == ntiers)
    line = {"correct": bool(correct), "attempted": len(window.due), "failed": 0,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["check"] = compared
    for name, c in compared.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return line


def sweep(cell: Cell, seed: int, seconds: float, rates: list[float], clock,
          t_start: float):
    """One set-up, then one window per rate, each drained before the next.
    A rate is sustained where the backlog does not grow over its window."""
    import numpy as np

    from bench.lib import serve
    from bench.lib.config import arch_config

    import jax

    from bench.lib.device import memory_peak_bytes

    arch = arch_config(cell.cfg)
    engine, _, _, _ = build(cell, seed, arch)
    warm_up(engine, cell, seed)
    log(phase="setup", setup_s=time.perf_counter() - t_start,
        memory_peak_bytes=memory_peak_bytes(jax.devices()), **clock.snapshot())
    for k, rate in enumerate(rates):
        arrivals = cell.generator.generate(cell.mix, rate_per_s=rate,
                                           seconds=seconds, seed=seed + k,
                                           vocab=arch.vocab_size)
        w = serve.run_window(engine, arrivals, seconds, clock)
        e2e = serve.end_to_end(w, seconds)
        t = serve.ttfts(w)
        done = sum(1 for r in w.requests.values()
                   if r.done and r.finished_at <= w.close)
        half = len(w.backlog) // 2
        first, second = ([q for _, q in w.backlog[:half]],
                         [q for _, q in w.backlog[half:]])
        print(json.dumps({
            "rate_per_s": rate, "requests": len(w.due), "finished": done,
            "unfinished_at_close": len(w.due) - done,
            "backlog_mean_first_half": float(np.mean(first)),
            "backlog_mean_second_half": float(np.mean(second)),
            "output_tok_s": e2e["output_tok_s"],
            "ttft_p50_ms": e2e["ttft_p50_ms"],
            "ttft_p95_ms": float(np.percentile(t, 95)) * 1e3,
            "itl_p95_ms": e2e["itl_p95_ms"],
            "backlog": w.backlog, "compiles_in_window": w.compiles,
            "lag_max_ms": float(max(w.lag)) * 1e3 if w.lag else None,
        }), flush=True)
        t0 = time.perf_counter()
        while engine.has_work and time.perf_counter() - t0 < 120:
            engine.step()
        if engine.has_work:
            break


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates (requests/s): one window each")
    ap.add_argument("--control", action="store_true",
                    help="decide correct from the float8 control in the "
                         "program's place (it has to read false)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench.lib.device import NoChip, require_chips

    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(benchmark, args.workload)
    try:
        devices = require_chips(int(cell.entry["chips"]))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1

    import jax

    from bench.lib.clock import CompileClock
    from repro.kernels import ops
    from repro.launch.compile_cache import use_compile_cache

    # the compile cache lives at one fixed path in the checkout, and holds
    # every program, so that only a checkout's first run compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock().install()
    log(phase="start", compile_cache=use_compile_cache(), seed=args.seed,
        workload=args.workload)
    with ops.interpret_mode(False):
        if args.sweep:
            sweep(cell, args.seed, args.seconds,
                  [float(r) for r in args.sweep.split(",")], clock, t_start)
            return 0
        line = run(cell, args.seed, args.seconds, args.trace, devices, clock,
                   t_start, control=args.control)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

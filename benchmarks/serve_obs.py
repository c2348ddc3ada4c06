"""Telemetry-overhead benchmark: the zero-cost-on-device-path claim, measured.

Three arms drive the SAME paged-engine workload (chunked prefill + prefix
cache — the hook-densest scheduler path) over the SAME closed-loop trace:

``telemetry_off``
    A ``NullTelemetry`` installed — every hook is a no-op, the timing
    context managers never read the clock.
``telemetry_on``
    Full registry accounting (counters, gauges, histograms, per-program
    wall clocks, the retrace detector).
``traced``
    Telemetry on PLUS a live ``RequestTracer`` recording per-request spans.

All three arms run on ONE engine instance, swapping only the installed
telemetry object between passes. A null experiment on this box showed three
bit-identical engines differing by up to ~6% steady-state tok/s purely from
construction order (jit code / allocator memory layout), so separate
per-arm engines cannot resolve a sub-2% effect; with one engine the jitted
programs, page pool, and caches are shared and the only variable left is
the hooks themselves. Each arm's cost is the mean of its 3 smallest wall
times over ``passes`` rotated rounds (a damped timeit estimator —
everything above the floor is scheduler noise).

The overhead run uses the FULL 60m config, not ``.reduced()``: the claim
is about serving overhead, so the hook cost must be weighed against a
realistic per-tick device workload (~ms), not the test-sized model's
sub-ms ticks where any fixed host cost is relatively inflated. ``--quick``
keeps the reduced model for CI smoke — there the bitwise-equality check is
the point and the overhead column is indicative only.

The benchmark asserts the greedy token streams are BITWISE IDENTICAL across
arms (telemetry is host-side only — it must never touch the device path),
then reports each instrumented arm's tok/s delta against the off arm. The
PR target is < 2% telemetry overhead; the measured delta and the verdict
ride in ``BENCH_obs.json`` along with the metric/event volume that bought
it.

  PYTHONPATH=src python -m benchmarks.serve_obs --quick
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import get_arch
from repro.models import model as model_lib
from repro.serving.elastic import ModelBank
from repro.serving.engine import EngineConfig, PagedServingEngine
from repro.serving.telemetry import NullTelemetry

from .common import emit, engine_provenance


def _trace(n: int, vocab: int, max_new: int, shared_len: int, seed: int):
    """Closed-loop prompt list: a shared system prefix + unique tails, so
    the prefix-cache hooks (lookup/hit/CoW) actually fire."""
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, vocab, size=shared_len).tolist()
    return [
        (shared + rng.randint(0, vocab, size=int(rng.randint(3, 8))).tolist(),
         max_new)
        for _ in range(n)
    ]


def _drive(engine, trace) -> tuple[float, list[list[int]]]:
    """Submit everything, run to completion; returns (wall seconds, streams
    in submission order) — the streams are the bitwise-equality evidence."""
    for prompt, max_new in trace:
        engine.submit(list(prompt), max_new_tokens=max_new)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    assert len(done) == len(trace), (len(done), len(trace))
    streams = [r.out_tokens for r in sorted(done, key=lambda r: r.uid)]
    return dt, streams


def run(
    requests: int = 8,
    max_new: int = 16,
    shared_len: int = 32,
    max_slots: int = 8,
    max_len: int = 96,
    block_size: int = 16,
    num_blocks: int = 64,
    prefill_chunk: int = 16,
    passes: int = 12,
    reduced: bool = False,
    seed: int = 0,
) -> dict:
    """Decode-dominated workload (long generations across many slots), so
    the overhead figure reflects the per-tick hook cost RELATIVE to a tick's
    device work — the claim the PR makes — rather than the pathological
    all-host toy regime."""
    cfg = get_arch("salaad_llama_60m")
    if reduced:
        cfg = cfg.reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
    bank = ModelBank.single(cfg, params)
    trace = _trace(requests, cfg.vocab_size, max_new, shared_len, seed)

    eng = PagedServingEngine(bank, EngineConfig(
        max_slots=max_slots, max_len=max_len, block_size=block_size,
        num_blocks=num_blocks, prefill_chunk=prefill_chunk,
        prefix_cache=True,
    ))
    on_tel = eng.metrics
    off_tel = NullTelemetry(type(eng).__name__)
    tracer = eng.start_trace()

    # ONE engine, three telemetry configurations swapped in between passes
    def install(arm: str):
        tel = off_tel if arm == "telemetry_off" else on_tel
        tr = tracer if arm == "traced" else None
        eng.metrics, eng.tracer = tel, tr

    _drive(eng, trace)                         # warmup (traced: every path)

    arms = ("telemetry_off", "telemetry_on", "traced")
    walls: dict[str, list[float]] = {a: [] for a in arms}
    streams: dict[str, list] = {}
    for p in range(passes):
        # rotate the arm order every round so slow-drift box noise (thermal,
        # cache pressure) never lands on one arm systematically
        for arm in arms[p % len(arms):] + arms[:p % len(arms)]:
            install(arm)
            dt, out = _drive(eng, trace)
            walls[arm].append(dt)
            streams[arm] = out
    install("telemetry_on")                    # leave a live registry behind

    identical = (streams["telemetry_off"] == streams["telemetry_on"]
                 == streams["traced"])
    assert identical, "telemetry/tracing changed the token stream"

    def floor(arm: str) -> float:
        """Mean of the 3 smallest walls — a damped minimum."""
        return sum(sorted(walls[arm])[:3]) / 3

    tokens = sum(len(s) for s in streams["telemetry_off"])
    rows: dict = {
        arm: {"tok_per_s": round(tokens / floor(arm), 1)}
        for arm in arms
    }
    snap = eng.stats_snapshot()
    rows["telemetry_on"]["jit_retraces"] = snap["jit_retraces"]
    rows["telemetry_on"]["metric_series"] = sum(
        len(m["values"]) if isinstance(m["values"], dict) else 1
        for m in snap["metrics"].values()
    )
    rows["traced"]["trace_events"] = len(tracer.events)
    rows["engine_config"] = engine_provenance(eng)

    def overhead(arm: str) -> float:
        base = floor("telemetry_off")
        return round(100 * (floor(arm) - base) / base, 2)

    rows["summary"] = {
        "streams_bitwise_identical": identical,
        "tok_per_s_off": rows["telemetry_off"]["tok_per_s"],
        "tok_per_s_on": rows["telemetry_on"]["tok_per_s"],
        "tok_per_s_traced": rows["traced"]["tok_per_s"],
        "telemetry_overhead_pct": overhead("telemetry_on"),
        "trace_overhead_pct": overhead("traced"),
        "overhead_under_2pct": overhead("telemetry_on") < 2.0,
        "passes": passes,
        "reduced_model": reduced,
    }
    return rows


def main(out: str = "BENCH_obs.json", **kw):
    rows = run(**kw)
    Path(out).write_text(json.dumps(rows, indent=2))
    s = rows["summary"]
    emit(
        "serve_obs", 0.0,
        f"tok/s off={s['tok_per_s_off']} on={s['tok_per_s_on']} "
        f"traced={s['tok_per_s_traced']} "
        f"(overhead {s['telemetry_overhead_pct']}% / "
        f"{s['trace_overhead_pct']}%); streams identical={s['streams_bitwise_identical']}",
    )
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced model, fewer passes: bitwise-equality "
                         "smoke; the overhead column is indicative only")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--passes", type=int, default=None)
    ap.add_argument("--out", default="BENCH_obs.json")
    a = ap.parse_args()
    main(out=a.out,
         requests=a.requests or 8,
         max_new=16,
         reduced=a.quick,
         passes=a.passes or (6 if a.quick else 12))

"""Latency SLO harness: Poisson arrivals against the paged engine.

Throughput (serve_throughput.py) hides the queueing story. This harness
drives the paged engine — a fixed KV page pool shared by its decode slots —
with a Poisson request trace in open loop (arrivals are submitted on the
clock, whether or not the engine is keeping up), then reports p50/p99
time-to-first-token, inter-token latency, admitted-request rate, and SLO
attainment → ``BENCH_latency.json``.
All latency math runs on ``time.monotonic()`` (the engines timestamp tokens
on that clock); only request DEADLINES stay wall-clock, as an absolute SLO
contract.

``--mixed`` runs the chunked-prefill story instead: a mixed long/short-prompt
trace against the paged engine with one-shot vs chunked prefill at the SAME
KV budget. One monolithic long-prompt prefill stalls every decoding slot for
a whole tick (head-of-line blocking — visible as a p99 inter-token-latency
spike on the short requests); chunked prefill caps per-tick prefill work at
``prefill_chunk`` tokens, so short-request ITL stays flat while the long
prompt streams in. Rows merge into the same ``BENCH_latency.json``.

Percentile basis (changed with the telemetry registry): every TTFT figure —
headline p50/p99 AND the per-class ``--mixed`` columns — is measured from the
request's SCHEDULED arrival. The driver backdates ``submit(...,
submitted_at=scheduled)`` so the engine's own ``serve_ttft_seconds``
histogram records that basis, and the rows read their percentiles from the
registry. Earlier revisions mixed bases across the ``--mixed`` arms
(scheduled arrival for the headline, actual submit time for per-class
columns), which understated TTFT exactly when a monolithic prefill blocked
the driver loop — the effect under measurement.

  PYTHONPATH=src python -m benchmarks.serve_latency --quick
  PYTHONPATH=src python -m benchmarks.serve_latency --mixed --quick
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
from repro.serving.engine import (
    EngineConfig,
    PagedServingEngine,
    decode_emitted_tokens,
)

from repro.serving.telemetry import request_itls, request_ttft

from .common import emit, engine_provenance


def build_trace(n: int, rate_hz: float, vocab: int, max_new: int, seed: int):
    """Poisson arrival trace: [(arrival_offset_s, prompt, max_new), ...]."""
    rng = np.random.RandomState(seed)
    offsets = np.cumsum(rng.exponential(1.0 / rate_hz, size=n))
    return [
        (float(offsets[i]),
         rng.randint(0, vocab, size=rng.randint(4, 8)).tolist(),
         max_new)
        for i in range(n)
    ]


def percentile(xs, p):
    return float(np.percentile(np.asarray(xs), p)) if len(xs) else float("nan")


def drive_open_loop(engine, trace, slo_ms: float) -> tuple[dict, list]:
    """Submit the trace on the clock; tick the engine whenever it has work.
    Every submit is backdated (``submitted_at``) to the request's SCHEDULED
    arrival, so the engine's registry histograms (``serve_ttft_seconds``,
    ``serve_itl_seconds``) record the scheduled-arrival basis and the row's
    percentiles read straight out of them. Latency math runs on the
    monotonic clock (matching the engine's token timestamps); deadlines
    stay wall-clock. Returns (metrics row, done)."""
    done = []
    i = 0
    calls0 = getattr(engine, "decode_calls", 0)   # exclude warmup ticks
    engine.metrics.reset_histograms()             # warmup observations too
    t0 = time.monotonic()
    wall0 = time.time()
    while i < len(trace) or engine.has_work:
        now = time.monotonic() - t0
        while i < len(trace) and trace[i][0] <= now:
            off, prompt, max_new = trace[i]
            engine.submit(prompt, max_new_tokens=max_new,
                          deadline=wall0 + off + slo_ms / 1e3,
                          submitted_at=t0 + off)
            i += 1
        if engine.has_work:
            done.extend(engine.step())
        elif i < len(trace):
            time.sleep(max(trace[i][0] - (time.monotonic() - t0), 0.0))
    dt = time.monotonic() - t0

    tel = engine.metrics
    ttft = [request_ttft(r) for r in done if r.first_token_at]
    itl = [g for r in done for g in request_itls(r)]

    def pct(hist, xs, p):
        # registry histogram when telemetry is on; raw list otherwise
        if hist.count(tel.engine):
            return hist.percentile(p, tel.engine)
        return percentile(xs, p)

    tokens = sum(len(r.out_tokens) for r in done)
    decode_tokens = decode_emitted_tokens(done)
    return {
        "requests": len(done),
        "tokens": tokens,
        "wall_s": round(dt, 3),
        "admitted_req_per_s": round(len(done) / max(dt, 1e-9), 3),
        "tok_per_s": round(tokens / max(dt, 1e-9), 1),
        "ttft_p50_ms": round(pct(tel.ttft, ttft, 50) * 1e3, 1),
        "ttft_p99_ms": round(pct(tel.ttft, ttft, 99) * 1e3, 1),
        "itl_mean_ms": round(float(np.mean(itl)) * 1e3, 1) if itl else None,
        "itl_p99_ms": round(pct(tel.itl, itl, 99) * 1e3, 1) if itl else None,
        "slo_ms": slo_ms,
        "slo_attainment": round(
            sum(t * 1e3 <= slo_ms for t in ttft) / max(len(ttft), 1), 3
        ),
        "evictions": getattr(engine, "evictions", 0),
        # decode-emitted tokens per jitted decode step, across ALL slots —
        # i.e. mean batch occupancy x per-slot burst length (<= decode_slots
        # without speculation; speculative bursts raise it beyond the slot
        # count). Compare engines at equal decode_slots (also recorded).
        # acceptance_rate is null when not drafting.
        "tokens_per_step": round(
            decode_tokens / max(getattr(engine, "decode_calls", 0) - calls0, 1), 2
        ),
        "acceptance_rate": (
            round(engine.acceptance_rate, 3)
            if hasattr(engine, "acceptance_rate") else None
        ),
    }, done


def warmup(engine, vocab: int, max_new: int):
    """Absorb prefill-bucket + decode compilation outside the measured window."""
    engine.submit([1, 2, 3, 4, 5], max_new_tokens=max_new)
    engine.submit([6, 7], max_new_tokens=max_new)
    engine.run()


def run(
    requests: int = 32,
    rate_hz: float = 400.0,
    max_new: int = 16,
    max_len: int = 64,
    block_size: int = 8,
    paged_slots: int = 10,
    num_blocks: int = 32,
    slo_ms: float = 2000.0,
    kv_dtype: str = "float32",
    seed: int = 0,
) -> dict:
    """The paged engine: ``paged_slots`` decode slots sharing a pool of
    ``num_blocks`` pages, over one Poisson arrival trace."""
    cfg = get_arch("salaad_llama_60m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
    bank = ModelBank.single(cfg, params)
    trace = build_trace(requests, rate_hz, cfg.vocab_size, max_new, seed)
    eng = PagedServingEngine(
        bank,
        EngineConfig(
            max_slots=paged_slots, max_len=max_len,
            block_size=block_size, num_blocks=num_blocks,
            kv_dtype=kv_dtype,
        ),
    )
    warmup(eng, cfg.vocab_size, max_new)
    row, _ = drive_open_loop(eng, trace, slo_ms)
    row["engine"] = "paged"
    row["kv_budget_tokens"] = num_blocks * block_size
    row["decode_slots"] = eng.ecfg.max_slots
    row["engine_config"] = engine_provenance(eng)
    return {"paged": row}


# ------------------------------------------------------ mixed (chunked) ---


def build_mixed_trace(n: int, rate_hz: float, vocab: int, max_new: int,
                      long_len: int, seed: int, long_every: int = 5):
    """Poisson arrivals where every ``long_every``-th request carries a
    ``long_len``-token prompt and the rest stay short (4-7 tokens) — the
    head-of-line-blocking workload: long prefills land while short requests
    are mid-decode. The long prompts share a common 2/3-length prefix (a
    system prompt) + unique tails — invisible to engines without a prefix
    cache, the whole point of the ``chunked_prefix`` arm."""
    rng = np.random.RandomState(seed)
    offsets = np.cumsum(rng.exponential(1.0 / rate_hz, size=n))
    shared = rng.randint(0, vocab, size=2 * long_len // 3).tolist()
    trace = []
    for i in range(n):
        if i % long_every == long_every - 1:
            prompt = shared + rng.randint(
                0, vocab, size=long_len - len(shared)
            ).tolist()
        else:
            prompt = rng.randint(0, vocab, size=int(rng.randint(4, 8))).tolist()
        trace.append((float(offsets[i]), prompt, max_new))
    return trace


def _class_metrics(done, long_len: int) -> dict:
    """Short-request tail metrics: the requests a long prefill stalls.
    Per-class TTFT uses ``request_ttft`` — the same scheduled-arrival basis
    (backdated ``submitted_at``) as the headline columns and the registry
    histogram, so the arms never mix timestamp bases again."""
    short = [r for r in done if len(r.prompt) < long_len]
    long_ = [r for r in done if len(r.prompt) >= long_len]
    itl = [g for r in short for g in request_itls(r)]
    return {
        "short_requests": len(short),
        "long_requests": len(long_),
        "short_itl_p50_ms": round(percentile(itl, 50) * 1e3, 1),
        "short_itl_p99_ms": round(percentile(itl, 99) * 1e3, 1),
        "short_itl_max_ms": round(max(itl) * 1e3, 1) if itl else None,
        "short_ttft_p99_ms": round(
            percentile([request_ttft(r) for r in short], 99) * 1e3, 1
        ),
        "long_ttft_p99_ms": round(
            percentile([request_ttft(r) for r in long_], 99) * 1e3, 1
        ),
    }


def run_mixed(
    requests: int = 30,
    rate_hz: float = 120.0,
    max_new: int = 24,
    max_len: int = 256,
    block_size: int = 16,
    slots: int = 8,
    num_blocks: int = 96,
    prefill_chunk: int = 32,
    long_len: int = 192,
    slo_ms: float = 2000.0,
    kv_dtype: str = "float32",
    seed: int = 0,
) -> dict:
    """One-shot vs chunked prefill on the SAME paged engine config (equal KV
    budget, equal trace): the only difference is whether a long prompt
    prefills in one monolithic tick or in ``prefill_chunk``-token slices
    interleaved with the other slots' decode steps."""
    cfg = get_arch("salaad_llama_60m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
    bank = ModelBank.single(cfg, params)
    trace = build_mixed_trace(
        requests, rate_hz, cfg.vocab_size, max_new, long_len, seed
    )
    rows = {}
    arms = (("oneshot", None, False), ("chunked", prefill_chunk, False),
            ("chunked_prefix", prefill_chunk, True))
    for name, chunk, pc in arms:
        eng = PagedServingEngine(
            bank,
            EngineConfig(
                max_slots=slots, max_len=max_len, block_size=block_size,
                num_blocks=num_blocks, prefill_chunk=chunk,
                kv_dtype=kv_dtype, prefix_cache=pc,
            ),
        )
        # absorb compilation of the short bucket, the long path (one-shot
        # bucket or chunk program), and decode outside the measured window;
        # the repeated long submit warms the prefix-cache hit-admission path
        # (a no-op for the cache-off arms)
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        eng.run()
        for _ in range(2):
            eng.submit(list(range(1, long_len + 1)), max_new_tokens=4)
            eng.run()
        row, done = drive_open_loop(eng, trace, slo_ms)
        row.update(_class_metrics(done, long_len))
        row["engine_config"] = engine_provenance(eng)
        if pc:
            row["prefix_hits"] = eng.prefix_hits
        rows[name] = row
    one, chk = rows["oneshot"], rows["chunked"]
    rows["summary"] = {
        "kv_budget_tokens": num_blocks * block_size,
        "long_len": long_len,
        "prefill_chunk": prefill_chunk,
        # the headline: tail ITL of SHORT requests decoding while a long
        # prompt prefills — chunking should cut it
        "short_itl_p99_speedup": round(
            one["short_itl_p99_ms"] / max(chk["short_itl_p99_ms"], 1e-9), 2
        ),
        "short_itl_max_speedup": round(
            (one["short_itl_max_ms"] or 0.0)
            / max(chk["short_itl_max_ms"] or 1e-9, 1e-9), 2
        ),
        "short_ttft_p99_speedup": round(
            one["short_ttft_p99_ms"] / max(chk["short_ttft_p99_ms"], 1e-9), 2
        ),
        # the price: the long prompt itself streams in over several ticks
        "long_ttft_p99_ratio": round(
            chk["long_ttft_p99_ms"] / max(one["long_ttft_p99_ms"], 1e-9), 2
        ),
        # the prefix-cache arm: same chunked config, radix cache on — long
        # prompts that hit the shared-prefix pages skip most of their prefill
        "prefix_long_ttft_p99_speedup": round(
            chk["long_ttft_p99_ms"]
            / max(rows["chunked_prefix"]["long_ttft_p99_ms"], 1e-9), 2
        ),
        "prefix_hits": rows["chunked_prefix"]["prefix_hits"],
    }
    return rows


def _merge_out(out: str, key: str, rows: dict):
    """Merge one section into BENCH_latency.json, preserving other rows (the
    default and --mixed runs write different sections of the same file)."""
    path = Path(out)
    payload = {}
    if path.exists():
        try:
            payload = json.loads(path.read_text())
        except ValueError:
            payload = {}
    payload[key] = rows
    path.write_text(json.dumps(payload, indent=2))


def main(out: str = "BENCH_latency.json", mixed: bool = False, **kw):
    if mixed:
        rows = run_mixed(**kw)
        _merge_out(out, "mixed_prefill", rows)
        s = rows["summary"]
        emit(
            "serve_latency_mixed", 0.0,
            f"short-req p99 ITL oneshot={rows['oneshot']['short_itl_p99_ms']}"
            f"ms chunked={rows['chunked']['short_itl_p99_ms']}ms "
            f"(x{s['short_itl_p99_speedup']}) at chunk={s['prefill_chunk']}",
        )
        return rows
    rows = run(**kw)
    _merge_out(out, "engines", rows)
    pg = rows["paged"]
    emit(
        "serve_latency", 0.0,
        f"p99_ttft paged={pg['ttft_p99_ms']}ms; slo {pg['slo_attainment']}",
    )
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed long/short-prompt workload: one-shot vs "
                         "chunked prefill on the paged engine")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate-hz", type=float, default=None)
    ap.add_argument("--slo-ms", type=float, default=2000.0)
    ap.add_argument("--kv-dtype", default="float32")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--long-len", type=int, default=192)
    ap.add_argument("--out", default="BENCH_latency.json")
    a = ap.parse_args()
    if a.mixed:
        n = a.requests or (20 if a.quick else 30)
        main(out=a.out, mixed=True, requests=n,
             rate_hz=a.rate_hz or 120.0, slo_ms=a.slo_ms,
             kv_dtype=a.kv_dtype, prefill_chunk=a.prefill_chunk,
             long_len=a.long_len)
    else:
        n = a.requests or (24 if a.quick else 32)
        main(out=a.out, requests=n, rate_hz=a.rate_hz or 400.0,
             slo_ms=a.slo_ms, kv_dtype=a.kv_dtype)

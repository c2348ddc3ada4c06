"""Serving launcher: load a checkpoint (or fresh init), materialize the HPA
budget spectrum as ONE ModelBank, and serve batched requests through a single
engine — elastic deployment as a serving-time dimension, not a rebuild loop.

``--keep-ratios`` names the bank's budget tiers (tier 0 = largest). Requests
spread round-robin across the tiers (pin them all with ``--tier``); the paged
engine runs one pre-compiled jitted step per active tier over the shared
paged KV, and ``--tier-policy pressure`` turns on the controller that
downshifts the serving tier under page pressure before resorting to
eviction. All engines implement the ``serving.elastic.Engine`` protocol
(submit / step / run / has_work / capabilities); the per-engine capability
table is printed in ``--help``.

``--adapters N`` switches to multi-tenant serving: N SLR adapters (HPA views
at spread budgets) are registered over ONE shared base and served through a
single ``serving.adapters.AdapterBank`` engine, requests round-robin across
tenants; ``--max-resident-adapters`` bounds the device adapter pool and turns
on LRU swapping (docs/serving.md, "Multi-tenant adapters").

Observability (docs/observability.md): ``--trace-out trace.json`` records a
per-request span trace and writes Chrome trace-event JSON (open in Perfetto);
``--metrics-port N`` serves the Prometheus text exposition of the engine's
metrics registry on ``127.0.0.1:N/metrics`` for the duration of the run
(0 = ephemeral); ``--metrics-out`` persists one scrape to a file. The
printed stats derive from ``engine.stats_snapshot()`` — the same registry
the exporter serves.

  python -m repro.launch.serve --arch salaad_llama_60m --reduced \
      --keep-ratios 1.0,0.6,0.3 --fmt factored --kappa 0.7 --requests 8 \
      --block-size 16 --slo-ms 2000 --tier-policy pressure \
      --trace-out trace.json --metrics-port 0 --metrics-out metrics.txt
"""
from __future__ import annotations

import argparse
import json
import time
import urllib.request

import jax
import numpy as np

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig, init_slr_state
from repro.core.hpa import hpa_keep_ratio
from repro.core.selection import SelectionConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as model_lib
from repro.serving.adapters import AdapterBank, adapterize
from repro.serving.deployed import DeployedModel
from repro.serving.elastic import ModelBank, format_capability_table
from repro.serving.engine import (
    BATCHED_FAMILIES,
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
    decode_emitted_tokens,
)
from repro.serving.slr_params import deployment_report
from repro.serving.speculative import SpeculativeEngine
from repro.serving.telemetry import start_metrics_server

ENGINES = {
    "paged": PagedServingEngine,
    "reference": ReferenceEngine,
    "speculative": SpeculativeEngine,
}


def serve_batch(engine, vocab: int, requests: int, max_new: int, seed: int,
                slo_ms: float | None = None, tiers=(None,),
                adapters=(None,)) -> dict:
    """Drive one engine (Engine protocol) over a random trace, requests
    spread round-robin over ``tiers`` (and, for an AdapterBank engine, over
    ``adapters``); per-tier / per-adapter token counts ride in the stats so
    the elastic spectrum and the tenant mix stay visible in one output."""
    rng = np.random.RandomState(seed)
    # with the prompt cache on, give the trace something to share: every
    # request opens with the same two-page "system prompt"
    shared: list[int] = []
    if getattr(engine, "_prefix", None) is not None:
        shared = rng.randint(0, vocab, size=2 * engine.ecfg.block_size).tolist()
    submitted = time.time()          # deadlines are a wall-clock contract
    for i in range(requests):
        prompt = shared + rng.randint(0, vocab, size=rng.randint(2, 8)).tolist()
        engine.submit(
            prompt, max_new_tokens=max_new,
            deadline=None if slo_ms is None else submitted + slo_ms / 1e3,
            tier=tiers[i % len(tiers)],
            adapter=adapters[i % len(adapters)],
        )
    # engine timestamps (first_token_at etc.) are time.monotonic() values, so
    # latency math must use the same clock — an NTP step mid-run would
    # otherwise produce negative TTFT
    t0 = time.monotonic()
    done = engine.run()
    dt = time.monotonic() - t0
    total_tokens = sum(len(r.out_tokens) for r in done)
    snap = engine.stats_snapshot()
    stats = {
        "requests": len(done),
        "tokens": total_tokens,
        "tok_per_s": round(total_tokens / max(dt, 1e-9), 2),
        "sample": done[0].out_tokens if done else [],
        "steps": snap["steps"],
        "jit_retraces": snap["jit_retraces"],
    }
    by_tier: dict[int, int] = {}
    for r in done:
        by_tier[r.tier] = by_tier.get(r.tier, 0) + len(r.out_tokens)
    if len(by_tier) > 1 or (by_tier and next(iter(by_tier)) != 0):
        stats["tokens_by_tier"] = {str(k): v for k, v in sorted(by_tier.items())}
    by_adapter: dict[int, int] = {}
    for r in done:
        if r.adapter is not None:
            by_adapter[r.adapter] = by_adapter.get(r.adapter, 0) \
                + len(r.out_tokens)
    if by_adapter:
        stats["tokens_by_adapter"] = {
            str(k): v for k, v in sorted(by_adapter.items())
        }
    # TTFT on the submitted_at basis (every request here is submitted before
    # run() starts, so this matches the old run-start basis); percentiles
    # come from the registry histogram when telemetry is on
    ttft = [r.first_token_at - r.submitted_at for r in done if r.first_token_at]
    tel = engine.metrics
    if tel.ttft.count(tel.engine):
        stats["ttft_p50_ms"] = round(tel.ttft.percentile(50, tel.engine) * 1e3, 1)
        stats["ttft_p99_ms"] = round(tel.ttft.percentile(99, tel.engine) * 1e3, 1)
    elif ttft:
        stats["ttft_p50_ms"] = round(float(np.percentile(ttft, 50)) * 1e3, 1)
        stats["ttft_p99_ms"] = round(float(np.percentile(ttft, 99)) * 1e3, 1)
    if slo_ms is not None and ttft:
        stats["slo_ms"] = slo_ms
        stats["slo_attainment"] = round(
            sum(t * 1e3 <= slo_ms for t in ttft) / len(ttft), 3
        )
    if hasattr(engine, "evictions"):
        stats["evictions"] = engine.evictions
    if getattr(engine, "_prefix", None) is not None:
        stats["prefix_cache"] = {
            "lookups": engine.prefix_lookups,
            "hits": engine.prefix_hits,
            "hit_tokens": engine.prefix_hit_tokens,
            "cow_copies": engine.cow_copies,
            "reattached_pages": engine.reattached_pages,
            "cached_pages": engine._prefix.pages,
        }
    if getattr(engine, "tier_controller", None) is not None:
        stats["downshift_ticks"] = engine.downshift_ticks
        stats["tier_switches"] = engine.tier_switches
    if hasattr(engine, "acceptance_rate"):
        stats["acceptance_rate"] = round(engine.acceptance_rate, 3)
        stats["tokens_per_step"] = round(
            decode_emitted_tokens(done) / max(engine.decode_calls, 1), 2
        )
    return stats


def serve_with_observability(engine, args, vocab: int, tiers=(None,),
                             adapters=(None,)) -> dict:
    """Run ``serve_batch`` with the requested exports attached: a request
    tracer when ``--trace-out``/``--trace-events`` is set, and a live
    Prometheus endpoint when ``--metrics-port`` is set (``--metrics-out``
    scrapes it over HTTP so CI validates the real exposition path)."""
    tracer = None
    if args.trace_out or args.trace_events:
        tracer = engine.start_trace()
    server = None
    if args.metrics_port is not None:
        server = start_metrics_server(engine.metrics.registry,
                                      port=args.metrics_port)
    stats = serve_batch(engine, vocab, args.requests, args.max_new,
                        args.seed, args.slo_ms, tiers=tiers,
                        adapters=adapters)
    if server is not None:
        port = server.server_address[1]
        stats["metrics_port"] = port
        if args.metrics_out:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics"
            ) as resp:
                text = resp.read().decode()
            with open(args.metrics_out, "w") as f:
                f.write(text)
            stats["metrics_out"] = args.metrics_out
        server.shutdown()
    elif args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(engine.metrics.registry.prometheus_text())
        stats["metrics_out"] = args.metrics_out
    if tracer is not None:
        if args.trace_out:
            tracer.save_chrome(args.trace_out)
            stats["trace_out"] = args.trace_out
        if args.trace_events:
            tracer.save_jsonl(args.trace_events)
            stats["trace_events"] = args.trace_events
    return stats


def main():
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="engine capabilities (serving.elastic.Engine protocol):\n\n"
        + format_capability_table(ENGINES),
    )
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument(
        "--keep-ratios", default=None,
        help="comma-separated HPA budgets materialized as ONE ModelBank's "
             "tiers, largest first, e.g. 1.0,0.6,0.3 (omit: serve dense init)",
    )
    ap.add_argument(
        "--fmt", "--format", dest="fmt", default="factored",
        choices=("dense", "factored", "bsr", "fused"),
        help="deployment format (docs/serving.md#deployment-formats): 'fused' "
             "runs one Pallas pass per linear site with layer-stacked tables",
    )
    ap.add_argument("--engine", default="paged", choices=tuple(ENGINES))
    ap.add_argument("--kappa", type=float, default=0.7)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV page size in tokens (paged engine)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV page pool size; None = max_slots * max_len worth")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: process prompts in block-aligned "
                         "chunks of this many tokens interleaved with decode "
                         "ticks (paged engine; None = one-shot prefill)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prompt cache: share KV pages across requests "
                         "with a common prompt prefix; copy-on-write on first "
                         "divergent write (paged engine)")
    ap.add_argument("--prefix-min-hit", type=int, default=1,
                    help="minimum matched pages before a prefix-cache hit is "
                         "attached (smaller hits prefill from scratch)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="TTFT SLO; reports attainment and sets request deadlines")
    ap.add_argument("--kv-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8"),
                    help="KV storage dtype; int8 stores quantized pages (paged)")
    ap.add_argument("--tier", type=int, default=None,
                    help="pin every request to this bank tier (default: "
                         "round-robin across all tiers)")
    ap.add_argument("--tier-policy", default="static",
                    choices=("static", "pressure"),
                    help="pressure: downshift the serving tier under page "
                         "pressure before evicting (paged engine)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft window (tokens/slot/tick); > 0 "
                         "serves through the SpeculativeEngine")
    ap.add_argument("--spec-budget", type=float, default=0.4,
                    help="HPA keep-ratio of the self-speculation draft tier "
                         "(appended to the bank as its cheapest tier)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="adapt the draft window from observed acceptance")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON of the run here "
                         "(one track per slot + one per jitted program; "
                         "open in Perfetto — see docs/observability.md)")
    ap.add_argument("--trace-events", default=None,
                    help="write the structured JSONL event log here "
                         "(same events as --trace-out, one dict per line)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the Prometheus text exposition on "
                         "127.0.0.1:PORT/metrics during the run (0 = "
                         "ephemeral; the bound port rides in the stats)")
    ap.add_argument("--metrics-out", default=None,
                    help="persist one Prometheus scrape to this file after "
                         "the run (over HTTP when --metrics-port is set)")
    ap.add_argument("--mesh", default=None,
                    help="tensor-parallel serving mesh spec, e.g. "
                         "'model=2,data=1': weights and KV pools shard the "
                         "head/ffn dims over 'model' (must divide the head "
                         "counts); on CPU force devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--adapters", type=int, default=0,
                    help="multi-tenant mode: register N SLR adapters (HPA "
                         "views at spread budgets) over ONE shared base and "
                         "serve them through a single AdapterBank engine, "
                         "requests round-robin across tenants "
                         "(docs/serving.md#multi-tenant-adapters)")
    ap.add_argument("--max-resident-adapters", type=int, default=None,
                    help="device adapter-pool rows; fewer than --adapters "
                         "turns on LRU swapping (None = all resident)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    key = jax.random.PRNGKey(args.seed)
    params = model_lib.init_params(cfg, key)

    scfg = SalaadConfig(selection=SelectionConfig(min_dim=16))
    if args.ckpt_dir:
        from repro.train import checkpoint
        from repro.train.state import init_train_state

        state, blocks = init_train_state(params, scfg)
        state = checkpoint.restore(args.ckpt_dir, state)
        slr, params = state.slr, state.params
    else:
        slr, blocks = init_slr_state(params, scfg)

    engine_cls = ENGINES[args.engine]
    spec_k = args.spec_k
    if engine_cls is SpeculativeEngine and spec_k == 0:
        spec_k = 4
    if spec_k > 0 and engine_cls is PagedServingEngine:
        engine_cls = SpeculativeEngine            # --spec-k implies speculation
    if engine_cls is not ReferenceEngine and cfg.family not in BATCHED_FAMILIES:
        # explicit capability line (the structured dict a constructor-time
        # EngineCapabilityError would carry); paged-only features requested
        # on this family then fail loudly in the ReferenceEngine constructor
        # instead of silently degrading
        print(json.dumps({
            "note": f"family {cfg.family!r} has no per-slot-length cache "
                    "yet; falling back to the reference engine",
            "capabilities": ReferenceEngine.capabilities(),
        }))
        engine_cls = ReferenceEngine
    ecfg = EngineConfig(
        max_slots=args.max_slots, max_len=args.max_len,
        block_size=args.block_size, num_blocks=args.num_blocks,
        kv_dtype=args.kv_dtype, prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache,
        prefix_min_hit_pages=args.prefix_min_hit,
        tier_policy=args.tier_policy,
        spec_k=spec_k, spec_adaptive=args.spec_adaptive,
        mesh=args.mesh,
        adapters=args.adapters > 0,
        max_resident_adapters=args.max_resident_adapters,
    )

    if args.adapters:
        # N tenants as HPA views at spread budgets over ONE shared base —
        # each adapterized onto the base so only the SLR sites differ per
        # tenant and the rest of the tree is stored once
        spread = np.linspace(1.0, 0.4, args.adapters)
        slr_c, _ = hpa_keep_ratio(slr, blocks, 1.0, args.kappa)
        base = DeployedModel.build(cfg, params, slr_c, blocks, fmt=args.fmt)
        tenants = []
        for keep in spread:
            slr_k, _ = hpa_keep_ratio(slr, blocks, float(keep), args.kappa)
            tenants.append(adapterize(
                base, DeployedModel.build(cfg, params, slr_k, blocks,
                                          fmt=args.fmt)))
        bank = AdapterBank(base, tenants,
                           names=[f"tenant{i}" for i in range(args.adapters)])
        engine = engine_cls(bank, ecfg)
        stats = serve_with_observability(
            engine, args, cfg.vocab_size,
            adapters=tuple(range(args.adapters)))
        print(json.dumps({
            "fmt": args.fmt,
            "adapters": bank.adapter_report(),
            **stats,
        }))
        return

    if args.keep_ratios is None:
        bank = ModelBank.single(cfg, params)
        engine = engine_cls(bank, ecfg)
        print(json.dumps({
            "budget": None, "fmt": "dense-init",
            **serve_with_observability(engine, args, cfg.vocab_size),
        }))
        return

    # one SALAAD state, ONE bank, a spectrum of served capacities — every
    # budget is a tier of the same engine (the speculative engine serves its
    # largest budget and drafts with --spec-budget)
    keeps = sorted({float(k) for k in args.keep_ratios.split(",")},
                   reverse=True)
    if engine_cls is SpeculativeEngine:
        target_keep = keeps[0]
        draft_keep = min(args.spec_budget, target_keep)
        dropped = [k for k in keeps[1:] if k != draft_keep]
        if dropped:
            print(json.dumps({
                "note": "speculative mode serves ONE target tier: "
                        f"keep={target_keep} verifies, keep={draft_keep} "
                        f"(--spec-budget) drafts; --keep-ratios {dropped} "
                        "not materialized",
            }))
        keeps = [target_keep] + ([draft_keep] if draft_keep < target_keep
                                 else [])
        ecfg.spec_draft_tier = -1                 # the cheapest tier drafts

    # ONE HPA truncation + deployment per budget: the bank serves these
    # views, and the SAME pass yields the per-tier accounting (no second
    # truncation just for the report)
    models, tier_rows = [], []
    for keep in keeps:
        slr_c, rep = hpa_keep_ratio(slr, blocks, keep, args.kappa)
        models.append(
            DeployedModel.build(cfg, params, slr_c, blocks, fmt=args.fmt)
        )
        dep = deployment_report(params, slr_c, blocks)
        tier_rows.append({
            "slr_params": rep["params_after"],
            "slr_total_bytes": dep["slr_total_bytes"],
            "compression": round(dep["compression"], 3),
        })
    bank = ModelBank(cfg, models, keeps=keeps)
    for tier, row in zip(bank, tier_rows):
        row.update(tier=tier.index, name=tier.name,
                   served_bytes=tier.param_bytes)

    engine = engine_cls(bank, ecfg)
    tiers: tuple = (args.tier,)
    if args.tier is None:
        # round-robin across the budgets (SpeculativeEngine pins every slot
        # to its target tier; its draft tier only drafts)
        tiers = (None,) if engine_cls is SpeculativeEngine \
            else tuple(range(len(bank)))
    stats = serve_with_observability(engine, args, cfg.vocab_size,
                                     tiers=tiers)
    print(json.dumps({
        "fmt": args.fmt,
        "bank": bank.report(widths=(ecfg.max_slots,)),
        "tier_policy": args.tier_policy,
        **stats,
        "tiers": tier_rows,
    }))


if __name__ == "__main__":
    main()

"""Serving-telemetry tests: the observability PR's invariants.

The load-bearing claim is ZERO COST ON THE DEVICE PATH: greedy token
streams must be bitwise identical with telemetry (and tracing) on vs off,
across every engine configuration — paged, chunked prefill, speculative,
elastic pressure tiers, prefix cache — and with a profiler recording the
engine's ``serve.*`` spans or not. The rest covers the registry
primitives (counters / gauges / histograms and their pre-bound fast
paths), the ``serve.*`` span tree and its ``mono_ns`` anchor in a
recorded profile, the slowest-step record, the Chrome-trace export schema,
the Prometheus text exposition + HTTP endpoint, exactly-once token
accounting through eviction/resume (satellite: the accounting audit),
centralized provenance key identity across engines, and the retrace
detector.

Each (config, telemetry on/off) engine is built and driven EXACTLY ONCE
through the module-scoped ``driven`` fixture and every test reads from
that one run — engines jit their own programs, and pointless re-compiles
are what pushes a long single-core suite run over the edge.
"""
import json
import sys
import urllib.request
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_arch
from repro.models import model as model_lib
from repro.serving.elastic import ModelBank
from repro.serving.engine import (
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
)
from repro.serving.speculative import SpeculativeEngine
from repro.serving.telemetry import (
    EngineTelemetry,
    MetricsRegistry,
    NullTelemetry,
    engine_provenance,
    request_itls,
    request_ttft,
    start_metrics_server,
    validate_prometheus_text,
)
from repro.serving.trace import validate_chrome_trace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.lib import spans as bench_spans  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("salaad_llama_60m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(vocab: int, n: int = 6):
    """Shared prefix + unique tails: exercises the radix cache when on."""
    shared = [(7 * i + 3) % (vocab - 2) + 1 for i in range(12)]
    return [shared + [(i * 5 + j) % (vocab - 2) + 1 for j in range(2 + i % 3)]
            for i in range(n)]


# engine-builder per configuration: (engine class, bank tiers, ecfg kwargs)
_BASE = dict(max_slots=2, max_len=48, block_size=8, num_blocks=24)
CONFIGS = {
    "paged": (PagedServingEngine, 1, dict(_BASE)),
    "chunked_prefill": (PagedServingEngine, 1,
                        dict(_BASE, prefill_chunk=8)),
    "speculative": (SpeculativeEngine, 1, dict(_BASE, spec_k=2)),
    "elastic_pressure": (PagedServingEngine, 2,
                         dict(_BASE, num_blocks=16, tier_policy="pressure")),
    "prefix_cache": (PagedServingEngine, 1,
                     dict(_BASE, prefill_chunk=8, prefix_cache=True)),
    # starved page pool + long generations: forces eviction + resume for
    # the exactly-once accounting audit (same jitted shapes as "paged")
    "paged_tight": (PagedServingEngine, 1,
                    dict(_BASE, num_blocks=6)),
    # requests alternate between two tiers: one decode program per tier a
    # tick, as a multi-tier deployment runs
    "two_tiers": (PagedServingEngine, 2, dict(_BASE)),
}
_MAX_NEW = {"paged_tight": 16}


def _submit_all(eng, name: str, vocab: int) -> int:
    """Submit the shared prompts; "two_tiers" alternates their tiers."""
    prompts = _prompts(vocab)
    for i, p in enumerate(prompts):
        eng.submit(list(p), max_new_tokens=_MAX_NEW.get(name, 6),
                   tier=i % 2 if name == "two_tiers" else None)
    return len(prompts)


def _build(tiny, name: str, telemetry: bool):
    cfg, params = tiny
    cls, tiers, kw = CONFIGS[name]
    keeps = [1.0, 0.5][:tiers] if tiers > 1 else None
    bank = ModelBank(cfg, [params] * tiers, keeps=keeps)
    return cls(bank, EngineConfig(telemetry=telemetry, **kw))


@pytest.fixture(scope="module")
def driven(tiny):
    """Memoized (config, telemetry) -> (engine, streams, done): every engine
    is constructed, traced (when instrumented), and driven over the shared
    prompt trace ONCE; tests read the run instead of re-jitting engines."""
    cfg, _ = tiny
    cache = {}

    def get(name: str, telemetry: bool):
        key = (name, telemetry)
        if key not in cache:
            eng = _build(tiny, name, telemetry)
            if telemetry:
                eng.start_trace()
            n = _submit_all(eng, name, cfg.vocab_size)
            done = eng.run()
            assert len(done) == n
            streams = [r.out_tokens
                       for r in sorted(done, key=lambda r: r.uid)]
            cache[key] = (eng, streams, done)
        return cache[key]

    return get


# ------------------------------------------------- bitwise on/off identity ---


class TestBitwiseInvariance:
    """Telemetry and tracing are host-side observers: turning them on must
    not change a single emitted token, in any engine configuration."""

    @pytest.mark.parametrize("name", sorted(CONFIGS))
    def test_streams_identical_on_off(self, driven, name):
        eng_off, s_off, _ = driven(name, False)
        eng_on, s_on, _ = driven(name, True)
        assert s_off == s_on
        assert isinstance(eng_off.metrics, NullTelemetry)
        assert not isinstance(eng_on.metrics, NullTelemetry)
        # the instrumented run actually recorded something
        tel = eng_on.metrics
        assert tel.counter_value(tel.tokens, "emitted") == \
            sum(len(s) for s in s_on)

    def test_null_telemetry_records_nothing(self, driven):
        eng, streams, _ = driven("paged", False)
        tel = eng.metrics
        assert tel.enabled is False
        assert sum(len(s) for s in streams) > 0
        assert tel.counter_value(tel.tokens, "emitted") == 0
        assert tel.ttft.count(tel.engine) == 0
        tel.snapshot()                    # still callable, reads empty


# ------------------------------------------------------- registry internals ---


class TestRegistryPrimitives:
    def test_counter_monotone_and_incrementer(self):
        r = MetricsRegistry()
        c = r.counter("t_total", "x", ("engine",))
        c.inc(2, "E")
        inc = c.incrementer("E")
        inc()
        inc(3)
        assert c.value("E") == 6
        with pytest.raises(ValueError):
            c.inc(-1, "E")

    def test_histogram_exact_percentiles(self):
        r = MetricsRegistry()
        h = r.histogram("h_seconds", "x", ("engine",))
        for v in (0.010, 0.020, 0.030, 0.040):
            h.observe(v, "E")
        assert h.count("E") == 4
        assert h.sum_("E") == pytest.approx(0.100)
        assert h.percentile(0, "E") == pytest.approx(0.010)
        assert h.percentile(100, "E") == pytest.approx(0.040)

    def test_histogram_reset_keeps_bound_observers_live(self):
        """reset() zeroes IN PLACE so engines' pre-bound observer closures
        (the per-token fast path) survive a benchmark's warmup reset."""
        r = MetricsRegistry()
        h = r.histogram("h_seconds", "x", ("engine",))
        obs = h.observer("E")
        obs(0.5)
        assert h.count("E") == 1
        h.reset()
        assert h.count("E") == 0
        obs(0.25)                          # the old handle must still land
        assert h.count("E") == 1
        assert h.percentile(50, "E") == pytest.approx(0.25)

    def test_gauge_setter(self):
        r = MetricsRegistry()
        g = r.gauge("g", "x", ("engine",))
        set_ = g.setter("E")
        set_(3)
        assert g.value("E") == 3.0

    def test_duplicate_declaration_rejected(self):
        r = MetricsRegistry()
        r.counter("c_total", "x", ("engine",))
        with pytest.raises(ValueError):
            r.gauge("c_total", "x", ("engine",))


# ---------------------------------------------------------- retrace detector ---


class TestRetraceDetector:
    def test_first_compile_then_steady_then_retrace(self):
        tel = EngineTelemetry("T")
        n = {"traces": 0}

        def bump():
            with tel.measure_program("p", 0, traces=lambda: n["traces"]):
                n["traces"] += 1

        def steady():
            with tel.measure_program("p", 0, traces=lambda: n["traces"]):
                pass

        bump()                             # first use: compile, NOT a retrace
        assert tel.counter_value(tel.jit_compiles, "p", "0") == 1
        assert tel.retraces() == 0
        steady()                           # warm call
        assert tel.retraces() == 0
        bump()                             # seen program compiles again
        assert tel.counter_value(tel.jit_retraces, "p", "0") == 1
        assert tel.retraces() == 1

    def test_tiers_tracked_independently(self):
        tel = EngineTelemetry("T")
        n = {"traces": 0}
        for tier in (0, 1):
            with tel.measure_program("p", tier, traces=lambda: n["traces"]):
                n["traces"] += 1
        assert tel.retraces() == 0         # tier 1's first compile is not a
        #                                    retrace of tier 0's program

    def test_engine_steady_state_has_no_retraces(self, tiny, driven):
        cfg, _ = tiny
        eng, _, _ = driven("chunked_prefill", True)
        # a SECOND full drive on the warm engine: every program re-runs,
        # nothing may recompile
        for p in _prompts(cfg.vocab_size):
            eng.submit(list(p), max_new_tokens=6)
        eng.run()
        snap = eng.stats_snapshot()
        assert snap["jit_retraces"] == 0
        assert snap["steps"] > 0


# ------------------------------------------------- exactly-once accounting ---


class TestTokenAccounting:
    """Satellite: each emitted token is counted exactly once — eviction,
    resume re-prefill, and prefix-cache hits must not double-count."""

    def _audit(self, eng, done):
        tel = eng.metrics
        emitted = sum(len(r.out_tokens) for r in done)
        assert tel.counter_value(tel.tokens, "emitted") == emitted
        for r in done:
            assert len(r.token_times) == len(r.out_tokens)
        # TTFT + ITLs partition the token timeline per request
        assert tel.ttft.count(tel.engine) == len(done)
        assert tel.itl.count(tel.engine) == emitted - len(done)

    def test_eviction_resume_counts_once(self, driven):
        # paged_tight starves the pool: admission pressure forces eviction
        eng, _, done = driven("paged_tight", True)
        self._audit(eng, done)
        tel = eng.metrics
        assert eng.evictions > 0
        # resumed work re-prefills, but re-prefill is compute accounting —
        # the emitted count stays exactly-once
        assert tel.counter_value(tel.tokens, "reprefill") > 0
        assert tel.counter_value(tel.tokens, "prefill_compute") >= \
            tel.counter_value(tel.tokens, "reprefill")

    def test_prefix_hits_split_from_compute(self, tiny, driven):
        cfg, _ = tiny
        eng, _, done = driven("prefix_cache", True)
        self._audit(eng, done)
        tel = eng.metrics
        assert eng.prefix_hits > 0
        hit = tel.counter_value(tel.tokens, "prefix_hit")
        compute = tel.counter_value(tel.tokens, "prefill_compute")
        total_prompt = sum(len(p) for p in _prompts(cfg.vocab_size))
        assert hit > 0
        # every prompt token is either prefix-hit or prefilled, never both
        assert hit + compute == total_prompt

    def test_latency_helpers_are_canonical(self, driven):
        _, _, done = driven("paged", True)
        for r in done:
            assert request_ttft(r) >= 0
            gaps = request_itls(r)
            assert len(gaps) == len(r.out_tokens) - 1
            assert all(g >= 0 for g in gaps)


# ----------------------------------------------------------- chrome traces ---


class TestChromeTrace:
    def test_roundtrip_schema(self, driven, tmp_path):
        eng, _, _ = driven("chunked_prefill", True)
        path = tmp_path / "trace.json"
        eng.tracer.save_chrome(path)
        doc = json.loads(path.read_text())
        rep = validate_chrome_trace(doc)
        assert rep["events"] > 0
        assert rep["tracks"] >= 2          # one track per slot (2 slots)
        # jitted programs are on the profiler's trace (serve.program), not
        # on a track of their own here
        assert {ev["pid"] for ev in doc["traceEvents"]} == {1}
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("X", "i", "M")
            assert "pid" in ev and "tid" in ev and "name" in ev
            if ev["ph"] == "X":
                assert ev["dur"] >= 0 and ev["ts"] >= 0

    def test_jsonl_event_log(self, driven, tmp_path):
        eng, _, _ = driven("paged", True)
        path = tmp_path / "events.jsonl"
        eng.tracer.save_jsonl(path)
        lines = path.read_text().splitlines()
        assert lines
        for ln in lines:
            ev = json.loads(ln)
            assert "name" in ev and "kind" in ev

    def test_validator_rejects_unbalanced(self):
        with pytest.raises(ValueError):
            validate_chrome_trace({"traceEvents": [
                {"ph": "B", "ts": 0, "pid": 1, "tid": 1, "name": "x"},
            ]})


# ------------------------------------------------------------- prometheus ---


class TestPrometheus:
    def test_text_exposition_valid(self, driven):
        eng, _, _ = driven("paged", True)
        text = eng.metrics.registry.prometheus_text()
        rep = validate_prometheus_text(text)
        assert rep["families"] > 10
        assert "serve_tokens_total" in text
        assert "serve_ttft_seconds_bucket" in text

    def test_http_endpoint(self, driven):
        eng, _, _ = driven("paged", True)
        server = start_metrics_server(eng.metrics.registry, port=0)
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics"
            ) as resp:
                assert resp.status == 200
                body = resp.read().decode()
            validate_prometheus_text(body)
            assert "serve_tokens_total" in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
        finally:
            server.shutdown()


# -------------------------------------------------------------- provenance ---


class TestProvenance:
    """Satellite: BENCH payload provenance is generated centrally, so every
    engine's payload carries IDENTICAL keys."""

    def test_keys_identical_across_engines(self, tiny):
        cfg, params = tiny
        bank = ModelBank.single(cfg, params)
        # construction only — provenance never runs the model, so these
        # engines jit nothing
        engines = [
            PagedServingEngine(bank, EngineConfig(max_slots=1, max_len=16,
                                                  block_size=8)),
            ReferenceEngine(bank, EngineConfig(max_slots=1, max_len=16)),
            SpeculativeEngine(bank, EngineConfig(max_slots=1, max_len=16,
                                                 block_size=8, spec_k=2)),
        ]
        provs = [engine_provenance(e) for e in engines]
        keysets = [frozenset(p) for p in provs]
        assert len(set(keysets)) == 1, keysets
        cfg_keys = [frozenset(p["config"]) for p in provs]
        assert len(set(cfg_keys)) == 1
        for p in provs:
            json.dumps(p)                  # serializable by contract

    def test_stats_snapshot_schema(self, driven):
        eng, _, _ = driven("paged", True)
        snap = eng.stats_snapshot()
        for key in ("engine", "steps", "jit_retraces", "slowest_step",
                    "metrics"):
            assert key in snap, key
        assert snap["engine"] == "PagedServingEngine"
        assert "serve_tokens_total" in snap["metrics"]

    def test_stats_snapshot_keys_identical_across_engines(self, tiny):
        cfg, params = tiny
        bank = ModelBank.single(cfg, params)
        engines = [
            PagedServingEngine(bank, EngineConfig(max_slots=1, max_len=16,
                                                  block_size=8)),
            ReferenceEngine(bank, EngineConfig(max_slots=1, max_len=16)),
            SpeculativeEngine(bank, EngineConfig(max_slots=1, max_len=16,
                                                 block_size=8, spec_k=2)),
        ]
        keysets = {frozenset(e.stats_snapshot()) for e in engines}
        assert len(keysets) == 1, keysets
        assert all(e.stats_snapshot()["slowest_step"] is None
                   for e in engines)          # no step has run yet


# ----------------------------------------------------------- serve.* spans ---


def _serve_spans(profile_dir) -> list[tuple[str, float, float, dict]]:
    """(name, start_ns, end_ns, stats) of the ``serve.*`` host events of the
    newest profile under ``profile_dir``, by start, as the benchmark's
    readers load them."""
    return bench_spans.load(profile_dir)[1]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.fixture(scope="module")
def profiled(tiny, tmp_path_factory):
    """Memoized (config, telemetry) -> (engine, streams, done, spans): a
    fresh engine driven over the shared prompts while ``jax.profiler``
    records, and the ``serve.*`` spans it left in the profile."""
    cfg, _ = tiny
    cache = {}

    def get(name: str, telemetry: bool):
        key = (name, telemetry)
        if key not in cache:
            eng = _build(tiny, name, telemetry)
            d = str(tmp_path_factory.mktemp(f"profile_{name}_{telemetry}"))
            _submit_all(eng, name, cfg.vocab_size)
            jax.profiler.start_trace(d)
            try:
                done = eng.run()
            finally:
                jax.profiler.stop_trace()
            streams = [r.out_tokens for r in sorted(done, key=lambda r: r.uid)]
            cache[key] = (eng, streams, done, _serve_spans(d))
        return cache[key]

    return get


class TestServeSpans:
    """The engine's tick on the profiler's timeline: ``serve.step`` >
    ``serve.admit`` > ``serve.program`` > ``serve.fetch``, and
    ``serve.record`` after each program's outputs arrive."""

    @pytest.mark.parametrize("telemetry", [True, False])
    def test_spans_nest_with_their_stats(self, profiled, telemetry):
        eng, _, _, spans = profiled("two_tiers", telemetry)
        by = {n: [s for s in spans if s[0] == n] for n in
              ("serve.step", "serve.admit", "serve.program", "serve.fetch",
               "serve.record")}
        assert all(by.values()), {n: len(v) for n, v in by.items()}
        assert {s[0] for s in spans} == set(by)
        steps = by["serve.step"]
        assert len(steps) == eng.stats_snapshot()["steps"]
        assert [s[3]["step"] for s in steps] == list(range(1, len(steps) + 1))
        for s in steps:
            assert set(s[3]) == {"step", "mono_ns", "active", "queued"}
        for name in ("serve.admit", "serve.program", "serve.record"):
            for child in by[name]:
                assert any(_inside(child, s) for s in steps), child
        for f in by["serve.fetch"]:
            assert any(_inside(f, p) for p in by["serve.program"]), f
        programs = by["serve.program"]
        for p in programs:
            prefill = p[3]["program"].startswith("prefill[")
            assert set(p[3]) == {"program", "tier", "rows"} | (
                {"tokens", "positions"} if prefill else set())
            assert p[3]["rows"] >= 1
        names = {p[3]["program"] for p in programs}
        assert "decode" in names and any(n.startswith("prefill[")
                                         for n in names)
        # every prefill runs inside admission; both tiers decode
        for p in programs:
            if p[3]["program"].startswith("prefill["):
                assert any(_inside(p, a) for a in by["serve.admit"]), p
        assert {p[3]["tier"] for p in programs
                if p[3]["program"] == "decode"} == {0, 1}

    def test_speculative_and_chunked_call_sites_mark_their_fetch(
            self, profiled):
        for name, prog in (("speculative", "spec_decode[k="),
                           ("chunked_prefill", "chunk[")):
            _, _, _, spans = profiled(name, False)
            programs = [s for s in spans if s[0] == "serve.program"
                        and s[3]["program"].startswith(prog)]
            fetches = [s for s in spans if s[0] == "serve.fetch"]
            assert programs, name
            for p in programs:
                assert any(_inside(f, p) for f in fetches), (name, p)

    def test_mono_ns_anchor_maps_admission_into_its_admit_span(
            self, profiled):
        _, _, done, spans = profiled("two_tiers", True)
        steps = [s for s in spans if s[0] == "serve.step"]
        admits = [s for s in spans if s[0] == "serve.admit"]
        for r in done:
            assert any(
                _inside(a, st)
                and a[1] <= r.admitted_at * 1e9 + st[1] - st[3]["mono_ns"]
                <= a[2]
                for st in steps for a in admits), r.uid

    @pytest.mark.parametrize("telemetry", [True, False])
    def test_streams_identical_with_profiler_recording(self, driven,
                                                       profiled, telemetry):
        _, s_plain, _ = driven("two_tiers", telemetry)
        _, s_prof, _, _ = profiled("two_tiers", telemetry)
        assert s_prof == s_plain


class TestPrefillPadding:
    """Prefill and chunk programs carry the real prompt tokens they compute
    (``tokens``) and the positions they compute (``positions``: rows x
    bucket width); the per-tier counters add them up over the ticks."""

    @staticmethod
    def _prefills(spans, prefix):
        return [s[3] for s in spans if s[0] == "serve.program"
                and s[3]["program"].startswith(prefix)]

    @pytest.mark.parametrize("telemetry", [True, False])
    def test_one_shot_prefill_span_carries_tokens_and_positions(
            self, profiled, telemetry):
        eng, _, done, spans = profiled("two_tiers", telemetry)
        progs = self._prefills(spans, "prefill[")
        assert progs
        for p in progs:
            bucket = int(p["program"][len("prefill["):-1])
            assert p["positions"] == eng.ecfg.max_slots * bucket
            assert p["rows"] <= p["tokens"] <= p["rows"] * bucket
        assert sum(p["tokens"] for p in progs) == sum(
            len(r.prompt) for r in done)

    @pytest.mark.parametrize("telemetry", [True, False])
    def test_counters_add_up_across_ticks(self, profiled, telemetry):
        for name, prefix in (("two_tiers", "prefill["),
                             ("chunked_prefill", "chunk[")):
            eng, _, done, spans = profiled(name, telemetry)
            progs = self._prefills(spans, prefix)
            assert len(progs) > 1, name
            m = eng.metrics
            for tier in {p["tier"] for p in progs}:
                mine = [p for p in progs if p["tier"] == tier]
                want = (sum(p["tokens"] for p in mine),
                        sum(p["positions"] for p in mine))
                got = (m.counter_value(m.prefill_tokens, str(tier)),
                       m.counter_value(m.prefill_positions, str(tier)))
                assert got == (want if telemetry else (0, 0)), (name, tier)
            assert sum(p["tokens"] for p in progs) == sum(
                len(r.prompt) for r in done)
            assert all(p["tokens"] <= p["positions"] for p in progs)


class TestSlowestStep:
    def test_filled_with_telemetry_on(self, driven):
        eng, _, _ = driven("two_tiers", True)
        rec = eng.stats_snapshot()["slowest_step"]
        assert set(rec) == {"step", "seconds", "phases_ms", "programs",
                            "compiles"}
        assert 1 <= rec["step"] <= eng.stats_snapshot()["steps"]
        assert rec["seconds"] >= max(
            s for _, _, s in rec["programs"]) / 1e3 > 0
        assert rec["phases_ms"]["serve.step"] == pytest.approx(
            rec["seconds"] * 1e3, rel=0.1)
        assert set(rec["phases_ms"]) <= {"serve.step", "serve.admit",
                                         "serve.program", "serve.fetch",
                                         "serve.record"}
        # the first step compiles; so the slowest step is one that did
        assert rec["compiles"] >= 1
        json.dumps(rec)

    def test_none_with_telemetry_off(self, driven):
        eng, _, _ = driven("two_tiers", False)
        assert eng.stats_snapshot()["slowest_step"] is None

    def test_reset_after_warmup_keeps_only_later_steps(self):
        tel = EngineTelemetry("T")
        with tel.measure_tick(1):
            with tel.measure_program("p", 0):
                pass
        assert tel.slowest_step()["step"] == 1
        tel.reset_histograms()
        assert tel.slowest_step() is None
        with tel.measure_tick(2):
            with tel.span("serve.record"):
                pass
        rec = tel.slowest_step()
        assert rec["step"] == 2 and rec["programs"] == []
        assert set(rec["phases_ms"]) == {"serve.step", "serve.record"}

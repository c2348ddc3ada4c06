"""The program's ``serve.*`` spans in a trace: the host gap split into
dispatch, fetch, bookkeeping and the unattributed rest, decode programs per
step, gaps labelled by the innermost span, on hand-built traces; and the
loader and the metric readers on a profile recorded on the CPU."""
import types

import _paths  # noqa: F401
import numpy as np
import pytest

from bench import run
from bench.lib import spans as sp
from bench.lib import trace as tr

# two engine steps. The first admits (a prefill program, whose outputs are
# fetched), then decodes (one program and its fetch) and records; the second
# runs one op inside its serve.step and nothing else
OPS = [("fusion.1", 0.0, 10.0), ("fusion.2", 30.0, 50.0),
       ("fusion.3", 80.0, 100.0), ("fusion.4", 135.0, 145.0)]
STEPS = [("engine.step", 0.0, 120.0), ("engine.step", 130.0, 150.0)]
SERVE = sorted([
    ("serve.step", 2.0, 118.0, {"step": 1}),
    ("serve.admit", 5.0, 60.0, {}),
    ("serve.program", 12.0, 55.0, {"program": "prefill[256]", "tier": 0}),
    ("serve.fetch", 40.0, 55.0, {}),
    ("serve.program", 60.0, 105.0, {"program": "decode", "tier": 0}),
    ("serve.fetch", 85.0, 104.0, {}),
    ("serve.record", 106.0, 115.0, {}),
    ("serve.step", 131.0, 149.0, {"step": 2}),
], key=lambda s: (s[1], -s[2]))


def test_split_sums_to_the_host_gap_with_nested_and_sibling_spans():
    split = sp.split_ns(OPS, STEPS, SERVE)
    # idle [10,30]: admit 2, prefill dispatch 18; [50,80]: fetch 5, admit 5,
    # decode dispatch 20; [100,120]: fetch 4, dispatch 1, step 1, record 9,
    # step 3, outside serve.step 2; second step: 1 + 1 outside, 4 + 4 step
    assert split == {"dispatch": 39.0, "fetch": 9.0, "bookkeep": 28.0,
                     "unattributed": 4.0}
    assert sum(split.values()) == sum(tr.host_gap_ns(OPS, STEPS)) == 80.0


def test_idle_under_fetch_inside_a_program_is_fetch_not_dispatch():
    ops = [("fusion.1", 0.0, 10.0), ("fusion.2", 40.0, 50.0)]
    steps = [("engine.step", 0.0, 50.0)]
    serve = [("serve.program", 5.0, 45.0, {"program": "decode"}),
             ("serve.fetch", 12.0, 38.0, {})]
    assert sp.split_ns(ops, steps, serve) == {
        "dispatch": 4.0, "fetch": 26.0, "bookkeep": 0.0, "unattributed": 0.0}


# one step, two decode calls. On the trace's clock the device runs 2 early:
# the first call's program starts 2 before its serve.program opens, the
# second's 1.5 after
LEAD_STEP = [("engine.step", 0.0, 100.0)]
LEAD_SERVE = [
    ("serve.step", 1.0, 99.0, {"step": 1}),
    ("serve.program", 10.0, 45.0, {"program": "decode", "tier": 0}),
    ("serve.fetch", 20.0, 44.0, {}),
    ("serve.program", 50.0, 90.0, {"program": "decode", "tier": 1}),
    ("serve.fetch", 60.0, 89.0, {}),
    ("serve.record", 91.0, 98.0, {}),
]
LEAD_MODULES = [("jit__prefill_fn(7)", 0.0, 6.0),
                ("jit__decode_fn(8)", 8.0, 40.0),
                ("jit__decode_fn(9)", 51.5, 80.0)]
LEAD_OPS = [("fusion.1", 8.0, 40.0), ("fusion.2", 51.5, 80.0)]


def test_clock_lead_is_the_largest_lead_of_a_decode_program():
    assert sp.clock_lead_ns(LEAD_MODULES, LEAD_SERVE) == 2.0
    # programs that all start after their spans open: the device clock may
    # run late, by up to the least of their delays
    late = [(n, s + 5.0, e + 5.0) for n, s, e in LEAD_MODULES]
    assert sp.clock_lead_ns(late, LEAD_SERVE) == -3.0
    assert sp.clock_lead_ns([], LEAD_SERVE) == 0.0


def test_split_moves_device_ops_that_lead_their_program_onto_the_host_clock(
        monkeypatch):
    ctx = types.SimpleNamespace(ops=LEAD_OPS, modules=LEAD_MODULES,
                                step_spans=LEAD_STEP)
    monkeypatch.setattr(sp, "for_context", lambda c: LEAD_SERVE)
    # as given, fetch holds the 4 the first program seems to end early and
    # dispatch loses the 2 it seems to start early
    assert sp.split_ns(LEAD_OPS, LEAD_STEP, LEAD_SERVE) == {
        "dispatch": 3.5, "fetch": 13.0, "bookkeep": 21.0, "unattributed": 2.0}
    # ops 2 later: [10, 42] and [53.5, 82]
    split = sp.split_for(ctx)
    assert split == {"dispatch": 5.5, "fetch": 9.0, "bookkeep": 23.0,
                     "unattributed": 2.0}
    assert sum(split.values()) == sum(tr.host_gap_ns(LEAD_OPS, LEAD_STEP))


def test_decode_programs_counts_only_decode_programs_inside_steps():
    serve = SERVE + [("serve.program", 200.0, 210.0, {"program": "decode"}),
                     ("serve.program", 132.0, 140.0, {"program": "chunk[8]"}),
                     ("serve.program", 141.0, 148.0, {"program": "decode"})]
    assert sp.decode_programs(STEPS, serve) == 2


def test_innermost_label_picks_fetch_over_engine_step():
    ops = [("fusion.1", 0.0, 10.0), ("fusion.2", 40.0, 50.0)]
    harness = [("engine.step", 0.0, 50.0)]
    serve = [("serve.program", 5.0, 45.0), ("serve.fetch", 12.0, 38.0)]
    idle = tr.gaps(ops, 0.0, 50.0)
    assert sp.label_gaps(idle, sorted(harness + serve, key=lambda s: s[1])) \
        == [("serve.fetch", 30e-9)]
    assert tr.label_gaps(idle, harness) == [("engine.step", 30e-9)]


@pytest.mark.parametrize("window", [(0.0, 110.0), (0.0, 130.0), (20.0, 98.0)])
def test_harness_spans_alone_keep_todays_labels(window):
    ops = [("fusion.1", 0.0, 40.0), ("fusion.2", 30.0, 50.0),
           ("slr_matmul_stacked_pallas.3", 70.0, 90.0),
           ("lowrank_matmul_pallas.4", 95.0, 100.0)]
    harness = [("engine.submit", 0.0, 5.0), ("engine.step", 5.0, 60.0),
               ("bench.idle", 60.0, 75.0), ("engine.step", 75.0, 110.0)]
    idle = tr.gaps(ops, *window)
    assert sp.label_gaps(idle, harness) == tr.label_gaps(idle, harness)


def _record_profile(root, serve: bool):
    """A CPU profile under ``root/bench_out/<cell>/trace`` with one harness
    ``engine.step``; with ``serve``, the program's spans inside it."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    d = root / "bench_out" / "cell" / "trace"
    jax.profiler.start_trace(str(d))
    try:
        with TraceAnnotation("engine.step"):
            if serve:
                with TraceAnnotation("serve.step", step=7, mono_ns=123,
                                     active=2, queued=1):
                    with TraceAnnotation("serve.program", program="decode",
                                         tier=1, rows=2):
                        x = jnp.arange(3) + 1
                        with TraceAnnotation("serve.fetch"):
                            np.asarray(x)
                    with TraceAnnotation("serve.record"):
                        pass
            else:
                np.asarray(jnp.arange(3) + 1)
    finally:
        jax.profiler.stop_trace()
    return str(d)


def _ctx(profile_dir):
    steps = tr.load(profile_dir, {"engine.step"}).spans
    return types.SimpleNamespace(step_spans=steps, ops=[], modules=[])


def test_load_reads_serve_spans_and_their_stats(tmp_path):
    d = _record_profile(tmp_path, serve=True)
    steps, spans = sp.load(d)
    assert [s[0] for s in steps] == ["engine.step"]
    assert [s[0] for s in spans] == ["serve.step", "serve.program",
                                     "serve.fetch", "serve.record"]
    stats = {s[0]: s[3] for s in spans}
    assert stats["serve.step"] == {"step": 7, "mono_ns": 123, "active": 2,
                                   "queued": 1}
    assert stats["serve.program"] == {"program": "decode", "tier": 1,
                                      "rows": 2}
    assert stats["serve.fetch"] == {}
    (_, s0, e0), = steps
    assert all(s0 <= s[1] <= s[2] <= e0 for s in spans)


def test_readers_find_the_contexts_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(sp, "ROOT", tmp_path)
    ctx = _ctx(_record_profile(tmp_path, serve=True))
    read = {n: run.load_module("metrics", n).read for n in (
        "sched.dispatch_gap_ms", "sched.fetch_gap_ms",
        "sched.bookkeep_gap_ms", "sched.decode_programs_per_step")}
    assert read["sched.decode_programs_per_step"](ctx) == 1.0
    split = sp.split_for(ctx)
    # no device ops on the CPU: all of the step is host gap
    gap = run.load_module("metrics", "sched.host_gap_ms").read(ctx)
    parts = [read[f"sched.{k}_gap_ms"](ctx) for k in
             ("dispatch", "fetch", "bookkeep")]
    assert all(p > 0 for p in parts)
    assert sum(parts) + split["unattributed"] / 1e6 == pytest.approx(gap)
    # a context from another trace finds nothing
    other = types.SimpleNamespace(step_spans=[("engine.step", 0.0, 1.0)],
                                  ops=[], modules=[])
    assert all(r(other) is None for r in read.values())


def test_readers_read_nothing_from_a_program_without_serve_spans(
        tmp_path, monkeypatch):
    monkeypatch.setattr(sp, "ROOT", tmp_path)
    ctx = _ctx(_record_profile(tmp_path, serve=False))
    for name in ("sched.dispatch_gap_ms", "sched.fetch_gap_ms",
                 "sched.bookkeep_gap_ms", "sched.decode_programs_per_step"):
        assert run.load_module("metrics", name).read(ctx) is None

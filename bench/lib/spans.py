"""The program's own ``serve.*`` spans in a profiler trace, and the device's
idle time inside each engine step given to the innermost one that covers it.

The serving engine opens ``serve.step`` around each tick, ``serve.admit``
around admission, ``serve.program`` around each jitted call site with a
``serve.fetch`` child around the blocking read of its outputs, and
``serve.record`` around the bookkeeping after outputs arrive
(``repro.serving.telemetry``). ``split_ns`` gives each device-idle interval
inside a harness ``engine.step`` span, the time ``sched.host_gap_ms``
integrates, to the innermost ``serve.*`` span that covers it:

    dispatch       serve.program: argument flattening, table pushes, dispatch
    fetch          serve.fetch: the read of outputs the device has finished
    bookkeep       serve.step, serve.admit, serve.record
    unattributed   under no serve.* span

so that the four sum to the host gap.

The trace's device and host clocks disagree (on a TPU v5e the device's
reads from 0.2 ms late to 2.2 ms early, fixed within a trace).
``split_for`` first shifts the device's ops by the least offset that
causality allows, ``clock_lead_ns``: no decode program starts before the
``serve.program`` that dispatches it opens. The true offset can
only be larger, so dispatch reads at least, and fetch at most, what it is.

Stopgaps, until ``bench/lib/trace.py`` and ``bench/run.py`` take them over:
a reader gets a ``LayerContext``, which holds no path, so ``for_context``
finds the trace again as the newest under ``bench_out/`` whose
``engine.step`` spans are the context's own, and gives None where there is
none (as under ``run.run(out_dir=...)``) or where the program emits no
``serve.*`` span (a program older than these spans). ``label_gaps`` and
``report`` give by hand what ``breakdown.idle_gaps`` and the
``phase="trace"`` line do not carry yet:

    python -m bench.lib.spans bench_out/<cell>/trace

prints the split, the clock offset, the decode programs per step and the
ten longest idle gaps, each labelled by the innermost span that covers most
of it.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import sys
from pathlib import Path

from . import trace as tr

ROOT = Path(__file__).resolve().parents[2]
PREFIX = "serve."
STEP = "engine.step"          # the harness's span around engine.step()
HARNESS = {STEP, "engine.submit", "bench.idle"}
KINDS = ("dispatch", "fetch", "bookkeep", "unattributed")

Span = tuple[str, float, float, dict]   # (name, start_ns, end_ns, stats)


def kind(name: str | None) -> str:
    """The part of the host gap a piece of idle time under ``name`` (the
    innermost ``serve.*`` span over it, or None) belongs to."""
    if name is None:
        return "unattributed"
    return {"serve.program": "dispatch", "serve.fetch": "fetch"}.get(
        name, "bookkeep")


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> tuple[list[tr.Event], list[Span]]:
    from jax.profiler import ProfileData

    steps, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == STEP:
                    steps.append((e.name, e.start_ns, e.end_ns))
                elif e.name.startswith(PREFIX):
                    spans.append((e.name, e.start_ns, e.end_ns,
                                  {k: v for k, v in e.stats}))
    steps.sort(key=lambda s: s[1])
    spans.sort(key=lambda s: (s[1], -s[2]))
    return steps, spans


def newest_profile(root: str) -> str | None:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(profile_dir: str) -> tuple[list[tr.Event], list[Span]]:
    """(harness ``engine.step`` spans, ``serve.*`` spans with their stats),
    each by start, of the newest profile under ``profile_dir``."""
    path = newest_profile(profile_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return _load(path, os.path.getmtime(path))


def for_context(ctx) -> list[Span] | None:
    """The ``serve.*`` spans of the trace ``ctx`` was reduced from."""
    if not ctx.step_spans:
        return None
    path = newest_profile(str(ROOT / "bench_out"))
    if path is None:
        return None
    steps, spans = _load(path, os.path.getmtime(path))
    if [s[1:] for s in steps] != [s[1:] for s in ctx.step_spans]:
        return None
    return spans or None


def pieces(s: float, e: float, spans) -> list[tuple[float, float, int | None]]:
    """[s, e] cut at the edges of the spans that overlap it, each piece with
    the index in ``spans`` of the innermost span over it (the latest to
    start, then the first to end), or None where no span is."""
    over = [(i, sp) for i, sp in enumerate(spans) if sp[1] < e and sp[2] > s]
    cuts = sorted({s, e} | {t for _, sp in over for t in sp[1:3] if s < t < e})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [(sp[1], -sp[2], i) for i, sp in over
                    if sp[1] <= a and b <= sp[2]]
        out.append((a, b, max(covering)[2] if covering else None))
    return out


def idle_within(merged: list[tuple[float, float]], s: float, e: float
                ) -> list[tuple[float, float]]:
    """The parts of [s, e] that the disjoint sorted busy intervals
    ``merged`` leave idle."""
    out, t = [], s
    i = bisect.bisect_right(merged, (s, float("inf"))) - 1
    for a, b in merged[max(i, 0):]:
        if a >= e:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if e > t:
        out.append((t, e))
    return out


def split_ns(ops: list[tr.Event], steps: list[tr.Event],
             spans: list[Span]) -> dict[str, float]:
    """Device-idle ns inside the engine steps, summed per kind."""
    merged = tr.union(ops)
    starts = [sp[1] for sp in spans]
    out = dict.fromkeys(KINDS, 0.0)
    for _, s, e in steps:
        # the program's spans nest inside the harness span around its step
        inside = spans[bisect.bisect_left(starts, s):
                       bisect.bisect_left(starts, e)]
        for a, b in idle_within(merged, s, e):
            for x, y, i in pieces(a, b, inside):
                out[kind(None if i is None else inside[i][0])] += y - x
    return out


def clock_lead_ns(modules: list[tr.Event], spans: list[Span]) -> float:
    """At least how far the device clock runs early of the host's: the
    largest lead of a decode program's execution (the decode module that
    overlaps its ``serve.program`` the most) over the span's opening. Below
    0 where every execution starts after its span opens (the device clock
    may run late); 0 where no decode call is found."""
    runs = sorted((s, e) for name, s, e in modules if "decode" in name)
    starts = [s for s, _ in runs]
    lead = None
    for name, p, q, stats in spans:
        if name != "serve.program" or stats.get("program") != "decode":
            continue
        # one chip runs one program at a time: the ends rise with the starts
        i, best, cover = bisect.bisect_left(starts, q), None, 0.0
        while i > 0 and runs[i - 1][1] > p:
            i -= 1
            c = min(q, runs[i][1]) - max(p, runs[i][0])
            if c > cover:
                best, cover = runs[i][0], c
        if best is not None:
            lead = p - best if lead is None else max(lead, p - best)
    return 0.0 if lead is None else lead


def shift(ops: list[tr.Event], ns: float) -> list[tr.Event]:
    return [(name, s + ns, e + ns) for name, s, e in ops]


def split_for(ctx) -> dict[str, float] | None:
    """Mean device-idle ns per engine step of the traced window, per kind,
    with the device's ops moved onto the host's clock."""
    spans = for_context(ctx)
    if spans is None:
        return None
    ops = shift(ctx.ops, clock_lead_ns(ctx.modules, spans))
    total = split_ns(ops, ctx.step_spans, spans)
    return {k: v / len(ctx.step_spans) for k, v in total.items()}


def decode_programs(steps: list[tr.Event], spans: list[Span]) -> int:
    """``serve.program`` spans of decode programs that start inside a step."""
    return sum(1 for name, s, _, stats in spans
               if name == "serve.program" and stats.get("program") == "decode"
               and any(a <= s < b for _, a, b in steps))


def label_gaps(idle: list[tuple[float, float]], spans: list[tr.Event],
               k: int = 10) -> list[tuple[str, float]]:
    """The ``k`` longest idle gaps, each named by the span that is innermost
    over the largest part of it ("none" where no span covers it). Where no
    spans nest, that is the span that covers most of the gap."""
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:k]
    labelled = []
    for s, e in longest:
        held: dict[int, float] = {}
        for a, b, i in pieces(s, e, spans):
            if i is not None:
                held[i] = held.get(i, 0.0) + (b - a)
        best = max(sorted(held), key=lambda i: held[i], default=None)
        labelled.append(("none" if best is None else spans[best][0],
                         (e - s) / 1e9))
    return labelled


def report(profile_dir: str) -> dict:
    """The split of a traced window's host gap and its labelled gaps."""
    t = tr.load(profile_dir, HARNESS)
    steps, spans = load(profile_dir)
    lo, hi = steps[0][1], steps[-1][2]
    ops = tr.clip(t.ops.get(0, []), lo, hi)
    lead = clock_lead_ns(tr.clip(t.modules.get(0, []), lo, hi), spans)
    n = len(steps)
    split = {f"{k}_ms": v / n / 1e6
             for k, v in split_ns(shift(ops, lead), steps, spans).items()}
    host_gap = sum(tr.host_gap_ns(ops, steps)) / n / 1e6
    everything = sorted(t.spans + [sp[:3] for sp in spans],
                        key=lambda x: x[1])
    return {
        "steps": n, "serve_spans": len(spans), "host_gap_ms": host_gap,
        "clock_lead_ms": lead / 1e6,
        **split, "sum_ms": sum(split.values()),
        "decode_programs_per_step": decode_programs(steps, spans) / n,
        "idle_gaps": label_gaps(tr.gaps(shift(ops, lead), lo, hi),
                                everything),
        "idle_gaps_harness_only": tr.label_gaps(tr.gaps(ops, lo, hi),
                                                t.spans),
    }


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1])))

"""From a profiler trace to intervals, and from intervals to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device's
operations (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), its
program executions (``XLA Modules``) and the host's ``TraceAnnotation``
spans, all on the profiler's one clock, in nanoseconds. The reductions below
take plain lists, so a test can hand them a trace made up by hand.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

Event = tuple[str, float, float]     # (name, start_ns, end_ns)


@dataclass
class Trace:
    ops: dict[int, list[Event]] = field(default_factory=dict)      # per chip
    modules: dict[int, list[Event]] = field(default_factory=dict)  # per chip
    spans: list[Event] = field(default_factory=list)               # host


def op_name(text: str) -> str:
    """An XLA op's own name from its event text, which on a TPU is the HLO
    instruction (``%slr_matmul_stacked_pallas.90 = bf16[16,2048] custom-call(
    ...)``): the part before `` = ``, without the ``%``. A Pallas kernel's op
    is named after the jitted function that calls it."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``slr_matmul_stacked_pallas.90`` -> ``slr_matmul_stacked_pallas``."""
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


# control flow ops span the ops they run; they are not work of their own
CONTAINERS = ("while", "conditional", "call")


def load(profile_dir: str, span_names: set[str]) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.ops[chip] = [(op_name(e.name), e.start_ns, e.end_ns)
                                     for e in line.events]
                elif line.name == "XLA Modules":
                    out.modules[chip] = [(e.name, e.start_ns, e.end_ns)
                                         for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        out.spans.append((e.name, e.start_ns, e.end_ns))
    out.spans.sort(key=lambda s: s[1])
    return out


def clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    """Events cut to [lo, hi]; those outside dropped."""
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union(events: list[Event]) -> list[tuple[float, float]]:
    """Disjoint intervals covered by any event."""
    merged: list[list[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events: list[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(events, lo, hi)))


def idle_share(events: list[Event], lo: float, hi: float) -> float:
    return 1.0 - busy_ns(events, lo, hi) / (hi - lo)


def time_by_name(events: list[Event], kind: str) -> tuple[float, int]:
    """(summed duration, count) of the ops of kind ``kind``."""
    total, count = 0.0, 0
    for name, s, e in events:
        if op_kind(name) == kind:
            total += e - s
            count += 1
    return total, count


def top_ops(events: list[Event], k: int = 10) -> list[tuple[str, float]]:
    """The ``k`` op kinds with the most summed device time (seconds),
    control flow left out."""
    by: dict[str, float] = {}
    for name, s, e in events:
        key = op_kind(name)
        if key.startswith(CONTAINERS):
            continue
        by[key] = by.get(key, 0.0) + (e - s)
    ranked = sorted(by.items(), key=lambda x: -x[1])[:k]
    return [(name, ns / 1e9) for name, ns in ranked]


def gaps(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of the device within [lo, hi]."""
    out, t = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def covered_ns(merged: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by the disjoint sorted intervals ``merged``."""
    i = bisect.bisect_right(merged, (lo, float("inf"))) - 1
    total = 0.0
    for s, e in merged[max(i, 0):]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def label_gaps(idle: list[tuple[float, float]], spans: list[Event],
               k: int = 10) -> list[tuple[str, float]]:
    """The ``k`` longest idle gaps, each named by the host span that covers
    most of it ("none" where no span does)."""
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:k]
    labelled = []
    for s, e in longest:
        best, cover = "none", 0.0
        for name, hs, he in spans:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        labelled.append((best, (e - s) / 1e9))
    return labelled


def host_gap_ns(events: list[Event], spans: list[Event]) -> list[float]:
    """Per span: its length less the device-busy time inside it."""
    merged = union(events)
    return [(e - s) - covered_ns(merged, s, e) for _, s, e in spans]

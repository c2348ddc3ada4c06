"""What a per-layer metric reader gets: the traced window reduced to the
intervals of one chip, the engine steps that ran in it, and the tiers'
mathematics. A reader (``bench/metrics/<name>.py``) defines
``read(ctx) -> float | None`` and returns None where it finds nothing to
read."""
from __future__ import annotations

from dataclasses import dataclass

from . import trace as tr
from .counts import TierMath
from .serve import StepWork, Window


@dataclass
class LayerContext:
    ops: list[tr.Event]          # device ops of chip 0 inside [lo, hi]
    modules: list[tr.Event]      # program executions of chip 0 inside [lo, hi]
    step_spans: list[tr.Event]   # host "engine.step" spans inside [lo, hi]
    steps: list[StepWork] | None  # the same steps' work (None: not aligned)
    lo: float                    # ns
    hi: float
    window: Window
    tiers: list[TierMath]
    peaks: dict

    @property
    def seconds(self) -> float:
        return (self.hi - self.lo) / 1e9

    def model_flops(self) -> float | None:
        """FLOPs of the mathematics of every prompt and decode row the
        window's steps processed."""
        if self.steps is None:
            return None
        total = 0.0
        for s in self.steps:
            for tier, lens in s.prefill.items():
                total += sum(self.tiers[tier].prefill_flops(p) for p in lens)
            for tier, ctxs in s.decode.items():
                total += sum(self.tiers[tier].decode_flops(c) for c in ctxs)
        return total

    def ops_in_modules(self, needle: str) -> list[tr.Event]:
        """Device ops that start inside an execution of a program whose name
        holds ``needle``."""
        import bisect

        spans = tr.union([m for m in self.modules if needle in m[0]])
        starts = [s for s, _ in spans]
        out = []
        for op in self.ops:
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[1] < spans[i][1]:
                out.append(op)
        return out


def context(trace: tr.Trace, window: Window, tiers: list[TierMath],
            peaks: dict) -> LayerContext:
    steps_spans = [s for s in trace.spans if s[0] == "engine.step"]
    lo, hi = steps_spans[0][1], steps_spans[-1][2]
    a, b = window.trace_steps
    steps = window.steps[a:b]
    if len(steps) != len(steps_spans):
        steps = None
    ops = tr.clip(trace.ops.get(0, []), lo, hi)
    modules = tr.clip(trace.modules.get(0, []), lo, hi)
    return LayerContext(ops, modules, steps_spans, steps, lo, hi, window,
                        tiers, peaks)

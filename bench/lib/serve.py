"""Drive the serving engine in open loop and measure what its users see.

Arrivals are submitted when they fall due, with ``submitted_at`` backdated
to the due time, so a request that waits behind a long step is timed from
when it was due. The window closes ``seconds`` after it opens; a request due
in it that has no first token by then counts in the TTFT median at the time
it has waited. All times are ``time.monotonic`` seconds, the engine's own clock.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np
from jax.profiler import TraceAnnotation


@dataclass
class StepWork:
    """What one ``engine.step`` computed, read from the requests it touched:
    per tier, the prompt lengths it prefilled and the key counts of the
    decode rows it ran."""

    t0: float
    t1: float
    prefill: dict[int, list[int]] = field(default_factory=dict)
    decode: dict[int, list[int]] = field(default_factory=dict)


@dataclass
class Window:
    t0: float                       # monotonic time the window opened
    close: float                    # monotonic time it closed
    due: dict[int, float]           # uid -> due time (monotonic)
    lag: list[float]                # per submit: seconds after its due time
    requests: dict[int, object]     # uid -> the engine's Request
    steps: list[StepWork]
    compiles: int                   # backend compiles inside the window
    backlog: list[tuple[float, int]]  # (seconds into window, outstanding)
    trace_steps: tuple[int, int] = (0, 0)   # steps [a, b) ran under the profiler
    # (seconds, seconds into the window) of the longest engine step, and of
    # the longest pass of the loop outside steps and sleeps: where a stall fell
    longest_step: tuple[float, float] = (0.0, 0.0)
    longest_other: tuple[float, float] = (0.0, 0.0)


def run_window(engine, arrivals, seconds: float, clock, *,
               trace_dir: str | None = None, trace_span: tuple[float, float] = (0, 0),
               tiers=None) -> Window:
    """Serve ``arrivals`` for ``seconds``. With ``trace_dir`` the profiler
    records ``trace_span`` (seconds into the window)."""
    import jax

    compiles0 = clock.compiles
    n = len(arrivals)
    due, lag, requests, steps, backlog = {}, [], {}, [], []
    inflight: dict[int, object] = {}
    i = 0
    trace_state = "off" if trace_dir is None else "waiting"
    trace_steps = [0, 0]
    next_backlog = 0.0
    longest_step = longest_other = (0.0, 0.0)
    t0 = time.monotonic()
    while True:
        now = time.monotonic()
        rel = now - t0
        step_s = slept = 0.0
        if rel >= seconds:
            break
        if trace_state == "waiting" and rel >= trace_span[0]:
            jax.profiler.start_trace(trace_dir)
            trace_state, trace_steps[0] = "on", len(steps)
        elif trace_state == "on" and rel >= trace_span[1]:
            jax.profiler.stop_trace()
            trace_state, trace_steps[1] = "done", len(steps)
        if rel >= next_backlog:
            # requests due and not finished, queued or in a slot: it grows
            # over the window above the rate the engine sustains
            backlog.append((rel, len(inflight)))
            next_backlog = (int(rel / (seconds / 20)) + 1) * (seconds / 20)
        with TraceAnnotation("engine.submit"):
            while i < n and arrivals[i].due_s <= rel:
                a = arrivals[i]
                at = t0 + a.due_s
                uid = engine.submit(a.prompt, max_new_tokens=a.max_new,
                                    tier=a.tier, submitted_at=at)
                req = engine._queue[-1]
                assert req.uid == uid
                due[uid], requests[uid], inflight[uid] = at, req, req
                lag.append(time.monotonic() - at)
                i += 1
        if engine.has_work:
            before = {u: len(r.out_tokens) for u, r in inflight.items()}
            s0 = time.monotonic()
            with TraceAnnotation("engine.step"):
                engine.step()
            work = StepWork(s0, time.monotonic())
            for u, r in list(inflight.items()):
                for j in range(before[u], len(r.out_tokens)):
                    if j == 0:
                        work.prefill.setdefault(r.tier, []).append(len(r.prompt))
                    else:
                        work.decode.setdefault(r.tier, []).append(len(r.prompt) + j)
                if r.done:
                    del inflight[u]
            steps.append(work)
            step_s = work.t1 - work.t0
            longest_step = max(longest_step, (step_s, s0 - t0))
        else:
            wait = (arrivals[i].due_s if i < n else seconds) - (time.monotonic() - t0)
            if wait > 0:
                with TraceAnnotation("bench.idle"):
                    time.sleep(min(wait, seconds))
                slept = min(wait, seconds)
        other = time.monotonic() - now - step_s - slept
        longest_other = max(longest_other, (other, rel))
    close = time.monotonic()
    if trace_state == "on":
        jax.profiler.stop_trace()
        trace_steps[1] = len(steps)
    return Window(t0, close, due, lag, requests, steps,
                  clock.compiles - compiles0, backlog, tuple(trace_steps),
                  longest_step, longest_other)


def ttfts(w: Window) -> list[float]:
    out = []
    for uid, d in w.due.items():
        first = w.requests[uid].first_token_at
        out.append((first if 0 < first <= w.close else w.close) - d)
    return out


def itls(w: Window) -> list[float]:
    out = []
    for r in w.requests.values():
        times = [t for t in r.token_times if t <= w.close]
        out.extend(np.diff(times).tolist())
    return out


def tokens_in_window(w: Window) -> int:
    return sum(sum(1 for t in r.token_times if t <= w.close)
               for r in w.requests.values())


def end_to_end(w: Window, seconds: float) -> dict:
    t, g = ttfts(w), itls(w)
    return {
        "output_tok_s": tokens_in_window(w) / seconds,
        "ttft_p50_ms": float(np.median(t)) * 1e3,
        "itl_p95_ms": float(np.percentile(g, 95)) * 1e3 if g else float("nan"),
        "_samples": {"ttft": len(t), "itl": len(g)},
    }

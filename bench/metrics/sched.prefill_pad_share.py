"""Scheduler: the share of the token positions the traced window's prefill
programs computed that held no prompt token, 1 - sum(tokens) /
sum(positions) over their ``serve.program`` spans, in %. None where the
program's spans carry no ``tokens`` and ``positions``."""
from bench.lib import spans


def read(ctx):
    found = spans.for_context(ctx)
    if found is None:
        return None
    progs = [stats for name, s, _, stats in found
             if name == "serve.program" and "positions" in stats
             and any(a <= s < b for _, a, b in ctx.step_spans)]
    positions = sum(p["positions"] for p in progs)
    if not positions:
        return None
    return 100.0 * (1.0 - sum(p["tokens"] for p in progs) / positions)

"""Scheduler: jitted decode programs the engine ran per engine step of the
traced window (``serve.program`` spans with ``program`` = decode; one per
active tier a tick). None where the program emits no ``serve.*`` spans."""
from bench.lib import spans


def read(ctx):
    found = spans.for_context(ctx)
    if found is None:
        return None
    return spans.decode_programs(ctx.step_spans, found) / len(ctx.step_spans)

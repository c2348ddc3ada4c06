"""Scheduler: mean host time per engine step in which the chip ran nothing
while the program was in its own bookkeeping, under ``serve.step``,
``serve.admit`` or ``serve.record`` and in no jitted call site, in ms. None
where the program emits no ``serve.*`` spans."""
from bench.lib import spans


def read(ctx):
    split = spans.split_for(ctx)
    return None if split is None else split["bookkeep"] / 1e6

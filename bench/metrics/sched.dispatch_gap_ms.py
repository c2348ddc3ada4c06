"""Scheduler: mean host time per engine step in which the chip ran nothing
while the program was inside a jitted call site (``serve.program``) and not
reading its outputs (``serve.fetch``): argument flattening, block-table
pushes and the dispatch, in ms. None where the program emits no
``serve.*`` spans."""
from bench.lib import spans


def read(ctx):
    split = spans.split_for(ctx)
    return None if split is None else split["dispatch"] / 1e6

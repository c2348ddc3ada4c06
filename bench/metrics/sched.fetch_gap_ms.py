"""Scheduler: mean host time per engine step in which the chip ran nothing
while the program read a finished program's outputs back (``serve.fetch``),
in ms. None where the program emits no ``serve.*`` spans."""
from bench.lib import spans


def read(ctx):
    split = spans.split_for(ctx)
    return None if split is None else split["fetch"] / 1e6

"""Scheduler: mean host time per engine step in which the chip ran nothing
(the step's span less the device-busy time inside it), in ms."""
import numpy as np

from bench.lib import trace as tr


def read(ctx):
    if not ctx.step_spans:
        return None
    return float(np.mean(tr.host_gap_ns(ctx.ops, ctx.step_spans))) / 1e6

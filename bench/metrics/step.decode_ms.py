"""Model step: mean device time of one execution of a decode program, in ms,
from the program executions in the trace."""
import numpy as np


def read(ctx):
    runs = [e - s for name, s, e in ctx.modules if "decode" in name]
    return float(np.mean(runs)) / 1e6 if runs else None

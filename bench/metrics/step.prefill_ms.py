"""Model step: mean device time of one execution of a prefill program, in
ms, from the program executions in the trace (names holding ``prefill``)."""
import numpy as np


def read(ctx):
    runs = [e - s for name, s, e in ctx.modules if "prefill" in name]
    return float(np.mean(runs)) / 1e6 if runs else None

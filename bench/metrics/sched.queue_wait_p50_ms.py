"""Scheduler: median of admission time less due time over the requests due
in the window (a request not admitted by the close has waited until then),
in ms, from the engine's request timestamps."""
import numpy as np


def read(ctx):
    w = ctx.window
    waits = []
    for uid, due in w.due.items():
        adm = w.requests[uid].admitted_at
        waits.append((adm if 0 < adm <= w.close else w.close) - due)
    return float(np.median(waits)) * 1e3 if waits else None

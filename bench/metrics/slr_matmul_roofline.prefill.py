"""Kernels: least time of the fused stacked SLR kernel's calls in the prefill
programs of the traced window (per call the larger of FLOPs over peak and
bytes over bandwidth, at the served rank and kept entries, over the prompt
tokens' rows) over the summed device time of its events there, in %."""
from bench.lib import roofline


def read(ctx):
    return roofline.stacked_share(ctx, "prefill")

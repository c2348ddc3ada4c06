"""The fused stacked SLR kernel's share of its roofline in one phase."""
from __future__ import annotations

from . import trace as tr

# the op of the stacked fused kernel (`_stacked_kernel`) takes the name of
# the jitted function that calls it
KERNEL = "slr_matmul_stacked_pallas"


def stacked_share(ctx, phase: str) -> float | None:
    """``phase`` is "decode" or "prefill": the programs whose kernel calls
    count. None where the phase ran no such kernel in the window."""
    if ctx.steps is None:
        return None
    ops = ctx.ops_in_modules(phase)
    kernel_ns, count = tr.time_by_name(ops, KERNEL)
    if not count:
        return None
    pf, bw = ctx.peaks["bf16_flop_per_s"], ctx.peaks["hbm_bytes_per_s"]
    least = 0.0
    for s in ctx.steps:
        work = s.decode if phase == "decode" else s.prefill
        for tier, items in work.items():
            t = ctx.tiers[tier]
            if not any(site.fused_sparse for site in t.sites):
                continue
            # one program per tier per step: its rows are the decode rows,
            # or the prompt tokens it prefilled
            rows = len(items) if phase == "decode" else sum(items)
            least += t.stacked_kernel_least_s(rows, pf, bw)
    return 100.0 * least / (kernel_ns / 1e9)

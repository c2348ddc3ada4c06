"""Model step: model FLOPs of the prompts the traced window's prefill
programs computed (each prompt at its tier's kept rank and entries) over
those programs' summed device time times the chip's bfloat16 peak, in %.
Padded rows and positions count as time, not as work."""


def read(ctx):
    if ctx.steps is None:
        return None
    device_ns = sum(e - s for name, s, e in ctx.modules if "prefill" in name)
    flops = sum(ctx.tiers[tier].prefill_flops(p) for step in ctx.steps
                for tier, lens in step.prefill.items() for p in lens)
    if not device_ns or not flops:
        return None
    return 100.0 * flops / (device_ns / 1e9 * ctx.peaks["bf16_flop_per_s"])

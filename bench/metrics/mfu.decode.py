"""Model step: model FLOPs of every token processed in the traced window
(prompts and decode rows at each tier's kept rank and entries) over the
window's length times the chip's bfloat16 peak, in %."""


def read(ctx):
    flops = ctx.model_flops()
    if not flops:
        return None
    return 100.0 * flops / (ctx.seconds * ctx.peaks["bf16_flop_per_s"])

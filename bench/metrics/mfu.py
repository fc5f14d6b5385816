"""Model FLOPs of the traced window's steps (forward and backward, no
recomputation, no optimizer; per token as the configuration's reference
module counts them) over the window, the chips and the chip's bf16 peak,
in percent."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    work = ctx.flops_per_token * ctx.facts["tokens_per_step"] * ctx.steps
    return 100.0 * work / (ctx.window_s * ctx.chips * ctx.peak["bf16_flops"])

"""Model FLOPs of the traced window's rounds (counted from shapes by the
family's file under ``bench/flops``) over the window's length times the
chips' bf16 peak."""


def read(ctx):
    flops = ctx.flops_per_round * ctx.rounds
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks["flops_bf16"])

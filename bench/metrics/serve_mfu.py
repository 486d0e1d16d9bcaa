"""The whole step, device: model FLOPs of every prompt position computed
and every token generated in the window, over the window's length and
the chip's bf16 peak."""


def read(ctx):
    cfg, w = ctx.config, ctx.window
    flops = ctx.decode_flops + sum(
        ctx.counts.prefill_flops(cfg, lo, n) for lo, n in w["prefills"])
    if not flops:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])

"""Model FLOPs of the window's useful local training over the window's
seconds times chips times the chip's published bf16 peak, in percent."""
import workcount


def read(ctx):
    flops = workcount.train_flops(ctx.cfg, sum(ctx.n_active))
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])

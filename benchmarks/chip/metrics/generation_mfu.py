"""Model FLOPs of a generation (flops.generation_flops, at the graphs'
real node counts) over the traced time per generation times the chip's
bf16 peak, in percent."""


def read(ctx):
    if not ctx.gen_flops or ctx.peaks is None:
        return None
    per_gen_s = ctx.reduced.window_s / ctx.generations
    return 100.0 * ctx.gen_flops / (per_gen_s * ctx.peaks["bf16_flops"])

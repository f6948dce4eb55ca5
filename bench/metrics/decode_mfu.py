"""Share of the chip's bf16 peak in the operations the window's tokens
require: every lane's token through all layers and the head, attention over
the positions below its length."""


def read(ctx):
    w, f = ctx.window, ctx.flops
    if not w["steps"]:
        return None
    flops = sum(f.decode_step_flops(ctx.config, w["lanes"], p + 1) for p in w["positions"])
    return 100.0 * flops / w["seconds"] / ctx.peak["bf16_flops"]

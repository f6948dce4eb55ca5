"""Share of the chip's bf16 peak in the operations the window's tokens
require in the hybrid decode cell: every lane's token through all layers
(Mamba2 state updates and attention below its length included) and the
head, over the window's wall time."""

import hybrid_flops


def read(ctx):
    w = ctx.window
    if not w["steps"]:
        return None
    flops = sum(hybrid_flops.decode_step_flops(ctx.config, w["lanes"], p + 1)
                for p in w["positions"])
    return 100.0 * flops / w["seconds"] / ctx.peak["bf16_flops"]

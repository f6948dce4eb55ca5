"""Roofline share of the Mamba2 state-update kernel (kernels/ssm_decode.py,
``ssm_decode`` in the trace): the least time its calls could take (each
call's bytes over the HBM peak, or its operations over the bf16 peak,
whichever is larger; the bytes bound it) over their device time. A call
updates one layer's float32 state of every lane in place: the state read
and written, plus its inputs and y."""

import hybrid_flops


def read(ctx):
    ops = ctx.trace.kernel_ops(("ssm_decode",))
    if not ops:
        return None
    flops, nbytes = hybrid_flops.ssm_decode_cost(ctx.config, ctx.window["lanes"])
    least = max(nbytes / ctx.peak["hbm_bytes_per_s"], flops / ctx.peak["bf16_flops"])
    return 100.0 * least * len(ops) / (sum(o.dur for o in ops) / 1e9)

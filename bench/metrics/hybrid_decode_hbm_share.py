"""Share of the HBM peak in what the hybrid decode steps must move (every
bf16 weight once, each Mamba2 layer's float32 state read and written and
its convolution window, each lane's float32 keys and values up to its
length) over the device time of the step program (`jit_step` in the
trace)."""

import hybrid_flops

STEP_MODULE = "jit_step"


def read(ctx):
    w = ctx.window
    busy = ctx.trace.module_seconds(STEP_MODULE)
    if not w["steps"] or busy <= 0:
        return None
    need = sum(hybrid_flops.decode_step_bytes(ctx.config, w["lanes"], p + 1)
               for p in w["positions"])
    return 100.0 * need / busy / ctx.peak["hbm_bytes_per_s"]

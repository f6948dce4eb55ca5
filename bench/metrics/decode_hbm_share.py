"""Share of the HBM peak in what the decode steps must read (every bf16
weight once, each lane's float32 keys and values up to its length) over the
device time of the step program (`jit_step` in the trace)."""

STEP_MODULE = "jit_step"


def read(ctx):
    w, f, c = ctx.window, ctx.flops, ctx.config
    busy = ctx.trace.module_seconds(STEP_MODULE)
    if not w["steps"] or busy <= 0:
        return None
    need = sum(f.decode_step_bytes(c, w["lanes"], p + 1, 2, 4) for p in w["positions"])
    return 100.0 * need / busy / ctx.peak["hbm_bytes_per_s"]

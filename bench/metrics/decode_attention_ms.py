"""Device time of the decode attention kernel (kernels/decode_attention.py,
``decode_attention`` in the trace) per fleet step of the traced window.

A time and not a roofline share: XLA stages each layer's keys and values
into VMEM (the kernel's operands sit in memory space S(1)) with copies
outside the kernel, so the kernel's own calls have no HBM bound to be held
against. The step's share of the HBM peak is ``decode_hbm_share``."""


def read(ctx):
    ops = ctx.trace.kernel_ops(("decode_attention",))
    steps = ctx.window["steps"]
    if not ops or not steps:
        return None
    return sum(o.dur for o in ops) / 1e6 / ctx.trace.chips / steps

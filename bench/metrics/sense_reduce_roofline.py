"""Share of the HBM roofline reached by the fused ``sense_reduce`` kernel
(``kernels/fused.py``: a chain of same-plan senses folded in VMEM)."""


def read(ctx):
    return ctx.roofline("sense_reduce")

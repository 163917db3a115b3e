"""Share of the HBM roofline reached by the ``mlc_sense`` kernel (grouped
senses and leaf page reads)."""


def read(ctx):
    return ctx.roofline("mlc_sense")

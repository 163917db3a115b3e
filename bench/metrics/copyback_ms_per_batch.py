"""Device milliseconds per batch of the FTL's copyback realignments: the
``_copyback_rows`` program (``flash/device.py``), one per realignment, that
a batch's lowering runs before the batch dispatches.  Reads 0 where a
traced window ran no copyback program (a program whose copybacks are not
such programs included), since a declared metric that reads nothing fails
the run."""

COPYBACK_PROGRAMS = r"^jit_+_?copyback"


def read(ctx):
    if ctx.trace is None or ctx.batches <= 0:
        return None
    events = ctx.trace.module_events(COPYBACK_PROGRAMS)
    return sum(e.seconds for e in events) * 1e3 / ctx.batches

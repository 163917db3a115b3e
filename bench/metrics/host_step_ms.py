"""Mean host time of one ``QueryEngine.step`` (batch formation, lowering,
plan verification, executable lookup, gathers and dispatch), from the
benchmark's ``bench.step`` spans in the trace."""


def read(ctx):
    spans = ctx.trace.host.get("bench.step", []) if ctx.trace else []
    if not spans or ctx.batches <= 0:
        return None
    return sum(b - a for a, b in spans) * 1e-6 / ctx.batches

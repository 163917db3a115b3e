"""Device milliseconds per batch of the arena gathers (``flash/arena.py``):
the eager ``jnp.take`` of a die shard's rows and the jitted cross-shard
``_gather_parts``, which run as programs of their own outside the batch's
executable."""

GATHER_PROGRAMS = r"^jit_+(take|_?gather_parts)\b"


def read(ctx):
    if ctx.trace is None or ctx.batches <= 0:
        return None
    events = ctx.trace.module_events(GATHER_PROGRAMS)
    if not events:
        return None
    return sum(e.seconds for e in events) * 1e3 / ctx.batches

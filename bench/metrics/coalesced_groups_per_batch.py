"""Sense groups shared by more than one request, per dispatched batch: the
delta of the session's ``coalesced_sense_groups`` counter over the delta of
the engine's ``batches_dispatched`` in the window."""


def read(ctx):
    if ctx.batches <= 0:
        return None
    return ctx.window.delta("coalesced_sense_groups") / ctx.batches

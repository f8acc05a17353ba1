"""Pages faulted into the paged bank per round (the bank's own count)."""


def read(ctx):
    if ctx.faults is None:
        return None
    return ctx.faults / ctx.rounds

"""Host milliseconds per round spent in the paged bank's `prepare`."""


def read(ctx):
    if "paging" not in ctx.span_s:
        return None
    return 1e3 * ctx.span_s["paging"] / ctx.rounds

"""Host milliseconds per round spent in the batcher's `sample_round`."""


def read(ctx):
    if "batch_assembly" not in ctx.span_s:
        return None
    return 1e3 * ctx.span_s["batch_assembly"] / ctx.rounds

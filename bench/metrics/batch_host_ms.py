"""Host milliseconds per round spent in the batcher's `sample_round`, or in
its `sample_round_rows` where the driver gathers batches on the chip from
the batcher's row table and ships row indices (`harness.BatcherProxy`)."""


def read(ctx):
    if "batch_assembly" not in ctx.span_s:
        return None
    return 1e3 * ctx.span_s["batch_assembly"] / ctx.rounds

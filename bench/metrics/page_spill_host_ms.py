"""Host milliseconds per round reading evicted pages back to host RAM (the
program's span `spill`, inside `paging`)."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "spill", parent="paging")

"""Host milliseconds per round choosing slots and eviction victims for
faulted pages (the program's span `victims`, inside `paging`)."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "victims", parent="paging")

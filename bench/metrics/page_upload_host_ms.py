"""Host milliseconds per round writing faulted pages and the page table to
the device (the program's span `page_upload`, inside `paging`)."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "page_upload", parent="paging")

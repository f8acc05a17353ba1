"""Megabytes per round copied host to device: the chunk inputs, the paged
bank's uploaded pages and page table (the program's `h2d_bytes`)."""
import program_spans


def read(ctx):
    return program_spans.counter_mb(ctx, "h2d_bytes")

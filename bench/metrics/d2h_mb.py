"""Megabytes per round read device to host: the paged bank's evicted pages
and the chunk results (the program's `d2h_bytes`)."""
import program_spans


def read(ctx):
    return program_spans.counter_mb(ctx, "d2h_bytes")

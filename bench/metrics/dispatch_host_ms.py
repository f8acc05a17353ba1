"""Host milliseconds per round in the chunk call: with NumPy inputs, their
host-to-device copy and the enqueue (the program's span `dispatch`)."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "dispatch")

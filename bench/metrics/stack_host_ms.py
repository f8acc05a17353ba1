"""Host milliseconds per round stacking the rounds' inputs into a chunk
(the program's span `stack`, inside `batch_assembly`)."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "stack")

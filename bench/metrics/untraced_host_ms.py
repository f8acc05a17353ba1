"""Host milliseconds per round inside the program's chunk loop (`run`) that
no span of it names."""
import program_spans


def read(ctx):
    return program_spans.untraced_ms(ctx)

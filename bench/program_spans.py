"""The program's own spans and bus counters over the timed window.

`repro.spans.last("run")` is the aggregate of the newest chunk loop the
program ran in this process; in a benchmark run that is the window's
`ScanDriver.run`. A reader gets nothing (None) from a program that keeps no
such record, or from a record whose `rounds` counter is not the window's.
"""


def record(ctx):
    """The window's `run` aggregate, or None."""
    try:
        from repro import spans
    except ImportError:
        return None
    rec = spans.last("run")
    if rec is None or rec["counts"].get("rounds") != ctx.rounds:
        return None
    return rec


def span_ms(ctx, name: str, parent: str | None = None):
    """Host milliseconds per round in span `name`. Where `parent` is given
    and ran, a `name` that never opened under it reads 0."""
    rec = record(ctx)
    if rec is None:
        return None
    got = rec["spans"].get(name)
    if got is None:
        return 0.0 if parent is not None and parent in rec["spans"] else None
    return 1e3 * got["seconds"] / ctx.rounds


def untraced_ms(ctx):
    """Host milliseconds per round inside `run` that no span names."""
    rec = record(ctx)
    return None if rec is None else 1e3 * rec["self_s"] / ctx.rounds


def counter_mb(ctx, name: str):
    """Counter `name` in MB (1e6 bytes) per round."""
    rec = record(ctx)
    if rec is None or name not in rec["counts"]:
        return None
    return rec["counts"][name] / 1e6 / ctx.rounds

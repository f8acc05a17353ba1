"""Host spans and byte counters of a run, kept as aggregates.

The host steps of a chunked run (availability, batch assembly, paging,
dispatch, flush) open a `span`; the bytes that cross the host–device bus
are added with `count`. A span is a `jax.profiler.TraceAnnotation`, so a
profiler trace shows it on the device trace's clock, and its host seconds
(`time.perf_counter`) are summed into the aggregate of the innermost open
*root* span, under the span's name. `last(root)` returns the aggregate of
the most recently closed root of that name:

    {"seconds": s, "self_s": s,              # the root's own
     "counts": {"rounds": n, "h2d_bytes": b, "d2h_bytes": b},
     "spans": {name: {"parent": p, "count": n, "seconds": s,
                      "self_s": s}}}

`self_s` is a span's seconds minus what its child spans cover, so a root's
`self_s` is the host time inside it that no span names. `parent` is the
name of the enclosing span (None if the name ran under two different
parents). Only aggregates are kept, one small dict per root: the profiler
holds the timeline. There is no switch: with no profiler session a span
costs two clock reads and a dict update, so spans sit at per-chunk and
per-step granularity, never per client or per row. Spans and counts
outside any root are annotations only. Open spans are per thread.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

_local = threading.local()
_last: dict[str, dict] = {}


class _Frame:
    __slots__ = ("name", "agg", "child_s")

    def __init__(self, name: str, agg: dict | None):
        self.name, self.agg, self.child_s = name, agg, 0.0


def _open() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, *, root: bool = False):
    """Time the enclosed host step as `name`. A `root` span starts a new
    aggregate, published by `last(name)` when it closes; a root opened
    inside another root is also a span of the outer one."""
    stack = _open()
    parent = stack[-1] if stack else None
    outer = parent.agg if parent is not None else None
    agg = {"spans": {}, "counts": {}} if root else outer
    frame = _Frame(name, agg)
    stack.append(frame)
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            self_s = dt - frame.child_s
            if parent is not None:
                parent.child_s += dt
            if outer is not None:
                _add(outer["spans"], name, parent.name, dt, self_s)
            if root:
                agg["seconds"], agg["self_s"] = dt, self_s
                _last[name] = agg


def _add(spans: dict, name: str, parent: str, dt: float,
         self_s: float) -> None:
    e = spans.get(name)
    if e is None:
        e = spans[name] = {"parent": parent, "count": 0, "seconds": 0.0,
                           "self_s": 0.0}
    elif e["parent"] != parent:
        e["parent"] = None
    e["count"] += 1
    e["seconds"] += dt
    e["self_s"] += self_s


def count(name: str, n: int | float) -> None:
    """Add `n` to counter `name` of the innermost open root."""
    stack = _open()
    if stack and stack[-1].agg is not None:
        counts = stack[-1].agg["counts"]
        counts[name] = counts.get(name, 0) + n


def last(root: str = "run") -> dict | None:
    """A copy of the aggregate of the newest closed root span `root`, or
    None if none has closed in this process."""
    agg = _last.get(root)
    if agg is None:
        return None
    return {**agg, "counts": dict(agg["counts"]),
            "spans": {k: dict(v) for k, v in agg["spans"].items()}}

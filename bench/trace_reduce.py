"""From a profiler trace to the numbers the per-layer metrics read.

`load_xspace` turns the `.xplane.pb` file JAX's profiler writes into a flat
list of events (`plane`, `line`, `name`, `start_ns`, `dur_ns`): the device
operations of each chip and the benchmark's own host spans. Everything else
works on that list, so the tests check it on a small recorded trace without
a chip. Device time is the union of operation intervals on a chip's
``XLA Ops`` line; an idle gap is a stretch of the traced window with no
operation running, named by the host span that covers most of it.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_LINE = "XLA Ops"
WINDOW = "window"
# control-flow operations span the operations of their bodies
CONTROL_FLOW = re.compile(r"^%(while|conditional|call)[.\s]")


def load_xspace(path: str, span_names) -> list[dict]:
    """Device operations and the host spans named in `span_names`."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name != DEVICE_LINE:
                continue
            for e in line.events:
                if dev or e.name in span_names:
                    out.append({"plane": plane.name, "line": line.name,
                                "name": e.name, "start_ns": e.start_ns,
                                "dur_ns": e.duration_ns})
    return out


def window_bounds(events) -> tuple[float, float]:
    """(start_ns, end_ns) of the host span that marks the timed window."""
    w = [e for e in events if e["name"] == WINDOW
         and not DEVICE_PLANE.match(e["plane"])]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(w)}")
    return w[0]["start_ns"], w[0]["start_ns"] + w[0]["dur_ns"]


def device_ops(events, device: int | None = None) -> list[dict]:
    """Operation events of one chip (`device`), or of every chip."""
    out = []
    for e in events:
        m = DEVICE_PLANE.match(e["plane"])
        if m and (device is None or int(m.group(1)) == device):
            out.append(e)
    return out


def devices(events) -> list[int]:
    """The chips that ran an operation in the trace."""
    return sorted({int(DEVICE_PLANE.match(e["plane"]).group(1))
                   for e in device_ops(events)})


def busy_intervals(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the operation intervals, clipped to [lo, hi], in order."""
    spans = sorted((max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"],
                                                hi)) for e in ops)
    merged: list[list[float]] = []
    for a, b in spans:
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(events) -> float:
    """Seconds with an operation running inside the window, averaged over
    the chips that ran any."""
    lo, hi = window_bounds(events)
    chips = devices(events)
    if not chips:
        return 0.0
    total = sum(b - a for d in chips
                for a, b in busy_intervals(device_ops(events, d), lo, hi))
    return total / len(chips) / 1e9


def idle_gaps(events, device: int = 0) -> list[tuple[float, float]]:
    """Stretches of the window in which chip `device` ran nothing."""
    lo, hi = window_bounds(events)
    gaps, at = [], lo
    for a, b in busy_intervals(device_ops(events, device), lo, hi):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def name_gaps(events, gaps, top: int = 10) -> list[list]:
    """The `top` longest gaps as [host span covering most of it, seconds]
    ("other" where no span covers it)."""
    spans = [e for e in events if not DEVICE_PLANE.match(e["plane"])
             and e["name"] != WINDOW]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "other", 0.0
        for s in spans:
            c = min(b, s["start_ns"] + s["dur_ns"]) - max(a, s["start_ns"])
            if c > cover:
                best, cover = s["name"], c
        out.append([best, (b - a) / 1e9])
    return out


def op_seconds(events, pattern: str, device: int | None = None) -> float:
    """Summed device seconds of the operations whose name matches
    `pattern` (a regular expression), inside the window."""
    lo, hi = window_bounds(events)
    rx = re.compile(pattern)
    return sum(max(0, min(e["start_ns"] + e["dur_ns"], hi)
                   - max(e["start_ns"], lo))
               for e in device_ops(events, device)
               if rx.search(e["name"])) / 1e9


def short_name(name: str) -> str:
    """An operation's HLO instruction name and opcode, without its shapes:
    "%fusion.108 = f32[32000]{...} fusion(...)" -> "%fusion.108 fusion"."""
    head, _, rest = name.partition(" = ")
    m = re.search(r" ([a-z][a-z0-9_-]*)\(", " " + rest)
    return f"{head} {m.group(1)}" if m else head


def top_ops(events, top: int = 10) -> list[list]:
    """The `top` operations by summed device seconds in the window
    (averaged over chips), as [name, seconds]; loops and other control
    flow, which contain operations of their own, are left out."""
    lo, hi = window_bounds(events)
    chips = max(len(devices(events)), 1)
    acc: dict[str, float] = {}
    for e in device_ops(events):
        if CONTROL_FLOW.match(e["name"]):
            continue
        d = min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo)
        if d > 0:
            k = short_name(e["name"])
            acc[k] = acc.get(k, 0.0) + d / 1e9 / chips
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            [:top]]

"""From the profiler's trace to device busy time, idle gaps and op time.

:func:`extract` reads an ``.xplane.pb`` into a small dict that
everything else works on, and that tests keep as a recorded fixture::

    {"window": [start_ns, end_ns],                 # the bench.window span
     "devices": {"<id>": [[op, start_ns, dur_ns], ...]},
     "host": [[span, start_ns, dur_ns], ...]}      # the benchmark's spans

A device's ops are those of its ``XLA Ops`` line, the ops the chip
executes one after another; an op is named by its HLO instruction
(``while.201``, ``fusion.7``), and ops nest: a while loop's event spans
its body's.  The ``Async XLA Ops`` line, which holds async copies and
collectives from start to done, is left out: an op in flight there is
not work of the chip, and a collective's cost to the chip is the time
its start and done ops hold the ``XLA Ops`` line.  Device and host
events share the trace's clock, which the profiler aligns to about a
millisecond.  Busy time is the union of a device's op intervals inside
the window; an idle gap is a stretch of the window with no op, named by
the host span that covers most of it.
"""
from __future__ import annotations

import collections
import re
from typing import Any, Iterable

# the line of a TPU plane that holds one event per op the chip executes
SYNC_OPS = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

WINDOW_SPAN = "bench.window"
IDLE_HOST = "no benchmark span"


def extract(xplane_path: str, host_spans: Iterable[str]) -> dict[str, Any]:
    """The device ops and the named host spans of one recorded trace."""
    from jax.profiler import ProfileData

    wanted = set(host_spans) | {WINDOW_SPAN}
    devices: dict[str, list[list[Any]]] = {}
    host: list[list[Any]] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == SYNC_OPS:
                devices[m.group(1)] = [
                    [op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                    for e in line.events]
            elif plane.name.startswith("/host:"):
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events if e.name in wanted)
    windows = [h for h in host if h[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW_SPAN} spans")
    _, start, dur = windows[0]
    return {"window": [start, start + dur], "devices": devices,
            "host": [h for h in host if h[0] != WINDOW_SPAN]}


def op_name(text: str) -> str:
    """``%while.201 = (s32[], ...) while(...)`` -> ``while.201``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _ops(trace: dict[str, Any], device: str) -> list[list[Any]]:
    return trace["devices"].get(device, [])


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def _clip(events: list[list[Any]], window: list[int]) -> list[tuple[int, int]]:
    lo, hi = window
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def busy_intervals(trace: dict[str, Any], device: str) -> list[tuple[int, int]]:
    """Union of the device's op intervals inside the window, in order."""
    return _union(_clip(_ops(trace, device), trace["window"]))


def has_ops(trace: dict[str, Any], device: str) -> bool:
    """Whether any op of the device was recorded: a trace without the
    device's plane has nothing to read."""
    return bool(_ops(trace, device))


def window_s(trace: dict[str, Any]) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def busy_s(trace: dict[str, Any], device: str) -> float:
    return sum(b - a for a, b in busy_intervals(trace, device)) / 1e9


def idle_share(trace: dict[str, Any], device: str) -> float:
    """1 - busy / window, as a fraction."""
    return 1.0 - busy_s(trace, device) / window_s(trace)


def op_seconds(trace: dict[str, Any], device: str, pattern: str) -> float:
    """Device time, inside the window, in which an op whose name matches
    the regular expression ``pattern`` ran: the union of their intervals,
    so nested matches count once."""
    rx = re.compile(pattern)
    events = [e for e in _ops(trace, device) if rx.search(e[0])]
    return sum(b - a for a, b in _union(_clip(events, trace["window"]))) / 1e9


def top_ops(trace: dict[str, Any], device: str, n: int = 10
            ) -> list[list[Any]]:
    """The ``n`` ops with the most self time (their time less the ops
    nested in them) inside the window, as [name, seconds]."""
    lo, hi = trace["window"]
    events = sorted(_ops(trace, device), key=lambda e: (e[1], -e[2]))
    total: collections.Counter[str] = collections.Counter()
    open_: list[tuple[str, int]] = []  # enclosing ops: (name, end)
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        while open_ and open_[-1][1] <= s:
            open_.pop()
        if b > a:
            total[name] += b - a
            if open_:
                total[open_[-1][0]] -= b - a
        open_.append((name, s + d))
    return [[name, ns / 1e9] for name, ns in total.most_common(n) if ns > 0]


def _innermost(spans: list[list[Any]]) -> list[tuple[int, int, str]]:
    """The host spans cut into pieces that do not overlap, each named by
    the innermost span open over it (spans of one thread nest)."""
    pieces: list[tuple[int, int, str]] = []
    stack: list[tuple[str, int]] = []  # open spans: (name, end)
    at = 0
    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            inner, end = stack.pop()
            pieces.append((at, end, inner))
            at = end
        if stack:
            pieces.append((at, s, stack[-1][0]))
        stack.append((name, s + d))
        at = s
    while stack:
        inner, end = stack.pop()
        pieces.append((at, end, inner))
        at = end
    return [p for p in pieces if p[1] > p[0]]


def idle_gaps(trace: dict[str, Any], device: str, n: int = 10
              ) -> list[list[Any]]:
    """Idle time of the device by what the host was doing, as
    [host span, seconds], the ``n`` largest: each stretch of the window
    with no op goes to the innermost benchmark span open over it."""
    lo, hi = trace["window"]
    gaps, at = [], lo
    for a, b in busy_intervals(trace, device):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    pieces = _innermost(trace["host"])
    total: collections.Counter[str] = collections.Counter()
    i = 0
    for a, b in gaps:
        covered = 0
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, name = pieces[j]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                total[name] += overlap
                covered += overlap
            j += 1
        if b - a > covered:
            total[IDLE_HOST] += b - a - covered
    return [[name, ns / 1e9] for name, ns in total.most_common(n)]

"""The reduction from trace events to numbers. Input is plain tuples
``(plane, line, name, start_ns, duration_ns)``; nothing here touches jax
or the profiler's file format (xplane.py does), so the arithmetic is
tested on hand-written events with known answers.

Definitions (on-chip-measurement guide, section 4):
- busy: the union of the intervals in which an operation runs on a
  device, clipped to the window; idle share = 1 - busy / window.
- kernel time: the sum of the device durations of that kernel's events.
- exposed collective time: time inside collective operations on a chip
  during which no other operation runs on that chip.
All per-chip figures are averaged over the chips in the trace.
"""
from __future__ import annotations

import re

#: planes that are devices, and the line of a device plane that holds one
#: event per executed operation (pinned by the recorded v5e trace under
#: tests/benchmark/data/)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: the window markers the benchmark writes as TraceAnnotations
MARK_START = "bench:window_start"
MARK_END = "bench:window_end"
ANNOTATION_PREFIX = "bench:"

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


def device_planes(events, pattern=DEVICE_PLANE) -> list[str]:
    return sorted({e[0] for e in events if pattern.match(e[0])})


def op_events(events, plane: str, line: str = OPS_LINE) -> list[tuple]:
    """(name, start, end) of the operations of one device plane."""
    return [(e[2], e[3], e[3] + e[4]) for e in events
            if e[0] == plane and e[1] == line]


def union(intervals) -> list[tuple]:
    """Merge (start, end) intervals; the result is sorted and disjoint."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, window) -> list[tuple]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes) -> list[tuple]:
    """The parts of the disjoint sorted ``intervals`` not covered by the
    disjoint sorted ``holes``."""
    out = []
    for a, b in intervals:
        cur = a
        for ha, hb in holes:
            if hb <= cur:
                continue
            if ha >= b:
                break
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def marked_window(events) -> tuple | None:
    """The traced window on the profiler's clock: from the END of the
    ``bench:window_start`` annotation to the START of
    ``bench:window_end``. None when either marker is missing."""
    start = [e[3] + e[4] for e in events if e[2].startswith(MARK_START)]
    end = [e[3] for e in events if e[2].startswith(MARK_END)]
    if not start or not end or min(end) <= max(start):
        return None
    return (max(start), min(end))


def extent(events, planes) -> tuple | None:
    spans = [(s, e) for p in planes for _, s, e in op_events(events, p)]
    if not spans:
        return None
    return (min(s for s, _ in spans), max(e for _, e in spans))


def busy(events, window, planes=None) -> dict:
    """``{"busy_s", "window_s", "idle_share", "per_chip"}``: device-busy
    seconds inside ``window`` (ns), averaged over the chips."""
    planes = device_planes(events) if planes is None else planes
    per_chip = []
    for p in planes:
        spans = union(clip([(s, e) for _, s, e in op_events(events, p)],
                           window))
        per_chip.append(length(spans) / 1e9)
    window_s = (window[1] - window[0]) / 1e9
    busy_s = sum(per_chip) / len(per_chip) if per_chip else 0.0
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "per_chip": per_chip}


def kernel_seconds(events, pattern, window, planes=None) -> dict:
    """Summed device seconds (clipped to the window) and call count of
    the operations whose name matches ``pattern``, per chip average."""
    rx = re.compile(pattern)
    planes = device_planes(events) if planes is None else planes
    secs, calls = [], []
    for p in planes:
        hit = [(s, e) for n, s, e in op_events(events, p) if rx.search(n)]
        hit = clip(hit, window)
        secs.append(length(hit) / 1e9)
        calls.append(len(hit))
    n = max(len(planes), 1)
    return {"seconds": sum(secs) / n, "calls": sum(calls) / n}


MODULES_LINE = "XLA Modules"


def kernel_seconds_within(events, pattern, module_pattern, window,
                          planes=None) -> dict:
    """As :func:`kernel_seconds`, but only inside the runs of the
    programs whose name (``XLA Modules`` line) matches
    ``module_pattern``, and only runs that lie whole inside the window.
    Also returns how many such runs there were (per chip average)."""
    rx, mrx = re.compile(pattern), re.compile(module_pattern)
    planes = device_planes(events) if planes is None else planes
    secs, calls, runs = [], [], []
    for p in planes:
        mods = [(s, e) for n, s, e in op_events(events, p, MODULES_LINE)
                if mrx.search(n) and s >= window[0] and e <= window[1]]
        mods = union(mods)
        hit = [(s, e) for n, s, e in op_events(events, p) if rx.search(n)]
        inside = [(s, e) for s, e in hit
                  if any(ms <= s and e <= me for ms, me in mods)]
        secs.append(length(inside) / 1e9)
        calls.append(len(inside))
        runs.append(len(mods))
    n = max(len(planes), 1)
    return {"seconds": sum(secs) / n, "calls": sum(calls) / n,
            "runs": sum(runs) / n}


def collective_split(events, window, planes=None) -> dict:
    """Per-chip average seconds inside collective operations, split into
    the part during which no other operation runs on that chip
    (``exposed_s``) and the part hidden behind one (``hidden_s``).

    An asynchronous collective appears as a ``-start`` and a ``-done``
    operation; the transfer is in flight between them. Its interval is
    taken from the start of the ``-start`` to the end of the matching
    ``-done`` (matched in order per base name); whatever other operation
    runs in that interval hides it."""
    planes = device_planes(events) if planes is None else planes
    exposed, hidden = [], []
    for p in planes:
        ops = sorted(op_events(events, p), key=lambda o: o[1])
        coll, other, open_starts = [], [], {}
        for name, s, e in ops:
            # the v5e names an operation by its HLO text: "%all-reduce.1 = ..."
            first = name.split(" ", 1)[0].lstrip("%")
            if not COLLECTIVE.match(first):
                other.append((s, e))
                continue
            base = re.sub(r"-(start|done)(\.\d+)?$", "", first)
            if re.search(r"-start(\.\d+)?$", first):
                open_starts.setdefault(base, []).append(s)
            elif re.search(r"-done(\.\d+)?$", first) \
                    and open_starts.get(base):
                coll.append((open_starts[base].pop(0), e))
            else:
                coll.append((s, e))
        for starts in open_starts.values():      # never completed
            coll.extend((s, window[1]) for s in starts)
        coll = union(clip(coll, window))
        other = union(clip(other, window))
        exp = subtract(coll, other)
        exposed.append(length(exp) / 1e9)
        hidden.append((length(coll) - length(exp)) / 1e9)
    n = max(len(planes), 1)
    return {"exposed_s": sum(exposed) / n, "hidden_s": sum(hidden) / n}


def short_name(name: str, width: int = 110) -> str:
    """A trace operation's name cut to something a person can read: the
    leading ``%``, layout annotations and the numeric suffix of the
    instruction go, so the same operation of every layer sums under one
    name; the output shapes and the operation kind stay."""
    name = re.sub(r"\{[^{}]*\}", "", name.lstrip("%"))
    name = re.sub(r"^([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*)\.\d+", r"\1",
                  name)
    return re.sub(r"\s+", " ", name)[:width]


def top_ops(events, window, n: int = 10, planes=None) -> list[list]:
    """The ``n`` operations with the most device time in the window,
    summed by name over calls and averaged over chips:
    ``[[name, seconds], ...]``."""
    planes = device_planes(events) if planes is None else planes
    total: dict[str, float] = {}
    for p in planes:
        for name, s, e in op_events(events, p):
            got = clip([(s, e)], window)
            if got:
                key = short_name(name)
                total[key] = total.get(key, 0.0) + length(got) / 1e9
    k = max(len(planes), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / k] for name, secs in ranked]


def idle_gaps(events, window, n: int = 5, plane=None) -> list[list]:
    """The ``n`` longest gaps with no operation on the (first) device,
    each labelled with the benchmark annotation that covers most of it,
    or ``unattributed``: ``[[label, seconds], ...]``."""
    planes = device_planes(events)
    if not planes:
        return []
    plane = planes[0] if plane is None else plane
    spans = union(clip([(s, e) for _, s, e in op_events(events, plane)],
                       window))
    gaps = subtract([window], spans)
    notes = [(e[2], e[3], e[3] + e[4]) for e in events
             if e[2].startswith(ANNOTATION_PREFIX)
             and not e[2].startswith((MARK_START, MARK_END))
             and not DEVICE_PLANE.match(e[0])]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        cover: dict[str, float] = {}
        for name, s, e in notes:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                label = name.split(" ", 1)[0]
                cover[label] = cover.get(label, 0.0) + ov
        label = max(cover, key=cover.get) if cover else "unattributed"
        if cover and cover[label] < 0.5 * (b - a):
            label = f"unattributed+{label}"
        out.append([label, (b - a) / 1e9])
    return out

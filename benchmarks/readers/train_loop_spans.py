"""The training loop's own spans (``bigdl:host:*`` annotations the
program writes through ``observability/tracing.py``) against the device
timeline of the same trace.

params ``metric``:

- ``host_ms_per_step``: the summed duration of the ``train_iteration``
  spans that lie whole inside the traced window, less their
  ``input_wait`` and ``loss_drain`` children (the two waits), over the
  number of those iterations: the host's own work a step. (The iteration
  in which the profiler starts and the one in which it stops are not
  recorded whole; they are left out, not guessed.) ms.
- ``gap_attributed_share``: of the FIRST device's idle time in the
  traced window (as ``trace_reduce.idle_gaps`` finds it), the part that
  lies inside a LEAF span of the loop's thread. A parent's self time
  counts as unattributed. The note gives idle seconds by span and names
  the span that holds most of each of the five longest gaps. %.

The loop's thread is the line that holds ``train_iteration``. The host
plane's lines carry the thread's OS name, which every thread of a
python process shares, so spans the program writes from other threads
are named in ``other_threads`` and left out.
"""
from __future__ import annotations

from benchmarks import trace_reduce as tr

PREFIX = "bigdl:"
ITERATION = "bigdl:host:train_iteration"
WAITS = ("bigdl:host:input_wait", "bigdl:host:loss_drain")


def loop_spans(events, other_threads=()) -> list[tuple]:
    """``(label, start, end)`` of the program's spans on the line(s)
    that hold ``train_iteration``, sorted by start."""
    def label(e):
        return e[2].split(" ", 1)[0]
    lines = {(e[0], e[1]) for e in events if label(e) == ITERATION}
    return sorted(
        ((label(e), e[3], e[3] + e[4]) for e in events
         if (e[0], e[1]) in lines and label(e).startswith(PREFIX)
         and label(e) not in other_threads and e[4] > 0),
        key=lambda s: (s[1], -s[2]))


def leaves(spans) -> list[tuple]:
    """The spans that hold no other span."""
    return [s for s in spans
            if not any(o is not s and s[1] <= o[1] and o[2] <= s[2]
                       for o in spans)]


def _inside(intervals, cover) -> float:
    """Length of the part of ``intervals`` that ``cover`` covers."""
    return tr.length(intervals) - tr.length(
        tr.subtract(intervals, tr.union(cover)))


def host_ms_per_step(rec, spans, window):
    its = [s for s in spans if s[0] == ITERATION
           and window[0] <= s[1] and s[2] <= window[1]]
    if not its:
        return None
    total = sum(e - s for _, s, e in its)
    waits = sum(e - s for n, s, e in spans if n in WAITS
                and any(a <= s and e <= b for _, a, b in its))
    return {"value": (total - waits) / 1e6 / len(its),
            "iterations": len(its), "iteration_ms": total / 1e6 / len(its),
            "waits_ms": waits / 1e6 / len(its)}


def gap_attributed_share(rec, spans, window):
    tw = rec["trace_window"]
    plane = tw["planes"][0]
    busy = tr.union(tr.clip(
        [(s, e) for _, s, e in tr.op_events(rec["trace_events"], plane)],
        window))
    idle = tr.subtract([window], busy)
    idle_ns = tr.length(idle)
    if idle_ns <= 0:
        return None
    leaf = leaves(spans)
    by_name: dict[str, float] = {}
    for name in {s[0] for s in leaf}:
        by_name[name] = _inside(
            idle, [(s, e) for n, s, e in leaf if n == name]) / 1e9
    longest = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:5]:
        cover = {n: _inside([(a, b)], [(s, e)]) for n, s, e in leaf
                 if s < b and e > a}
        name = max(cover, key=cover.get) if cover else "unattributed"
        longest.append([name, (b - a) / 1e9,
                        cover.get(name, 0.0) / (b - a)])
    attributed = _inside(idle, [(s, e) for _, s, e in leaf])
    return {"value": 100.0 * attributed / idle_ns,
            "idle_s": idle_ns / 1e9,
            "idle_s_by_span": dict(sorted(by_name.items(),
                                          key=lambda kv: -kv[1])),
            "longest_gaps": longest}


METRICS = {"host_ms_per_step": host_ms_per_step,
           "gap_attributed_share": gap_attributed_share}


def read(rec, params):
    tw = rec.get("trace_window")
    if not tw or not rec.get("trace_events"):
        return None
    spans = loop_spans(rec["trace_events"],
                       tuple(params.get("other_threads", ())))
    if not spans:
        return None
    return METRICS[params["metric"]](rec, spans, tw["window_ns"])

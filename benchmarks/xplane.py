"""A thin reader: the profiler's ``.xplane.pb`` -> plain event tuples
``(plane, line, name, start_ns, duration_ns)``, the only form the trace
reduction (trace_reduce.py) takes. Uses nothing but jax
(``jax.profiler.ProfileData``).

An event's ``name`` is the profiler's own name for it. Where the event
carries the framework's operation name in its stats (``tf_op`` /
``long_name``: for a Pallas kernel that is where the ``pallas_call``
name sits), it is appended after one space, so a kernel can be found by
its stable name whatever XLA called the custom call.
"""
from __future__ import annotations

import glob
import os

_DETAIL_STATS = ("tf_op", "long_name", "name")


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log dir."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _detail(event) -> str:
    try:
        stats = dict(event.stats)
    except Exception:
        return ""
    for key in _DETAIL_STATS:
        v = stats.get(key)
        if isinstance(v, str) and v and v != event.name:
            return v
    return ""


def read_events(path: str, *, keep_plane=None) -> list[tuple]:
    """Every event of every line of every plane (or of the planes
    ``keep_plane(name)`` accepts) as plain tuples."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if keep_plane is not None and not keep_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                detail = _detail(ev)
                name = f"{ev.name} {detail}" if detail else ev.name
                out.append((plane.name, line.name, name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out

"""Profiler control for the traced run: a few steps, or a few seconds,
INSIDE the measured window, bracketed by two marker annotations so the
reduction knows the window on the profiler's own clock."""
from __future__ import annotations

import os
import shutil

from benchmarks import trace_reduce, xplane
from benchmarks.manifest import REPO

TRACE_DIR = os.path.join(REPO, ".bench_trace")


class TraceSession:
    """start() ... stop() around the traced part; ``events()`` afterwards
    reads the trace into plain tuples and removes the files."""

    def __init__(self, tag: str):
        self.dir = os.path.join(TRACE_DIR, tag)
        self.active = False
        self.done = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # no Python stack sampling: it slows the host being measured and
        # fills the trace; TraceAnnotations need only the host tracer
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.active = True
        with jax.profiler.TraceAnnotation(trace_reduce.MARK_START):
            pass

    def stop(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(trace_reduce.MARK_END):
            pass
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def events(self, keep_dir: str | None = None) -> list[tuple]:
        path = xplane.find_xplane(self.dir)
        if path is None:
            return []
        events = xplane.read_events(path)
        if keep_dir:
            os.makedirs(keep_dir, exist_ok=True)
            shutil.copy(path, os.path.join(keep_dir,
                                           os.path.basename(path)))
        shutil.rmtree(self.dir, ignore_errors=True)
        return events


def reduce_window(events: list[tuple]) -> dict | None:
    """The traced window and the device-busy time in it."""
    planes = trace_reduce.device_planes(events)
    if not planes:
        return None
    marked = trace_reduce.marked_window(events)
    window = marked or trace_reduce.extent(events, planes)
    if window is None:
        return None
    out = trace_reduce.busy(events, window, planes)
    out.update(window_ns=window, planes=planes, marked=marked is not None)
    return out

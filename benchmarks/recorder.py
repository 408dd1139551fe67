"""The benchmark's own clocks on the program's events.

- :class:`EventRecorder` is a ``RequestTracker`` that stamps EVERY event
  of EVERY request on the benchmark's clock and keeps them all (the
  program's default tracker tail-samples and keeps 256). It is handed to
  ``Router(tracker=...)``; the program calls ``begin/event/finish``.
- :class:`StepRecorder` is the summary object handed to
  ``Optimizer.set_train_summary``: the program calls ``add_scalar`` as it
  drains each step's loss, and the recorder stamps the arrival.

Both wrap their callbacks in ``jax.profiler.TraceAnnotation`` so a traced
run can label a device gap with what the benchmark saw the host doing.
"""
from __future__ import annotations

import threading
import time

from bigdl_tpu.observability.request_trace import RequestTracker

clock = time.monotonic


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class EventRecorder(RequestTracker):
    """Keeps (request id, event name, benchmark-clock time, fields) for
    every event the serving path emits."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._rec_mu = threading.Lock()
        self.events: list[tuple] = []

    def _stamp(self, rid, event, fields) -> None:
        t = clock()
        with self._rec_mu:
            self.events.append((rid, event, t, fields))

    def begin(self, request_id, **fields):
        self._stamp(request_id, "submit", fields)
        return super().begin(request_id, **fields)

    def event(self, request_id, event, **fields):
        with _annotate(f"bench:event:{event}"):
            self._stamp(request_id, event, fields)
            return super().event(request_id, event, **fields)

    def finish(self, request_id, *, status: str = "ok"):
        self._stamp(request_id, "finish", {"status": status})
        return super().finish(request_id, status=status)

    def snapshot(self) -> list[tuple]:
        with self._rec_mu:
            return list(self.events)


class StepRecorder:
    """In-memory stand-in for a TrainSummary. One row per drained step:
    ``{"neval", "t", "loss", "input_s"}`` (the program's ``Throughput``
    and ``DeviceStepTime`` scalars are host clock around dispatch and are
    not used); ``t`` is when the
    loss arrived on the benchmark's clock. ``on_loss(row)`` runs after
    every Loss scalar (the window and trace control hang off it)."""

    def __init__(self, on_loss=None):
        self.rows: list[dict] = []
        self._by_step: dict[int, dict] = {}
        self.on_loss = on_loss

    def add_scalar(self, tag, value, step):
        with _annotate(f"bench:summary:{tag}"):
            row = self._by_step.get(step)
            if row is None:
                row = {"neval": int(step)}
                self._by_step[step] = row
                self.rows.append(row)
            if tag == "Loss":
                row["t"] = clock()
                row["loss"] = float(value)
                if self.on_loss is not None:
                    self.on_loss(row)
            elif tag == "HostInputTime":
                row["input_s"] = float(value)
        return self

"""Span tracer exporting Chrome trace-event JSON (Perfetto-loadable).

``trace.span("device step")`` wraps a HOST phase in a complete ("X")
trace event; :meth:`Tracer.export` writes the standard
``{"traceEvents": [...]}`` JSON that chrome://tracing and
https://ui.perfetto.dev open directly (SURVEY §2.7 per-module timing
hooks, rebuilt for the XLA era where per-op host timers cannot see
inside a compiled step).

THE NO-SYNC CONTRACT. Spans read ``time.monotonic()`` and append to a
host list — nothing else. They must wrap code OUTSIDE jitted functions
(dispatch, host input, readback); they never call ``block_until_ready``
and never make a span boundary force one. Where the surrounding loop
*intentionally* blocks on a device value (the optimizers' packed loss
drain, ``np.asarray(tokens)``), pass ``host_sync="why"`` to
:meth:`span` or call :meth:`host_sync` so the sync is EXPLICIT in the
trace instead of an invisible stall. dev/lint.py enforces that this
package never imports jax at module top level.

A process-wide tracer (disabled by default) sits behind module-level
``span`` / ``instant`` / ``enable`` / ``export`` so call sites just do::

    from bigdl_tpu.observability import trace
    with trace.span("loss drain", host_sync="packed loss readback"):
        ...

THE PROFILER'S CLOCK. Every span and instant is ALSO a
``jax.profiler.TraceAnnotation`` named ``bigdl:<cat>:<name>`` (spaces as
``_``: ``bigdl:host:loss_drain``, ``bigdl:serving:decode_burst``) with
the span's scalar ``args`` as its stats — whether or not this tracer is
enabled. An annotation is a no-op unless a profiler session is running
(``Optimizer.set_profiler``, the benchmark's traced run), so it needs no
switch; inside one, the program's spans sit on ``/host:CPU`` on the same
clock as the device's operations, nested per thread. jax is imported on
first use, never at module import.
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import re
import threading
import time

__all__ = ["Tracer", "get_tracer", "enable", "disable", "enabled",
           "span", "instant", "host_sync", "export", "to_dict", "clear",
           "ProgramScopes", "matmuls_fed_by", "in_profiler_session",
           "state"]

_annotation_cls = None


class _NoAnnotation(contextlib.nullcontext):
    """Stands in for the profiler annotation where jax is not installed
    (the package's host-only contract)."""

    def __init__(self, label: str, **stats):
        super().__init__()

    @staticmethod
    def is_enabled() -> bool:
        return False


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, imported on first use."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:       # a host-only process without jax
            TraceAnnotation = _NoAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls


def _annotation(name: str, cat: str, args: dict):
    """The span as a profiler annotation. Outside a profiler session
    that is an empty context manager, and the stats are not even
    built."""
    cls = _annotation_class()
    label = f"bigdl:{cat}:{name.replace(' ', '_')}"
    if args and cls.is_enabled():
        return cls(label, **{k: v for k, v in args.items()
                             if isinstance(v, (bool, int, float, str))})
    return cls(label)


def in_profiler_session() -> bool:
    """Whether a profiler session is running: what a statement that
    costs something to make asks first."""
    return _annotation_class().is_enabled()


def _payload(payload: dict) -> str:
    """``payload`` as the JSON an annotation's ``long_name`` stat
    carries. (The profiler's encoding ends an annotation's stats at
    ``#``.)"""
    return json.dumps(payload, separators=(",", ":")).replace("#", "_")


def state(name: str, cat: str, payload: dict) -> None:
    """What the program knows and a trace cannot show, stated into the
    running profiler session as ONE annotation ``bigdl:<cat>:<name>``
    whose ``long_name`` stat is ``payload`` as JSON: a reader of the
    trace finds it by its label, on the device's clock. Nothing outside
    a session."""
    if in_profiler_session():
        with _annotation(name, cat, {"long_name": _payload(payload)}):
            pass


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_HLO_CALLED = re.compile(r" (?:calls|to_apply)=%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r' metadata=\{[^}]*op_name="([^"]*)"')
#: instructions that never run as a device operation of their own
_HLO_NO_OP = frozenset({"parameter", "get-tuple-element", "tuple",
                        "constant", "bitcast"})


def _hlo_rows(hlo_text: str):
    """``(module name, {computation: [(instruction, opcode, op_name or
    None, [called computations], [operand instructions])]})`` of a
    compiled program's text."""
    program, current = "", ""
    rows: dict[str, list] = {}
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            if line.startswith("HloModule "):
                program = line.split()[1].rstrip(",")
            else:
                m = _HLO_COMPUTATION.match(line)
                if m:
                    current = m.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m:
            op_name = _HLO_OP_NAME.search(line)
            operands = line[m.end():].partition(")")[0].split(",")
            rows.setdefault(current, []).append(
                (m.group(1), m.group(2), op_name and op_name.group(1),
                 _HLO_CALLED.findall(line),
                 [o.split()[-1].lstrip("%") for o in operands if o.strip()]))
    return program, rows


def matmuls_fed_by(hlo_text: str, opcode: str) -> dict:
    """``{instruction: op_name}`` of the operations of a compiled
    program (``compiled.as_text()``) in which a ``convolution`` reads,
    through producers fused into it, the result of an ``opcode``
    instruction. Such a producer is evaluated as the matmul streams that
    operand, once for every pass over it (PERF.md section 6, PR 28). An
    ``opcode`` in the matmul's epilogue, after the ``convolution``, is
    evaluated once and is not counted."""
    _, rows = _hlo_rows(hlo_text)
    called = {c for body in rows.values() for row in body for c in row[3]}

    def holds(computation):
        return any(op == opcode or any(map(holds, callees))
                   for _, op, _, callees, _ in rows.get(computation, ()))

    def fed(computation):
        tainted = set()
        for name, op, _, callees, operands in rows.get(computation, ()):
            reads = not tainted.isdisjoint(operands)
            if (op == "convolution" and reads) or any(map(fed, callees)):
                return True
            if reads or op == opcode or any(map(holds, callees)):
                tainted.add(name)
        return False

    return {name: op_name or ""
            for computation, body in rows.items() if computation not in called
            for name, _, op_name, callees, _ in body
            if any(map(fed, callees))}


def _program_scopes(hlo_text: str) -> dict:
    """``{"program": <module name>, "scopes": {op_name: [instruction
    names]}, "inside": {scope: [instruction names]}}`` of a compiled
    program's text (``compiled.as_text()``).

    The TPU's profiler names a device operation by its HLO instruction
    and carries nothing of ``jax.named_scope``; the compiled text does,
    as each instruction's ``op_name`` (``jit(train_step)/transpose(
    jvp(model))/block_3/...``). ``scopes`` is the join between the two.
    Only instructions that run as operations of their own are kept: what
    sits inside a fusion, a reduction's body or an asynchronous wrapper
    runs under the calling instruction's name, which is its ROOT's
    ``op_name``. ``inside`` says what else such an operation holds: the
    scopes (``op_name`` less its last part, the primitive) of the
    instructions in the computations it calls, other than its own — XLA
    fuses a weight's optimizer update into that weight's gradient
    matmul, and the fusion reads as backward."""
    program, rows = _hlo_rows(hlo_text)
    called = {c for body in rows.values() for row in body for c in row[3]}

    def scopes_in(computation, seen):
        for _, _, op_name, callees, _ in rows.get(computation, ()):
            if op_name and "/" in op_name:
                seen.add(op_name.rpartition("/")[0])
            for c in callees:
                scopes_in(c, seen)
        return seen

    scopes: dict[str, list] = {}
    inside: dict[str, list] = {}
    for computation, body in rows.items():
        if computation in called:
            continue
        for name, opcode, op_name, callees, _ in body:
            if not op_name or opcode in _HLO_NO_OP:
                continue
            scopes.setdefault(op_name, []).append(name)
            held = set()
            for c in callees:
                scopes_in(c, held)
            for scope in sorted(held - {op_name.rpartition("/")[0]}):
                inside.setdefault(scope, []).append(name)
    return {"program": program, "scopes": scopes, "inside": inside}


class ProgramScopes:
    """The instruction -> scope tables of the programs a training loop
    compiled, handed to every profiler session once.

    ``add(compiled)`` at compile time (a profiler session starts long
    after it); ``annotate()`` once an iteration: in the first one that
    finds a session running it writes each table as ONE annotation,
    ``bigdl:compile:step_scopes``, so whoever reads the trace can name
    each device operation of that program's runs by the part of the step
    it belongs to. The table is that annotation's ``long_name`` stat, as
    JSON, serialised in ``add`` and not inside the session.

    The table's ``"memory"`` is what the compiler says the program
    holds (``compile_watch.memory_stats``: ``arg_bytes``,
    ``output_bytes``, ``alias_bytes``, ``temp_bytes``, ``code_bytes``
    and ``peak_hbm_bytes`` = arguments + outputs + temporaries -
    aliased; one device's, under a mesh), or ``null`` where the
    executable gives no memory analysis: never zeros."""

    def __init__(self):
        self._tables: list[str] = []
        self._written = 0

    def add(self, compiled) -> None:
        from bigdl_tpu.observability.compile_watch import memory_stats
        log = logging.getLogger(__name__)
        try:
            table = _program_scopes(compiled.as_text())
        except Exception as e:    # a trace aid must not stop training
            log.warning(
                "no scope table for a compiled step, so a profiler "
                "trace cannot name its device operations by scope: %r", e)
            return
        table["memory"] = memory_stats(compiled) or None
        if table["memory"] is None:
            log.warning(
                "no memory analysis for compiled step %s, so a profiler "
                "trace cannot say what it holds", table["program"])
        self._tables.append(_payload(table))

    def annotate(self) -> None:
        if not in_profiler_session():
            self._written = 0
        elif self._written < len(self._tables):
            for table in self._tables[self._written:]:
                with _annotation("step scopes", "compile",
                                 {"long_name": table}):
                    pass
            self._written = len(self._tables)


class Tracer:
    """Thread-safe event buffer on monotonic clocks. ``ts`` is
    microseconds since tracer creation; ``pid``/``tid`` identify the
    emitting process/thread; the buffer is bounded (drops counted, not
    grown) so an unattended server can leave tracing on."""

    def __init__(self, max_events: int = 1_000_000,
                 enabled: bool = False):
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._dropped = 0
        self._max = max_events
        self._enabled = bool(enabled)
        self._t0 = time.monotonic()
        self._pid = os.getpid()
        # taps see every event even while export-tracing is off — the
        # flight recorder's ring buffer rides here (flight_recorder.py)
        self._taps: list = []

    # -- lifecycle --
    def enable(self):
        self._enabled = True
        return self

    def disable(self):
        self._enabled = False
        return self

    @property
    def enabled(self) -> bool:
        return self._enabled

    def clear(self):
        with self._lock:
            self._events = []
            self._dropped = 0
        return self

    # -- taps (flight recorder et al.) --
    def add_tap(self, fn) -> None:
        """Subscribe ``fn(event_dict)`` to every span/instant event,
        INDEPENDENT of the enabled flag — a disabled tracer with
        a tap still builds events (but buffers nothing). Tap errors are
        swallowed: observability must never take down the loop."""
        with self._lock:
            if fn not in self._taps:
                self._taps.append(fn)

    def remove_tap(self, fn) -> None:
        with self._lock:
            if fn in self._taps:
                self._taps.remove(fn)

    # -- recording --
    def _now_us(self) -> float:
        return (time.monotonic() - self._t0) * 1e6

    def _emit(self, ev: dict) -> None:
        for tap in list(self._taps):
            try:
                tap(ev)
            except Exception:
                pass
        if not self._enabled:
            return
        with self._lock:
            if len(self._events) >= self._max:
                self._dropped += 1
                return
            self._events.append(ev)

    def span(self, name: str, cat: str = "host", **args):
        """Complete-event context manager. Extra kwargs land in the
        event's ``args`` (use ``host_sync="why"`` to mark that the
        wrapped code intentionally blocks on a device value). The span
        is a profiler annotation too (module docstring); with the
        tracer disabled and untapped it is nothing else."""
        annotation = _annotation(name, cat, args)
        if not self._enabled and not self._taps:
            return annotation
        return self._recorded_span(annotation, name, cat, args)

    @contextlib.contextmanager
    def _recorded_span(self, annotation, name: str, cat: str, args: dict):
        t0 = self._now_us()
        try:
            with annotation:
                yield
        finally:
            t1 = self._now_us()
            ev = {"name": name, "cat": cat, "ph": "X", "ts": t0,
                  "dur": t1 - t0, "pid": self._pid,
                  "tid": threading.get_ident()}
            if args:
                ev["args"] = args
            self._emit(ev)

    def instant(self, name: str, cat: str = "host", **args):
        with _annotation(name, cat, args):
            pass
        if not self._enabled and not self._taps:
            return
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": self._now_us(), "pid": self._pid,
              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def host_sync(self, what: str, **args):
        """Annotate an INTENTIONAL host<-device sync point (loss
        readback, token fetch). Also counts into the default registry's
        ``trace_host_syncs_total`` so a sync added to a hot loop shows
        up in metrics even with tracing off."""
        from bigdl_tpu.observability.registry import default_registry
        default_registry().counter(
            "trace_host_syncs_total",
            "intentional host<-device sync annotations").inc()
        self.instant(what, cat="host_sync", **args)

    # -- export --
    def to_dict(self, last: int | None = None) -> dict:
        """Chrome trace JSON. ``last=N`` keeps only the N most recent
        events (the exporter's ``/trace?last=`` cap — a live scrape of
        a long run must not ship the whole 1M-event ring); elided
        events are reported in ``otherData.elided_events``."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        elided = 0
        if last is not None and len(events) > max(int(last), 0):
            elided = len(events) - max(int(last), 0)
            events = events[elided:]
        out = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"dropped_events": dropped,
                             "clock": "monotonic_us"}}
        if elided:
            out["otherData"]["elided_events"] = elided
        return out

    def export(self, path: str) -> str:
        """Write Chrome trace JSON; open in chrome://tracing or
        ui.perfetto.dev. Parent directories are created (a postmortem
        dump must not fail on a fresh run dir). Returns ``path``."""
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_dict(), f)
        return path


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def enable():
    return _TRACER.enable()


def disable():
    return _TRACER.disable()


def enabled() -> bool:
    return _TRACER.enabled


def span(name: str, cat: str = "host", **args):
    return _TRACER.span(name, cat=cat, **args)


def instant(name: str, cat: str = "host", **args):
    return _TRACER.instant(name, cat=cat, **args)


def host_sync(what: str, **args):
    return _TRACER.host_sync(what, **args)


def export(path: str) -> str:
    return _TRACER.export(path)


def to_dict(last: int | None = None) -> dict:
    return _TRACER.to_dict(last=last)


def clear():
    return _TRACER.clear()

"""Compile telemetry: who compiled, how often, and what it costs.

Recompile storms and HBM footprints are the dominant SILENT performance
killers on TPU — a serving loop that retraces per request length, or a
train step whose peak HBM creeps toward the ceiling, looks healthy in
every throughput metric until it falls over. Spark-era BigDL never had
this failure mode (no tracing JIT); the JAX-native telemetry plane
watches it explicitly.

Three entry points:

- :func:`watch` wraps a callable (jitted or not): every call is keyed
  by the ABSTRACT SHAPE SIGNATURE of its arguments (shapes + dtypes of
  array leaves, values of everything else — the same key jax retraces
  on). A new signature counts as a compile; crossing
  ``storm_threshold`` distinct signatures for one name logs a
  structured recompile-storm warning carrying the offending shape diff.
  For jitted callables (anything with ``.lower``) the first call per
  signature also extracts the executable's ``cost_analysis()`` /
  ``memory_analysis()`` (the extraction perf.py:157,326 does inline).
- :func:`note_compile` records a compile the caller already performed
  (DistriOptimizer's AOT ``.lower().compile()`` path hands its
  executable straight in — zero extra tracing).
- :func:`record_executable` exports one executable's cost/memory table
  as registry gauges (bench.py / collective_bench rows).

Registry series (label ``name``): ``compile_watch_calls_total``,
``compile_watch_compiles_total``, ``compile_watch_signatures``,
``compile_watch_storms_total``, and per-executable gauges
``compile_watch_flops`` / ``_bytes_accessed`` / ``_arg_bytes`` /
``_output_bytes`` / ``_temp_bytes`` / ``_peak_hbm_bytes``. Each compile
also emits a trace instant (cat ``compile_watch``) so retraces are
visible on the Perfetto timeline next to the host spans.

HOST-ONLY CONTRACT: no module-level jax import (jaxlint JX5) — jax is
lazily imported only inside the stats path, and only for abstract
avals. Watching a function never changes what XLA compiles and never
blocks on a device value; stats extraction reuses the jit cache
(verified: ``lower().compile()`` after a call is cache-hit, see
models/utils/perf.py:324).
"""
from __future__ import annotations

import logging
import threading

__all__ = ["CompileWatch", "default_watch", "watch", "note_compile",
           "note_cache_hit", "note_cache_miss", "record_executable",
           "executable_stats", "signature_of", "table", "reset",
           "DEFAULT_STORM_THRESHOLD"]

logger = logging.getLogger("bigdl_tpu.observability.compile_watch")

DEFAULT_STORM_THRESHOLD = 8


def signature_of(args, kwargs=None) -> tuple:
    """Flatten a call's arguments to a hashable abstract signature:
    array-likes contribute ``dtype[shape]``, plain containers recurse,
    everything else contributes its type and (when hashable) value —
    the same information a jit cache keys on, computed host-side."""
    out: list[tuple[str, str]] = []

    def walk(path, x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            dims = ",".join(str(int(d)) for d in shape)
            out.append((path, f"{dtype}[{dims}]"))
        elif isinstance(x, dict):
            for k in sorted(x, key=str):
                walk(f"{path}.{k}", x[k])
        elif isinstance(x, (list, tuple)):
            for i, v in enumerate(x):
                walk(f"{path}[{i}]", v)
        elif isinstance(x, (int, float, bool, str, bytes,
                            type(None))):
            out.append((path, repr(x)))
        else:
            # opaque object (a model, a cache): identity-stable by type
            out.append((path, f"<{type(x).__name__}>"))

    for i, a in enumerate(args):
        walk(f"arg{i}", a)
    for k in sorted(kwargs or {}):
        walk(f"kw:{k}", (kwargs or {})[k])
    return tuple(out)


def _sig_diff(old: tuple | None, new: tuple) -> str:
    """Human-readable leaf-level diff between two signatures — the
    'what changed shape' line a storm warning needs."""
    if old is None:
        return "first signature"
    o, n = dict(old), dict(new)
    parts = []
    for path in sorted(set(o) | set(n)):
        a, b = o.get(path), n.get(path)
        if a != b:
            parts.append(f"{path}: {a or '<absent>'} -> "
                         f"{b or '<absent>'}")
    return "; ".join(parts) if parts else "structure changed"


def memory_stats(executable) -> dict:
    """What the compiler says one device holds while the executable
    runs: ``arg_bytes``, ``output_bytes``, ``temp_bytes``,
    ``alias_bytes``, ``code_bytes`` and their ``peak_hbm_bytes``. Empty
    where the backend gives no memory analysis."""
    out: dict[str, float] = {}
    try:
        mem = executable.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        for attr, name in (("argument_size_in_bytes", "arg_bytes"),
                           ("output_size_in_bytes", "output_bytes"),
                           ("temp_size_in_bytes", "temp_bytes"),
                           ("alias_size_in_bytes", "alias_bytes"),
                           ("generated_code_size_in_bytes",
                            "code_bytes")):
            v = getattr(mem, attr, None)
            if v is not None:
                out[name] = float(v)
        if {"arg_bytes", "output_bytes", "temp_bytes"} <= out.keys():
            # aliased (donated) buffers overlap args and outputs —
            # don't double-count them in the peak-HBM estimate
            out["peak_hbm_bytes"] = max(
                out["arg_bytes"] + out["output_bytes"]
                + out["temp_bytes"] - out.get("alias_bytes", 0.0), 0.0)
    return out


def executable_stats(executable) -> dict:
    """Cost/memory table of one compiled executable (the extraction
    models/utils/perf.py does inline at :157/:326, shared).

    Every field is best-effort: backends differ in what they expose
    (CPU has cost_analysis but may lack memory_analysis), and telemetry
    must never break the caller."""
    out: dict[str, float] = {}
    try:
        cost = executable.cost_analysis()
    except Exception:
        cost = None
    if cost:
        for key, name in (("flops", "flops"),
                          ("bytes accessed", "bytes_accessed")):
            v = cost.get(key)
            if v is not None:
                out[name] = float(v)
    out.update(memory_stats(executable))
    return out


class CompileWatch:
    """Per-name compile ledger. One process-wide instance lives behind
    :func:`default_watch`; components take ``watch=``/construct their
    own to isolate (tests do)."""

    _GAUGES = ("flops", "bytes_accessed", "arg_bytes", "output_bytes",
               "temp_bytes", "peak_hbm_bytes")

    def __init__(self, registry=None, tracer=None,
                 storm_threshold: int = DEFAULT_STORM_THRESHOLD):
        if int(storm_threshold) < 2:
            raise ValueError(f"storm_threshold must be >= 2, got "
                             f"{storm_threshold}")
        self._registry = registry
        self._tracer = tracer
        self.storm_threshold = int(storm_threshold)
        self._lock = threading.Lock()
        self._names: dict[str, dict] = {}
        # compile taps: fn(name, n_signatures) on every NEW signature,
        # invoked OUTSIDE the ledger lock; errors swallowed (mirrors
        # Tracer taps). The serving batcher rides one to attribute a
        # compile to the request whose prefill triggered it
        # (observability/request_trace.py).
        self._taps: list = []

    # -- plumbing --
    def _reg(self):
        if self._registry is None:
            from bigdl_tpu.observability.registry import default_registry
            return default_registry()
        return self._registry

    def _trace(self):
        if self._tracer is None:
            from bigdl_tpu.observability.tracing import get_tracer
            return get_tracer()
        return self._tracer

    # -- taps --
    def add_tap(self, fn) -> None:
        """Subscribe ``fn(name, n_signatures)`` to every new-signature
        (= compile) event. Tap errors are swallowed: observability
        must never take down the loop."""
        with self._lock:
            if fn not in self._taps:
                self._taps.append(fn)

    def remove_tap(self, fn) -> None:
        with self._lock:
            if fn in self._taps:
                self._taps.remove(fn)

    def _entry(self, name: str) -> dict:
        e = self._names.get(name)
        if e is None:
            e = self._names[name] = {
                "calls": 0, "compiles": 0, "storms": 0,
                "signatures": {},       # sig -> call count
                "last_signature": None, "stats": {},
            }
        return e

    # -- recording --
    def note_call(self, name: str, signature: tuple,
                  storm_threshold: int | None = None) -> bool:
        """Count one call; returns True when ``signature`` is new for
        ``name`` (i.e. this call compiled)."""
        threshold = int(storm_threshold or self.storm_threshold)
        with self._lock:
            e = self._entry(name)
            e["calls"] += 1
            new = signature not in e["signatures"]
            prev = e["last_signature"]
            if new:
                e["signatures"][signature] = 0
                e["compiles"] += 1
                e["last_signature"] = signature
            e["signatures"][signature] += 1
            n_sigs = len(e["signatures"])
            storm = new and n_sigs >= threshold
            if storm:
                e["storms"] += 1
        reg = self._reg()
        reg.counter("compile_watch_calls_total",
                    "calls through compile_watch-wrapped functions",
                    labelnames=("name",)).inc(name=name)
        if new:
            reg.counter("compile_watch_compiles_total",
                        "distinct abstract-shape signatures "
                        "(= compiles) per watched name",
                        labelnames=("name",)).inc(name=name)
            reg.gauge("compile_watch_signatures",
                      "live distinct signatures per watched name",
                      labelnames=("name",)).set(n_sigs, name=name)
            self._trace().instant("compile", cat="compile_watch",
                                  watch=name, signatures=n_sigs)
            for tap in list(self._taps):
                try:
                    tap(name, n_sigs)
                except Exception:
                    pass
        if storm:
            diff = _sig_diff(prev, signature)
            reg.counter("compile_watch_storms_total",
                        "recompile-storm warnings fired",
                        labelnames=("name",)).inc(name=name)
            self._trace().instant("recompile storm",
                                  cat="compile_watch", watch=name,
                                  signatures=n_sigs, diff=diff)
            logger.warning(
                "recompile storm: %r has %d distinct compile "
                "signatures (threshold %d) — every new shape pays a "
                "full XLA compile; pad/bucket the offending input. "
                "Newest shape diff: %s", name, n_sigs, threshold, diff)
        return new

    def note_cache_hit(self, name: str) -> None:
        """One AOT-cache hit for ``name`` (tuning/aot_cache.py): the
        executable was deserialized instead of compiled."""
        with self._lock:
            e = self._entry(name)
            e["cache_hits"] = e.get("cache_hits", 0) + 1
        self._reg().counter(
            "tuning_cache_hits_total",
            "AOT executable cache hits (deserialized, not compiled)",
            labelnames=("name",)).inc(name=name)
        self._trace().instant("aot cache hit", cat="compile_watch",
                              watch=name)

    def note_cache_miss(self, name: str, reason: str) -> None:
        """One AOT-cache miss for ``name`` with its reason (absent /
        deserialize_failed / ...) — the caller falls back to a fresh
        compile."""
        with self._lock:
            e = self._entry(name)
            e["cache_misses"] = e.get("cache_misses", 0) + 1
        self._reg().counter(
            "tuning_cache_misses_total",
            "AOT executable cache misses (fresh compile follows)",
            labelnames=("name",)).inc(name=name)
        self._trace().instant("aot cache miss", cat="compile_watch",
                              watch=name, reason=reason)
        logger.info("tuning_cache_miss name=%s reason=%s", name, reason)

    def note_compile(self, name: str, signature, executable=None):
        """Record a compile the caller performed itself (AOT
        ``.lower().compile()`` paths). ``signature`` may be any
        key with a stable repr; ``executable`` adds its cost/memory
        table."""
        self.note_call(name, (("key", repr(signature)),))
        if executable is not None:
            self.record_executable(name, executable)

    def record_executable(self, name: str, executable) -> dict:
        """Export one executable's cost/memory table as gauges and
        remember it in the per-name ledger. Returns the table."""
        stats = executable_stats(executable)
        with self._lock:
            self._entry(name)["stats"] = dict(stats)
        reg = self._reg()
        for key in self._GAUGES:
            if key in stats:
                reg.gauge(f"compile_watch_{key}",
                          f"latest executable {key.replace('_', ' ')} "
                          "per watched name",
                          labelnames=("name",)).set(stats[key],
                                                    name=name)
        return stats

    # -- the wrapper --
    def watch(self, fn, *, name: str | None = None,
              storm_threshold: int | None = None, stats: bool = True):
        """Wrap ``fn`` with signature-keyed compile counting.

        ``stats=True`` (default) extracts cost/memory analysis on each
        new signature when ``fn`` has the jit AOT surface (``.lower``)
        — abstract avals only, compile-cache shared with the live call.
        ``stats=False`` is pure counting for hot loops that must add
        zero tracing work (LocalOptimizer's step).
        """
        import functools
        label = name or getattr(fn, "__name__", None) or repr(fn)
        can_stats = stats and hasattr(fn, "lower")

        @functools.wraps(fn, updated=())
        def wrapped(*args, **kwargs):
            sig = signature_of(args, kwargs)
            new = self.note_call(label, sig, storm_threshold)
            abstract = None
            if new and can_stats:
                abstract = _abstractify(args, kwargs)
            out = fn(*args, **kwargs)
            if abstract is not None:
                try:
                    self.record_executable(
                        label, fn.lower(*abstract[0],
                                        **abstract[1]).compile())
                except Exception as e:    # telemetry never breaks math
                    logger.debug("compile stats for %r unavailable: %s",
                                 label, e)
            return out

        wrapped.__wrapped__ = fn
        wrapped.watch_name = label
        return wrapped

    # -- inspection --
    def table(self) -> dict:
        """JSON-able per-name ledger (the flight recorder dumps this):
        calls / compiles / storms / signature list with call counts /
        latest executable stats."""
        with self._lock:
            out = {}
            for name, e in sorted(self._names.items()):
                out[name] = {
                    "calls": e["calls"], "compiles": e["compiles"],
                    "storms": e["storms"],
                    "cache_hits": e.get("cache_hits", 0),
                    "cache_misses": e.get("cache_misses", 0),
                    "signatures": [
                        {"signature": ["=".join(p) for p in sig],
                         "calls": count}
                        for sig, count in e["signatures"].items()],
                    "stats": dict(e["stats"]),
                }
        return out

    def reset(self) -> None:
        with self._lock:
            self._names.clear()


def _abstractify(args, kwargs):
    """Replace array leaves with ShapeDtypeStructs so ``.lower`` can
    run without live buffers (donated args are consumed by the real
    call). jax import is function-local (JX5)."""
    import jax

    def leaf(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return x
        try:
            return jax.ShapeDtypeStruct(
                shape, dtype, weak_type=bool(getattr(x, "weak_type",
                                                     False)))
        except TypeError:           # older ShapeDtypeStruct signature
            return jax.ShapeDtypeStruct(shape, dtype)

    def walk(x):
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        if isinstance(x, tuple):
            return tuple(walk(v) for v in x)
        if isinstance(x, list):
            return [walk(v) for v in x]
        return leaf(x)

    return walk(tuple(args)), walk(dict(kwargs))


_DEFAULT = CompileWatch()


def default_watch() -> CompileWatch:
    """The process-wide compile ledger (pass ``watch=`` / construct a
    CompileWatch to isolate)."""
    return _DEFAULT


def watch(fn, *, name=None, storm_threshold=None, stats=True):
    return _DEFAULT.watch(fn, name=name, storm_threshold=storm_threshold,
                          stats=stats)


def note_compile(name, signature, executable=None):
    return _DEFAULT.note_compile(name, signature, executable)


def note_cache_hit(name):
    return _DEFAULT.note_cache_hit(name)


def note_cache_miss(name, reason):
    return _DEFAULT.note_cache_miss(name, reason)


def record_executable(name, executable):
    return _DEFAULT.record_executable(name, executable)


def table() -> dict:
    return _DEFAULT.table()


def reset() -> None:
    _DEFAULT.reset()

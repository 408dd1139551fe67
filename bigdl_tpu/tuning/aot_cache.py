"""Persistent AOT executable cache: load compiled steps instead of
recompiling them (ROADMAP 4 — fleet cold-start).

Every worker restart today pays the full XLA compile for every step
signature it meets. This module makes step construction an explicit

    lower -> compile -> cache

pipeline: the compiled executable is serialized
(``jax.experimental.serialize_executable`` — the backend's own
executable serialization, NOT a re-traceable StableHLO export) under a
key derived from

- the step's **abstract shape signature** (what jax retraces on),
- the **mesh** (axis names, shape, device kinds, process count),
- the **donation mask** (a donated-argument executable is not
  interchangeable with an undonated one),
- a **library + device fingerprint** (jax/jaxlib versions, backend,
  device kind — a jaxlib upgrade or a different chip generation must
  miss, never reuse a stale binary),
- caller-supplied **extra** key material (the optimizer fingerprints its
  model/criterion/optim-method configuration here, since hyperparameters
  like the learning rate are compiled into the executable as constants).

A restarting or newly-elastic worker with a warm cache directory reaches
its first step in deserialize time (~10 ms) instead of compile time
(seconds to minutes) — measured by the ``compile_cold_start`` bench row.

Correctness backstop: ANY failure on the load path — unreadable blob,
deserialization error, backend rejection — logs a structured
``tuning_cache_miss`` with the reason, counts it in the registry
(``tuning_cache_misses_total``), and falls back to a fresh
lower/compile whose result re-populates the cache. A cache directory
can be deleted at any time; it is never a correctness dependency.
Executions from cache are BIT-IDENTICAL to fresh compiles (same
backend binary — pinned in tests/test_tuning.py).

HOST-ONLY CONTRACT (jaxlint JX5): jax imports live inside functions.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import re
import threading

__all__ = ["AOTCache", "StepCompiler", "cache_key", "env_cache",
           "fingerprint", "mesh_descriptor", "stable_repr", "PATH_ENV"]

logger = logging.getLogger("bigdl_tpu.tuning")

#: environment variable naming the cache directory; optimizers with no
#: explicit ``set_aot_cache`` pick it up so a fleet can be warmed by env
PATH_ENV = "BIGDL_TPU_AOT_CACHE_DIR"

_ADDR = re.compile(r" at 0x[0-9a-fA-F]+")


def stable_repr(obj) -> str:
    """``repr`` with memory addresses stripped — key material must be
    identical across processes or the fleet never shares a cache."""
    return _ADDR.sub("", repr(obj))


def fingerprint() -> dict:
    """Library + device identity baked into every key. Any field
    changing ⇒ a miss (no stale-executable reuse across jaxlib
    upgrades, backends, or chip generations)."""
    import jax
    import jaxlib
    # a backend that cannot be read raises here, in the process that is
    # about to compile for it — it is never a cache key
    d = jax.devices()[0]
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": d.platform, "device_kind": d.device_kind,
            "processes": int(jax.process_count())}


def mesh_descriptor(mesh) -> tuple | None:
    """The key's mesh component: axis names + sizes and the device-kind
    set. Device IDs are deliberately EXCLUDED — the same program on the
    same mesh shape must hit regardless of which physical hosts joined
    the slice (that is the elastic-restart case)."""
    if mesh is None:
        return None
    kinds = sorted({str(getattr(d, "device_kind", d.platform))
                    for d in mesh.devices.flat})
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(kinds))


def cache_key(name: str, signature, *, mesh=None, donate_argnums=(),
              extra=None, fp: dict | None = None) -> str:
    """sha256 hex over the canonical JSON of all key components."""
    doc = {
        "name": name,
        "signature": stable_repr(signature),
        "mesh": mesh_descriptor(mesh),
        "donate": sorted(int(i) for i in donate_argnums),
        "fingerprint": fp if fp is not None else fingerprint(),
        "extra": stable_repr(extra) if extra is not None else None,
    }
    blob = json.dumps(doc, sort_keys=True, default=stable_repr)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


class AOTCache:
    """One cache directory of serialized executables (``<key>.exe``).

    Writes are atomic (temp file + rename), so concurrent workers
    warming the same shared directory race benignly — last writer wins
    with an identical payload. ``hits``/``misses`` count this
    instance's traffic; the process-wide registry carries
    ``tuning_cache_{hits,misses}_total`` per step name.
    """

    def __init__(self, path: str, *, watch=None):
        self.path = str(path)
        self._watch = watch
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        os.makedirs(self.path, exist_ok=True)

    def _file(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.exe")

    def _count(self, name: str, hit: bool, reason: str | None = None):
        with self._lock:
            if hit:
                self.hits += 1
            else:
                self.misses += 1
        watch = self._watch
        if watch is None:
            from bigdl_tpu.observability.compile_watch import default_watch
            watch = default_watch()
        try:
            if hit:
                watch.note_cache_hit(name)
            else:
                watch.note_cache_miss(name, reason or "unknown")
        except Exception:       # telemetry must never break the pipeline
            pass

    def load(self, key: str, *, name: str = "step"):
        """The compiled executable for ``key``, or None (counted +
        reason-logged) when absent or unloadable. Never raises."""
        path = self._file(key)
        if not os.path.exists(path):
            self._count(name, False, "absent")
            return None
        try:
            with open(path, "rb") as f:
                blob = pickle.load(f)
            payload, in_tree, out_tree = (blob["payload"],
                                          blob["in_tree"],
                                          blob["out_tree"])
            import jax
            from jax.experimental import serialize_executable as se
            # load onto the devices the executable was compiled for;
            # the default is every device of the backend, which a
            # one-device executable rejects at its first call
            by_id = {d.id: d for d in jax.devices()}
            compiled = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in blob["device_ids"]])
        except Exception as e:
            # the backstop: a bad blob is a miss, not a crash — fresh
            # compilation follows and overwrites it
            logger.warning("tuning_cache_miss name=%s key=%s "
                           "reason=deserialize_failed error=%r — "
                           "falling back to fresh compile", name,
                           key[:12], e)
            self._count(name, False, f"deserialize_failed: {e}")
            return None
        self._count(name, True)
        logger.info("tuning_cache_hit name=%s key=%s (%d bytes)", name,
                    key[:12], len(payload))
        return compiled

    def store(self, key: str, compiled, *, name: str = "step",
              meta: dict | None = None) -> bool:
        """Serialize ``compiled`` under ``key``; best-effort (an
        unserializable executable — some backends — just leaves the
        cache cold). Returns True on a successful write."""
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            blob = {"payload": payload, "in_tree": in_tree,
                    "out_tree": out_tree, "device_ids": device_ids,
                    "meta": dict(meta or {}, name=name)}
            tmp = self._file(key) + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                pickle.dump(blob, f, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, self._file(key))
        except Exception as e:
            logger.warning("AOT cache store failed for %s key=%s: %r",
                           name, key[:12], e)
            return False
        return True


def env_cache() -> AOTCache | None:
    """The cache named by ``$BIGDL_TPU_AOT_CACHE_DIR``, or None."""
    path = os.environ.get(PATH_ENV)
    return AOTCache(path) if path else None


class StepCompiler:
    """The explicit step-construction pipeline both optimizers use:
    per-signature ``lower -> compile -> cache`` with compile_watch
    accounting, replacing implicit jit-on-first-call compilation.

    ``quick_key`` is the caller's cheap per-iteration dispatch key (batch
    shapes/dtypes); the full cache key — abstract signature of ALL
    arguments plus mesh/donation/fingerprint/extra — is only computed on
    a quick-key miss, so steady-state iterations cost one dict probe.
    """

    def __init__(self, jit_fn, *, name: str, cache: AOTCache | None
                 = None, mesh=None, donate_argnums=(), extra=None,
                 watch=None, count_calls: bool = False):
        self.jit_fn = jit_fn
        self.name = name
        # None = follow the environment; False = explicitly off
        self.cache = (None if cache is False
                      else cache if cache is not None else env_cache())
        self.mesh = mesh
        self.donate_argnums = tuple(donate_argnums)
        self.extra = extra
        self._count_calls = count_calls
        self._watch = watch
        self._executables: dict = {}
        self._fp = None

    # -- plumbing ------------------------------------------------------
    def _cw(self):
        if self._watch is None:
            from bigdl_tpu.observability.compile_watch import default_watch
            self._watch = default_watch()
        return self._watch

    def signature(self, args) -> tuple:
        from bigdl_tpu.observability.compile_watch import signature_of
        return signature_of(args)

    def key_for(self, args) -> str:
        if self._fp is None:
            self._fp = fingerprint()
        return cache_key(self.name, self.signature(args),
                         mesh=self.mesh,
                         donate_argnums=self.donate_argnums,
                         extra=self.extra, fp=self._fp)

    # -- the pipeline --------------------------------------------------
    def get(self, quick_key, args):
        """The executable for this iteration's ``quick_key``, building
        it through the cache on first sight. Returns
        ``(compiled, compiled_this_call)``."""
        compiled = self._executables.get(quick_key)
        if compiled is not None:
            if self._count_calls:
                self._cw().note_call(self.name, quick_key)
            return compiled, False
        loaded = False
        if self.cache is not None:
            key = self.key_for(args)
            compiled = self.cache.load(key, name=self.name)
            loaded = compiled is not None
        if compiled is None:
            from bigdl_tpu.observability import trace
            with trace.span("compile step", step=self.name,
                            shape=str(quick_key)):
                compiled = self.jit_fn.lower(*args).compile()
            if self.cache is not None:
                self.cache.store(key, compiled, name=self.name)
        self._executables[quick_key] = compiled
        # compile accounting: a cache LOAD still counts as this name's
        # signature appearing (storm detection keys on signatures, and a
        # load means the signature is new to this process)
        cw = self._cw()
        if self._count_calls:
            cw.note_call(self.name, quick_key)
        else:
            cw.note_call(self.name, (("key", repr(quick_key)),))
        try:
            cw.record_executable(self.name, compiled)
        except Exception:
            pass
        return compiled, not loaded

    def executables(self) -> dict:
        """``{quick_key: compiled}`` built so far (a copy)."""
        return dict(self._executables)

    def __len__(self):
        return len(self._executables)

    def __contains__(self, quick_key):
        return quick_key in self._executables

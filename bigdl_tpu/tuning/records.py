"""Persistent tuning records: measured kernel/knob winners, keyed by
(kernel, abstract-shape signature, device kind).

The autotuner (``tuning/autotuner.py``) writes one record per winning
configuration; consumers — the Pallas kernels' block pickers, the
sharded-update bucket sizing — look their key up at trace time and fall
back to their static menus on a miss, so a record file is always an
optimization and never a correctness dependency.

File format (JSON, one file for the whole fleet to share):

.. code-block:: json

    {"version": 1,
     "records": {
       "flash_attention|TPU v5 lite|skv=4096,sq=4096": {
         "config": {"bq": 512, "bk": 1024},
         "score": 0.00132, "meta": {"iters": 5}}}}

The key is ``kernel|device_kind|signature`` — a restarting worker on the
same chip generation adopts the fleet's tuned tiles; a different device
kind misses and re-tunes rather than importing another chip's winners.

Lookup cost matters: the kernel pickers consult records on EVERY trace,
so ``lookup`` is one dict probe on an in-memory index; the file is read
once (lazily) and written atomically on ``record``.

HOST-ONLY CONTRACT (jaxlint JX5): no module-level jax import — jax is
touched only inside :func:`device_kind`, lazily, to read the accelerator
name.
"""
from __future__ import annotations

import json
import logging
import os
import threading

__all__ = ["TuningRecords", "default_records", "set_default_records",
           "device_kind", "signature_str", "PATH_ENV"]

logger = logging.getLogger("bigdl_tpu.tuning")

#: environment variable naming the shared record file; when unset the
#: default store is in-memory only (still consultable/settable in-process)
PATH_ENV = "BIGDL_TPU_TUNING_FILE"

_VERSION = 1


def device_kind() -> str:
    """Accelerator name the records are keyed by (e.g. ``TPU v5
    lite``). A backend that cannot be read raises here — the caller is
    about to trace a kernel for it."""
    import jax
    return jax.devices()[0].device_kind


def signature_str(sig) -> str:
    """Canonical, order-independent string form of a signature: dicts
    and (name, value) pair tuples become sorted ``k=v`` lists; anything
    else falls back to ``repr``. The same logical signature must always
    produce the same key across processes."""
    if isinstance(sig, dict):
        items = sig.items()
    elif (isinstance(sig, (list, tuple))
          and all(isinstance(p, (list, tuple)) and len(p) == 2
                  for p in sig)):
        items = sig
    else:
        return repr(sig)
    return ",".join(f"{k}={v}" for k, v in sorted(
        ((str(k), v) for k, v in items)))


class TuningRecords:
    """One JSON-backed record store. ``path=None`` keeps the store
    in-memory (tests, or tuning without persistence)."""

    def __init__(self, path: str | None = None):
        self.path = path
        self._lock = threading.Lock()
        self._entries: dict[str, dict] = {}
        self._loaded = path is None

    # -- persistence ---------------------------------------------------
    def _ensure_loaded(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                doc = json.load(f)
            entries = doc.get("records", {})
            if not isinstance(entries, dict):
                raise ValueError("records is not an object")
            self._entries = entries
        except FileNotFoundError:
            pass
        except Exception as e:
            # a corrupt record file must never take training down —
            # start empty and let re-tuning rebuild it
            logger.warning("tuning records %s unreadable (%s) — "
                           "starting empty", self.path, e)
            self._entries = {}

    def _save_locked(self) -> None:
        if self.path is None:
            return
        tmp = f"{self.path}.tmp.{os.getpid()}"
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"version": _VERSION, "records": self._entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)   # atomic: readers see old or new

    # -- the API -------------------------------------------------------
    @staticmethod
    def key(kernel: str, sig, device: str | None = None) -> str:
        return f"{kernel}|{device or device_kind()}|{signature_str(sig)}"

    def lookup(self, kernel: str, sig, device: str | None = None
               ) -> dict | None:
        """The winning config dict for (kernel, signature) on this
        device kind, or None. One dict probe after the lazy file read."""
        with self._lock:
            self._ensure_loaded()
            e = self._entries.get(self.key(kernel, sig, device))
        return dict(e["config"]) if e and "config" in e else None

    def record(self, kernel: str, sig, config: dict, *,
               score: float | None = None, device: str | None = None,
               meta: dict | None = None) -> str:
        """Persist one winner; returns the record key."""
        k = self.key(kernel, sig, device)
        entry: dict = {"config": dict(config)}
        if score is not None:
            entry["score"] = float(score)
        if meta:
            entry["meta"] = dict(meta)
        with self._lock:
            self._ensure_loaded()
            self._entries[k] = entry
            self._save_locked()
        logger.info("tuning record %s -> %s (score %s)", k, config, score)
        return k

    def entries(self) -> dict:
        with self._lock:
            self._ensure_loaded()
            return {k: dict(v) for k, v in self._entries.items()}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._loaded = self.path is None
            if self.path is not None:
                try:
                    os.unlink(self.path)
                except FileNotFoundError:
                    pass
                self._loaded = True


_default: TuningRecords | None = None
_default_explicit = False
_default_lock = threading.Lock()


def default_records() -> TuningRecords:
    """The process-wide store: an explicitly-set one wins; otherwise
    backed by ``$BIGDL_TPU_TUNING_FILE`` when set, in-memory
    otherwise."""
    global _default
    with _default_lock:
        if _default_explicit and _default is not None:
            return _default
        path = os.environ.get(PATH_ENV) or None
        if _default is None or _default.path != path:
            _default = TuningRecords(path)
        return _default


def set_default_records(records: TuningRecords | None) -> None:
    """Swap the process-wide store (tests isolate with this). ``None``
    re-derives from the environment on next use."""
    global _default, _default_explicit
    with _default_lock:
        _default = records
        _default_explicit = records is not None

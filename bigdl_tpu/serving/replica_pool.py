"""Replica threads: N ``ContinuousBatcher`` step loops behind one pool.

A :class:`Replica` owns one batcher (its own metric registry, its own
KV page pool — conceptually one device/slice), a re-entrant lock that
serializes every batcher touch, and a daemon driver thread that keeps
calling ``step()`` while work is queued. :class:`ReplicaPool` builds N
identically configured replicas over a shared (read-only) model and
manages their lifecycle.

Per-replica registries are the isolation the router needs: gauges like
``serving_queue_depth`` are name-keyed, so two batchers writing one
process-wide registry would overwrite each other. Live load (queue
depth, free slots, page pressure) is read straight off batcher host
state under the replica lock; latency percentiles come from the
replica-local histograms (``Replica.histogram_snapshot``), and the
router republishes the fleet view into the process registry with a
``replica`` label.

Health: every replica answers two checks in the (shared) health
registry — ``serving_batcher_<name>`` (the batcher's own
admitting/saturated readiness) and ``serving_replica_<name>``
(lifecycle: flips not-ready the moment a drain begins, which is the
load-balancer signal for rolling restarts). The ``MetricsServer``'s
``/readyz?check=serving_replica_<name>`` filter gates one replica
without consulting the others.

Thread contract: the driver thread is the only caller of ``step()``;
router threads call ``submit``/``cancel``/``export``/``stats`` under
the same lock. A ``step()`` in flight simply delays those calls by one
burst. Locks are re-entrant so batcher hooks (``on_complete``) may
fire router code on the driver thread — that hook takes the router's
``_state_lock`` while holding ``replica.lock``, which is the ONE
sanctioned nesting direction; the declaration below has raceguard
(TS1) reject the inverse anywhere in the plane.

HOST-ONLY CONTRACT: never imports jax (jaxlint JX5). The batcher class
is imported lazily inside :class:`ReplicaPool` construction, so this
module stays importable in jax-free tooling.
"""
# raceguard: order state_lock < replica.lock
from __future__ import annotations

import contextlib
import threading
import time

from bigdl_tpu.observability import trace
from bigdl_tpu.observability.exporter import default_health
from bigdl_tpu.observability.registry import MetricRegistry
from bigdl_tpu.serving.slo import ReplicaStats

__all__ = ["Replica", "ReplicaPool", "ACTIVE", "DRAINING", "STOPPED"]

ACTIVE = "active"
DRAINING = "draining"
STOPPED = "stopped"

_EMPTY_SNAPSHOT = {"buckets": {}, "sum": 0.0, "count": 0}


class Replica:
    """One batcher + driver thread. Construct via :class:`ReplicaPool`
    (which wires registries and health names) or directly for tests."""

    def __init__(self, name: str, batcher, *, registry, burst=None,
                 health=None, poll_interval: float = 0.005):
        self.name = str(name)
        self.batcher = batcher
        # stamp our name onto the batcher so its request-timeline
        # events (observability/request_trace.py) carry the replica
        # identity — the same post-construction idiom as
        # ``batcher.weight_version``
        batcher.replica_name = self.name
        self.registry = registry
        self.lock = threading.RLock()
        self._burst = burst
        self._poll = float(poll_interval)
        self._state = ACTIVE
        self._stop = False
        # the last exception batcher.step raised on the driver thread;
        # Router.wait_all raises it instead of timing out on requests
        # a failing step will never finish
        self.step_error: BaseException | None = None
        self._wake = threading.Event()
        self._health = health if health is not None else default_health()
        self._health.register(f"serving_replica_{self.name}",
                              self._ready, kind="readiness")
        self._thread = threading.Thread(
            target=self._run, name=f"bigdl-serving-{self.name}",
            daemon=True)
        self._started = False

    # -- lifecycle --
    def start(self) -> "Replica":
        if not self._started:
            self._started = True
            self._thread.start()
        return self

    def _lock_wait(self, rid, spanned: bool = True):
        """A ``replica lock wait`` span, OPEN: the caller ``close()``s it
        as its first statement under ``with self.lock:`` (which stays a
        plain with-statement for raceguard). The driver holds the lock
        for a whole burst and takes it again at once; a submission's
        span beside the driver's re-take shows whether it waits behind
        that."""
        wait = contextlib.ExitStack()
        if spanned:
            wait.enter_context(trace.span(
                "replica lock wait", cat="serving", replica=self.name,
                rid=rid))
        return wait

    def _run(self):
        import logging
        log = logging.getLogger(__name__)
        stepped = 0
        while not self._stop:
            # the re-take right after a burst is the one in question; an
            # idle driver's poll ticks would only fill the trace
            wait = self._lock_wait("driver", spanned=stepped > 0)
            stepped = 0
            try:
                with self.lock:
                    wait.close()
                    if not self._stop and not self.batcher.idle:
                        stepped = self.batcher.step(self._burst)
            except Exception as e:
                # a crashing step must not kill the driver, and must
                # not pass for slowness either: keep it where the
                # router's wait_all raises it
                log.exception("replica %s step failed", self.name)
                wait = self._lock_wait("driver")
                with self.lock:
                    wait.close()
                    self.step_error = e
                stepped = 0
            if not stepped:
                # idle, or queued work that cannot admit yet: park
                # until a submit wakes us (or the poll tick re-checks)
                self._wake.wait(self._poll)
                self._wake.clear()

    def stop(self, timeout: float = 10.0) -> None:
        """Stop the driver thread and unregister health checks (a dead
        replica must stop answering for the process)."""
        self._stop = True
        self._wake.set()
        if self._started:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise RuntimeError(
                    f"replica {self.name} driver did not stop in "
                    f"{timeout}s")
        with self.lock:
            self._state = STOPPED
        self._health.unregister(f"serving_replica_{self.name}")
        self._health.unregister(self.batcher.health_name)

    # -- state --
    @property
    def state(self) -> str:
        return self._state

    def drain_begin(self) -> None:
        """Stop admissions: lifecycle readiness flips immediately; the
        driver keeps stepping so in-flight sequences finish."""
        with self.lock:
            if self._state == STOPPED:
                raise RuntimeError(f"replica {self.name} is stopped")
            self._state = DRAINING

    def resume(self) -> None:
        with self.lock:
            if self._state == STOPPED:
                raise RuntimeError(f"replica {self.name} is stopped")
            self._state = ACTIVE
        self._wake.set()

    def _ready(self):
        # lock-free racy read: a health probe must never block behind
        # a decode burst (HealthCheck.run already fences crashes)
        if self._state != ACTIVE:
            return False, f"replica {self.name} is {self._state}"
        ok, detail = self.batcher._ready()
        return ok, f"{self.name}: {detail}"

    # -- weight version (deploy plane; bigdl_tpu/deploy/) --
    @property
    def weight_version(self):
        """Which published weight set this replica serves (None =
        unversioned). Lives on the batcher so exported KV snapshots
        carry it."""
        return getattr(self.batcher, "weight_version", None)

    def set_weights(self, model=None, *, weight_version) -> None:
        """Swap the served weights (``model=None`` just re-stamps the
        version — the publisher uses that to mark a pre-existing fleet
        as version v0). The batcher enforces idleness and identical
        geometry; callers drain first (``Router.drain``) and ``resume``
        after."""
        with self.lock:
            if model is None:
                self.batcher.weight_version = weight_version
            else:
                self.batcher.set_weights(model, weight_version)

    # -- request plane (router-facing; all under the replica lock) --
    def submit(self, request_id, prompt=None, *, snapshot=None,
               prefill_from=None) -> None:
        wait = self._lock_wait(request_id)
        with self.lock:
            wait.close()
            if self._state != ACTIVE:
                raise RuntimeError(
                    f"replica {self.name} is {self._state}: not "
                    "admitting")
            self.batcher.submit(request_id, prompt, snapshot=snapshot,
                                prefill_from=prefill_from)
        self._wake.set()

    def cancel(self, request_id) -> bool:
        with self.lock:
            return self.batcher.cancel(request_id)

    def prefill_only(self, request_id, prompt):
        """Disaggregation entry: run a prefill here (the lock means it
        interleaves with THIS replica's bursts, never a decode
        replica's) and hand the KV snapshot back."""
        with self.lock:
            return self.batcher.prefill_only(request_id, prompt)

    def export_requests(self) -> list:
        with self.lock:
            return self.batcher.export_requests()

    def export_request(self, request_id):
        """Export ONE in-flight request's KV snapshot (frees its slot).
        The router's per-request drain policy uses this to migrate a
        chosen subset while the rest finish here."""
        with self.lock:
            return self.batcher.export_request(request_id)

    def inflight_ids(self) -> list:
        """Ids currently occupying slots (not the queue)."""
        with self.lock:
            return [s[0] for s in self.batcher.slots if s is not None]

    def pop_queued(self) -> list:
        with self.lock:
            return self.batcher.pop_queued()

    def wait_idle(self, timeout: float = 60.0) -> bool:
        """Block until the batcher has nothing queued or in flight."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self.lock:
                if self.batcher.idle:
                    return True
            time.sleep(self._poll)
        with self.lock:
            return self.batcher.idle

    # -- telemetry --
    def histogram_snapshot(self, name: str) -> dict:
        m = self.registry.get(name)
        return m.snapshot() if m is not None else dict(_EMPTY_SNAPSHOT)

    def stats(self) -> ReplicaStats:
        from bigdl_tpu.serving.slo import percentile
        with self.lock:
            b = self.batcher
            free_slots = sum(s is None for s in b.slots)
            queue_depth = len(b.queue)
            pages_free = b.cache.pages_free
            util = 1.0 - pages_free / b.cache.num_pages
            skips = int(b._m_skips.value())
            state = self._state
        ttft = self.histogram_snapshot("serving_ttft_seconds")
        dec = self.histogram_snapshot("serving_decode_token_seconds")
        return ReplicaStats(
            name=self.name, state=state, queue_depth=queue_depth,
            active_slots=b.max_batch - free_slots,
            free_slots=free_slots, pages_free=pages_free,
            kv_utilization=util,
            ttft_p50=percentile(ttft, 0.5),
            ttft_p99=percentile(ttft, 0.99),
            decode_token_p99=percentile(dec, 0.99),
            prefill_skips=skips)


class ReplicaPool:
    """N identically configured batcher replicas over one model.

    ``batcher_kwargs`` forwards to ``ContinuousBatcher`` (``max_batch``,
    ``num_pages``, ``page_size``, ``max_new_tokens``, ``max_burst``,
    ``eos_id``); identical geometry across replicas is what makes KV
    snapshots portable between them (the batcher validates on adopt).
    Each replica gets a private :class:`MetricRegistry` and health
    checks named per replica in the SHARED health registry."""

    def __init__(self, model, n_replicas: int = 2, *, names=None,
                 burst=None, health=None, start: bool = True,
                 aot_cache=None, weight_version=None, **batcher_kwargs):
        if n_replicas < 1:
            raise ValueError(f"need >= 1 replica, got {n_replicas}")
        if names is None:
            names = [f"r{i}" for i in range(n_replicas)]
        if len(names) != n_replicas or len(set(names)) != n_replicas:
            raise ValueError(f"need {n_replicas} distinct names, got "
                             f"{names}")
        self._health = health if health is not None else default_health()
        self._model = model
        self._weight_version = weight_version
        self._burst = burst
        self._batcher_kwargs = dict(batcher_kwargs)
        # ONE shared AOT pipeline for every replica this pool ever
        # builds (autoscaler spin-ups included): the first replica
        # compiles each step and stores the executable; the Nth replica
        # of identical geometry compiles nothing. ``aot_cache`` accepts
        # a PagedStepCompilers, an AOTCache, or a cache directory path.
        self.aot = None
        if aot_cache is not None:
            # lazy: keeps this module importable without jax (JX5)
            from bigdl_tpu.models.transformer.serving import \
                PagedStepCompilers
            self.aot = (aot_cache
                        if isinstance(aot_cache, PagedStepCompilers)
                        else PagedStepCompilers(aot_cache))
            self._batcher_kwargs["aot_cache"] = self.aot
        self._running = False
        self._next_auto = n_replicas
        self.replicas: dict[str, Replica] = {}
        for name in names:
            self._build_replica(name)
        if start:
            self.start()

    def _build_replica(self, name: str, *, model=None,
                       weight_version=None) -> Replica:
        # lazy: keeps this module importable without jax (JX5 contract)
        from bigdl_tpu.models.transformer.serving import ContinuousBatcher
        reg = MetricRegistry()
        batcher = ContinuousBatcher(
            model if model is not None else self._model,
            registry=reg, health=self._health,
            health_name=f"serving_batcher_{name}",
            **self._batcher_kwargs)
        # stamped post-construction so monkeypatched batcher fakes that
        # predate the kwarg keep working
        batcher.weight_version = (weight_version
                                  if weight_version is not None
                                  else self._weight_version)
        rep = Replica(name, batcher, registry=reg, burst=self._burst,
                      health=self._health)
        self.replicas[name] = rep
        return rep

    @property
    def model(self):
        """The default model newly built replicas serve."""
        return self._model

    def set_default_model(self, model, *, weight_version=None) -> None:
        """Point FUTURE replica builds (``add_replica`` — autoscaler
        spin-ups included) at a new weight set. Does not touch running
        replicas; the publisher rolls those one by one
        (``Replica.set_weights``) and then calls this so scale-ups
        never resurrect the old version."""
        self._model = model
        self._weight_version = weight_version

    # -- elastic membership (the autoscaler's primitives) --
    def add_replica(self, name: str | None = None, *, start: bool = True,
                    warm: bool = True, model=None,
                    weight_version=None) -> Replica:
        """Build one more identically configured replica and (with the
        pool running) put it in rotation. With the pool's shared AOT
        pipeline the new batcher compiles nothing — its executables
        come from the in-process table or the cache directory; with
        ``warm=True`` its default decode executable is readied before
        the driver starts, so the first routed request never waits on
        construction. Auto-names ``rN`` when ``name`` is omitted.
        Registers the replica's two health checks as a side effect of
        construction. Callers fronting the pool with a Router must also
        ``router.attach_replica(name)`` to wire completion hooks.
        ``model``/``weight_version`` override the pool defaults — the
        weight publisher's canary spins up on the CANDIDATE weights
        while the fleet keeps serving the current ones."""
        if name is None:
            while f"r{self._next_auto}" in self.replicas:
                self._next_auto += 1
            name = f"r{self._next_auto}"
            self._next_auto += 1
        if name in self.replicas:
            raise ValueError(f"replica {name!r} already exists")
        rep = self._build_replica(name, model=model,
                                  weight_version=weight_version)
        if warm:
            rep.batcher.warmup()
        if start and self._running:
            rep.start()
        return rep

    def remove_replica(self, name: str, timeout: float = 10.0) -> None:
        """Stop and drop replica ``name``: the driver thread joins and
        BOTH its health checks unregister, so ``/readyz`` of a
        scaled-down fleet reports only live replicas. The caller drains
        first (``Router.drain(name, migrate=True)``) — work still
        queued or in flight here is lost. KeyError for unknown names."""
        rep = self.replicas.pop(name)
        rep.stop(timeout)

    @property
    def names(self) -> list[str]:
        return list(self.replicas)

    def __getitem__(self, name: str) -> Replica:
        return self.replicas[name]

    def __iter__(self):
        # snapshot: scale events mutate the dict from other threads
        # while health probes / fleet-stats scrapes iterate it
        return iter(list(self.replicas.values()))

    def __len__(self) -> int:
        return len(self.replicas)

    def start(self) -> "ReplicaPool":
        self._running = True
        for r in list(self.replicas.values()):
            r.start()
        return self

    def stats(self) -> list[ReplicaStats]:
        return [r.stats() for r in list(self.replicas.values())]

    def close(self, timeout: float = 10.0) -> None:
        for r in list(self.replicas.values()):
            r.stop(timeout)

    def __enter__(self) -> "ReplicaPool":
        return self.start()

    def __exit__(self, *exc):
        self.close()
        return False

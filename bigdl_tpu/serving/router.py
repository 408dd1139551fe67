"""SLO-aware request router over a :class:`ReplicaPool`.

The front door that turns the single-replica serving stack into a
service (ROADMAP item 1; BigDL 2.0's pipeline-to-serving story,
arXiv:2204.01715). One ``submit()`` call per request; the router

- **places** it on the best admissible replica — admission gates on
  each replica's live queue depth, KV-page utilization and observed
  TTFT/decode p99 vs the :class:`SLOConfig` targets
  (``slo.admissible``), ranking survivors by ``slo.load_score``;
- **reuses prefixes**: a prompt seen before routes sticky to the
  replica that served it and ADOPTS the retained KV snapshot instead
  of re-prefilling (``router_prefix_hits_total`` at the router,
  ``serving_prefill_skips_total`` on the adopting replica); a prompt
  sharing only a PREFIX with a cached entry (the longest-prefix radix
  walk, page-granular) adopts the truncated snapshot and prefills
  just the suffix (``router_prefix_partial_hits_total``,
  ``router_prefix_tokens_reused_total``);
- **disaggregates** long prefills: prompts past
  ``slo.long_prefill_tokens`` prefill on the designated (or
  lowest-load) replica via ``prefill_only`` and the KV snapshot is
  handed to a different decode replica, so decode bursts never stall
  behind a long prompt;
- **overflows** to a bounded router-level pending queue when no
  replica admits, and raises :class:`RouterSaturated` past
  ``slo.max_pending`` (explicit load-shedding);
- **drains** replicas for rolling restarts: ``drain(name)`` stops
  admissions (the replica's ``/readyz`` check flips immediately),
  re-dispatches its still-queued requests to survivors, then either
  lets in-flight sequences finish or — ``migrate=True`` — exports
  their KV mid-decode and resumes them elsewhere, bitwise.

Results fan in through the batchers' ``on_complete`` hooks into one
``finished()`` stream; every accepted request completes exactly once
(no drops, no duplicates — test-pinned).

Every accepted request also carries a per-request TIMELINE
(observability/request_trace.py): admission, pending park,
prefix-cache outcome, placement, disaggregated handoff, migration and
completion land as structured events on ONE timeline that follows the
request across replicas and weight versions; the tracker tail-samples
at completion so only the interesting tail is retained in full. The
router-side wait (submit -> replica placement, admission plus any
pending park) is observed into ``router_queue_wait_seconds`` for
EVERY request — the component the batcher's TTFT clock cannot see —
with the request id attached to its histogram bucket as an
OpenMetrics exemplar, so a breached ``/metrics`` bucket links
straight to ``/requests/<id>``. ``latency_summary()`` carries the
queue-wait percentiles and the tracker's tail attribution
(docs/SERVING.md "diagnosing a slow request").

Locking: ``_state_lock`` guards only the router's own dicts and is
never held while a replica lock is being acquired; replica driver
threads call back into ``_on_complete`` holding their replica lock and
take ``_state_lock`` briefly, and the prefix-capture hook takes the
prefix cache's internal lock the same way (replica -> prefixcache).
The dispatch path queries the cache BEFORE touching any replica lock,
so ``prefixcache._lock`` nests strictly inside ``replica.lock`` and
never the reverse. The request tracker's lock is a strict LEAF inside
all of them: timeline events are recorded while ``_state_lock`` or a
replica lock is held, and the tracker never calls back into the
serving plane. Those one-way orders are what make the plane
deadlock-free, and the declaration below turns them into a
machine-checked gate (dev/analysis/raceguard.py TS1): acquiring
``replica.lock`` anywhere while ``state_lock`` or the cache lock is
held is a lint failure. The pending queue is flushed by a single
dispatcher thread, so batcher-level arrival order is preserved.

HOST-ONLY CONTRACT: never imports jax (jaxlint JX5) — routing is pure
host orchestration over the batcher API.
"""
# raceguard: order requesttracker.mu < state_lock
# raceguard: order state_lock < prefixcache._lock < replica.lock
from __future__ import annotations

import threading
import time
from collections import deque

from bigdl_tpu.observability import trace
from bigdl_tpu.observability.exporter import default_health
from bigdl_tpu.observability.registry import default_registry
from bigdl_tpu.observability.request_trace import default_tracker
from bigdl_tpu.serving.prefix_cache import PrefixCache
from bigdl_tpu.serving.slo import (SLOConfig, admissible, load_score,
                                   merge_snapshots, percentile)

__all__ = ["Router", "RouterSaturated"]


class RouterSaturated(RuntimeError):
    """No replica admits and the router-level pending queue is full."""


class Router:
    """See module docstring. ``pool`` is a started
    :class:`~bigdl_tpu.serving.replica_pool.ReplicaPool`; the router
    takes over each batcher's ``on_complete``/``on_prefill`` hooks.

    - ``prefill_replica``: name of the designated prefill replica for
      disaggregation (default: pick the lowest-load admissible one per
      request).
    - ``capture_prefixes``: snapshot prompts >= the prefix cache's
      ``min_tokens`` after their first prefill for later reuse.
    - ``registry``/``health``: the process-wide fleet view — labeled
      per-replica gauges, router counters, and the
      ``serving_router`` readiness check (ready while >= 1 replica
      admits).
    """

    def __init__(self, pool, *, slo: SLOConfig | None = None,
                 prefix_cache: PrefixCache | None = None,
                 registry=None, health=None, prefill_replica=None,
                 capture_prefixes: bool = True, tracker=None):
        self.pool = pool
        self.slo = slo if slo is not None else SLOConfig()
        # tracker=None -> the process-wide default; tracker=False ->
        # timelines off (queue-wait histogram still observed)
        if tracker is False:
            self._tracker = None
        else:
            self._tracker = (tracker if tracker is not None
                             else default_tracker())
        if self._tracker is not None and self._tracker.slo is None:
            # teach the default tracker this fleet's SLO so retention
            # (ttft > slo) and stall thresholds mean something
            self._tracker.slo = self.slo
        self.prefix = (prefix_cache if prefix_cache is not None
                       else PrefixCache())
        self._capture = bool(capture_prefixes)
        if prefill_replica is not None and \
                prefill_replica not in pool.replicas:
            raise ValueError(f"unknown prefill replica "
                             f"{prefill_replica!r} (have {pool.names})")
        self._prefill_name = prefill_replica

        reg = default_registry() if registry is None else registry
        self._m_requests = reg.counter(
            "router_requests_total", "requests accepted by the router")
        self._m_completed = reg.counter(
            "router_completed_total", "requests completed and collected")
        self._m_prefix_hits = reg.counter(
            "router_prefix_hits_total",
            "requests served from the prefix KV cache (prefill skipped)")
        self._m_prefix_partial = reg.counter(
            "router_prefix_partial_hits_total",
            "requests that adopted a truncated prefix snapshot and "
            "prefilled only their suffix (longest-prefix radix hits)")
        self._m_tokens_reused = reg.counter(
            "router_prefix_tokens_reused_total",
            "prompt tokens whose KV was adopted from the prefix cache "
            "instead of prefilled (exact + partial hits)")
        self._m_prompt_tokens = reg.counter(
            "router_prompt_tokens_total",
            "prompt tokens across all accepted requests (denominator "
            "for the tokens-reused fraction)")
        self._m_disagg = reg.counter(
            "router_disagg_prefills_total",
            "long prompts prefilled on one replica, decoded on another")
        self._m_rejected = reg.counter(
            "router_rejected_total",
            "requests shed because router + replicas were saturated")
        self._m_migrated = reg.counter(
            "router_migrations_total",
            "in-flight requests moved between replicas during drain")
        self._m_restarts = reg.counter(
            "router_version_restarts_total",
            "orphaned KV snapshots (weight version no longer served "
            "anywhere) restarted from their prompt on the current fleet")
        self._m_pending = reg.gauge(
            "router_pending_depth",
            "requests waiting at the router for an admissible replica")
        self._m_rq = reg.gauge(
            "router_replica_queue_depth",
            "per-replica batcher queue depth as last seen by the router",
            labelnames=("replica",))
        self._m_rutil = reg.gauge(
            "router_replica_kv_utilization",
            "per-replica KV page utilization as last seen by the router",
            labelnames=("replica",))
        self._m_qwait = reg.histogram(
            "router_queue_wait_seconds",
            "seconds between submit() and replica placement (admission "
            "+ pending park) — the TTFT component the batcher clock "
            "cannot see; observed for EVERY accepted request")

        self._health = health if health is not None else default_health()
        self._health.register("serving_router", self._ready,
                              kind="readiness")

        # _state_lock guards the dicts below; NEVER held while taking a
        # replica lock (see module docstring)
        self._state_lock = threading.Lock()
        self._inflight: dict = {}       # rid -> replica name | None
        self._enq: dict = {}            # rid -> (t_monotonic, cause)
        self._pending: deque = deque()  # (rid, payload, session)
        self._results: deque = deque()
        self._sessions: dict = {}       # session id -> replica name
        self._closed = False

        # replicas the router must NOT place live traffic on even
        # though they sit in the pool: the weight publisher's canary
        # qualifies on candidate weights and is driven directly, never
        # through live routing. Quarantine a name BEFORE add_replica
        # and there is no window where the dispatcher can see it.
        self._quarantined: set = set()

        # observer taps (assignable; both optional, crash-fenced): the
        # deploy plane's ShadowTap mirrors a fraction of live traffic
        # onto a canary replica through these without sitting in the
        # request path. on_submit(rid, prompt) fires once per ACCEPTED
        # prompt; on_result(rid, tokens) once per completion.
        self.on_submit = None
        self.on_result = None

        for name, rep in pool.replicas.items():
            rep.batcher.on_complete = self._make_on_complete(name)
            rep.batcher.tracker = self._tracker
            if self._capture:
                rep.batcher.on_prefill = self._make_on_prefill(name)

        self._pump_wake = threading.Event()
        self._pump_thread = threading.Thread(
            target=self._pump, name="bigdl-serving-router", daemon=True)
        self._pump_thread.start()

    # -- request timelines (tracker lock is a leaf; no-ops when off) --
    def _tev(self, rid, event, **fields) -> None:
        if self._tracker is not None:
            self._tracker.event(rid, event, **fields)

    def _t_finish(self, rid, status: str = "ok") -> None:
        if self._tracker is not None:
            self._tracker.finish(rid, status=status)

    # -- hooks (run on replica driver threads, replica lock held) --
    def _make_on_complete(self, name):
        def hook(rid, toks):
            with self._state_lock:
                self._inflight.pop(rid, None)
                self._results.append((rid, list(toks)))
            self._m_completed.inc()
            self._tev(rid, "complete", replica=name, tokens=len(toks))
            self._t_finish(rid)
            tap = self.on_result
            if tap is not None:
                try:
                    tap(rid, list(toks))
                except Exception:
                    import logging
                    logging.getLogger(__name__).exception(
                        "on_result tap failed for %r", rid)
            self._pump_wake.set()
        return hook

    def _make_on_prefill(self, name):
        def hook(rid, prompt, snapshot_fn):
            if len(prompt) < self.prefix.min_tokens:
                return
            # peek, not lookup: a presence probe must not count a
            # hit/miss or reshuffle LRU order — capture traffic would
            # otherwise pollute the cache telemetry (and with the radix
            # index, skip when a LONGER entry already covers us)
            if self.prefix.peek(prompt) is not None:
                return          # already retained; skip the re-export
            self.prefix.put(prompt, name, snapshot_fn())
        return hook

    # -- health --
    def _ready(self):
        n_ok = 0
        for rep in self.pool:
            # racy read by design: probes must not block on locks
            if (rep.state == "active" and rep.name not in
                    self._quarantined and rep.batcher._ready()[0]):
                n_ok += 1
        return (n_ok > 0,
                f"{n_ok}/{len(self.pool)} replicas admitting")

    # -- quarantine (the publisher's canary fence) --
    def quarantine(self, name: str) -> None:
        """Exclude ``name`` from live placement (see the field comment
        in ``__init__``). Safe to call before the replica exists."""
        self._quarantined.add(name)

    def unquarantine(self, name: str) -> None:
        self._quarantined.discard(name)
        self._pump_wake.set()

    # -- submission --
    def submit(self, request_id, prompt, *, session=None):
        """Accept one request (list of 1-based token ids). Returns the
        replica name it was placed on, or ``None`` if it parked in the
        router's pending queue (dispatched as soon as a replica
        admits). Raises on duplicate in-flight ids and
        :class:`RouterSaturated` past ``slo.max_pending``."""
        if self._closed:
            raise RuntimeError("router is closed")
        prompt = list(prompt)
        with self._state_lock:
            if request_id in self._inflight:
                raise ValueError(
                    f"duplicate request_id {request_id!r}: still "
                    "pending or in flight")
            self._inflight[request_id] = None    # reserve
            self._enq[request_id] = (time.monotonic(), "submit")
        self._m_requests.inc()
        if self._tracker is not None:
            self._tracker.begin(request_id, prompt_len=len(prompt))
        try:
            placed = self._dispatch(request_id, prompt, session)
        except Exception:
            with self._state_lock:
                self._inflight.pop(request_id, None)
                self._enq.pop(request_id, None)
            self._t_finish(request_id, "error")
            raise
        if placed is None:
            with self._state_lock:
                if len(self._pending) >= self.slo.max_pending:
                    self._inflight.pop(request_id, None)
                    self._enq.pop(request_id, None)
                    self._m_rejected.inc()
                    self._t_finish(request_id, "shed")
                    raise RouterSaturated(
                        f"no replica admits and {len(self._pending)} "
                        f"requests already pending "
                        f"(slo.max_pending={self.slo.max_pending})")
                self._pending.append((request_id, prompt, session))
                self._m_pending.set(len(self._pending))
                depth = len(self._pending)
            self._tev(request_id, "park", depth=depth)
        # counted once per ACCEPTED request (after the shed gate), so
        # the tokens-reused fraction has a clean denominator even when
        # pending work is re-dispatched several times
        self._m_prompt_tokens.inc(len(prompt))
        tap = self.on_submit
        if tap is not None:
            try:
                tap(request_id, list(prompt))
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "on_submit tap failed for %r", request_id)
        return placed

    def cancel(self, request_id) -> bool:
        """Cancel wherever the request is: router pending queue, a
        replica queue, or an in-flight slot. False if unknown/already
        finished."""
        with self._state_lock:
            for i, (rid, _, _) in enumerate(self._pending):
                if rid == request_id:
                    del self._pending[i]
                    self._m_pending.set(len(self._pending))
                    self._inflight.pop(request_id, None)
                    self._enq.pop(request_id, None)
                    self._t_finish(request_id, "cancelled")
                    return True
            owner = self._inflight.get(request_id)
        if owner is not None and self.pool[owner].cancel(request_id):
            with self._state_lock:
                self._inflight.pop(request_id, None)
            self._t_finish(request_id, "cancelled")
            return True
        return False

    # -- placement --
    def _fleet_stats(self) -> dict:
        stats = {}
        for rep in self.pool:
            if rep.name in self._quarantined:
                continue
            s = rep.stats()
            stats[s.name] = s
            self._m_rq.set(s.queue_depth, replica=s.name)
            self._m_rutil.set(s.kv_utilization, replica=s.name)
        return stats

    def _version_of(self, name):
        rep = self.pool.replicas.get(name)
        return getattr(rep, "weight_version", None) if rep else None

    def _version_ok(self, snapshot, name) -> bool:
        """May ``name`` adopt ``snapshot``? None on either side means
        unversioned and matches anything (mirrors the batcher's own
        adopt-time check — the router filters up front so a mismatch
        never even reaches a replica)."""
        sv = getattr(snapshot, "weight_version", None)
        rv = self._version_of(name)
        return sv is None or rv is None or sv == rv

    def _dispatch(self, rid, payload, session):
        """Try to place ``payload`` (a prompt list, or a KVSnapshot
        when re-dispatching drained/migrated work). Returns the replica
        name or None when nothing admits right now."""
        # prompts arrive as lists; anything else is a KV snapshot
        is_prompt = isinstance(payload, list)
        if not is_prompt and not any(self._version_ok(payload, n)
                                     for n in self.pool.names):
            # the snapshot's weight version is no longer served by ANY
            # pool member (a rolling publish retired it while this sat
            # in pending): its KV can never be adopted again, so
            # restart the sequence from its prompt — the result is then
            # attributable to exactly ONE (the current) version, and
            # the request still completes exactly once
            self._m_restarts.inc()
            self._tev(rid, "orphan_restart",
                      weight_version=getattr(payload, "weight_version",
                                             None))
            with self._state_lock:
                # this wait attributes to migration, not admission
                if rid in self._enq:
                    self._enq[rid] = (self._enq[rid][0], "restart")
            payload = list(payload.prompt)
            is_prompt = True
        stats = self._fleet_stats()
        cands = [s for s in stats.values()
                 if admissible(s, self.slo)[0]]
        if not is_prompt:
            # a snapshot's KV is only valid under the params that wrote
            # it: place it on a version-matching replica or keep it
            # parked (during a rolling publish the old-version
            # survivors are exactly that set)
            cands = [s for s in cands
                     if self._version_ok(payload, s.name)]
        if cands:
            # emitted only when something admits: a parked request's
            # retry loop must not spam its timeline every flush tick
            self._tev(rid, "route", candidates=len(cands))
        with trace.span("route", cat="serving",
                        prompt_len=len(payload) if is_prompt else
                        len(payload.prompt),
                        candidates=len(cands)):
            if is_prompt:
                hit, matched = self.prefix.lookup_longest(payload)
                if hit is not None and cands:
                    # materialize once: int8-stored entries dequantize
                    # per access, and version filter + adopt must see
                    # the SAME snapshot object
                    snap = hit.snapshot
                    vcands = [s for s in cands
                              if self._version_ok(snap, s.name)]
                    if vcands:
                        target = (hit.replica
                                  if hit.replica in {s.name
                                                     for s in vcands}
                                  else min(vcands,
                                           key=load_score).name)
                        if list(hit.prompt) == payload:
                            # exact: adopt everything, skip prefill
                            self.pool[target].submit(rid, snapshot=snap)
                            self._m_prefix_hits.inc()
                            self._m_tokens_reused.inc(len(payload))
                            self._tev(rid, "prefix_cache",
                                      outcome="exact",
                                      tokens_reused=len(payload))
                            self._place(rid, target, session)
                            return target
                        placed = self._adopt_partial(
                            rid, payload, matched, snap, target,
                            session)
                        if placed is not None:
                            return placed
                    # retained prefix from a superseded weight version
                    # (or no adoptable full page after truncation):
                    # fall through to a fresh prefill (the rollout's
                    # drains forget stale entries replica by replica)
                if (len(payload) >= self.slo.long_prefill_tokens
                        and len(cands) > 1):
                    return self._dispatch_disaggregated(
                        rid, payload, session, stats, cands)
            if not cands:
                return None
            target = self._pick(cands, session)
            if is_prompt:
                self._tev(rid, "prefix_cache", outcome="miss",
                          tokens_reused=0)
                self.pool[target].submit(rid, payload)
            else:
                self.pool[target].submit(rid, snapshot=payload)
            self._place(rid, target, session)
            return target

    def _adopt_partial(self, rid, prompt, matched, snap, target,
                       session):
        """Adopt the matched full pages of ``snap`` on ``target`` and
        prefill only the suffix. Returns the replica name, or None to
        fall back to a fresh prefill (no usable page boundary after
        truncation, or the replica refused the job)."""
        try:
            # leave >= 1 suffix token so there is a logit to sample:
            # truncate floors to the snapshot's page boundary
            trunc = snap.truncate(min(matched, len(prompt) - 1))
        except ValueError:
            return None           # under one full page after flooring
        if list(trunc.prompt) != prompt[:trunc.n_cached]:
            return None           # never adopt mismatched KV
        try:
            with trace.span("suffix adopt", cat="serving",
                            prompt_len=len(prompt),
                            reused=trunc.n_cached):
                self.pool[target].submit(
                    rid, prompt, snapshot=trunc,
                    prefill_from=trunc.n_cached)
        except (RuntimeError, ValueError):
            return None           # transient refusal -> fresh prefill
        self._m_prefix_partial.inc()
        self._m_tokens_reused.inc(trunc.n_cached)
        self._tev(rid, "prefix_cache", outcome="partial",
                  tokens_reused=trunc.n_cached)
        self._place(rid, target, session)
        return target

    def _pick(self, cands, session) -> str:
        if session is not None:
            sticky = self._sessions.get(session)
            if sticky is not None and any(s.name == sticky
                                          for s in cands):
                return sticky
        return min(cands, key=load_score).name

    def _place(self, rid, target, session) -> None:
        with self._state_lock:
            self._inflight[rid] = target
            if session is not None:
                self._sessions[session] = target
            enq = self._enq.pop(rid, None)
        if enq is not None:
            # the common success point for EVERY placement path: exact
            # / partial adopt, disaggregated, plain, and requeued work.
            # The exemplar ties the bucket to /requests/<id>.
            t_enq, cause = enq
            wait = time.monotonic() - t_enq
            self._m_qwait.observe(wait, exemplar=str(rid))
            self._tev(rid, "place", replica=target, cause=cause,
                      wait_s=round(wait, 9))

    def _dispatch_disaggregated(self, rid, prompt, session, stats,
                                cands):
        """Prefill on the designated/lowest-load replica, decode on the
        best OTHER candidate — a long prompt never parks a decode
        replica's bursts behind its prefill."""
        self._tev(rid, "prefix_cache", outcome="miss", tokens_reused=0)
        names = {s.name for s in cands}
        if self._prefill_name is not None and self._prefill_name in names:
            pre = self._prefill_name
        else:
            pre = min(cands, key=load_score).name
        decode_cands = [s for s in cands if s.name != pre]
        if not decode_cands:      # pre is the lone candidate
            self.pool[pre].submit(rid, prompt)
            self._place(rid, pre, session)
            return pre
        dec = self._pick(decode_cands, session)
        t_pre = time.monotonic()
        try:
            with trace.span("disagg prefill", cat="serving",
                            prefill=pre, decode=dec,
                            prompt_len=len(prompt)):
                snap = self.pool[pre].prefill_only(rid, prompt)
        except RuntimeError:
            # transient page pressure on the prefill side: fall back
            # to a plain placement rather than failing the request
            target = self._pick(cands, session)
            self.pool[target].submit(rid, prompt)
            self._place(rid, target, session)
            return target
        pre_dur = time.monotonic() - t_pre
        self._m_disagg.inc()
        self._tev(rid, "disagg", prefill=pre, decode=dec)
        self._tev(rid, "prefill_end", kind="disagg", replica=pre,
                  dur_s=round(pre_dur, 9))
        with self._state_lock:
            # the synchronous disagg prefill is prefill time, not
            # queue wait: push the enqueue clock past it so the place
            # event's wait_s (and router_queue_wait_seconds) measure
            # only admission + park
            if rid in self._enq:
                t_enq, cause = self._enq[rid]
                self._enq[rid] = (t_enq + pre_dur, cause)
        if self._capture:
            # long prompts are exactly the ones worth retaining
            self.prefix.put(prompt, dec, snap)
        self.pool[dec].submit(rid, snapshot=snap)
        self._place(rid, dec, session)
        return dec

    # -- pending pump (single consumer preserves arrival order) --
    def _pump(self):
        while not self._closed:
            self._pump_wake.wait(0.02)
            self._pump_wake.clear()
            try:
                self._flush_pending()
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "router pending flush failed")

    def _flush_pending(self):
        while True:
            with self._state_lock:
                if not self._pending:
                    self._m_pending.set(0)
                    return
                rid, payload, session = self._pending[0]
            if self._dispatch(rid, payload, session) is None:
                return            # still saturated; next wake retries
            with self._state_lock:
                self._pending.popleft()
                self._m_pending.set(len(self._pending))

    # -- results --
    def finished(self) -> list:
        """Pop completed ``(request_id, tokens)`` pairs (every accepted
        request appears exactly once)."""
        with self._state_lock:
            out = list(self._results)
            self._results.clear()
        return out

    @property
    def inflight_count(self) -> int:
        with self._state_lock:
            return len(self._inflight)

    @property
    def pending_count(self) -> int:
        with self._state_lock:
            return len(self._pending)

    def wait_all(self, timeout: float = 120.0) -> None:
        """Block until every accepted request has completed. Raises
        ``RuntimeError`` (chained to the replica's exception) as soon
        as a replica's step has failed while requests are outstanding —
        those requests would otherwise only ever time out."""
        deadline = time.monotonic() + timeout
        while True:
            with self._state_lock:
                busy = len(self._inflight) + len(self._pending)
            if not busy:
                return
            for rep in self.pool:
                err = rep.step_error
                if err is not None:
                    raise RuntimeError(
                        f"replica {rep.name} step failed with {busy} "
                        f"requests outstanding: {err!r}") from err
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"{busy} requests still outstanding after "
                    f"{timeout}s")
            time.sleep(0.005)

    # -- drain / rolling restart --
    def drain(self, name: str, *, migrate: bool = False,
              timeout: float = 120.0, policy=None) -> dict:
        """Take replica ``name`` out of rotation: admissions stop and
        its ``serving_replica_<name>`` readiness flips immediately;
        still-queued requests re-dispatch to the survivors; in-flight
        sequences either finish here (default) or — ``migrate=True`` —
        export their KV mid-decode and resume on other replicas,
        bitwise. ``policy`` decides per request instead:
        ``policy(request_id) -> "finish" | "migrate"`` — the weight
        publisher's version-skew knob (migrated snapshots carry the OLD
        weight version and only ever land on old-version survivors;
        with none left they would park, so the publisher forces
        "finish" for the last replica of a version). Returns a summary
        dict. ``resume(name)`` puts the replica back."""
        rep = self.pool[name]
        with trace.span("drain", cat="serving", replica=name,
                        migrate=migrate, policy=policy is not None):
            rep.drain_begin()
            requeued = rep.pop_queued()
            for rid, payload in requeued:
                self._tev(rid, "requeue", from_replica=name)
                self._requeue(rid, payload)
            migrated = []
            if policy is not None:
                for rid in rep.inflight_ids():
                    if policy(rid) != "migrate":
                        continue
                    snap = rep.export_request(rid)
                    migrated.append((rid, snap))
                    self._m_migrated.inc()
                    self._tev(rid, "migrate", from_replica=name,
                              weight_version=getattr(
                                  snap, "weight_version", None))
                    self._requeue(rid, snap, cause="migrate")
                if not rep.wait_idle(timeout):
                    raise TimeoutError(
                        f"replica {name} did not finish its kept "
                        f"in-flight requests in {timeout}s")
            elif migrate:
                migrated = rep.export_requests()
                for rid, snap in migrated:
                    self._m_migrated.inc()
                    self._tev(rid, "migrate", from_replica=name,
                              weight_version=getattr(
                                  snap, "weight_version", None))
                    self._requeue(rid, snap, cause="migrate")
            elif not rep.wait_idle(timeout):
                raise TimeoutError(
                    f"replica {name} did not drain in {timeout}s")
            self.prefix.forget_replica(name)
            with self._state_lock:
                dead_sessions = [k for k, v in self._sessions.items()
                                 if v == name]
                for k in dead_sessions:
                    del self._sessions[k]
        self._pump_wake.set()
        return {"replica": name, "requeued": len(requeued),
                "migrated": len(migrated)}

    def _requeue(self, rid, payload, *, cause: str = "requeue") -> None:
        with self._state_lock:
            self._inflight[rid] = None
            self._enq[rid] = (time.monotonic(), cause)
            self._pending.append((rid, payload, None))
            self._m_pending.set(len(self._pending))

    def resume(self, name: str) -> None:
        self.pool[name].resume()
        self._pump_wake.set()

    def attach_replica(self, name: str) -> None:
        """Wire a replica added to the pool AFTER router construction
        (``pool.add_replica``) into the result stream: completion /
        prefix-capture hooks plus a dispatcher wake so pending work
        spills onto the new capacity immediately. Idempotent."""
        rep = self.pool[name]
        rep.batcher.on_complete = self._make_on_complete(name)
        rep.batcher.tracker = self._tracker
        if self._capture:
            rep.batcher.on_prefill = self._make_on_prefill(name)
        self._pump_wake.set()

    # -- fleet latency view (bench serving rows) --
    def latency_summary(self) -> dict:
        """Fleet-wide latency percentiles: per-replica histograms
        merged by bucket (conservative upper-bound estimates)."""
        ttft = merge_snapshots(
            r.histogram_snapshot("serving_ttft_seconds")
            for r in self.pool if r.name not in self._quarantined)
        dec = merge_snapshots(
            r.histogram_snapshot("serving_decode_token_seconds")
            for r in self.pool if r.name not in self._quarantined)
        qw = self._m_qwait.snapshot()
        return {
            "ttft_p50_s": percentile(ttft, 0.5),
            "ttft_p99_s": percentile(ttft, 0.99),
            "ttft_count": ttft["count"],
            "decode_token_p50_s": percentile(dec, 0.5),
            "decode_token_p99_s": percentile(dec, 0.99),
            "queue_wait_p50_s": percentile(qw, 0.5),
            "queue_wait_p99_s": percentile(qw, 0.99),
            "queue_wait_count": qw["count"],
            "prefix_hits": int(self._m_prefix_hits.value()),
            "prefix_partial_hits": int(self._m_prefix_partial.value()),
            "prefix_tokens_reused": int(self._m_tokens_reused.value()),
            "prefix_tokens_reused_fraction": (
                self._m_tokens_reused.value()
                / max(1.0, self._m_prompt_tokens.value())),
            "disagg_prefills": int(self._m_disagg.value()),
            # where the retained tail's time went (None with the
            # tracker disabled) — docs/SERVING.md's runbook entry point
            "attribution": (self._tracker.attribution()
                            if self._tracker is not None else None),
        }

    def queue_wait_snapshot(self) -> dict:
        """The router-level ``router_queue_wait_seconds`` histogram as
        a mergeable snapshot (``slo.percentile``-ready). The autoscaler
        scrapes this alongside per-replica TTFT so scale-out decisions
        see the queue-wait component TTFT cannot."""
        return self._m_qwait.snapshot()

    # -- lifecycle --
    def close(self, timeout: float = 10.0) -> None:
        """Stop the dispatcher and unregister the router health check.
        The pool is NOT closed (the owner that started it closes it)."""
        if self._closed:
            return
        self._closed = True
        self._pump_wake.set()
        self._pump_thread.join(timeout)
        self._health.unregister("serving_router")

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc):
        self.close()
        return False

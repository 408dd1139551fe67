"""Driver kind ``serve``: one replica of a decoder LM behind the
program's normal serving path — ``Router(ReplicaPool(model, 1, ...))``
over a ``ContinuousBatcher``, as ``chip_smoke._serve`` builds it — under
an open-loop load generator that offers requests at the fixed rate of
the traffic file and times each one itself, from when it was DUE.

Traffic keys: see loadgen.open_loop_schedule, plus ``max_new_tokens``,
``serving`` (ContinuousBatcher geometry), ``slo`` (SLOConfig, set so that
nothing is shed), ``drain_seconds`` (how long after the window a request
may still finish), ``trace_after_s`` / ``trace_seconds`` (the traced
run's profile), ``check_sample`` (requests compared with the reference).
"""
from __future__ import annotations

import gc
import threading
import time
import types

from benchmarks import loadgen, model_setup, tracing
from benchmarks.manifest import plugin
from benchmarks.recorder import EventRecorder, clock

#: a finished request's every emitted token must have a reference logit
#: within this of that position's largest — see check() below
TOL_LOGIT = 0.15


class _System(types.SimpleNamespace):
    """The replica behind its router, built and warmed up."""

    def close(self) -> None:
        self.router.close()
        self.pool.close()


def build(ctx) -> "_System":
    """Set-up: the model, one replica behind the router, every program
    the cell's traffic uses compiled and run once."""
    import jax

    from bigdl_tpu.models.transformer.serving import PagedStepCompilers
    from bigdl_tpu.observability.exporter import HealthRegistry
    from bigdl_tpu.observability.registry import MetricRegistry
    from bigdl_tpu.serving import ReplicaPool, Router, SLOConfig

    cfg, traffic = ctx.config, ctx.traffic
    builder = plugin("builders", cfg["builder"])
    model_setup.set_dtype_policy(cfg["policy"])
    dev = ctx.devices[0]
    geometry = dict(traffic["serving"])
    new_tokens = int(traffic["max_new_tokens"])

    with jax.default_device(dev):
        model = builder.build(cfg)
        model_setup.materialize_lean(model, ctx.seed, dev)
        model.evaluate()
        ctx.log(f"serve: parameters "
                f"{model_setup.tree_bytes(model.params) / 1e9:.3f} GB, "
                f"live on device {model_setup.live_device_bytes() / 1e9:.3f}"
                " GB")
        compilers = PagedStepCompilers()
        health = HealthRegistry()
        pool = ReplicaPool(model, 1, max_new_tokens=new_tokens,
                           aot_cache=compilers, health=health, **geometry)
        slo = SLOConfig(**traffic["slo"])
        recorder = EventRecorder(slo=slo)
        router = Router(pool, slo=slo, registry=MetricRegistry(),
                        health=health, tracker=recorder)
    (replica,) = list(pool)
    ctx.log(f"serve: KV pool {geometry['num_pages']} pages x "
            f"{geometry['page_size']} tokens, live on device "
            f"{model_setup.live_device_bytes() / 1e9:.3f} GB")
    system = _System(model=model, builder=builder, pool=pool,
                     router=router, recorder=recorder, replica=replica,
                     geometry=geometry, new_tokens=new_tokens)
    try:
        buckets = loadgen.prompt_buckets(traffic, replica.batcher._bucket)
        _warm_up(ctx, router, replica, buckets, cfg["vocab_size"])
    except BaseException:
        system.close()
        raise
    return system


def run(ctx) -> dict:
    system = build(ctx)
    try:
        record = measure(ctx, system)
    finally:
        system.close()
    geometry, model = system.geometry, system.model
    record.update(kind="serve", chips=1, max_batch=geometry["max_batch"],
                  page_size=geometry["page_size"],
                  memory_peak_bytes=model_setup.memory_peak_bytes(
                      ctx.devices))
    ctx.log(f"serve: memory_stats {ctx.devices[0].memory_stats()}")
    # free the replica (its KV pool) before the reference runs
    builder, new_tokens = system.builder, system.new_tokens
    del system
    gc.collect()
    record["checks"] = check(ctx, builder, model, record, new_tokens)
    return record


def _warm_up(ctx, router, replica, buckets, vocab) -> None:
    """Every program this cell's traffic uses, and no other: the decode
    burst and one prefill per bucket are built without running
    (``warmup``), then ONE request of each bucket runs to its end, so
    whatever else the path compiles on first use (the prefix cache's
    page gathers) is compiled too. Set-up, not window."""
    import numpy as np
    t = clock()
    with replica.lock:
        got = replica.batcher.warmup(prompt_buckets=buckets)
    ctx.log(f"serve: warm-up built {got} for buckets {buckets} in "
            f"{clock() - t:.1f}s")
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 99])
    t = clock()
    for i, b in enumerate(buckets):
        n = min(int(b), replica.batcher.max_prompt)
        router.submit(f"warm-{i}",
                      rng.integers(1, vocab + 1, size=n).tolist())
    router.wait_all(timeout=900.0)
    router.finished()
    ctx.log(f"serve: warm-up ran {len(buckets)} requests in "
            f"{clock() - t:.1f}s")


class _Tracer(threading.Thread):
    """Starts and stops the profiler off the submit thread, a few
    seconds into the window, and notes both instants on the benchmark's
    clock."""

    def __init__(self, trace, t_start, seconds):
        super().__init__(name="bench-tracer", daemon=True)
        self.trace, self.t_start, self.seconds = trace, t_start, seconds
        self.clock_window = None

    def run(self):
        time.sleep(max(0.0, self.t_start - clock()))
        self.trace.start()
        a = clock()
        time.sleep(self.seconds)
        b = clock()
        self.trace.stop()
        self.clock_window = (a, b)


def measure(ctx, system, id_base: int = 0) -> dict:
    """The window: offer the schedule, follow the requests, read the
    recorder. ``id_base`` keeps ids apart when a sweep measures twice."""
    import jax
    router, pool = system.router, system.pool
    recorder, new_tokens = system.recorder, system.new_tokens
    traffic = ctx.traffic
    schedule = loadgen.open_loop_schedule(
        traffic, ctx.config["vocab_size"], ctx.seed, ctx.seconds)
    for req in schedule:            # ids unique across a sweep's windows
        req["id"] += id_base
    drain = float(traffic["drain_seconds"])
    trace = tracing.TraceSession(ctx.name) if ctx.trace else None
    sent: dict = {}
    errors: dict = {}

    t0 = clock()
    ctx.compiles.open()
    tracer = None
    if trace is not None:
        tracer = _Tracer(trace, t0 + float(traffic["trace_after_s"]),
                         float(traffic["trace_seconds"]))
        tracer.start()
    for req in schedule:
        wait = t0 + req["due_s"] - clock()
        if wait > 0:
            time.sleep(wait)
        sent[req["id"]] = clock()
        try:
            with jax.profiler.TraceAnnotation("bench:submit"):
                router.submit(req["id"], req["prompt"])
        except Exception as e:      # shed or refused: counted as failed
            errors[req["id"]] = repr(e)
    # the generator stops at the window's end; requests due inside it are
    # followed until they finish or `drain` seconds pass
    deadline = t0 + ctx.seconds + drain
    step_error = None
    while clock() < deadline:
        if router.inflight_count + router.pending_count == 0:
            break
        step_error = next((r.step_error for r in pool
                           if r.step_error is not None), None)
        if step_error is not None:
            break
        time.sleep(0.005)
    t_end = clock()
    ctx.compiles.close()
    if tracer is not None:
        tracer.join(timeout=120.0)
    if step_error is not None:
        ctx.log(f"serve: replica step failed: {step_error!r}")

    outputs = dict(router.finished())
    events = recorder.snapshot()
    requests = _requests(schedule, t0, sent, errors, events, outputs,
                         new_tokens)
    bursts = _bursts(events, {r["id"]: r for r in requests}, t0,
                     t0 + ctx.seconds)
    done = [r for r in requests if r["ok"]]
    ctx.log(f"serve: {len(schedule)} requests due in {ctx.seconds:.0f}s, "
            f"{len(done)} finished, last at +{t_end - t0:.1f}s; "
            f"{len(errors)} refused")
    return {
        "window": {"t0": t0, "t1": t0 + ctx.seconds}, "t_end": t_end,
        "requests": requests, "bursts": bursts, "outputs": outputs,
        "schedule": schedule,
        "attempted": len(requests),
        "failed": sum(not r["ok"] for r in requests),
        "trace_events": trace.events(ctx.keep_trace) if trace else None,
        "trace_clock": tracer.clock_window if tracer else None,
        "step_error": repr(step_error) if step_error else None,
    }


def _requests(schedule, t0, sent, errors, events, outputs, new_tokens):
    """One row per request due in the window, every time on the
    benchmark's clock and counted from when the request was DUE."""
    first: dict = {}
    for rid, event, t, fields in events:
        if event in ("prefill_start", "first_token", "retire"):
            first.setdefault((rid, event), (t, fields))
    rows = []
    for req in schedule:
        rid, due = req["id"], t0 + req["due_s"]
        pre = first.get((rid, "prefill_start"))
        tok = first.get((rid, "first_token"))
        ret = first.get((rid, "retire"))
        out = outputs.get(rid)
        ok = (rid not in errors and tok is not None and ret is not None
              and out is not None and len(out) == new_tokens)
        rows.append({
            "id": rid, "due_s": due, "sent_s": sent.get(rid),
            "prompt_len": len(req["prompt"]), "ok": ok,
            "queue_wait_s": None if pre is None else pre[0] - due,
            "ttft_s": tok[0] - due if ok else None,
            "tpot_s": ((ret[0] - tok[0]) / (new_tokens - 1)
                       if ok and new_tokens > 1 else None),
            "error": errors.get(rid)})
    return rows


def _bursts(events, by_id, w0, w1):
    """The decode bursts, from the program's ``decode`` events: all rows
    of one burst carry the same ``dur_s`` and arrive together. For each:
    when it ran, how many steps, and the context length of every live
    row at its first step (prompt + tokens decoded before it)."""
    seen: dict = {}
    bursts, cur, key = [], None, None
    for rid, event, t, fields in events:
        if event != "decode":
            continue
        k = fields.get("dur_s")
        if cur is None or k != key or t - cur["t1"] > 0.05:
            cur = {"t1": t, "t0": t - float(k or 0.0),
                   "steps": int(fields.get("tokens", 1)), "contexts": []}
            cur["in_window"] = w0 <= cur["t1"] <= w1
            key = k
            bursts.append(cur)
        n_before = seen.get(rid, 0)
        seen[rid] = n_before + cur["steps"]
        req = by_id.get(rid)
        if req is not None:
            cur["contexts"].append(req["prompt_len"] + n_before)
        else:
            cur["contexts"].append(n_before)    # a warm-up straggler
    return bursts


def check(ctx, builder, model, record, new_tokens) -> dict:
    """``correct`` for a serving run, outside the window.

    1. every request due in the window finished with its
       ``max_new_tokens`` tokens, all inside the vocabulary, and no
       replica step failed;
    2. for a seeded sample of finished requests, a teacher-forced pass of
       the plain float32 reference over prompt + output: every emitted
       token's reference logit lies within TOL_LOGIT of that position's
       largest. Logits, not token equality: random bf16 logits tie. At
       these widths the logits of a random-init model have a standard
       deviation near 0.4 and the largest of 50272 sits near 1.7; bf16
       weights and activations through 16 layers move a logit by a few
       1e-2 (the largest deficit seen on the chip is in PERF.md section
       6), while a dropped layer, a wrong mask or position, or a stale
       KV page makes the emitted token a random one: a deficit near 1.7.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg = ctx.config
    vocab = cfg["vocab_size"]
    reqs, outputs = record["requests"], record["outputs"]
    out = {"all_finished": record["failed"] == 0
           and record["step_error"] is None,
           "tokens_in_vocab": all(
               1 <= t <= vocab for r in reqs if r["ok"]
               for t in outputs[r["id"]])}
    done = [r for r in reqs if r["ok"]]
    k = min(int(ctx.traffic.get("check_sample", 4)), len(done))
    out["sampled"] = k
    worst = 0.0
    if k:
        ref = plugin("reference", cfg["reference"])
        rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 7])
        pick = rng.choice(len(done), size=k, replace=False)
        prompts = {r["id"]: r["prompt"] for r in record["schedule"]}
        # one fixed padded length: one compiled reference, and causal
        # attention makes padding at the end invisible to what precedes it
        longest = int(ctx.traffic["prompt_len"].get(
            "max", ctx.traffic["prompt_len"].get("value", 0)))
        width = longest + new_tokens
        ids = np.zeros((k, width), np.int32)
        pos = np.zeros((k, new_tokens), np.int32)
        emitted = np.zeros((k, new_tokens), np.int32)
        for row, j in enumerate(pick):
            rid = done[int(j)]["id"]
            seq = list(prompts[rid]) + list(outputs[rid])
            ids[row, :len(seq) - 1] = np.asarray(seq[:-1]) - 1
            pos[row] = len(prompts[rid]) - 1 + np.arange(new_tokens)
            emitted[row] = np.asarray(outputs[rid]) - 1
        w = builder.reference_weights(model.params, cfg)
        with jax.default_device(ctx.devices[0]):
            logits = ref.logits_at(w, jnp.asarray(ids), jnp.asarray(pos),
                                   cfg["num_attention_heads"])
            top = jnp.max(logits, axis=-1)
            got = jnp.take_along_axis(
                logits, jnp.asarray(emitted)[..., None], axis=-1)[..., 0]
            deficit = np.asarray(top - got)
        worst = float(deficit.max())
        out["logit_deficit_max"] = worst
        out["logit_deficit_p99"] = float(np.quantile(deficit, 0.99))
        out["tokens_equal_share"] = float((deficit == 0).mean())
    out["logits_ok"] = k > 0 and worst <= TOL_LOGIT
    out["ok"] = bool(out["all_finished"] and out["tokens_in_vocab"]
                     and out["logits_ok"])
    return out

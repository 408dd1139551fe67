"""Benchmark harness: one JSON line per metric, headline first.

Headline (line 1): Inception-v1 ImageNet training throughput per chip on
synthetic device-resident tensors — the roofline-audited number
(docs/PERF.md). Extra lines (VERDICT r3 #6, reference
models/utils/DistriOptimizerPerf.scala:33-70 multi-model harness):

  - inception_v1 REAL-DATA training: JPEG bytes from .brec shards through
    the native u8 decode path, normalize on-device (VERDICT r3 #1)
  - the same with the decoded-RAM cache warm (post-first-epoch rate)
  - resnet50 / vgg16 train throughput
  - transformer LM tokens/s + MFU (fused-CE head, flash attention)

Baseline derivation (BASELINE.md): the reference publishes NO quantitative
table; its README claims single-node Xeon training "comparable with
mainstream GPU" (README.md:9). A mainstream 2016 GPU (K80-class) trains
Inception-v1 at ~150 images/sec, so 150 img/s/device is the documented
stand-in baseline; ``vs_baseline`` = value / 150. MFU / achieved TFLOP/s
are reported so the gap stays honest.

Usage: ``python bench.py`` (all rows) / ``--headline-only`` (line 1 only).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_PER_SEC = 150.0
BATCH = 256
WARMUP = 3
ITERS = 30
SHARD_DIR = "/tmp/bigdl_tpu_bench_shards_v1"
SHARD_IMAGES = 4096
REAL_BATCH = 256

# bf16 peak TFLOP/s per chip, keyed by the exact ``device_kind`` jax
# reports, each with its source
_PEAK_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197.0,
}


def _chip_peak_tflops() -> float:
    """Peak of the device this process runs on. A device kind missing
    from the table is an error: an MFU against a guessed or absent peak
    is not a measurement."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_TFLOPS:
        raise RuntimeError(
            f"no bf16 peak recorded for device kind {kind!r} (known: "
            f"{sorted(_PEAK_TFLOPS)}) — add it to _PEAK_TFLOPS with its "
            "source before reporting utilization on this device")
    return _PEAK_TFLOPS[kind]


def _set_bf16_policy():
    import jax.numpy as jnp
    from bigdl_tpu.tensor import DTypePolicy, set_policy
    # f32 params, bf16 MXU compute, bf16 activations in HBM — the TPU
    # equivalent of the reference's FP16-on-the-wire + f32 math split
    # (SURVEY §5.8), extended to the memory system because conv steps are
    # bandwidth-bound (docs/PERF.md)
    set_policy(DTypePolicy(param_dtype=jnp.float32,
                           compute_dtype=jnp.bfloat16,
                           activation_dtype=jnp.bfloat16))


def _publish_registry(row: dict):
    """Mirror a bench row into the process-wide metric registry
    (bigdl_tpu.observability) so bench results export beside the
    training/serving series — one gauge per metric name."""
    val = row.get("value")
    if "metric" not in row or not isinstance(val, (int, float)):
        return
    from bigdl_tpu.observability.registry import (default_registry,
                                                  sanitize_name)
    default_registry().gauge(
        "bench_" + sanitize_name(str(row["metric"])),
        f"bench.py row (unit: {row.get('unit', '')})").set(float(val))


def _emit(row: dict):
    _publish_registry(row)
    print(json.dumps(row), flush=True)


def _record_compile_telemetry(name: str, compiled) -> None:
    """Export an AOT executable's cost/memory table (FLOPs, bytes
    accessed, arg/output/temp + peak HBM bytes) as registry gauges so
    ``--metrics-out`` carries compile telemetry beside the rates."""
    from bigdl_tpu.observability import compile_watch
    try:
        compile_watch.record_executable(name, compiled)
    except Exception as e:          # telemetry must never fail a row
        print(f"compile telemetry for {name} unavailable: {e}",
              file=sys.stderr)


def _convnet_pieces(model_name: str):
    import jax
    from bigdl_tpu import models, nn
    from bigdl_tpu.optim import SGD
    builders = {
        "inception_v1": lambda: models.Inception_v1_NoAuxClassifier(1000),
        # the BN-Inception profile (reference Inception_v2.scala:25-103) —
        # the architecture-level lever past v1's bandwidth ceiling
        # (docs/PERF.md): BN after every conv, 3x3 factorized 5x5s.
        # NoAux variant for the same single-head profile as the headline
        "inception_v2": lambda: models.Inception_v2_NoAuxClassifier(1000),
        "resnet50": lambda: models.ResNet(
            1000, {"depth": 50, "dataset": "imagenet"}),
        "vgg16": lambda: models.Vgg_16(1000),
    }
    model = builders[model_name]()
    model.materialize(jax.random.PRNGKey(0))
    model.training()
    criterion = nn.ClassNLLCriterion()
    optim = SGD(learning_rate=0.0898, momentum=0.9)
    params, mstate = model.params, model.state
    opt_state = optim.init_state(params)

    def train_step(params, mstate, opt_state, rng, data, labels):
        def loss_fn(p):
            y, new_state = model.apply(p, mstate, data, training=True,
                                       rng=rng)
            return criterion.apply(y, labels), new_state

        (loss, new_mstate), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params, new_opt_state = optim.update(grads, params, opt_state)
        return new_params, new_mstate, new_opt_state, loss

    return model, params, mstate, opt_state, train_step


def bench_convnet_synthetic(model_name: str, batch: int = BATCH,
                            iters: int = ITERS, headline: bool = False):
    import jax
    import jax.numpy as jnp
    _set_bf16_policy()
    model, params, mstate, opt_state, train_step = _convnet_pieces(
        model_name)
    jit_step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    rng = jax.random.PRNGKey(0)
    host = np.random.default_rng(0)
    data = jnp.asarray(host.standard_normal((batch, 3, 224, 224),
                                            np.float32))
    labels = jnp.asarray(host.integers(1, 1001, size=(batch,)))  # 1-based

    # AOT-compile once; the executable serves both XLA's FLOP count and
    # the timed loop (avoids any chance of a second trace/compile)
    compiled = jit_step.lower(params, mstate, opt_state, rng, data,
                              labels).compile()
    cost = compiled.cost_analysis()
    step_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    _record_compile_telemetry(f"bench_{model_name}_train_step", compiled)

    for _ in range(WARMUP):
        rng, k = jax.random.split(rng)
        params, mstate, opt_state, loss = compiled(params, mstate,
                                                   opt_state, k, data,
                                                   labels)
    float(loss)  # host read: returns once the device has finished

    t0 = time.perf_counter()
    for _ in range(iters):
        rng, k = jax.random.split(rng)
        params, mstate, opt_state, loss = compiled(params, mstate,
                                                   opt_state, k, data,
                                                   labels)
    float(loss)  # host read: returns once the device has finished
    dt = time.perf_counter() - t0

    value = batch * iters / dt
    achieved_tflops = step_flops * iters / dt / 1e12
    peak = _chip_peak_tflops()
    out = {
        "metric": f"{model_name}_train_images_per_sec_per_chip",
        "value": round(value, 2),
        "unit": "images/sec/chip",
        "achieved_tflops": round(achieved_tflops, 1),
    }
    if headline:
        out["metric"] = "inception_v1_train_images_per_sec_per_chip"
        # The reference publishes no quantitative number; 150 img/s is a
        # documented K80-class stand-in (see module docstring).
        out["vs_baseline"] = round(value / BASELINE_IMG_PER_SEC, 3)
        out["baseline_is_standin"] = True
    out["mfu"] = round(achieved_tflops / peak, 3)
    out["chip_peak_tflops_bf16"] = peak
    return out


# headline synthetic run shared by the headline and train_mfu rows (the
# row fns are what tests monkeypatch; this cache is what makes requesting
# both cost one training run)
_headline_cache = None


def _headline_row() -> dict:
    global _headline_cache
    if _headline_cache is None:
        _headline_cache = bench_convnet_synthetic("inception_v1",
                                                  headline=True)
    return dict(_headline_cache)


def bench_train_mfu():
    """Training MFU as a first-class gated metric (ISSUE 7): achieved
    model FLOP utilization of the headline Inception-v1 synthetic train
    step against the chip's bf16 peak. Shares the headline row's run."""
    row = _headline_row()
    return {
        "metric": "train_mfu",
        "value": row["mfu"],
        "unit": "fraction of bf16 peak",
        "images_per_sec_per_chip": row.get("value"),
        "achieved_tflops": row.get("achieved_tflops"),
        "chip_peak_tflops_bf16": row["chip_peak_tflops_bf16"],
    }


# cold-start probe geometries: model -> (input shape, classes). The
# headline Inception geometry is the bench workload; lenet5 is the
# fast geometry the contract tests exercise end to end.
_COLD_START_GEOMETRIES = {
    "inception_v1": ((3, 224, 224), 1000),
    "lenet5": ((1, 28, 28), 10),
}


def _cold_start_probe_main(cache_dir: str, model_name: str,
                           batch: int = 2) -> None:
    """--cold-start-probe subprocess entry: build the train step through
    the AOT-cache pipeline (tuning/aot_cache.py), run ONE step, and emit
    the phase timings. First run against an empty ``cache_dir`` pays the
    XLA compile; a second process against the same dir loads the
    serialized executable instead."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.tuning.aot_cache import AOTCache, StepCompiler
    if jax.default_backend() == "tpu":
        # the bench policy. On CPU, bf16 EMULATION makes the one
        # executed step dominate both phases and mask the
        # construction-time difference being measured — f32 (the
        # default policy) keeps the probe about compile vs load there
        _set_bf16_policy()
    t0 = time.perf_counter()
    shape, classes = _COLD_START_GEOMETRIES[model_name]
    if model_name == "lenet5":
        from bigdl_tpu import models, nn
        from bigdl_tpu.optim import SGD
        model = models.LeNet5(classes)
        model.materialize(jax.random.PRNGKey(0))
        model.training()
        criterion = nn.ClassNLLCriterion()
        optim = SGD(learning_rate=0.0898, momentum=0.9)
        params, mstate = model.params, model.state
        opt_state = optim.init_state(params)

        def train_step(params, mstate, opt_state, rng, data, labels):
            def loss_fn(p):
                y, st = model.apply(p, mstate, data, training=True,
                                    rng=rng)
                return criterion.apply(y, labels), st
            (loss, st), g = jax.value_and_grad(loss_fn,
                                               has_aux=True)(params)
            p2, o2 = optim.update(g, params, opt_state)
            return p2, st, o2, loss
    else:
        _, params, mstate, opt_state, train_step = _convnet_pieces(
            model_name)
    host = np.random.default_rng(0)
    data = jnp.asarray(host.standard_normal((batch,) + shape,
                                            np.float32))
    labels = jnp.asarray(host.integers(1, classes + 1, size=(batch,)))
    rng = jax.random.PRNGKey(0)
    setup_s = time.perf_counter() - t0

    cache = AOTCache(cache_dir)
    pipeline = StepCompiler(
        jax.jit(train_step, donate_argnums=(0, 1, 2)),
        name="cold_start_probe", cache=cache, donate_argnums=(0, 1, 2),
        extra=f"bench cold-start probe v1 {model_name} b{batch}")
    # start-to-first-step for the phase the cache controls: step
    # construction (lower+compile on a cold dir, deserialize on a warm
    # one) plus the first executed step, host-synced
    t1 = time.perf_counter()
    args = (params, mstate, opt_state, rng, data, labels)
    compiled, _ = pipeline.get((data.shape, labels.shape), args)
    params, mstate, opt_state, loss = compiled(*args)
    loss_v = float(jax.device_get(loss))
    first_step_s = time.perf_counter() - t1
    _emit({"first_step_s": first_step_s, "setup_s": setup_s,
           "loss": loss_v, "cache_hits": cache.hits,
           "cache_misses": cache.misses})


def bench_compile_cold_start(model: str = "inception_v1",
                             batch: int = 2,
                             cache_dir: str | None = None):
    """Worker start-to-first-step with a cold vs warmed AOT executable
    cache (ISSUE 8): the same probe workload runs in two fresh
    subprocesses sharing one cache directory — the first compiles and
    serializes, the second deserializes. ``value`` is the speedup of
    the phase the cache controls (step construction + first step);
    model/data setup time is reported alongside so the whole-process
    ratio stays honest. The probe batch is small so the one EXECUTED
    step does not mask the construction-time difference on slow
    backends. Children run on the CPU backend (like the wire probe —
    the parent may hold the TPU), which is the conservative side: TPU
    compiles are longer, deserializes are not."""
    import subprocess
    import tempfile
    cache_dir = cache_dir or tempfile.mkdtemp(
        prefix="bigdl_tpu_aot_bench_")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = {}
    for phase in ("cold", "warm"):
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--cold-start-probe", cache_dir,
             "--cold-start-model", model,
             "--cold-start-batch", str(batch)],
            capture_output=True, text=True, timeout=1200, env=env)
        payload = None
        for line in p.stdout.splitlines():
            if line.startswith("{"):
                payload = json.loads(line)
        if payload is None:
            tail = (p.stderr or "").strip().splitlines()[-3:]
            raise RuntimeError(
                f"cold-start {phase} probe rc={p.returncode}: "
                + (" | ".join(tail) or "no output"))
        out[phase] = payload
    cold, warm = out["cold"], out["warm"]
    ratio = cold["first_step_s"] / max(warm["first_step_s"], 1e-9)
    wall_cold = cold["setup_s"] + cold["first_step_s"]
    wall_warm = warm["setup_s"] + warm["first_step_s"]
    return {
        "metric": "compile_cold_start",
        "value": round(ratio, 2),
        "unit": "x (cold / warm start-to-first-step)",
        "cold_first_step_s": round(cold["first_step_s"], 3),
        "warm_first_step_s": round(warm["first_step_s"], 3),
        "setup_s": round(warm["setup_s"], 3),
        "wall_ratio_incl_setup": round(wall_cold /
                                       max(wall_warm, 1e-9), 2),
        "warm_cache_hits": warm["cache_hits"],
        "warm_cache_misses": warm["cache_misses"],
        "loss_bit_identical": cold["loss"] == warm["loss"],
        "probe_model": model,
        "cache_dir": cache_dir,
    }


def _elastic_probe_dataset():
    """Shared trainer/resume dataset for the elastic probes: the tiny
    XOR geometry — steps are milliseconds, so the parent's SIGKILL
    lands mid-run and the resume cost measured is the elastic machinery
    (load + redistribute + step construction), not the model."""
    from bigdl_tpu.dataset import Sample, SampleToBatch, array
    rs = np.random.RandomState(0)
    x = rs.rand(128, 2).astype(np.float32)
    y = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(np.int64) + 1
    return array([Sample(x[i], y[i]) for i in range(128)],
                 num_shards=1) >> SampleToBatch(16, drop_remainder=True)


def _elastic_model_optim():
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    model = nn.Sequential(nn.Linear(2, 16), nn.Tanh(), nn.Linear(16, 2),
                          nn.LogSoftMax())
    return model, optim.SGD(learning_rate=0.3, momentum=0.9)


def _elastic_train_probe_main(ckpt_dir: str) -> None:
    """--elastic-train-probe subprocess entry: a distributed training
    run checkpointing asynchronously every 8 iterations into
    ``ckpt_dir``. It never finishes on its own — the parent SIGKILLs it
    once a complete manifest lands, the same failure the elastic
    subsystem exists to absorb."""
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.parallel import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(5)
    Engine.init()
    model, method = _elastic_model_optim()
    o = optim.Optimizer(model=model, dataset=_elastic_probe_dataset(),
                        criterion=nn.ClassNLLCriterion())
    o.set_optim_method(method)
    o.set_checkpoint(ckpt_dir, optim.several_iteration(8))
    o.set_end_when(optim.max_iteration(1_000_000))
    o.optimize()


def _elastic_resume_probe_main(ckpt_dir: str, cache_dir: str) -> None:
    """--elastic-resume-probe subprocess entry: time kill-to-first-step
    on a RESIZED mesh (the parent forces a different virtual device
    count): load the latest manifest-complete snapshot, redistribute
    onto this mesh, and run ONE training step through the persistent
    AOT executable cache."""
    import logging

    import jax

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu import elastic
    from bigdl_tpu.parallel import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(5)
    t0 = time.perf_counter()
    model, state, man = elastic.load_checkpoint(ckpt_dir)
    load_s = time.perf_counter() - t0
    Engine.init()
    _, method = _elastic_model_optim()
    o = optim.Optimizer(model=model, dataset=_elastic_probe_dataset(),
                        criterion=nn.ClassNLLCriterion())
    o.set_optim_method(method)
    o.set_state(state)
    o.set_aot_cache(cache_dir)
    resumed_neval = int(man["neval"])
    o.set_end_when(lambda s: s["neval"] > resumed_neval + 1)
    losses = []

    class _Rec(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if "loss is" in msg:
                losses.append(float(
                    msg.split("loss is ")[1].split(",")[0]))

    lg = logging.getLogger("bigdl_tpu.optim")
    lg.addHandler(_Rec())
    lg.setLevel(logging.INFO)
    t1 = time.perf_counter()
    o.optimize()
    first_step_s = time.perf_counter() - t1
    cache = o._aot_cache()
    _emit({"load_s": load_s, "first_step_s": first_step_s,
           "resume_to_first_step_s": load_s + first_step_s,
           "resumed_neval": resumed_neval,
           "loss": losses[-1] if losses else None,
           "cache_hits": cache.hits, "cache_misses": cache.misses,
           "mesh_devices": jax.device_count()})


def bench_elastic_resume_secs(train_devices: int = 8,
                              resume_devices: int = 4,
                              ckpt_dir: str | None = None,
                              timeout_s: float = 300.0):
    """Elastic restart latency (ISSUE 14): SIGKILL a checkpointing
    trainer mid-run, then resume on a RESIZED mesh from the latest
    manifest-complete snapshot. Two resume subprocesses share one AOT
    cache directory: the first pays the step compile (first restart of
    a geometry), the second deserializes (the steady-state fleet
    restart). ``value`` is the warm kill-to-first-resumed-step wall
    time in seconds — the window of lost work a preemption costs beyond
    the steps since the last checkpoint. Children run on the CPU
    backend (the parent may hold the TPU); mesh sizes are virtual
    device counts."""
    import subprocess
    import tempfile

    from bigdl_tpu.elastic import latest_checkpoint
    ckpt_dir = ckpt_dir or tempfile.mkdtemp(
        prefix="bigdl_tpu_elastic_bench_")
    cache_dir = tempfile.mkdtemp(prefix="bigdl_tpu_elastic_aot_")
    env_train = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=_xla_flags_with_device_count(int(train_devices)))
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--elastic-train-probe", ckpt_dir],
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
        env=env_train)
    try:
        deadline = time.monotonic() + timeout_s
        man = None
        while time.monotonic() < deadline:
            man = latest_checkpoint(ckpt_dir)
            if man is not None:
                break
            if p.poll() is not None:
                tail = (p.stderr.read() or "").strip().splitlines()[-3:]
                raise RuntimeError(
                    f"elastic train probe exited rc={p.returncode} "
                    "before writing a checkpoint: "
                    + (" | ".join(tail) or "no output"))
            time.sleep(0.2)
        if man is None:
            raise RuntimeError("elastic train probe wrote no checkpoint "
                               f"within {timeout_s}s")
    finally:
        p.kill()
        p.wait(timeout=30)
    env_resume = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=_xla_flags_with_device_count(int(resume_devices)))
    out = {}
    for phase in ("cold", "warm"):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--elastic-resume-probe", ckpt_dir,
             "--elastic-resume-cache", cache_dir],
            capture_output=True, text=True, timeout=1200, env=env_resume)
        payload = None
        for line in r.stdout.splitlines():
            if line.startswith("{"):
                payload = json.loads(line)
        if payload is None:
            tail = (r.stderr or "").strip().splitlines()[-3:]
            raise RuntimeError(
                f"elastic {phase} resume probe rc={r.returncode}: "
                + (" | ".join(tail) or "no output"))
        out[phase] = payload
    cold, warm = out["cold"], out["warm"]
    return {
        "metric": "elastic_resume_secs",
        "value": round(warm["resume_to_first_step_s"], 3),
        "unit": "s (kill -> first resumed step, warm AOT cache, "
                f"{train_devices}->{resume_devices} mesh)",
        "cold_resume_s": round(cold["resume_to_first_step_s"], 3),
        "warm_resume_s": round(warm["resume_to_first_step_s"], 3),
        "load_s": round(warm["load_s"], 3),
        "resumed_neval": warm["resumed_neval"],
        "warm_cache_hits": warm["cache_hits"],
        "warm_cache_misses": warm["cache_misses"],
        "loss_bit_identical": cold["loss"] == warm["loss"],
        "ckpt_dir": ckpt_dir,
    }


def bench_train_peak_hbm(**geometry):
    """Static peak-HBM accounting for the transformer train step across
    remat policies at FIXED effective batch (ISSUE 10 — the tentpole's
    measured receipt): runs ``optim.remat.train_memory_probe`` in a CPU
    SUBPROCESS (same pattern as the wire/HBM probes — static analysis
    only, the parent's TPU backend is never touched). Per policy the
    probe counts the saved-residual bytes the backward holds (abstract
    ``jax.vjp`` partial-eval — backend-independent; the CPU executable's
    buffer assignment CSEs remat away, so ``memory_analysis`` alone
    cannot show it) plus the policy-invariant persistent state, and
    compiles the k=1 vs k=N gradient-accumulation steps to show the
    scan bounding activation liveness in the executable itself.
    ``value`` is the peak-HBM reduction of ``nothing_saveable`` vs
    ``none``."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--train-hbm-probe",
         "--train-hbm-geometry", json.dumps(geometry)],
        capture_output=True, text=True, timeout=900, env=env)
    payload = None
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            payload = json.loads(line)
    if payload is None:
        tail = (out.stderr or "").strip().splitlines()[-2:]
        raise RuntimeError(
            f"train-hbm probe subprocess rc={out.returncode}: "
            + (" | ".join(tail) or "no output"))
    peak = payload["peak_hbm_bytes"]
    resid = payload["saved_residual_bytes"]
    row = {
        "metric": "train_peak_hbm_bytes",
        "value": round(payload["reduction"], 2),
        "unit": "x (peak HBM none / nothing_saveable, fixed effective "
                "batch)",
        "persistent_bytes": payload["persistent_bytes"],
        "geometry": payload["geometry"],
    }
    for pol in sorted(peak):
        row[f"peak_hbm_bytes_{pol}"] = peak[pol]
        row[f"saved_residual_bytes_{pol}"] = resid[pol]
    for pol, r in sorted(payload.get("residual_reduction", {}).items()):
        if r is not None:
            row[f"residual_reduction_{pol}"] = round(r, 2)
    if payload.get("accum_temp_reduction") is not None:
        row["accum_k"] = payload.get("accum_k")
        row["accum_temp_reduction"] = round(
            payload["accum_temp_reduction"], 2)
        row["accum_executable_temp_bytes"] = {
            k: v.get("temp_bytes")
            for k, v in payload["accum_executable_stats"].items()}
    return row


def _train_hbm_probe_main(geometry_json: str):
    """--train-hbm-probe subprocess entry: run the static accounting on
    the CPU backend and emit the JSON payload."""
    from bigdl_tpu.optim.remat import train_memory_probe
    _emit(train_memory_probe(**json.loads(geometry_json or "{}")))


def _xla_flags_with_device_count(n: int) -> str:
    """This process's XLA_FLAGS with the virtual-device count forced to
    ``n`` (replacing any inherited setting)."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    return " ".join(flags)


def bench_multichip_scaling(device_counts=(1, 2, 4, 8),
                            batch_per_chip: int = 64, iters: int = 8):
    """Scaling curve over mesh sizes (ROADMAP item 5 remaining): the
    same data-parallel train step at fixed PER-CHIP batch on 1/2/4/8
    virtual CPU devices, one fresh subprocess per mesh size. ``value``
    is the per-chip throughput at the largest mesh relative to the
    1-device run (ideal weak scaling = 1.0). HONESTY NOTE: the CPU
    mesh emulates every chip on one host, so per-chip throughput falls
    roughly as 1/N here — the row exists to pin the wiring and the
    collective overhead TREND; on real ICI the same probe reads the
    scaling headroom."""
    import subprocess
    results = {}
    for n in device_counts:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=_xla_flags_with_device_count(int(n)))
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--scaling-probe", str(int(n)),
             "--scaling-batch-per-chip", str(int(batch_per_chip)),
             "--scaling-iters", str(int(iters))],
            capture_output=True, text=True, timeout=600, env=env)
        payload = None
        for line in p.stdout.splitlines():
            if line.startswith("{"):
                payload = json.loads(line)
        if payload is None:
            tail = (p.stderr or "").strip().splitlines()[-2:]
            raise RuntimeError(
                f"scaling probe (n={n}) rc={p.returncode}: "
                + (" | ".join(tail) or "no output"))
        results[int(n)] = payload["images_per_sec"]
    counts = sorted(results)
    per_chip = {n: results[n] / n for n in counts}
    base = per_chip[counts[0]]
    ratio = {n: per_chip[n] / base for n in counts}
    top = counts[-1]
    return {
        "metric": "multichip_scaling",
        "value": round(ratio[top], 4),
        "unit": f"per-chip throughput ratio vs ideal at {top} devices",
        "device_counts": counts,
        "images_per_sec": {str(n): round(results[n], 1) for n in counts},
        "per_chip_img_per_sec": {str(n): round(per_chip[n], 1)
                                 for n in counts},
        "ratio_vs_ideal": {str(n): round(ratio[n], 4) for n in counts},
        "batch_per_chip": batch_per_chip,
        "cpu_mesh_emulated": True,
    }


def _scaling_probe_main(n: int, batch_per_chip: int, iters: int):
    """--scaling-probe subprocess entry: time the data-parallel train
    step on this process's ``n``-device CPU mesh and emit the rate."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.parallel.engine import Engine, data_sharding, \
        replicated

    mesh = Engine.init()
    assert int(np.prod(mesh.devices.shape)) == n, \
        f"mesh has {mesh.devices.shape} devices, wanted {n}"
    rs = np.random.RandomState(0)
    d_in, d_hidden = 256, 512
    params = {"w1": jnp.asarray(rs.randn(d_in, d_hidden)
                                .astype(np.float32) * 0.05),
              "b1": jnp.zeros((d_hidden,), jnp.float32),
              "w2": jnp.asarray(rs.randn(d_hidden, d_in)
                                .astype(np.float32) * 0.05),
              "b2": jnp.zeros((d_in,), jnp.float32)}
    batch = batch_per_chip * n
    data = jnp.asarray(rs.rand(batch, d_in).astype(np.float32))
    labels = jnp.asarray(rs.rand(batch, d_in).astype(np.float32))
    repl, shard = replicated(mesh), data_sharding(mesh)
    data = jax.device_put(data, shard)
    labels = jax.device_put(labels, shard)
    params = jax.device_put(params, repl)

    def step(p, x, y):
        def loss_fn(pp):
            h = jnp.tanh(x @ pp["w1"] + pp["b1"])
            o = h @ pp["w2"] + pp["b2"]
            # mean over the GLOBAL batch: the induced gradient
            # allreduce is the collective whose overhead the curve
            # measures
            return jnp.mean((o - y) ** 2)

        g = jax.grad(loss_fn)(p)
        return jax.tree.map(lambda pp, gg: pp - 0.1 * gg, p, g)

    jit_step = jax.jit(step, donate_argnums=(0,),
                       in_shardings=(repl, shard, shard),
                       out_shardings=repl)
    compiled = jit_step.lower(params, data, labels).compile()
    for _ in range(2):
        params = compiled(params, data, labels)
    jax.device_get(jax.tree.leaves(params)[0])   # real sync
    t0 = time.perf_counter()
    for _ in range(iters):
        params = compiled(params, data, labels)
    jax.device_get(jax.tree.leaves(params)[0])
    dt = time.perf_counter() - t0
    _emit({"devices": n, "images_per_sec": batch * iters / dt})


def _pipeline_bubble_geometry() -> dict:
    # tiny fixed (S, M) geometry: big enough that the modeled bubbles
    # separate (gpipe 3/11 vs interleaved-1F1B 3/19), small enough that
    # the probe's jitted units compile in seconds on one CPU core
    return dict(n_stages=4, num_microbatches=8, virtual_stages=2,
                d_model=16, mb_rows=4, layers_per_stage=2, reps=5)


def bench_pipeline_bubble(**geometry):
    """Measured pipeline-schedule bubble fractions (ISSUE 11): real
    per-stage forward/backward span timings (jitted chunk units on the
    CPU backend, median of reps) composed through each schedule's exact
    dependency graph (``parallel.pipeline.measure_pipeline_bubble``),
    vs the extended ``pipeline_schedule_stats`` model. Runs in a CPU
    SUBPROCESS like the other static probes. ``value`` is the measured
    interleaved-1F1B bubble fraction — the production schedule — which
    must land strictly below GPipe's at the same (S, M) and within
    tolerance of the model (test_bench_contract.py pins both). Lower is
    better; the gate knows."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    geo = dict(_pipeline_bubble_geometry(), **geometry)
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--pipeline-bubble-probe",
         "--pipeline-bubble-geometry", json.dumps(geo)],
        capture_output=True, text=True, timeout=600, env=env)
    payload = None
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            payload = json.loads(line)
    if payload is None:
        tail = (out.stderr or "").strip().splitlines()[-2:]
        raise RuntimeError(
            f"pipeline-bubble probe subprocess rc={out.returncode}: "
            + (" | ".join(tail) or "no output"))
    sch = payload["schedules"]
    row = {
        "metric": "pipeline_bubble_fraction",
        "value": round(
            sch["interleaved_1f1b"]["measured_bubble_fraction"], 4),
        "unit": "measured interleaved-1F1B bubble fraction "
                "(fill-drain idle share; lower is better)",
        "n_stages": payload["n_stages"],
        "num_microbatches": payload["num_microbatches"],
        "virtual_stages": payload["virtual_stages"],
        "geometry": payload["geometry"],
    }
    for name, r in sch.items():
        row[f"measured_{name}"] = round(r["measured_bubble_fraction"], 4)
        row[f"modeled_{name}"] = round(r["modeled_bubble_fraction"], 4)
    row["fwd_span_us"] = round(
        sch["1f1b"]["fwd_span_s"] * 1e6, 1)
    row["bwd_span_us"] = round(
        sch["1f1b"]["bwd_span_s"] * 1e6, 1)
    return row


def _pipeline_bubble_probe_main(geometry_json: str):
    """--pipeline-bubble-probe subprocess entry: time the per-stage
    units on the CPU backend and emit the per-schedule measured/modeled
    bubble JSON."""
    from bigdl_tpu.parallel.pipeline import measure_pipeline_bubble
    _emit(measure_pipeline_bubble(**json.loads(geometry_json or "{}")))


def _wire_probe_geometry() -> dict:
    return dict(d_in=256, d_hidden=1024, layers=3, batch=512,
                bucket_kb=512)


def bench_collective_wire_bytes():
    """Static per-step collective wire accounting for the sharded-update
    step at fp32 vs bf16 vs int8 wire codecs (ISSUE 7): the compiled
    HLO's collective payloads under a ring schedule. Runs the lowering
    in a SUBPROCESS on the 8-virtual-CPU-device mesh — the accounting is
    static, backend-independent, and must not disturb (or hang on) this
    process's TPU backend."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8")
               .strip())
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--wire-probe"],
        capture_output=True, text=True, timeout=600, env=env)
    payload = None
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            payload = json.loads(line)
    if payload is None:
        tail = (out.stderr or "").strip().splitlines()[-2:]
        raise RuntimeError(
            f"wire probe subprocess rc={out.returncode}: "
            + (" | ".join(tail) or "no output"))
    wb = payload["wire_bytes_per_chip"]
    red = payload["reduction_vs_fp32"]
    return {
        "metric": "collective_wire_bytes_per_step",
        "value": wb["int8"],
        "unit": "bytes/chip/step (int8 wire)",
        "wire_bytes_per_chip_fp32": wb["fp32"],
        "wire_bytes_per_chip_bf16": wb["bf16"],
        "wire_bytes_per_chip_int8": wb["int8"],
        "reduction_bf16_vs_fp32": round(red["bf16"], 3),
        "reduction_int8_vs_fp32": round(red["int8"], 3),
        "geometry": payload["geometry"],
        "n_shards": payload["n_shards"],
    }


def _wire_probe_main():
    """--wire-probe subprocess entry: lower the explicit sharded step on
    the virtual CPU mesh at each codec and emit the accounting JSON."""
    from bigdl_tpu.optim.sharded_update import wire_bytes_probe
    from bigdl_tpu.parallel import Engine
    Engine.init()
    _emit(wire_bytes_probe(**_wire_probe_geometry()))


def _ensure_shards() -> str:
    """Synthetic ImageNet-like JPEG shards (photo-statistics content,
    shorter side 256 like the reference's seqfile generator), built once
    and cached on disk."""
    import io

    from PIL import Image

    from bigdl_tpu.dataset.recordio import RecordWriter, SHARD_SUFFIX
    marker = os.path.join(SHARD_DIR, "done")
    if os.path.exists(marker):
        return SHARD_DIR
    os.makedirs(SHARD_DIR, exist_ok=True)
    rs = np.random.default_rng(0)
    num_shards = 4
    writers = [RecordWriter(os.path.join(
        SHARD_DIR, f"shard-{i:05d}-of-{num_shards:05d}{SHARD_SUFFIX}"))
        for i in range(num_shards)]
    for i in range(SHARD_IMAGES):
        h = 256
        w = int(rs.integers(256, 341))
        if rs.random() < 0.5:
            h, w = w, h
        base = rs.integers(0, 256, size=(h // 8, w // 8, 3), dtype=np.uint8)
        img = np.asarray(Image.fromarray(base).resize((w, h),
                                                      Image.BILINEAR))
        img = np.clip(img + rs.normal(0, 10, img.shape), 0,
                      255).astype(np.uint8)
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, "JPEG", quality=90)
        writers[i % num_shards].write(buf.getvalue(),
                                      float(i % 1000 + 1))
    for w_ in writers:
        w_.close()
    with open(marker, "w") as f:
        f.write("ok")
    return SHARD_DIR


def host_pipeline_probe(cache_gb: float) -> float:
    """Host-only pipeline rate (shards -> u8 batches): run in a process
    that has issued NO device work. Prints/returns img/s."""
    from bigdl_tpu.dataset.image.native_batch import NativeBRecToBatch
    from bigdl_tpu.dataset.recordio import RecordShardDataSet
    from bigdl_tpu.models.inception.train import MEAN_RGB, STD_RGB
    from bigdl_tpu.utils.random import RandomGenerator

    shards = _ensure_shards()
    RandomGenerator.seed_thread(0)
    ds = RecordShardDataSet(shards)
    batcher = NativeBRecToBatch(
        REAL_BATCH, 224, 224, train=True, mean_rgb=MEAN_RGB,
        std_rgb=STD_RGB, device_normalize=True,
        cache_bytes=int(cache_gb * 1e9))
    it = batcher(ds.data(train=True))
    warm = (SHARD_IMAGES // REAL_BATCH) if cache_gb > 0 else 2
    for _ in range(warm):
        next(it)
    t0 = time.perf_counter()
    for _ in range(8):
        next(it)
    return REAL_BATCH * 8 / (time.perf_counter() - t0)


def _host_pipeline_probe_subprocess(cache_gb: float) -> float:
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--host-probe", str(cache_gb)],
            capture_output=True, text=True, timeout=600, env=env)
        for line in out.stdout.splitlines():
            if line.startswith("{"):
                return float(json.loads(line)["host_pipeline_img_per_sec"])
    except Exception as e:
        print(f"host probe subprocess failed: {e}", file=sys.stderr)
    return float("nan")


def bench_real_data(cache_gb: float = 0.0, timed_steps: int = 16):
    """End-to-end Inception train rate with JPEG bytes in the loop:
    .brec shards -> native u8 decode (crop-window, uint8 HWC) ->
    DevicePrefetcher -> in-step normalize on device (VERDICT r3 #1).

    Reports the end-to-end rate and its two components: the host
    pipeline alone and the device step alone on a resident batch."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.dataset.image.native_batch import NativeBRecToBatch
    from bigdl_tpu.dataset.recordio import (DevicePrefetcher,
                                            RecordShardDataSet)
    from bigdl_tpu.models.inception.train import MEAN_RGB, STD_RGB
    from bigdl_tpu.utils.random import RandomGenerator

    _set_bf16_policy()
    shards = _ensure_shards()
    RandomGenerator.seed_thread(0)
    ds = RecordShardDataSet(shards)
    batcher = NativeBRecToBatch(
        REAL_BATCH, 224, 224, train=True, mean_rgb=MEAN_RGB,
        std_rgb=STD_RGB, device_normalize=True,
        cache_bytes=int(cache_gb * 1e9))
    transform = batcher.device_transform()

    model, params, mstate, opt_state, base_step = _convnet_pieces(
        "inception_v1")

    def train_step(params, mstate, opt_state, rng, data, labels):
        return base_step(params, mstate, opt_state, rng, transform(data),
                         labels.astype(jnp.int32))

    jit_step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    rng = jax.random.PRNGKey(0)

    # -- component 1: host pipeline rate (decode -> u8 batch, no device),
    # measured in a CPU-pinned subprocess that never touches the chip
    host_ips = _host_pipeline_probe_subprocess(cache_gb)
    steps_per_epoch = SHARD_IMAGES // REAL_BATCH
    host_it = batcher(ds.data(train=True))
    warm_batches = steps_per_epoch if cache_gb > 0 else 2
    for _ in range(warm_batches):        # cache mode: fill on pass 1
        host_batch = next(host_it)

    # -- component 2: device step rate on a resident u8 batch
    dev_data = jax.device_put(host_batch.data)
    dev_labels = jax.device_put(host_batch.labels)
    compiled = jit_step.lower(params, mstate, opt_state, rng, dev_data,
                              dev_labels).compile()
    for _ in range(3):
        rng, k = jax.random.split(rng)
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, k, dev_data, dev_labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(10):
        rng, k = jax.random.split(rng)
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, k, dev_data, dev_labels)
    float(loss)
    device_ips = REAL_BATCH * 10 / (time.perf_counter() - t0)

    # -- end to end (includes host->device transfer)
    pipe = DevicePrefetcher()(host_it)
    for _ in range(2):
        b = next(pipe)
        rng, k = jax.random.split(rng)
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, k, b.data, b.labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        b = next(pipe)
        rng, k = jax.random.split(rng)
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, k, b.data, b.labels)
    float(loss)
    dt = time.perf_counter() - t0
    value = REAL_BATCH * timed_steps / dt
    name = ("inception_v1_train_real_jpeg_cached"
            if cache_gb > 0 else "inception_v1_train_real_jpeg")
    import math
    have_host = not math.isnan(host_ips)
    return {
        "metric": f"{name}_images_per_sec_per_chip",
        "value": round(value, 2),
        "unit": "images/sec/chip",
        "host_pipeline_img_per_sec": round(host_ips, 1) if have_host
        else None,
        "device_step_img_per_sec": round(device_ips, 1),
        "host_decode": "ram-cache" if cache_gb > 0 else "jpeg",
        "host_cores": os.cpu_count(),
    }


def bench_transformer_lm(b: int = 4, s: int = 2048, vocab: int = 32768,
                         d_model: int = 1024, layers: int = 12,
                         iters: int = 40):
    """LM train-step tokens/s + MFU at the docs/PERF.md flagship geometry
    (GPT-2-medium width), fused-CE head + flash attention.

    MFU uses ANALYTIC step FLOPs (6 * matmul-params * tokens + attention)
    — XLA's cost analysis cannot see inside the Pallas flash-attention
    and fused-CE custom calls, so its count is only a lower bound
    (reported as ``xla_counted_tflops``; round 3's 55.6% flagship figure
    was this undercount). ``mfu`` counts attention at the full S^2
    matrices (the PaLM-convention number most MFU figures quote);
    ``mfu_causal_attn`` counts the causal halves actually computed."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.optim import SGD

    _set_bf16_policy()
    model = TransformerLM(vocab, d_model=d_model, num_heads=d_model // 128,
                          num_layers=layers, max_len=s,
                          with_log_softmax=False)
    model.materialize(jax.random.PRNGKey(0))
    model.training()
    optim = SGD(learning_rate=0.01)
    params, mstate = model.params, model.state
    opt_state = optim.init_state(params)
    fused = jax.default_backend() == "tpu"
    head_idx = str(len(model.modules) - 1)
    crit = nn.CrossEntropyCriterion()

    def step(params, mstate, opt_state, data, labels):
        def loss_fn(p):
            if fused:
                from bigdl_tpu.ops.pallas.fused_ce import \
                    linear_cross_entropy
                x, new_mstate = data, dict(mstate)
                for i, m in enumerate(model.modules[:-1]):
                    x, new_mstate[str(i)] = m.apply(
                        p[str(i)], mstate[str(i)], x, training=True)
                loss = linear_cross_entropy(
                    x.reshape(-1, x.shape[-1]),
                    p[head_idx]["weight"].astype(x.dtype),
                    p[head_idx].get("bias"), labels.reshape(-1))
                return loss, new_mstate
            y, st = model.apply(p, mstate, data, training=True)
            return crit.apply(y, labels), st

        (loss, s2), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        p2, o2 = optim.update(g, params, opt_state)
        return p2, s2, o2, loss

    host = np.random.default_rng(0)
    data = jnp.asarray(host.integers(1, vocab + 1, size=(b, s)))
    labels = jnp.asarray(host.integers(1, vocab + 1, size=(b, s)))
    c = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        params, mstate, opt_state, data, labels).compile()
    cost = c.cost_analysis()
    xla_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    _record_compile_telemetry("bench_transformer_lm_train_step", c)
    # analytic step FLOPs: matmul params = 2-D weight leaves minus the
    # embedding tables (lookups, not matmuls)
    p2d = sum(int(np.prod(l.shape))
              for l in jax.tree.leaves(params) if l.ndim == 2)
    p_matmul = p2d - vocab * d_model - s * d_model
    tokens = b * s
    dense_attn = 12 * layers * s * d_model * tokens
    flops_dense = 6 * p_matmul * tokens + dense_attn
    flops_causal = 6 * p_matmul * tokens + dense_attn // 2
    for _ in range(3):
        params, mstate, opt_state, loss = c(params, mstate, opt_state,
                                            data, labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, mstate, opt_state, loss = c(params, mstate, opt_state,
                                            data, labels)
    final = float(loss)
    dt = time.perf_counter() - t0
    if not np.isfinite(final):
        raise SystemExit(f"transformer bench diverged: loss={final}")
    peak = _chip_peak_tflops()
    out = {
        "metric": "transformer_lm_train_tokens_per_sec_per_chip",
        "value": round(b * s * iters / dt, 1),
        "unit": "tokens/sec/chip",
        "geometry": f"d{d_model} L{layers} B{b} S{s} V{vocab}",
        "achieved_tflops": round(flops_dense * iters / dt / 1e12, 1),
        "xla_counted_tflops": round(xla_flops * iters / dt / 1e12, 1),
    }
    out["mfu"] = round(flops_dense * iters / dt / 1e12 / peak, 3)
    out["mfu_causal_attn"] = round(
        flops_causal * iters / dt / 1e12 / peak, 3)
    return out


def bench_decode(b: int = 128, kv_heads: int | None = 1,
                 iters: int = 30):
    """KV-cache decode throughput: 27M LM, prompt 512, +128 greedy
    tokens. ``kv_heads=1`` is the multi-query config (docs/PERF.md round
    4: the cache was the decode bound; MQA runs 4.1x MHA)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer.generate import (GenerationConfig,
                                                       generate)

    _set_bf16_policy()
    vocab, p_len, n_new = 8192, 512, 128
    model = TransformerLM(vocab, d_model=512, num_heads=4, num_layers=6,
                          max_len=p_len + n_new, with_log_softmax=False,
                          num_kv_heads=kv_heads)
    model.materialize(jax.random.PRNGKey(0))
    model.evaluate()
    host = np.random.default_rng(0)
    prompt = jnp.asarray(host.integers(1, vocab + 1, size=(b, p_len)))
    cfg = GenerationConfig(n_new)
    out = generate(model, prompt, cfg)          # compile + warm
    np.asarray(out)        # host read: returns once the device has finished
    t0 = time.perf_counter()
    for _ in range(iters):
        out = generate(model, prompt, cfg)
    int(np.asarray(out)[0, 0])                  # real sync
    dt = time.perf_counter() - t0
    return {
        "metric": "transformer_lm_decode_tokens_per_sec_per_chip",
        "value": round(b * n_new * iters / dt, 1),
        "unit": "tokens/sec/chip",
        "geometry": f"27M d512 L6 B{b} prompt{p_len} +{n_new} "
                    f"kv_heads={kv_heads or 4}",
    }


def bench_decode_ragged(b: int = 128, kv_heads: int | None = 1,
                        iters: int = 30):
    """Mixed-sequence-length serving decode (VERDICT r4 item 6): the same
    27M MQA geometry as ``bench_decode`` but with per-row prompt lengths
    drawn from [64, 512] through the ragged path
    (models/transformer/serving.py) — one compiled program, per-row
    positions/masks, no retrace across the length mix."""
    import jax

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer.generate import GenerationConfig
    from bigdl_tpu.models.transformer.serving import generate_ragged

    _set_bf16_policy()
    vocab, n_new = 8192, 128
    model = TransformerLM(vocab, d_model=512, num_heads=4, num_layers=6,
                          max_len=512 + n_new, with_log_softmax=False,
                          num_kv_heads=kv_heads)
    model.materialize(jax.random.PRNGKey(0))
    model.evaluate()
    host = np.random.default_rng(0)
    lengths = host.integers(64, 513, size=(b,)).astype(np.int32)
    prompts = [list(host.integers(1, vocab + 1, size=(n,)))
               for n in lengths]
    cfg = GenerationConfig(max_new_tokens=n_new, temperature=0.0)

    def run():
        return generate_ragged(model, prompts, cfg)

    np.asarray(run())      # compile + warm; the host read waits for it
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run()
    int(np.asarray(out)[0, 0])                  # real sync
    dt = time.perf_counter() - t0
    return {
        "metric": "transformer_lm_ragged_decode_tokens_per_sec_per_chip",
        "value": round(b * n_new * iters / dt, 1),
        "unit": "tokens/sec/chip",
        "geometry": f"27M d512 L6 B{b} prompts 64..512 +{n_new} "
                    f"kv_heads={kv_heads or 4}",
        "mean_prompt_len": round(float(lengths.mean()), 1),
    }


def bench_decode_speculative(b: int = 32, iters: int = 10):
    """Speculative decoding with a measured acceptance rate (VERDICT r4
    item 6): 27M MQA target, 2-layer d128 draft, gamma=4. HONESTY NOTE:
    both models have random weights, so the draft's greedy choices rarely
    match the target's over an 8k vocab — the reported acceptance rate is
    a floor, and the tokens/s here is the COST of speculation at that
    floor. On trained models acceptance (and the speedup) is a property
    of the model pair, not the harness; the harness's exactness is pinned
    by tests/test_serving.py (spec output == target greedy, any draft)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer.serving import speculative_generate

    _set_bf16_policy()
    vocab, n_new, gamma = 8192, 64, 4
    p_len = 128
    target = TransformerLM(vocab, d_model=512, num_heads=4, num_layers=6,
                           max_len=p_len + n_new + gamma + 1,
                           with_log_softmax=False, num_kv_heads=1)
    target.materialize(jax.random.PRNGKey(0))
    target.evaluate()
    draft = TransformerLM(vocab, d_model=128, num_heads=4, num_layers=2,
                          max_len=p_len + n_new + gamma + 1,
                          with_log_softmax=False, num_kv_heads=1)
    draft.materialize(jax.random.PRNGKey(1))
    draft.evaluate()
    host = np.random.default_rng(0)
    prompts = [list(host.integers(1, vocab + 1, size=(p_len,)))
               for _ in range(b)]
    out, stats = speculative_generate(target, draft, prompts,
                                      max_new_tokens=n_new, gamma=gamma)
    np.asarray(out)                             # compile + warm + sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out, stats = speculative_generate(target, draft, prompts,
                                          max_new_tokens=n_new,
                                          gamma=gamma)
    int(np.asarray(out)[0, 0])                  # real sync
    dt = time.perf_counter() - t0
    return {
        "metric": "transformer_lm_speculative_decode_tokens_per_sec",
        "value": round(b * n_new * iters / dt, 1),
        "unit": "tokens/sec/chip",
        "geometry": f"target 27M d512 L6 MQA, draft d128 L2 MQA, B{b} "
                    f"prompt{p_len} +{n_new} gamma={gamma}",
        "acceptance_rate": round(stats["acceptance_rate"], 4),
        "accepted": stats["accepted"],
        "proposed": stats["proposed"],
        "rounds": stats["rounds"],
        "acceptance_is_floor": True,   # random weights; see docstring
    }


def bench_input_pipeline_overlap(iters: int = 12, batch: int = 64):
    """How much host-input latency the prefetch pipeline hides
    (ISSUE 5): run the same tiny training recipe at prefetch depth 0
    (synchronous input) and depth 2 (overlapped), and report the
    fraction of step wall time spent blocked in ``input wait`` for
    each. ``value`` is the overlap won (frac@0 - frac@2). A deliberate
    per-batch host transform gives the pipeline real work to hide, so
    the row is meaningful on any backend (CPU included)."""
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import Sample, SampleToBatch, Transformer, array
    from bigdl_tpu.utils.random import RandomGenerator

    class HostWork(Transformer):
        """Stand-in for decode/augment cost: a few ms of numpy per
        batch, comparable to a real decode stage."""

        def __call__(self, it):
            scratch = np.linspace(0.0, 1.0, 1 << 19, dtype=np.float32)
            for b in it:
                for _ in range(8):
                    scratch = np.tanh(scratch)
                yield b

    rs = np.random.RandomState(0)
    x = rs.rand(4 * batch, 64).astype(np.float32)
    y = rs.randint(1, 5, size=(4 * batch,)).astype(np.int64)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]

    def run(depth: int) -> float:
        RandomGenerator.set_seed(0)
        ds = array(samples) >> SampleToBatch(batch) >> HostWork()
        # wide enough that the device step is real work to overlap with
        model = nn.Sequential(nn.Linear(64, 1024), nn.Tanh(),
                              nn.Linear(1024, 1024), nn.Tanh(),
                              nn.Linear(1024, 4), nn.LogSoftMax())
        o = optim.Optimizer(model=model, dataset=ds,
                            criterion=nn.ClassNLLCriterion())
        o.set_optim_method(optim.SGD(learning_rate=0.1))
        o.set_input_pipeline(depth=depth)
        o.set_end_when(optim.max_iteration(iters))
        o.optimize()
        # phase split from the loop's own honest metrics (input wait vs
        # device step, metrics.py), on medians: the one-off XLA compile
        # lands in step 1's device time and would swamp a sum at this
        # iteration count
        wait = o.metrics.stats("host input time")["p50"]
        dev = o.metrics.stats("device step time")["p50"]
        return wait / max(wait + dev, 1e-9)

    frac0 = run(0)
    frac2 = run(2)
    return {
        "metric": "input_pipeline_overlap",
        "value": round(max(frac0 - frac2, 0.0), 4),
        "unit": "fraction of step wall time",
        "input_wait_frac_depth0": round(frac0, 4),
        "input_wait_frac_depth2": round(frac2, 4),
        "iters": iters,
    }


def bench_input_pipeline_nhost(host_counts=(1, 2, 4), iters: int = 6,
                               batch: int = 32, chunk_records: int = 64):
    """The input_pipeline_overlap receipt at mesh scale (ISSUE 20): the
    same overlapped training recipe run as 1/2/4 parallel CPU "host"
    processes, each a shard of a ``DistributedShuffleDataSet`` over one
    shared chunked record store. ``value`` is the mean input-wait
    fraction at the LARGEST host count (lower is better); shard-local IO
    means it should stay flat as hosts scale — every host reads only its
    own chunks, so per-host input bandwidth does not shrink with N.

    Two hard receipts ride along and fail the row on violation:
    the reader open-accounting proves each host touched ONLY its pass-0
    assignment (pairwise-disjoint across hosts), and an in-process 4->2
    resize sub-drill proves the chunk-granular mid-epoch resume
    reconstructs the remaining stream bit-identically."""
    import subprocess
    import tempfile

    from bigdl_tpu.dataset.distributed import (chunk_assignment,
                                               chunk_record_order,
                                               redistribute_chunk_positions,
                                               DistributedShuffleDataSet)
    from bigdl_tpu.dataset.recordstore import (ChunkedRecordReader,
                                               write_sample_store)
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.utils.random import RandomGenerator

    # the probes seed 0; the parent-side assignment oracle and the
    # resize sub-drill must rotate from the same key
    RandomGenerator.set_seed(0)
    max_hosts = max(int(n) for n in host_counts)
    # size the store so each host's pulls (iters consumed + the depth-2
    # worker's bounded read-ahead) stay strictly inside pass 0 — the
    # shard-local receipt below pins opens against the PASS-0 assignment
    n_records = max_hosts * batch * (iters + 8)
    rs = np.random.RandomState(0)
    x = rs.rand(n_records, 64).astype(np.float32)
    y = rs.randint(1, 5, size=(n_records,)).astype(np.int64)
    tmp = tempfile.mkdtemp(prefix="bench_dataplane_")
    store = os.path.join(tmp, "train.bcs")
    write_sample_store(store, (Sample(x[i], y[i])
                               for i in range(n_records)),
                       chunk_records=chunk_records)
    n_chunks = ChunkedRecordReader(store).n_chunks

    wait_fracs = {}
    for n in sorted(int(c) for c in host_counts):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=_xla_flags_with_device_count(1))
        procs = []
        for shard in range(n):
            cfg = json.dumps({"path": store, "num_shards": n,
                              "shard_index": shard, "batch": batch,
                              "iters": iters})
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--dataplane-probe", cfg],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env))
        payloads = []
        for shard, p in enumerate(procs):
            out, err = p.communicate(timeout=600)
            payload = None
            for line in out.splitlines():
                if line.startswith("{"):
                    payload = json.loads(line)
            if payload is None:
                tail = (err or "").strip().splitlines()[-2:]
                raise RuntimeError(
                    f"dataplane probe (n={n}, shard={shard}) "
                    f"rc={p.returncode}: "
                    + (" | ".join(tail) or "no output"))
            payloads.append(payload)
        # shard-local IO receipt: every host opened ONLY chunks from its
        # own pass-0 assignment — disjoint across hosts by construction
        assign = chunk_assignment(n_chunks, n, 0, seed=0)
        opened_all: set = set()
        for payload in payloads:
            opened = set(payload["chunks_opened"])
            shard = int(payload["shard"])
            if not opened <= set(assign[shard]):
                raise RuntimeError(
                    f"host {shard}/{n} opened chunks outside its "
                    f"assignment: {sorted(opened - set(assign[shard]))}")
            if opened & opened_all:
                raise RuntimeError(
                    f"chunks opened by more than one host at n={n}: "
                    f"{sorted(opened & opened_all)}")
            opened_all |= opened
        wait_fracs[n] = sum(p["wait_frac"] for p in payloads) / n

    # resize receipt (no subprocess needed — pure host machinery):
    # 4 hosts consume one chunk each mid-pass, positions redistribute to
    # 2 hosts, and the remaining stream must reconstruct bit-identically
    old_n, new_n = 4, 2
    dss = [DistributedShuffleDataSet(store, num_shards=old_n,
                                     shard_index=i, window_chunks=1)
           for i in range(old_n)]
    consumed = {}
    for i, ds in enumerate(dss):
        it = ds.data(train=True)
        cid = chunk_assignment(n_chunks, old_n, 0, seed=0)[i][0]
        for _ in range(ds.reader.chunk_record_count(cid)):
            next(it)
        consumed[i] = cid
    states = [ds.get_position_state() for ds in dss]
    new_states = redistribute_chunk_positions(states, new_n, seed=0)
    post = {}
    for st in new_states:
        ds2 = DistributedShuffleDataSet(store, num_shards=new_n,
                                        shard_index=int(st["shard_index"]),
                                        window_chunks=1)
        ds2.set_position_state(st, mid_pass=True)
        it = ds2.data(train=True)
        for cid in st["remaining_chunks"]:
            post[cid] = [bytes(memoryview(
                next(it).feature)) for _ in
                range(ds2.reader.chunk_record_count(cid))]
    base_reader = ChunkedRecordReader(store)
    for cid in set(range(n_chunks)) - set(consumed.values()):
        recs = base_reader.read_chunk(cid)
        from bigdl_tpu.dataset.recordstore import decode_sample
        expect = [bytes(memoryview(decode_sample(*recs[j]).feature))
                  for j in chunk_record_order(len(recs), 0, cid, seed=0)]
        if post.get(cid) != expect:
            raise RuntimeError(
                f"{old_n}->{new_n} resize resume NOT bit-identical at "
                f"chunk {cid}")

    counts = sorted(wait_fracs)
    return {
        "metric": "input_pipeline_nhost_wait_frac",
        "value": round(wait_fracs[counts[-1]], 4),
        "unit": f"mean input-wait fraction at {counts[-1]} hosts",
        "wait_frac_by_hosts": {str(n): round(wait_fracs[n], 4)
                               for n in counts},
        "wait_frac_spread": round(wait_fracs[counts[-1]]
                                  - wait_fracs[counts[0]], 4),
        "chunks": n_chunks,
        "shard_local_reads_verified": True,
        "resize_resume_bit_identical": True,
        "iters": iters,
    }


def _dataplane_probe_main(config_json: str):
    """--dataplane-probe subprocess entry: one emulated host of the
    N-host drill — train over its shard of the shared record store and
    emit the measured input-wait fraction plus the reader's chunk-open
    accounting (the shard-local-IO receipt)."""
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import SampleToBatch, Transformer
    from bigdl_tpu.dataset.distributed import DistributedShuffleDataSet
    from bigdl_tpu.utils.random import RandomGenerator

    cfg = json.loads(config_json)
    RandomGenerator.set_seed(0)

    class HostWork(Transformer):
        """Same decode/augment stand-in as the overlap row."""

        def __call__(self, it):
            scratch = np.linspace(0.0, 1.0, 1 << 19, dtype=np.float32)
            for b in it:
                for _ in range(8):
                    scratch = np.tanh(scratch)
                yield b

    ds = DistributedShuffleDataSet(cfg["path"],
                                   num_shards=int(cfg["num_shards"]),
                                   shard_index=int(cfg["shard_index"]))
    pipeline = ds >> SampleToBatch(int(cfg["batch"])) >> HostWork()
    model = nn.Sequential(nn.Linear(64, 1024), nn.Tanh(),
                          nn.Linear(1024, 1024), nn.Tanh(),
                          nn.Linear(1024, 4), nn.LogSoftMax())
    o = optim.Optimizer(model=model, dataset=pipeline,
                        criterion=nn.ClassNLLCriterion())
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_input_pipeline(depth=2)
    o.set_end_when(optim.max_iteration(int(cfg["iters"])))
    o.optimize()
    wait = o.metrics.stats("host input time")["p50"]
    dev = o.metrics.stats("device step time")["p50"]
    _emit({"shard": int(cfg["shard_index"]),
           "wait_frac": wait / max(wait + dev, 1e-9),
           "chunks_opened": sorted(ds.reader.chunks_opened)})


# shared result of the serving-router workload, keyed by its arguments:
# both serving rows report one run (the row fns are what tests monkeypatch)
_serving_run_cache = None


def _bench_serving_run(*, n_requests: int = 16, replicas: int = 2,
                       max_new: int = 32, d_model: int = 256,
                       num_layers: int = 4):
    """Mixed long-prefill / short-decode workload through a 2-replica
    Router at a FIXED SLO (ISSUE 6): every 4th request repeats a long
    "system prompt" (exercising the prefix cache and prefill/decode
    disaggregation), the rest are short random prompts. A
    bucket-covering warmup pays the XLA compiles outside the measured
    window; the second submission wave repeats the first's long prompt
    so prefill skips land inside it. Returns the raw numbers both
    serving rows report."""
    global _serving_run_cache
    key = (n_requests, replicas, max_new, d_model, num_layers)
    if _serving_run_cache is not None and _serving_run_cache[0] == key:
        return _serving_run_cache[1]
    import jax

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer.serving import ContinuousBatcher
    from bigdl_tpu.observability.exporter import HealthRegistry
    from bigdl_tpu.observability.registry import MetricRegistry
    from bigdl_tpu.serving import ReplicaPool, Router, SLOConfig

    _set_bf16_policy()
    vocab, max_len = 8192, 320
    slo = SLOConfig(ttft_p99_s=2.5, decode_token_p99_s=0.5,
                    max_queue_depth=8, long_prefill_tokens=128)
    model = TransformerLM(vocab, d_model=d_model, num_heads=4,
                          num_layers=num_layers, max_len=max_len,
                          with_log_softmax=False, num_kv_heads=1)
    model.materialize(jax.random.PRNGKey(0))
    model.evaluate()
    host = np.random.default_rng(0)
    long_prompt = list(host.integers(1, vocab + 1, size=(192,)))
    prompts = []
    for i in range(n_requests):
        if i % 4 == 0:
            prompts.append(list(long_prompt))
        else:
            n = int(host.integers(16, 97))
            prompts.append(list(host.integers(1, vocab + 1, size=(n,))))
    geo = dict(max_batch=4, num_pages=96, page_size=16,
               max_new_tokens=max_new, max_burst=8)
    # warmup batcher: one prompt per distinct prefill bucket + the
    # decode/adopt shapes (jit caches are module-level, so the replica
    # pool below reuses every compile)
    warm = ContinuousBatcher(model, registry=MetricRegistry(),
                             health=HealthRegistry(), **geo)
    for i, n in enumerate((16, 32, 64, 96, 192)):
        warm.submit(f"w{i}",
                    list(host.integers(1, vocab + 1, size=(n,))))
    warm.run_to_completion()
    warm.submit("ws", snapshot=warm.prefill_only("wp", long_prompt))
    warm.run_to_completion()
    health = HealthRegistry()
    pool = ReplicaPool(model, replicas, health=health, **geo)
    router = Router(pool, slo=slo, health=health,
                    registry=MetricRegistry())
    try:
        half = n_requests // 2
        t0 = time.perf_counter()
        for i in range(half):
            router.submit(i, prompts[i])
        router.wait_all(timeout=600)
        for i in range(half, n_requests):
            router.submit(i, prompts[i])
        router.wait_all(timeout=600)
        dt = time.perf_counter() - t0
        results = dict(router.finished())
        lat = router.latency_summary()
    finally:
        router.close()
        pool.close()
    if len(results) != n_requests:
        raise RuntimeError(f"router returned {len(results)} results "
                           f"for {n_requests} requests")
    out = {
        "wall_s": dt,
        "tokens_per_sec": n_requests * max_new / dt,
        "n_requests": n_requests, "replicas": replicas,
        "geometry": (f"{_fmt_params(d_model, num_layers)} MQA "
                     f"{replicas}x(4 slots, 96 pages x 16) "
                     f"prompts 16..192 +{max_new}"),
        "slo": {"ttft_p99_s": slo.ttft_p99_s,
                "decode_token_p99_s": slo.decode_token_p99_s,
                "max_queue_depth": slo.max_queue_depth,
                "long_prefill_tokens": slo.long_prefill_tokens},
        **lat,
    }
    _serving_run_cache = (key, out)
    return out


def _fmt_params(d_model: int, num_layers: int) -> str:
    return f"d{d_model} L{num_layers}"


def bench_serving_ttft(**kw):
    """Router-level TTFT percentiles at the fixed serving SLO —
    conservative (bucket-upper-bound) estimates merged across replica
    histograms. ``value`` is the p50; the p99 and the SLO verdict ride
    as fields."""
    r = _bench_serving_run(**kw)
    p50 = r["ttft_p50_s"] or 0.0
    p99 = r["ttft_p99_s"] or 0.0
    return {
        "metric": "serving_ttft",
        "value": round(p50, 4),
        "unit": "seconds",
        "ttft_p50_s": round(p50, 4),
        "ttft_p99_s": round(p99, 4),
        "within_slo": bool(p99 <= r["slo"]["ttft_p99_s"]),
        "prefix_prefill_skips": r["prefix_hits"],
        "disagg_prefills": r["disagg_prefills"],
        "n_requests": r["n_requests"],
        "replicas": r["replicas"],
        "geometry": r["geometry"],
        "slo": r["slo"],
    }


def bench_serving_tokens_per_sec(**kw):
    """End-to-end router throughput for the same fixed-SLO workload:
    generated tokens / wall clock across all replicas (queue wait,
    prefill, disaggregation handoffs and prefix skips included)."""
    r = _bench_serving_run(**kw)
    p99 = r["ttft_p99_s"] or 0.0
    return {
        "metric": "serving_tokens_per_sec",
        "value": round(r["tokens_per_sec"], 1),
        "unit": "tokens/sec",
        "wall_s": round(r["wall_s"], 3),
        "within_slo": bool(p99 <= r["slo"]["ttft_p99_s"]),
        "n_requests": r["n_requests"],
        "replicas": r["replicas"],
        "geometry": r["geometry"],
        "slo": r["slo"],
    }


def _bench_prefix_reuse_run(*, n_requests: int = 10, max_new: int = 8,
                            d_model: int = 256, num_layers: int = 4):
    """Shared-system-prompt workload through a 1-replica router, run
    twice: longest-prefix reuse ON vs exact-only matching. Every
    prompt is a common 3-page (48-token) prefix plus a distinct
    16-token suffix, so exact matching gets ZERO reuse while the radix
    index adopts the 3 shared pages and prefills only the suffix.
    Requests are submitted sequentially with the TTFT histogram's
    ``sum`` read around each one, so per-request TTFTs are exact (not
    bucket-upper-bound) and the p50/p99 comparison is meaningful at
    sub-bucket resolution. Compiles are paid by a warmup batcher
    (module-level jit caches) before either mode runs."""
    import jax

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer.serving import ContinuousBatcher
    from bigdl_tpu.observability.exporter import HealthRegistry
    from bigdl_tpu.observability.registry import MetricRegistry
    from bigdl_tpu.serving import (PrefixCache, ReplicaPool, Router,
                                   SLOConfig)

    _set_bf16_policy()
    vocab, page = 8192, 16
    model = TransformerLM(vocab, d_model=d_model, num_heads=4,
                          num_layers=num_layers, max_len=320,
                          with_log_softmax=False, num_kv_heads=1)
    model.materialize(jax.random.PRNGKey(0))
    model.evaluate()
    host = np.random.default_rng(7)
    shared = list(host.integers(1, vocab + 1, size=(3 * page,)))
    prompts = [shared + list(host.integers(1, vocab + 1, size=(page,)))
               for _ in range(n_requests + 1)]   # +1 seed
    geo = dict(max_batch=4, num_pages=96, page_size=page,
               max_new_tokens=max_new, max_burst=8)
    # warmup: pay the full-prefill (bucket 64), suffix-prefill
    # (bucket 16 at start 48), adopt and decode compiles once
    warm = ContinuousBatcher(model, registry=MetricRegistry(),
                             health=HealthRegistry(), **geo)
    warm.submit("wf", prompts[0])
    warm.run_to_completion()
    wsnap = warm.prefill_only("wp", prompts[0]).truncate(3 * page)
    warm.submit("ws", prompts[1], snapshot=wsnap,
                prefill_from=3 * page)
    warm.run_to_completion()
    warm.submit("wa", snapshot=warm.prefill_only("wq", prompts[0]))
    warm.run_to_completion()

    out = {}
    for mode in ("reuse", "exact"):
        health = HealthRegistry()
        reg = MetricRegistry()
        pool = ReplicaPool(model, 1, health=health, **geo)
        router = Router(
            pool, slo=SLOConfig(long_prefill_tokens=10_000),
            prefix_cache=PrefixCache(min_tokens=page, page_size=page,
                                     longest_match=(mode == "reuse"),
                                     registry=reg),
            registry=reg, health=health)
        try:
            router.submit("seed", prompts[0])
            router.wait_all(timeout=300)
            router.finished()

            def _ttft_sum():
                return sum(
                    r.histogram_snapshot("serving_ttft_seconds")["sum"]
                    for r in pool)

            partial0 = reg.get(
                "router_prefix_partial_hits_total").value()
            reused0 = reg.get(
                "router_prefix_tokens_reused_total").value()
            tokens0 = reg.get("router_prompt_tokens_total").value()
            ttfts, firsts = [], []
            for i in range(1, n_requests + 1):
                s0 = _ttft_sum()
                router.submit(i, prompts[i])
                router.wait_all(timeout=300)
                ttfts.append(_ttft_sum() - s0)
                firsts.append(int(dict(router.finished())[i][0]))
            out[mode] = {
                "ttft_p50_s": float(np.percentile(ttfts, 50)),
                "ttft_p99_s": float(np.percentile(ttfts, 99)),
                "firsts": firsts,
                "partial_hits": int(reg.get(
                    "router_prefix_partial_hits_total").value()
                    - partial0),
                "tokens_reused_fraction": float(
                    (reg.get("router_prefix_tokens_reused_total")
                     .value() - reused0)
                    / max(1.0, reg.get("router_prompt_tokens_total")
                          .value() - tokens0)),
            }
        finally:
            router.close()
            pool.close()
    return out, prompts, geo


def bench_prefix_reuse_ttft(**kw):
    """TTFT win from fleet-global longest-prefix KV reuse on the
    shared-system-prompt workload (ISSUE 18): ``value`` is the
    reuse-ON p50; the exact-only baseline p50/p99, the measured
    tokens-reused fraction and first-token parity ride as fields."""
    out, prompts, geo = _bench_prefix_reuse_run(**kw)
    reuse, exact = out["reuse"], out["exact"]
    params = _fmt_params(kw.get("d_model", 256), kw.get("num_layers", 4))
    return {
        "metric": "prefix_reuse_ttft",
        "value": round(reuse["ttft_p50_s"], 5),
        "unit": "seconds",
        "ttft_p50_s": round(reuse["ttft_p50_s"], 5),
        "ttft_p99_s": round(reuse["ttft_p99_s"], 5),
        "exact_ttft_p50_s": round(exact["ttft_p50_s"], 5),
        "exact_ttft_p99_s": round(exact["ttft_p99_s"], 5),
        "speedup_p50": round(exact["ttft_p50_s"]
                             / max(reuse["ttft_p50_s"], 1e-9), 2),
        "partial_hits": reuse["partial_hits"],
        "tokens_reused_fraction": round(
            reuse["tokens_reused_fraction"], 4),
        "first_tokens_match": bool(reuse["firsts"] == exact["firsts"]),
        "n_requests": len(prompts) - 1,
        "geometry": (f"{params} MQA 1x"
                     f"({geo['max_batch']} slots, {geo['num_pages']} "
                     f"pages x {geo['page_size']}) 48-token shared "
                     f"prefix + 16-token suffixes"),
    }


def _bench_request_trace_run(*, n_requests: int = 10, max_new: int = 8,
                             d_model: int = 256, num_layers: int = 4):
    """Per-request timeline cost + attribution drill (ISSUE 19).

    Overhead: the same single-bucket workload through a 1-replica
    router twice — request tracker ON (``sample_every=1``: every
    timeline retained, the worst case) vs OFF (``tracker=False``) —
    with per-request TTFTs read exactly off the TTFT histogram ``sum``
    around each sequential submit (the ``prefix_reuse`` measurement
    pattern). The modes run identical code paths except the tracker
    events, so the p50 ratio IS the tentpole's hot-path cost.

    Drill: a fresh tracker-ON plane whose replica driver is NOT
    started and whose admission gate allows one queued request, so
    submissions wait (router pending or replica queue) for an induced
    delay before the driver starts. ~All of the tail's latency is
    queue wait by construction, and the tracker's attribution must say
    so (the ISSUE 19 receipt wants >= 80% queue fraction)."""
    import jax

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer.serving import ContinuousBatcher
    from bigdl_tpu.observability.exporter import HealthRegistry
    from bigdl_tpu.observability.registry import MetricRegistry
    from bigdl_tpu.observability.request_trace import RequestTracker
    from bigdl_tpu.serving import ReplicaPool, Router, SLOConfig

    _set_bf16_policy()
    vocab, page = 8192, 16
    model = TransformerLM(vocab, d_model=d_model, num_heads=4,
                          num_layers=num_layers, max_len=320,
                          with_log_softmax=False, num_kv_heads=1)
    model.materialize(jax.random.PRNGKey(0))
    model.evaluate()
    host = np.random.default_rng(11)
    prompts = [list(host.integers(1, vocab + 1, size=(page,)))
               for _ in range(n_requests + 1)]
    geo = dict(max_batch=4, num_pages=96, page_size=page,
               max_new_tokens=max_new, max_burst=8)
    # pay the (bucket-16 prefill, decode) compiles once up front —
    # jit caches are module-level, so every plane below reuses them
    warm = ContinuousBatcher(model, registry=MetricRegistry(),
                             health=HealthRegistry(), **geo)
    warm.submit("wf", prompts[0])
    warm.run_to_completion()

    slo = SLOConfig(ttft_p99_s=2.5, decode_token_p99_s=0.5,
                    long_prefill_tokens=10_000)
    out = {}
    for mode in ("on", "off"):
        health = HealthRegistry()
        reg = MetricRegistry()
        pool = ReplicaPool(model, 1, health=health, **geo)
        tracker = (RequestTracker(slo=slo, sample_every=1)
                   if mode == "on" else False)
        router = Router(pool, slo=slo, registry=reg, health=health,
                        tracker=tracker, capture_prefixes=False)
        try:
            router.submit("seed", prompts[0])
            router.wait_all(timeout=300)
            router.finished()

            def _ttft_sum():
                return sum(
                    r.histogram_snapshot("serving_ttft_seconds")["sum"]
                    for r in pool)

            ttfts = []
            for i in range(1, n_requests + 1):
                s0 = _ttft_sum()
                router.submit(i, prompts[i])
                router.wait_all(timeout=300)
                ttfts.append(_ttft_sum() - s0)
            row = {"ttft_p50_s": float(np.percentile(ttfts, 50)),
                   "ttft_p99_s": float(np.percentile(ttfts, 99))}
            if mode == "on":
                st = tracker.stats()
                row["timelines"] = st["started"]
                row["retained"] = st["retained"]
            out[mode] = row
        finally:
            router.close()
            pool.close()

    # -- induced queue-delay drill --
    delay_s = 0.3
    drill_slo = SLOConfig(ttft_p99_s=2.5, decode_token_p99_s=0.5,
                          max_queue_depth=1,
                          long_prefill_tokens=10_000)
    health = HealthRegistry()
    pool = ReplicaPool(model, 1, health=health, start=False, **geo)
    tracker = RequestTracker(slo=drill_slo, sample_every=1)
    router = Router(pool, slo=drill_slo, registry=MetricRegistry(),
                    health=health, tracker=tracker,
                    capture_prefixes=False)
    try:
        for i in range(6):
            router.submit(f"d{i}", prompts[i])
        time.sleep(delay_s)
        pool.start()
        router.wait_all(timeout=300)
        router.finished()
        attr = tracker.attribution()
        out["drill"] = {"delay_s": delay_s,
                        "queue_fraction": attr["fractions"]["queue_s"],
                        "attribution": attr}
    finally:
        router.close()
        pool.close()
    return out, geo


def bench_request_trace_overhead(**kw):
    """What per-request timelines cost on the TTFT path: ``value`` is
    the tracker-ON p50 TTFT over the tracker-OFF p50 (1.0 = free; the
    ISSUE 19 acceptance wants <= 1.05), with the induced
    queue-delay drill's attribution verdict riding as fields."""
    out, geo = _bench_request_trace_run(**kw)
    on, off = out["on"], out["off"]
    ratio = on["ttft_p50_s"] / max(off["ttft_p50_s"], 1e-9)
    qfrac = out["drill"]["queue_fraction"]
    params = _fmt_params(kw.get("d_model", 256),
                         kw.get("num_layers", 4))
    return {
        "metric": "request_trace_overhead",
        "value": round(ratio, 4),
        "unit": "x (tracker-ON p50 TTFT / tracker-OFF)",
        "ttft_p50_on_s": round(on["ttft_p50_s"], 5),
        "ttft_p50_off_s": round(off["ttft_p50_s"], 5),
        "ttft_p99_on_s": round(on["ttft_p99_s"], 5),
        "ttft_p99_off_s": round(off["ttft_p99_s"], 5),
        "within_overhead_budget": bool(ratio <= 1.05),
        "timelines": on["timelines"],
        "retained": on["retained"],
        "drill_queue_fraction": round(qfrac, 4),
        "drill_queue_attributed": bool(qfrac >= 0.8),
        "drill_delay_s": out["drill"]["delay_s"],
        "n_requests": kw.get("n_requests", 10),
        "geometry": (f"{params} MQA 1x({geo['max_batch']} slots, "
                     f"{geo['num_pages']} pages x {geo['page_size']}) "
                     f"16-token prompts +{geo['max_new_tokens']}"),
    }


def bench_serving_decode_hbm(**geometry):
    """Static per-decode-step HBM accounting, dense view vs the Pallas
    paged kernel (ISSUE 9 — the tentpole's measured receipt): lowers
    one single-token decode step both ways in a CPU SUBPROCESS (same
    pattern as ``collective_wire_bytes_per_step``; lowering only, no
    execution, and the parent's TPU backend is never touched) and
    reports (a) the view-sized gather materializations each compiled
    HLO carries — exactly 2*layers for the dense path, ZERO for the
    kernel — and (b) the static attention-traffic model: dense pays 3x
    the (B, P*S, KV, D) view per k/v consumption, paged reads each
    row's live pages once. ``value`` is the dense/paged reduction."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--decode-hbm-probe",
         "--decode-hbm-geometry", json.dumps(geometry)],
        capture_output=True, text=True, timeout=600, env=env)
    payload = None
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            payload = json.loads(line)
    if payload is None:
        tail = (out.stderr or "").strip().splitlines()[-2:]
        raise RuntimeError(
            f"decode-hbm probe subprocess rc={out.returncode}: "
            + (" | ".join(tail) or "no output"))
    mg = payload["materialized_gathers"]
    ab = payload["attn_hbm_bytes"]
    ex = payload["executable"]
    return {
        "metric": "serving_decode_hbm_bytes",
        "value": round(payload["reduction"], 2),
        "unit": "x (dense-view / paged attention HBM bytes per "
                "decode step)",
        "attn_hbm_bytes_dense": ab["dense"],
        "attn_hbm_bytes_paged": ab["paged"],
        "materialized_gather_ops_dense": mg["dense"]["ops"],
        "materialized_gather_bytes_dense": mg["dense"]["bytes"],
        "materialized_gather_ops_paged": mg["paged"]["ops"],
        "materialized_gather_bytes_paged": mg["paged"]["bytes"],
        "view_shape": payload["view_shape"],
        "view_bytes": payload["view_bytes"],
        "peak_view_bytes_per_layer_eliminated":
            payload["peak_view_bytes_per_layer"],
        "bytes_accessed_dense_exec": ex["dense"].get("bytes_accessed"),
        "peak_hbm_bytes_dense_exec": ex["dense"].get("peak_hbm_bytes"),
        # off-TPU the paged step compiles in interpreter mode, so its
        # executable numbers describe the emulation; the static rows
        # above are the backend-independent receipt
        "paged_compiled_as": payload["paged_compiled_as"],
        # int8 quantized serving (serving/quantized.py): resident
        # weight + KV-pool argument bytes, fp32 vs int8-at-rest
        "int8_weight_kv_bytes_fp32":
            payload["int8"]["weight_kv_bytes_fp32"],
        "int8_weight_kv_bytes_int8":
            payload["int8"]["weight_kv_bytes_int8"],
        "int8_kv_pool_bytes_fp32": payload["int8"]["kv_pool_bytes_fp32"],
        "int8_kv_pool_bytes_int8": payload["int8"]["kv_pool_bytes_int8"],
        "int8_reduction": round(payload["int8"]["reduction"], 2),
        "geometry": payload["geometry"],
    }


def _autoscale_drill(model, cache_dir, *, prompts, geo, slo, cfg,
                     target_replicas):
    """One autoscaler spin-up drill (ISSUE 15): a 1-replica AOT-cached
    pool behind a Router + Autoscaler, hit with a synthetic admission
    spike; the closed loop runs until the fleet reaches
    ``target_replicas``. Returns time-to-capacity plus the AOT cache
    counters (the warm-vs-cold receipt) and the conservation check."""
    from bigdl_tpu.observability.exporter import HealthRegistry
    from bigdl_tpu.observability.registry import MetricRegistry
    from bigdl_tpu.serving import (Autoscaler, ReplicaPool, Router)

    health = HealthRegistry()
    pool = ReplicaPool(model, 1, health=health, start=False,
                       aot_cache=cache_dir, **geo)
    t0 = time.perf_counter()
    pool["r0"].batcher.warmup(prompt_buckets=(16,))
    first_spinup_s = time.perf_counter() - t0
    pool.start()
    router = Router(pool, slo=slo, health=health,
                    registry=MetricRegistry(), capture_prefixes=False)
    asc = Autoscaler(router, config=cfg, registry=MetricRegistry())
    try:
        t_spike = time.perf_counter()
        for i, p in enumerate(prompts):
            router.submit(f"q{i}", p)
        t_capacity = None
        while time.perf_counter() - t_spike < 300:
            asc.evaluate()
            if len(pool) >= target_replicas:
                t_capacity = time.perf_counter() - t_spike
                break
            time.sleep(0.01)
        if t_capacity is None:
            raise RuntimeError(
                f"fleet never reached {target_replicas} replicas "
                f"(pending={router.pending_count})")
        router.wait_all(timeout=600)
        results = dict(router.finished())
        # quiet period: hysteresis retires the spike capacity via
        # drain/migrate (conservation across scale-down is the
        # wait_all/finished accounting above plus the late stragglers)
        scale_downs = 0
        for _ in range(cfg.hysteresis_evals * (cfg.cooldown_evals + 1)
                       + 12):
            if asc.evaluate().action == "down":
                scale_downs += 1
            if len(pool) <= cfg.min_replicas:
                break
        results.update(router.finished())
    finally:
        router.close()
        pool.close()
    if len(results) != len(prompts):
        raise RuntimeError(f"autoscale drill dropped/duplicated work: "
                           f"{len(results)} results for "
                           f"{len(prompts)} requests")
    return {
        "time_to_capacity_s": t_capacity,
        "first_spinup_s": first_spinup_s,
        "aot_hits": pool.aot.hits, "aot_misses": pool.aot.misses,
        "replicas_peak": max(target_replicas, len(pool)),
        "scale_downs": scale_downs,
        "n_results": len(results),
    }


def bench_autoscale_time_to_capacity(*, n_requests: int = 24,
                                     target_replicas: int = 3):
    """Fleet autoscaler receipt (ISSUE 15): seconds from a synthetic
    admission spike against a 1-replica pool until the closed loop has
    scaled the fleet to ``target_replicas``, warm vs cold AOT
    executable cache. The drill runs twice over ONE cache directory:
    the cold pass pays every prefill/decode compile; the warm pass is a
    fresh pool + compiler table over the same directory — the PR 8
    warm-restart machinery as time-to-capacity — and must report ZERO
    cache misses (every spin-up deserializes stored executables).
    ``value`` is the warm time-to-capacity (lower is better)."""
    import tempfile

    import jax

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.serving import AutoscalerConfig, SLOConfig

    vocab = 256
    model = TransformerLM(vocab, d_model=64, num_heads=4, num_layers=2,
                          max_len=64, with_log_softmax=False)
    model.materialize(jax.random.PRNGKey(0))
    model.evaluate()
    host = np.random.default_rng(0)
    prompts = [list(host.integers(1, vocab + 1,
                                  size=(int(host.integers(5, 14)),)))
               for _ in range(n_requests)]
    geo = dict(max_batch=2, num_pages=64, page_size=4,
               max_new_tokens=8, max_burst=4)
    slo = SLOConfig(long_prefill_tokens=64, max_queue_depth=2)
    cfg = AutoscalerConfig(min_replicas=1, max_replicas=target_replicas,
                           pending_per_replica=2, hysteresis_evals=2,
                           cooldown_evals=0, interval_s=0.05)
    with tempfile.TemporaryDirectory() as cache_dir:
        drill = dict(prompts=prompts, geo=geo, slo=slo, cfg=cfg,
                     target_replicas=target_replicas)
        cold = _autoscale_drill(model, cache_dir, **drill)
        warm = _autoscale_drill(model, cache_dir, **drill)
    if warm["aot_misses"] != 0:
        raise RuntimeError(
            f"warm spin-up compiled: {warm['aot_misses']} AOT cache "
            "misses (expected 0 — every executable should load)")
    return {
        "metric": "autoscale_time_to_capacity",
        "value": round(warm["time_to_capacity_s"], 3),
        "unit": f"seconds to {target_replicas} replicas (warm AOT "
                "cache)",
        "cold_time_to_capacity_s": round(cold["time_to_capacity_s"], 3),
        "warm_time_to_capacity_s": round(warm["time_to_capacity_s"], 3),
        "cold_first_spinup_s": round(cold["first_spinup_s"], 3),
        "warm_first_spinup_s": round(warm["first_spinup_s"], 3),
        "cold_aot_misses": cold["aot_misses"],
        "warm_aot_misses": warm["aot_misses"],
        "warm_aot_hits": warm["aot_hits"],
        "warm_zero_misses": warm["aot_misses"] == 0,
        "scale_downs_warm": warm["scale_downs"],
        "n_requests": n_requests,
        "conserved": (cold["n_results"] == n_requests
                      and warm["n_results"] == n_requests),
        "geometry": (f"d64 L2 1->{target_replicas} replicas, "
                     f"{n_requests} reqs, 2 slots x 64 pages x 4"),
    }


def bench_publish_to_fleet(*, n_requests: int = 12):
    """Continuous-deployment receipt (ISSUE 16): seconds from a newly
    COMMITTED trainer checkpoint (manifest on disk) until 100% of a
    2-replica serving fleet serves it — warm canary qualification
    (pinned-prompt parity + zero compiles off the shared AOT cache),
    then a replica-by-replica drain -> reload -> resume rollout, with
    live traffic in flight the whole time. The drill asserts the
    zero-downtime contract: every request submitted before, during and
    after the publish is delivered exactly once, and the warm canary
    spin-up pays ZERO XLA compiles. A second, parity-failing commit
    then drills the rollback path: the canary fails and the fleet
    stays 100% on the published version. ``value`` is the measured
    commit-to-fleet latency (lower is better)."""
    import tempfile

    import jax

    from bigdl_tpu.deploy import (CanaryConfig, PublisherConfig,
                                  WeightPublisher,
                                  write_model_checkpoint)
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer.generate import (GenerationConfig,
                                                       generate)
    from bigdl_tpu.observability.exporter import HealthRegistry
    from bigdl_tpu.observability.registry import MetricRegistry
    from bigdl_tpu.serving import (PrefixCache, ReplicaPool, Router,
                                   SLOConfig)

    vocab = 256

    def _lm(seed):
        m = TransformerLM(vocab, d_model=64, num_heads=4, num_layers=2,
                          max_len=64, with_log_softmax=False)
        m.materialize(jax.random.PRNGKey(seed))
        m.evaluate()
        return m

    model, model2 = _lm(0), _lm(1)
    host = np.random.default_rng(0)
    prompts = [list(host.integers(1, vocab + 1,
                                  size=(int(host.integers(5, 14)),)))
               for _ in range(n_requests)]
    pin = prompts[0]
    gen = GenerationConfig(max_new_tokens=8, temperature=0.0)
    expected_new = [int(t) for t in np.asarray(
        generate(model2, np.asarray([pin], np.int32), gen))[0]]
    geo = dict(max_batch=2, num_pages=64, page_size=4,
               max_new_tokens=8, max_burst=4)

    health = HealthRegistry()
    reg = MetricRegistry()
    with tempfile.TemporaryDirectory() as tmp:
        ck = os.path.join(tmp, "ck")
        cache_dir = os.path.join(tmp, "aot")
        write_model_checkpoint(ck, model, neval=1)
        pool = ReplicaPool(model, 2, health=health, aot_cache=cache_dir,
                           **geo)
        router = Router(pool, slo=SLOConfig(long_prefill_tokens=64),
                        prefix_cache=PrefixCache(min_tokens=4),
                        registry=reg, health=health)
        pub = WeightPublisher(
            router, ck,
            config=PublisherConfig(
                CanaryConfig(prompts=[(pin, expected_new)],
                             require_zero_compiles=True),
                drain_timeout_s=120),
            registry=reg, health=health)
        try:
            third = max(1, n_requests // 3)
            for i in range(third):                  # before the commit
                router.submit(f"q{i}", prompts[i])
            router.wait_all(timeout=600)
            # the trainer commits checkpoint N+1 mid-serving
            write_model_checkpoint(ck, model2, neval=2)
            for i in range(third, 2 * third):       # in flight/queued
                router.submit(f"q{i}", prompts[i])
            t0 = time.perf_counter()
            report = pub.poll_once()
            publish_s = time.perf_counter() - t0
            if report is None or report.outcome != "ok":
                raise RuntimeError(
                    "publish drill did not roll the fleet: "
                    f"{None if report is None else report.as_dict()}")
            for i in range(2 * third, n_requests):  # after the rollout
                router.submit(f"q{i}", prompts[i])
            router.wait_all(timeout=600)
            results = dict(router.finished())
            versions = {pool[n].weight_version for n in pool.names}
            # rollback sub-drill: commit a third checkpoint whose
            # canary CANNOT satisfy the pinned expectation (old
            # weights vs the v2 expectation) — the fleet must stay put
            write_model_checkpoint(ck, model, neval=3)
            rb = pub.poll_once()
            rb_versions = {pool[n].weight_version for n in pool.names}
        finally:
            pub.close()
            router.close()
            pool.close()
    if len(results) != n_requests:
        raise RuntimeError(
            f"publish drill dropped/duplicated work: {len(results)} "
            f"results for {n_requests} requests")
    if versions != {"v2"} or rb_versions != {"v2"}:
        raise RuntimeError(
            f"fleet not uniformly on the published version: {versions} "
            f"after publish, {rb_versions} after rollback drill")
    if report.canary.compiles != 0:
        raise RuntimeError(
            f"warm canary compiled: {report.canary.compiles} AOT "
            "misses (expected 0 — the candidate shares every "
            "executable)")
    return {
        "metric": "publish_to_fleet_secs",
        "value": round(publish_s, 3),
        "unit": "seconds committed checkpoint -> 100% of fleet "
                "(2 replicas, warm canary)",
        "canary_compiles": report.canary.compiles,
        "replicas_rolled": len(report.rolled),
        "rollback_drill_outcome": rb.outcome,
        "rollback_kept_fleet": rb_versions == {"v2"},
        "fleet_version": sorted(versions)[0],
        "n_requests": n_requests,
        "conserved": len(results) == n_requests,
        "aot_hits": int(pool.aot.hits),
        "aot_misses": int(pool.aot.misses),
        "geometry": ("d64 L2 2 replicas + canary, "
                     f"{n_requests} reqs, 2 slots x 64 pages x 4"),
    }


def _decode_hbm_probe_main(geometry_json: str):
    """--decode-hbm-probe subprocess entry: run the static accounting
    on the CPU backend and emit the JSON payload. ``geometry_json``
    overrides probe dimensions (the contract tests use a tiny one)."""
    from bigdl_tpu.models.transformer.serving import decode_hbm_probe
    _emit(decode_hbm_probe(**json.loads(geometry_json or "{}")))


def _backend_info() -> str:
    """``platform|device_kind|count`` of the default jax backend, read
    in THIS process: the process that touches jax holds the chip, so
    there is no child to ask. A backend that cannot start raises."""
    import jax
    ds = jax.devices()
    return f"{ds[0].platform}|{ds[0].device_kind}|{len(ds)}"


# ---------------------------------------------------------------------------
# regression gate (ROADMAP item 5): compare this run's rows against a
# recorded baseline with per-row thresholds; a real slowdown fails the
# run with a distinct exit code.
# ---------------------------------------------------------------------------

#: a row passes while value >= baseline * min_ratio (higher-is-better)
#: or value <= baseline / min_ratio (lower-is-better) — 20% headroom by
#: default so scheduler noise does not flap the gate; tighten per row
#: in the baseline file
GATE_DEFAULT_MIN_RATIO = 0.8

# metrics where a SMALLER value is the better one; everything else
# (throughput-style rows) gates higher-is-better. Baseline entries can
# override with an explicit "direction".
_GATE_LOWER_IS_BETTER = {"serving_ttft", "pipeline_bubble_fraction",
                         "collective_wire_bytes_per_step",
                         "autoscale_time_to_capacity",
                         "publish_to_fleet_secs",
                         "prefix_reuse_ttft",
                         "request_trace_overhead",
                         "input_pipeline_nhost_wait_frac"}

GATE_EXIT_CODE = 4

#: the committed baseline a plain ``python bench.py`` gates against by
#: default (ROADMAP item 5: record with ``--baseline-out BASELINE.json``,
#: opt out with ``--no-gate``; docs/PERFORMANCE.md has the refresh
#: procedure). Only armed for CLI invocations — embedding callers and
#: tests pass explicit argv and keep explicit gating.
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BASELINE.json")


def _is_gate_baseline(path: str) -> bool:
    """True when ``path`` is a recorded gate baseline (a ``rows``
    object). The repo's seed-era BASELINE.json predates the gate and
    carries reference metadata instead — gating against it would fail
    every run, so the default gate arms only on the real format."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        return isinstance(doc.get("rows"), dict)
    except Exception:
        return False

# row key -> emitted metric name, where they differ: a row that FAILS
# mid-run is recorded under its row key, so the gate must recognize a
# baselined metric behind either name
_ROW_METRICS = {
    "headline": "inception_v1_train_images_per_sec_per_chip",
    "inception_v2": "inception_v2_train_images_per_sec_per_chip",
    "resnet50": "resnet50_train_images_per_sec_per_chip",
    "vgg16": "vgg16_train_images_per_sec_per_chip",
    "real": "inception_v1_train_real_jpeg_images_per_sec_per_chip",
    "real_cached":
        "inception_v1_train_real_jpeg_cached_images_per_sec_per_chip",
    "transformer": "transformer_lm_train_tokens_per_sec_per_chip",
    "decode": "transformer_lm_decode_tokens_per_sec_per_chip",
    "decode_ragged":
        "transformer_lm_ragged_decode_tokens_per_sec_per_chip",
    "decode_spec": "transformer_lm_speculative_decode_tokens_per_sec",
    "input_pipeline": "input_pipeline_overlap",
    "input_pipeline_nhost": "input_pipeline_nhost_wait_frac",
}
_METRIC_TO_ROW = {v: k for k, v in _ROW_METRICS.items()}


def _gate_check(path: str, rows_out: list[dict]) -> tuple[dict, bool]:
    """Evaluate the recorded baseline at ``path`` against this run's
    rows. Returns (gate row, ok). Only metrics present in BOTH the
    baseline and the run are judged (the baseline may cover rows this
    invocation did not request — reported as skipped, never silently
    dropped); a baselined row that ERRORED this run is a failure."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        base = doc["rows"]
        if not isinstance(base, dict):
            raise ValueError("baseline 'rows' is not an object")
    except Exception as e:
        row = {"metric": "bench_gate", "value": 0.0, "unit": "1 = pass",
               "baseline": path,
               "error": f"unreadable baseline: {type(e).__name__}: {e}"}
        return row, False
    by_metric = {r.get("metric"): r for r in rows_out}
    checked, skipped, failures = [], [], []
    for metric, spec in sorted(base.items()):
        row = by_metric.get(metric) \
            or by_metric.get(_METRIC_TO_ROW.get(metric))
        if row is None:
            skipped.append(metric)
            continue
        if "error" in row:
            failures.append({"metric": metric,
                             "reason": f"row errored: {row['error']}"})
            continue
        val = row.get("value")
        bval = float(spec["value"])
        ratio = float(spec.get("min_ratio", GATE_DEFAULT_MIN_RATIO))
        direction = spec.get(
            "direction",
            "lower" if metric in _GATE_LOWER_IS_BETTER else "higher")
        checked.append(metric)
        if not isinstance(val, (int, float)):
            failures.append({"metric": metric,
                             "reason": f"non-numeric value {val!r}"})
            continue
        if direction == "lower":
            ok = val <= bval / max(ratio, 1e-9)
            reason = (f"{val} > baseline {bval} / min_ratio {ratio} "
                      f"(lower is better)")
        else:
            ok = val >= bval * ratio
            reason = f"{val} < baseline {bval} * min_ratio {ratio}"
        if not ok:
            failures.append({"metric": metric, "value": val,
                             "baseline": bval, "min_ratio": ratio,
                             "direction": direction, "reason": reason})
    row = {"metric": "bench_gate", "value": 0.0 if failures else 1.0,
           "unit": "1 = pass", "baseline": path, "checked": checked,
           "skipped": skipped, "failures": failures}
    return row, not failures


def _write_baseline(path: str, rows_out: list[dict]) -> None:
    """Record this run as the new gate baseline: every successful
    numeric row, with the default threshold and its direction spelled
    out so the file is hand-editable."""
    rows = {}
    for r in rows_out:
        val = r.get("value")
        if ("error" in r or "metric" not in r
                or r["metric"] in ("aggregate", "bench_gate")
                or not isinstance(val, (int, float))):
            continue
        rows[r["metric"]] = {
            "value": val,
            "min_ratio": GATE_DEFAULT_MIN_RATIO,
            "direction": ("lower" if r["metric"] in _GATE_LOWER_IS_BETTER
                          else "higher"),
            "unit": r.get("unit", ""),
        }
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "rows": rows}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    print(f"# gate baseline written to {path}", file=sys.stderr)


# the driver's parser keeps only the LAST JSON line (BENCH_r03 lesson), so
# after the per-row lines we re-emit everything in one aggregate line that
# carries the headline fields at top level plus every row under "rows"
def _emit_aggregate(rows_out: list[dict]) -> None:
    agg = {"metric": "aggregate", "value": 0.0, "unit": "",
           "vs_baseline": 0.0}
    # hoist only the FIRST requested row (the headline when present) and
    # only if it succeeded — promoting a different row's number into the
    # headline slot would misreport a degraded run as healthy
    if rows_out and "error" not in rows_out[0]:
        agg.update({k: rows_out[0][k] for k in
                    ("metric", "value", "unit", "vs_baseline")
                    if k in rows_out[0]})
    agg["rows"] = rows_out
    _emit(agg)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--headline-only", action="store_true")
    parser.add_argument("--rows", default="all",
                        help="comma list: headline,inception_v2,real,"
                             "real_cached,resnet50,vgg16,transformer,"
                             "decode,decode_ragged,decode_spec,"
                             "input_pipeline,serving_ttft,"
                             "serving_tokens_per_sec,train_mfu,"
                             "collective_wire_bytes_per_step,"
                             "compile_cold_start,"
                             "serving_decode_hbm_bytes,"
                             "train_peak_hbm_bytes,multichip_scaling,"
                             "pipeline_bubble_fraction,"
                             "elastic_resume_secs,"
                             "autoscale_time_to_capacity,"
                             "input_pipeline_nhost")
    parser.add_argument("--gate", default=None, metavar="BASELINE_JSON",
                        help="compare this run's rows against a "
                             "recorded baseline (per-row thresholds); "
                             f"a real slowdown exits {GATE_EXIT_CODE}. "
                             "A CLI run with no --gate gates against "
                             f"{DEFAULT_BASELINE} automatically when "
                             "that file is a recorded baseline "
                             "(--no-gate opts out)")
    parser.add_argument("--no-gate", action="store_true",
                        help="skip the default BASELINE.json gate")
    parser.add_argument("--baseline-out", default=None, metavar="PATH",
                        help="record this run's rows as the new gate "
                             "baseline (written alongside "
                             "--metrics-out)")
    parser.add_argument("--metrics-out", default=None,
                        help="write the metric-registry state here "
                             "after the run (.json -> JSON dump, else "
                             "Prometheus text exposition)")
    parser.add_argument("--serve-metrics", type=int, default=None,
                        metavar="PORT",
                        help="expose the live registry over HTTP for "
                             "the duration of the run (/metrics, "
                             "/metrics.json, /trace, /healthz, "
                             "/readyz; 0 = ephemeral port)")
    parser.add_argument("--host-probe", type=float, default=None,
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--wire-probe", action="store_true",
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--decode-hbm-probe", action="store_true",
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--decode-hbm-geometry", default="{}",
                        help=argparse.SUPPRESS)
    parser.add_argument("--cold-start-probe", default=None,
                        metavar="CACHE_DIR",
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--cold-start-model", default="inception_v1",
                        help=argparse.SUPPRESS)
    parser.add_argument("--cold-start-batch", type=int, default=16,
                        help=argparse.SUPPRESS)
    parser.add_argument("--elastic-train-probe", default=None,
                        metavar="CKPT_DIR",
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--elastic-resume-probe", default=None,
                        metavar="CKPT_DIR",
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--elastic-resume-cache", default=None,
                        help=argparse.SUPPRESS)
    parser.add_argument("--train-hbm-probe", action="store_true",
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--train-hbm-geometry", default="{}",
                        help=argparse.SUPPRESS)
    parser.add_argument("--scaling-probe", type=int, default=None,
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--pipeline-bubble-probe", action="store_true",
                        help=argparse.SUPPRESS)   # subprocess entry
    parser.add_argument("--pipeline-bubble-geometry", default="{}",
                        help=argparse.SUPPRESS)
    parser.add_argument("--scaling-batch-per-chip", type=int, default=64,
                        help=argparse.SUPPRESS)
    parser.add_argument("--scaling-iters", type=int, default=8,
                        help=argparse.SUPPRESS)
    parser.add_argument("--dataplane-probe", default=None,
                        metavar="CONFIG_JSON",
                        help=argparse.SUPPRESS)   # subprocess entry
    args = parser.parse_args(argv)
    if argv is None and args.gate is None and not args.no_gate:
        # ROADMAP item 5: the committed baseline is ENFORCED on plain
        # CLI runs once one is recorded; a legacy/non-gate file skips
        # with a note instead of failing every run
        if _is_gate_baseline(DEFAULT_BASELINE):
            args.gate = DEFAULT_BASELINE
            print(f"# gating against {DEFAULT_BASELINE} "
                  "(--no-gate to skip)", file=sys.stderr)
        elif os.path.exists(DEFAULT_BASELINE):
            print(f"# {DEFAULT_BASELINE} is not a recorded gate "
                  "baseline (no 'rows') — default gate skipped; record "
                  "one with --baseline-out", file=sys.stderr)
    if args.host_probe is not None:
        _emit({"host_pipeline_img_per_sec":
               round(host_pipeline_probe(args.host_probe), 1)})
        return
    if args.wire_probe:
        _wire_probe_main()
        return
    if args.decode_hbm_probe:
        _decode_hbm_probe_main(args.decode_hbm_geometry)
        return
    if args.cold_start_probe is not None:
        _cold_start_probe_main(args.cold_start_probe,
                               args.cold_start_model,
                               args.cold_start_batch)
        return
    if args.elastic_train_probe is not None:
        _elastic_train_probe_main(args.elastic_train_probe)
        return
    if args.elastic_resume_probe is not None:
        _elastic_resume_probe_main(args.elastic_resume_probe,
                                   args.elastic_resume_cache)
        return
    if args.train_hbm_probe:
        _train_hbm_probe_main(args.train_hbm_geometry)
        return
    if args.scaling_probe is not None:
        _scaling_probe_main(args.scaling_probe,
                            args.scaling_batch_per_chip,
                            args.scaling_iters)
        return
    if args.pipeline_bubble_probe:
        _pipeline_bubble_probe_main(args.pipeline_bubble_geometry)
        return
    if args.dataplane_probe is not None:
        _dataplane_probe_main(args.dataplane_probe)
        return
    global _metrics_server
    if args.serve_metrics is not None:
        from bigdl_tpu.observability.exporter import MetricsServer
        _metrics_server = MetricsServer(port=args.serve_metrics).start()
        print(f"# telemetry plane: {_metrics_server.url}",
              file=sys.stderr)
    try:
        return _run(args)
    finally:
        if _metrics_server is not None:
            _metrics_server.close()
            _metrics_server = None


# the live exporter for the current run (None outside one) — tests and
# embedding harnesses read the bound port here
_metrics_server = None


def _run(args):
    global _headline_cache
    _headline_cache = None      # per-invocation cache (tests re-enter)
    rows = (["headline"] if args.headline_only
            else [r.strip() for r in args.rows.split(",")])
    if args.rows == "all" and not args.headline_only:
        rows = ["headline", "train_mfu", "inception_v2", "real",
                "real_cached", "resnet50", "vgg16", "transformer",
                "decode", "decode_ragged", "decode_spec",
                "input_pipeline", "serving_ttft",
                "serving_tokens_per_sec",
                "collective_wire_bytes_per_step",
                "compile_cold_start", "serving_decode_hbm_bytes",
                "train_peak_hbm_bytes", "multichip_scaling",
                "pipeline_bubble_fraction", "elastic_resume_secs",
                "autoscale_time_to_capacity", "publish_to_fleet_secs",
                "prefix_reuse_ttft", "request_trace_overhead",
                "input_pipeline_nhost"]

    known = {"headline", "inception_v2", "real", "real_cached",
             "resnet50", "vgg16", "transformer", "decode",
             "decode_ragged", "decode_spec", "input_pipeline",
             "serving_ttft", "serving_tokens_per_sec", "train_mfu",
             "collective_wire_bytes_per_step", "compile_cold_start",
             "serving_decode_hbm_bytes", "train_peak_hbm_bytes",
             "multichip_scaling", "pipeline_bubble_fraction",
             "elastic_resume_secs", "autoscale_time_to_capacity",
             "publish_to_fleet_secs", "prefix_reuse_ttft",
             "request_trace_overhead", "input_pipeline_nhost"}
    unknown = set(rows) - known
    if unknown:
        raise SystemExit(f"unknown bench rows: {sorted(unknown)} "
                         f"(known: {sorted(known)})")

    try:
        info = _backend_info()
    except Exception as e:
        # no backend, no bench: one error row per REQUESTED metric, so
        # the record shows exactly which rows the failure cost
        err = f"jax backend init failed: {type(e).__name__}: {e}"
        rows_out = []
        for row in rows:
            r = {"metric": ("inception_v1_train_images_per_sec_per_chip"
                            if row == "headline" else row),
                 "value": 0.0,
                 "unit": "images/sec/chip" if row == "headline" else "",
                 "error": err}
            if row == "headline":
                r["vs_baseline"] = 0.0
            rows_out.append(r)
            _emit(r)
        _emit_aggregate(rows_out)
        raise SystemExit(3)
    print(f"# backend: {info}", file=sys.stderr)

    fns = {
        "headline": _headline_row,
        "train_mfu": bench_train_mfu,
        "collective_wire_bytes_per_step": bench_collective_wire_bytes,
        "compile_cold_start": bench_compile_cold_start,
        "inception_v2": lambda: bench_convnet_synthetic("inception_v2"),
        "real": lambda: bench_real_data(0.0),
        "real_cached": lambda: bench_real_data(2.0),
        "resnet50": lambda: bench_convnet_synthetic("resnet50"),
        "vgg16": lambda: bench_convnet_synthetic("vgg16"),
        "transformer": bench_transformer_lm,
        "decode": bench_decode,
        "decode_ragged": bench_decode_ragged,
        "decode_spec": bench_decode_speculative,
        "input_pipeline": bench_input_pipeline_overlap,
        "serving_ttft": bench_serving_ttft,
        "serving_tokens_per_sec": bench_serving_tokens_per_sec,
        "serving_decode_hbm_bytes": bench_serving_decode_hbm,
        "train_peak_hbm_bytes": bench_train_peak_hbm,
        "multichip_scaling": bench_multichip_scaling,
        "pipeline_bubble_fraction": bench_pipeline_bubble,
        "elastic_resume_secs": bench_elastic_resume_secs,
        "autoscale_time_to_capacity": bench_autoscale_time_to_capacity,
        "publish_to_fleet_secs": bench_publish_to_fleet,
        "prefix_reuse_ttft": bench_prefix_reuse_ttft,
        "request_trace_overhead": bench_request_trace_overhead,
        "input_pipeline_nhost": bench_input_pipeline_nhost,
    }
    rows_out: list[dict] = []
    failed: list[str] = []
    for row in rows:
        try:
            out = fns[row]()
            rows_out.append(out)
            _emit(out)
        except Exception as e:   # a broken row must not lose the others
            rows_out.append({"metric": row,
                             "error": f"{type(e).__name__}: {e}"})
            print(f"bench row {row} failed: {e}", file=sys.stderr)
            failed.append(row)
    gate_ok = True
    if args.gate:
        # the gate verdict rides INSIDE the aggregate (the driver keeps
        # only the last JSON line) as well as its own structured row
        gate_row, gate_ok = _gate_check(args.gate, rows_out)
        rows_out.append(gate_row)
        _emit(gate_row)
    _emit_aggregate(rows_out)
    if args.baseline_out:
        _write_baseline(args.baseline_out, rows_out)
    if args.metrics_out:
        from bigdl_tpu.observability.registry import default_registry
        reg = default_registry()
        if args.metrics_out.endswith(".json"):
            reg.dump_json(args.metrics_out)
        else:
            with open(args.metrics_out, "w", encoding="utf-8") as f:
                f.write(reg.expose())
        print(f"# metrics registry written to {args.metrics_out}",
              file=sys.stderr)
    if not gate_ok:
        raise SystemExit(GATE_EXIT_CODE)
    if failed:
        # ANY requested row that errored fails the run: a row that
        # silently drops out must not read as a healthy bench
        print(f"# bench rows failed: {failed}", file=sys.stderr)
        raise SystemExit(2)


if __name__ == "__main__":
    main()

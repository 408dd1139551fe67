"""Optimizer facade + LocalOptimizer.

Reference parity: abstract Optimizer (optim/Optimizer.scala:29-128 —
setValidation / setCheckpoint / setState / setOptimMethod / setEndWhen /
overWriteCheckpoint), factory dispatch on dataset type (:150-186), and
LocalOptimizer (optim/LocalOptimizer.scala:39-242).

TPU-first: the reference clones one model per core, shares a flat weight
storage, runs thread-parallel fwd/bwd and merges gradients chunk-parallel
(:64-141). All of that collapses into ONE jit-compiled train step — XLA owns
op parallelism on the chip; there are no replicas to merge. The step fn is
donated-argument jitted so weights update in place in HBM.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.dataset import (to_jax_batch)
from bigdl_tpu.observability import compile_watch, trace
from bigdl_tpu.observability.flight_recorder import FlightRecorder
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import OptimMethod
from bigdl_tpu.optim.sgd import SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.utils.table import Table, T

logger = logging.getLogger("bigdl_tpu.optim")

__all__ = ["Optimizer", "LocalOptimizer"]


def _clip_gradients(grads, clip):
    """Global-L2 and/or constant clipping, traced into the train step."""
    if not clip:
        return grads
    if clip["min_value"] is not None:
        grads = jax.tree.map(
            lambda g: jnp.clip(g, clip["min_value"], clip["max_value"]),
            grads)
    if clip["l2_norm"] is not None:
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip["l2_norm"] / (norm + 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
    return grads


def _require_process_sharded(dataset, what: str):
    """Multi-host evaluation double-counts unless each process holds its
    OWN shard: refuse unsharded datasets, shard counts that don't match
    the process count, and duplicate shard indices (e.g. every process
    left shard_index at the default 0 — round-5 review findings).

    COLLECTIVE: gathers every process's local view FIRST so all hosts
    reach the same verdict from the same data — a host-local raise while
    peers proceed into a later collective would hang the job."""
    from bigdl_tpu.parallel.collective import process_allgather_pyobj
    sharded = hasattr(dataset, "is_sharded") and dataset.is_sharded()
    count_fn = getattr(dataset, "process_shard_count", None)
    idx_fn = getattr(dataset, "process_shard_index", None)
    infos = process_allgather_pyobj(
        (bool(sharded), count_fn() if count_fn is not None else None,
         idx_fn() if idx_fn is not None else None))
    nproc = jax.process_count()
    if not all(s for s, _, _ in infos):
        raise ValueError(
            f"multi-host evaluation requires a process-sharded {what} "
            f"(each of the {nproc} processes must hold its own shard); "
            "an unsharded dataset would be double-counted in the "
            "cross-host reduce")
    bad = {c for _, c, _ in infos if c is not None and c != nproc}
    if bad:
        raise ValueError(
            f"{what} was built for {sorted(bad)} process shards but the "
            f"job has {nproc} processes — the cross-host reduce would "
            "mis-count")
    indices = [i for _, _, i in infos if i is not None]
    if len(indices) == len(infos) and len(set(indices)) != len(indices):
        raise ValueError(
            f"{what} shard indices {indices} are not distinct across "
            "processes (every process must pass its own process_index, "
            "not the default) — duplicated shards would be "
            "double-counted and the rest never evaluated")


@dataclasses.dataclass(frozen=True)
class _TrainRun:
    """What a subclass's ``_prepare_run`` hands the one training loop
    (``Optimizer._optimize_impl``): everything in which training one
    program on one device and training over a mesh differ. The two
    hooks default to what the local optimizer needs: nothing."""
    # the placed training state and the resume point
    params: Any
    mstate: Any
    opt_state: Any
    rng: Any
    count_this_epoch: int
    batches_to_skip: int
    step_compiler: Any                 # tuning/aot_cache.StepCompiler
    place: Callable                    # host batch -> device batch
    eval_fn: Callable                  # (params, mstate, data) -> out
    records_scale: int = 1             # processes feeding one batch
    # the first executable of the run, once (collective accounting)
    on_first_compile: Callable = lambda compiled: None
    # the params-shaped trees validation, a checkpoint and the exit
    # read: (params, opt_state, with_opt) -> (params tree, opt state);
    # opt state is re-shaped only where ``with_opt``
    export: Callable = lambda params, opt_state, with_opt: (params,
                                                            opt_state)


class Optimizer:
    """Facade + factory (reference optim/Optimizer.scala)."""

    def __new__(cls, model=None, dataset=None, criterion=None,
                batch_size=None, **kw):
        if cls is Optimizer:
            # factory dispatch (reference Optimizer.apply :150-186); the
            # is_sharded() walk sees through transform wrappers
            sharded = dataset is not None and hasattr(dataset, "is_sharded") \
                and dataset.is_sharded()
            if sharded or kw.get("mesh") is not None:
                from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
                return super().__new__(DistriOptimizer)
            return super().__new__(LocalOptimizer)
        return super().__new__(cls)

    def __init__(self, model, dataset, criterion, batch_size=None, *,
                 remat_policy: str | None = None,
                 grad_accumulation: int = 1,
                 pipeline_stages: int = 1,
                 pipeline_schedule: str = "1f1b",
                 pipeline_virtual_stages: int = 1,
                 expert_parallel: bool | str = False,
                 expert_aux_weight: float = 1e-2, **kw):
        from bigdl_tpu.dataset.transformer import SampleToBatch
        from bigdl_tpu.optim.remat import check_remat_policy
        self.model = model
        if batch_size is not None:
            # RDD[Sample]+batchSize overload (reference :150-162)
            dataset = dataset >> SampleToBatch(batch_size)
        self.dataset = dataset
        self.criterion = criterion
        self.state = T()
        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger | None = None
        self.validation_trigger = None
        self.validation_dataset = None
        self.validation_methods = None
        self.checkpoint_trigger = None
        self.checkpoint_path = None
        self.is_overwrite = False
        # async checkpointing (bigdl_tpu/elastic/, docs/ELASTICITY.md):
        # _checkpoint snapshots device state with one packed device_get
        # and hands serialization to a background CheckpointWriter; the
        # loops barrier at epoch end and drain it at exit. receipt =
        # handoff_s vs write_s split after the run.
        self.checkpoint_async = True
        self.checkpoint_keep = None
        self._ckpt_writer = None
        self._ckpt_mesh = None
        self.checkpoint_receipt = None
        self.metrics = Metrics()
        # per-epoch input-wait accounting (host-side span timers only;
        # step_time already contains data_time, so the fraction is
        # wait / total — reset at each epoch boundary)
        self._epoch_wait_s = 0.0
        self._epoch_total_s = 0.0
        self.profile_dir = None
        self.profile_start = 0
        self.profile_iters = 0
        self._profiling = False
        # instruction -> scope tables of the steps compiled so far, for
        # the profiler sessions to come
        self._step_scopes = trace.ProgramScopes()
        self.grad_clip = None
        self.input_transform = None
        # memory-for-throughput knobs (optim/remat.py,
        # optim/accumulation.py, docs/PERFORMANCE.md): a named
        # jax.checkpoint policy applied to the model forward at step
        # construction, and the number of microbatches one compiled
        # step scans with the gradient accumulated before the single
        # optimizer update. Both are AOT-cache key material.
        self.remat_policy = check_remat_policy(remat_policy)
        self.grad_accumulation = self._check_grad_accumulation(
            grad_accumulation)
        # pipeline + expert parallelism (parallel/pipeline.py,
        # parallel/expert.py, docs/PERFORMANCE.md): stage count/schedule
        # for Sequential stacks over a 'pipe' mesh axis, and the MoE
        # aux-loss/telemetry wiring for models carrying MoE layers over
        # an 'expert' mesh axis. All of it is AOT-cache key material.
        self.pipeline_stages = 1
        self.pipeline_schedule = "1f1b"
        self.pipeline_virtual_stages = 1
        if pipeline_stages != 1 or pipeline_virtual_stages != 1 \
                or pipeline_schedule != "1f1b":
            self.set_pipeline(pipeline_stages,
                              schedule=pipeline_schedule,
                              virtual_stages=pipeline_virtual_stages)
        self.expert_parallel = None
        self.expert_aux_weight = float(expert_aux_weight)
        if expert_parallel:
            self.set_expert_parallel(expert_parallel,
                                     aux_weight=expert_aux_weight)
        self.train_summary = None
        self.val_summary = None
        # async dispatch: how many steps may be in flight before the loop
        # drains their losses with one packed readback (docs/PERFORMANCE.md)
        self.max_in_flight = 2
        # fully sharded weight update + wire-compressed collectives
        # (optim/sharded_update.py, docs/PERFORMANCE.md): active on the
        # distributed path; the local single-program path has no
        # collectives, so the setting is accepted and inert there
        self.shard_weight_update = False
        self.wire_codec = None
        # None = resolve per run: the autotuned record for this
        # (param count, shard count) when one exists, else the 4 MB
        # default (optim/sharded_update.py tuned_bucket_mb)
        self.bucket_mb = None
        # persistent AOT executable cache (tuning/aot_cache.py):
        # "env" = $BIGDL_TPU_AOT_CACHE_DIR when set, else off;
        # set_aot_cache() overrides either way
        self._aot_cache_cfg = "env"
        # the run's lower -> compile -> cache pipeline (set by
        # _prepare_run); its executables() are the compiled train
        # steps, e.g. to read the optimized program text
        self.step_compiler = None
        # overlapped input pipeline (dataset/prefetch.py): batches are
        # assembled + device-placed on a worker thread, `depth` ahead of
        # the loop; 0 = the synchronous path (docs/PERFORMANCE.md)
        self.prefetch_depth = 2
        self.pad_partial_batches = False
        self._pad_stage = None
        self._epoch_position_state = None
        # telemetry plane (docs/OBSERVABILITY.md): the flight recorder's
        # black box is ON by default (steady-state cost: a deque append
        # per warning/span event); the HTTP exporter is opt-in
        self.flight_recorder: FlightRecorder | None = FlightRecorder()
        self._metrics_server_cfg = None
        self._metrics_server = None
        self._liveness_deadline = 600.0
        self._last_step_mono = None
        self._liveness_registered = False

    # -- builder API (reference Optimizer.scala:66-123) --
    def set_validation(self, trigger, dataset, methods):
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = list(methods)
        return self

    def set_checkpoint(self, path, trigger, *, async_save: bool = True,
                       keep: int | None = None):
        """Checkpoint the full training state to ``path`` on ``trigger``
        (reference Optimizer.setCheckpoint). The directory is validated
        EAGERLY — created if absent, write-probed — so a bad path fails
        here, not minutes into training at the first trigger fire.

        ``async_save=True`` (default) serializes checkpoints on a
        background writer thread (bigdl_tpu/elastic/, saved bytes
        bit-identical to the synchronous path); ``False`` restores the
        fully synchronous save. ``keep=K`` enables retention GC
        (``elastic.manifest.sweep_checkpoints``): after each manifest
        commit only the newest K numbered checkpoints survive, and
        torn/orphaned member files from never-committed manifests are
        swept — long runs stop filling the store (ROADMAP 1(c)).
        Ignored under ``overwrite_checkpoint`` (one unsuffixed
        snapshot, nothing to retain)."""
        from bigdl_tpu.utils.file import ensure_writable_dir
        ensure_writable_dir(path)
        if keep is not None and keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.checkpoint_async = bool(async_save)
        self.checkpoint_keep = keep
        return self

    def overwrite_checkpoint(self):
        self.is_overwrite = True
        return self

    def set_state(self, state):
        self.state = Table(state)
        return self

    def set_gradient_clipping(self, *, l2_norm: float | None = None,
                              min_value: float | None = None,
                              max_value: float | None = None):
        """Clip gradients inside the jitted train step: by global L2 norm
        (transformer-era staple) and/or constant min/max (the clipping
        style later BigDL releases expose). Applies to Local and Distri
        optimizers alike; returns self."""
        if l2_norm is None and min_value is None and max_value is None:
            raise ValueError(
                "set_gradient_clipping needs l2_norm and/or "
                "min_value+max_value")
        if l2_norm is not None and l2_norm <= 0:
            raise ValueError(f"l2_norm must be > 0, got {l2_norm}")
        if ((min_value is None) != (max_value is None)):
            raise ValueError("min_value and max_value must be set together")
        if min_value is not None and min_value >= max_value:
            raise ValueError(f"min_value {min_value} must be < "
                             f"max_value {max_value}")
        self.grad_clip = {"l2_norm": l2_norm, "min_value": min_value,
                          "max_value": max_value}
        return self

    def set_optim_method(self, method: OptimMethod):
        self.optim_method = method
        return self

    def set_train_summary(self, summary):
        """Per-iteration scalar event log (reference-parity
        ``TrainSummary``, observability/summary.py): the loop appends
        Loss / Throughput / HostInputTime / DeviceStepTime for every
        step, emitted at window-drain time under the step's original
        ``neval`` (docs/PERFORMANCE.md). Host floats only — recording
        never adds a device sync the loop wasn't already paying.
        Returns self."""
        self.train_summary = summary
        return self

    def set_val_summary(self, summary):
        """``ValidationSummary`` event log: one scalar per validation
        method per validation pass, tagged by the method's repr, plus
        ValidationThroughput. Returns self."""
        self.val_summary = summary
        return self

    def set_input_transform(self, fn):
        """Pure function applied to each batch's DATA inside the jitted
        train/eval step — the hook the u8 input pipeline uses to run
        normalize/BGR/NCHW on-device
        (``dataset.image.device_transform.u8_to_model_input``) so the host
        ships raw uint8 crops (4x smaller transfers) and the reference's
        host-side BGRImgNormalizer work rides the TPU. Returns self."""
        self.input_transform = fn
        return self

    def set_async_dispatch(self, max_in_flight: int = 2):
        """Bound how far the train loop's dispatch pipeline may run ahead
        of the host before draining the pending losses with ONE packed
        ``jax.device_get``. ``max_in_flight=1`` is the classic lockstep
        loop (a readback every iteration); larger windows let XLA's async
        dispatch overlap host-input work with device steps at the cost of
        the logged loss lagging ``neval`` by up to the window
        (docs/PERFORMANCE.md). Triggers whose ``requires`` includes
        ``"loss"`` (``min_loss``) force lockstep regardless. Returns
        self."""
        if int(max_in_flight) < 1:
            raise ValueError(
                f"max_in_flight must be >= 1, got {max_in_flight}")
        self.max_in_flight = int(max_in_flight)
        return self

    def set_input_pipeline(self, depth: int = 2, *,
                           pad_partial_batches: bool | None = None):
        """Configure the overlapped input pipeline
        (``dataset/prefetch.py``, docs/PERFORMANCE.md). ``depth`` >= 1
        runs ``next(batch)`` + transforms + device placement on a
        prefetch worker, ``depth`` batches ahead of the train loop, so
        the loop's input phase is a queue pop (the ``input wait``
        span); ``depth=0`` restores the synchronous path. On by
        default (depth 2) — trajectories are bit-identical either way
        (tests/test_prefetch.py pins it).

        ``pad_partial_batches=True`` additionally pads each pass's
        final short batch to the full batch shape with an in-step
        validity mask (``nn.MaskedCriterion``): one compiled train-step
        signature per run instead of one per distinct batch shape, with
        padded rows contributing exactly zero to loss and gradient.
        Returns self."""
        if int(depth) < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {depth}")
        self.prefetch_depth = int(depth)
        if pad_partial_batches is not None:
            self.pad_partial_batches = bool(pad_partial_batches)
        return self

    def set_end_when(self, end_when: Trigger):
        self.end_when = end_when
        return self

    @staticmethod
    def _check_grad_accumulation(k) -> int:
        if int(k) < 1:
            raise ValueError(f"num_microbatches must be >= 1, got {k}")
        return int(k)

    def set_remat_policy(self, policy: str | None):
        """Select the activation-rematerialization policy applied to
        the model forward when the train step is constructed
        (optim/remat.py, docs/PERFORMANCE.md): ``"none"`` (default,
        save every residual), ``"dots_saveable"`` (save matmul/conv
        outputs), ``"per_block"`` (checkpoint each top-level block of a
        Sequential stack — the selective policy for transformer/
        inception stacks), ``"nothing_saveable"`` (save only region
        inputs; maximum HBM savings, one forward of recompute). Loss
        and gradients are BIT-identical across policies — only peak
        activation memory and recompute move. The policy keys the AOT
        executable cache, so switching it misses correctly. Returns
        self."""
        from bigdl_tpu.optim.remat import check_remat_policy
        self.remat_policy = check_remat_policy(policy)
        return self

    def set_grad_accumulation(self, num_microbatches: int = 1):
        """Compile the train step to ``lax.scan`` ``num_microbatches``
        microbatches through forward/backward with gradients
        accumulated on device, then run the optimizer update (and, on
        the sharded-update path, the bucketed gradient reduce-scatter)
        EXACTLY ONCE per step (optim/accumulation.py,
        docs/PERFORMANCE.md). The loop still feeds full batches; the
        split is internal and strided, so an effectively k×-larger
        batch runs at near-constant peak activation HBM.
        ``num_microbatches=1`` IS the plain step — same construction,
        same AOT cache key. The batch must divide by k (refused loudly
        at step construction otherwise). Returns self."""
        self.grad_accumulation = self._check_grad_accumulation(
            num_microbatches)
        return self

    def set_pipeline(self, num_stages: int, *, schedule: str = "1f1b",
                     virtual_stages: int = 1):
        """Partition a ``Sequential`` model's top-level blocks into
        ``num_stages`` pipeline stages over the mesh ``pipe`` axis and
        compile ONE train step that scans the combined forward/backward
        schedule (``"gpipe"`` / ``"1f1b"`` / ``"interleaved_1f1b"``;
        parallel/pipeline.py, docs/PERFORMANCE.md).
        ``set_grad_accumulation(M)`` sets the microbatch count the
        schedule streams — the optimizer update still fires exactly once
        per step, and the trained trajectory matches the non-pipelined
        accumulated step (tests/test_pipeline_train.py pins it
        bit-identical on the pure-pipe mesh). ``virtual_stages > 1``
        (interleaved schedule only) assigns each device that many
        round-robin chunks, shrinking the bubble fraction from
        (S-1)/(M+S-1) to (S-1)/(v·M+S-1). Distributed path only: the
        local optimizer has no mesh to pipeline over. The knobs key the
        AOT executable cache. Returns self."""
        from bigdl_tpu.parallel.pipeline import check_pipeline_schedule
        if int(num_stages) < 1:
            raise ValueError(
                f"pipeline_stages must be >= 1, got {num_stages}")
        if int(virtual_stages) < 1:
            raise ValueError(
                f"virtual_stages must be >= 1, got {virtual_stages}")
        self.pipeline_stages = int(num_stages)
        self.pipeline_schedule = check_pipeline_schedule(schedule)
        self.pipeline_virtual_stages = int(virtual_stages)
        return self

    def set_expert_parallel(self, axis: bool | str = True, *,
                            aux_weight: float = 1e-2):
        """Wire the model's MoE layers (parallel/expert.py ``MoE``) into
        the training objective: the load-balancing aux loss the layers
        stash in module state joins the criterion with weight
        ``aux_weight``, and the dispatch telemetry (token drops,
        overflow, load imbalance) is published to the metric registry at
        epoch boundaries — one batched readback per epoch, never a
        per-step sync. ``axis`` names the mesh axis experts shard over
        (True = ``"expert"``); the mesh must carry it. Keys the AOT
        executable cache. Returns self."""
        if aux_weight < 0:
            raise ValueError(
                f"aux_weight must be >= 0, got {aux_weight}")
        self.expert_parallel = ("expert" if axis is True else axis) \
            if axis else None
        self.expert_aux_weight = float(aux_weight)
        return self

    def _aux_loss_fn(self):
        """The aux-loss hook ``make_train_step`` folds into the
        objective (None when expert parallelism is off)."""
        if not self.expert_parallel:
            return None
        from bigdl_tpu.parallel.expert import moe_aux_total
        w = self.expert_aux_weight

        def aux(new_mstate):
            return w * moe_aux_total(new_mstate)

        return aux

    def set_sharded_update(self, enabled: bool = True, *,
                          wire_codec=None, bucket_mb: float | None = None):
        """Configure the fully cross-replica-sharded weight update
        (optim/sharded_update.py, docs/PERFORMANCE.md): reduce-scatter
        gradients in size-targeted buckets, update parameters +
        optimizer state 1/N per replica, all-gather updated parameters.

        ``wire_codec``: ``None`` keeps implicit full-width collectives
        (trajectories bit-identical to the replicated update);
        ``"fp32"``/``"bf16"``/``"int8"`` switch to explicit per-shard
        collectives at that wire width — ``"bf16"`` is the reference's
        FP16 wire, ``"int8"`` adds stochastic rounding + error feedback
        (the residual rides the optimizer state and checkpoints).
        ``bucket_mb`` targets the per-bucket payload the backward
        overlaps against. Only the distributed optimizer has
        collectives; on the local path this is accepted and inert.
        Returns self."""
        from bigdl_tpu.parameters.compression import get_codec
        get_codec(wire_codec)          # validate the name eagerly
        self.shard_weight_update = bool(enabled) or wire_codec is not None
        self.wire_codec = wire_codec
        if bucket_mb is not None:
            if bucket_mb <= 0:
                raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
            self.bucket_mb = float(bucket_mb)
        return self

    def set_aot_cache(self, cache):
        """Configure the persistent AOT executable cache
        (``tuning/aot_cache.py``, docs/PERFORMANCE.md): train-step
        construction becomes an explicit lower → compile → cache
        pipeline, and a restarting worker whose cache directory is warm
        LOADS its compiled step (~ms) instead of recompiling it
        (seconds to minutes) — results are bit-identical either way,
        and any unreadable/stale entry falls back to a fresh compile.

        ``cache``: a directory path, an ``AOTCache``, or ``None`` to
        disable (overriding ``$BIGDL_TPU_AOT_CACHE_DIR``, which
        otherwise applies when this method was never called). Returns
        self."""
        if isinstance(cache, str):
            from bigdl_tpu.tuning.aot_cache import AOTCache
            cache = AOTCache(cache)
        self._aot_cache_cfg = cache
        return self

    def _aot_cache(self):
        """The effective cache for this run (None = caching off)."""
        if self._aot_cache_cfg == "env":
            from bigdl_tpu.tuning.aot_cache import env_cache
            return env_cache()
        return self._aot_cache_cfg

    def _step_key_extra(self) -> tuple:
        """Program-identity key material for the AOT executable cache.
        The abstract shape signature alone cannot tell two programs
        with identical shapes apart, and jit-constant hyperparameters
        (learning rate, clip bounds, dtype policy) are compiled into
        the executable — so they all key the cache. ``stable_repr``
        strips object addresses so the material matches across worker
        processes."""
        from bigdl_tpu.tensor import get_policy
        from bigdl_tpu.tuning.aot_cache import stable_repr
        optim = self.optim_method
        transform = None
        if self.input_transform is not None:
            fn = self.input_transform
            transform = getattr(fn, "__qualname__", None) or repr(fn)
            try:        # a lambda's qualname alone would collide
                import hashlib
                import inspect
                transform += ":" + hashlib.sha1(
                    inspect.getsource(fn).encode()).hexdigest()[:12]
            except Exception:
                pass
        return (stable_repr(self.model), stable_repr(self.criterion),
                type(optim).__name__, stable_repr(vars(optim)),
                stable_repr(self.grad_clip), stable_repr(get_policy()),
                transform, self._pad_stage is not None,
                self.shard_weight_update, self.wire_codec,
                self.bucket_mb,
                getattr(self, "tensor_parallel", None),
                getattr(self, "sequence_parallel", None),
                getattr(self, "shard_optim_state", None),
                # remat + accumulation change the compiled program at
                # identical shapes — they must miss the cache; k=1 and
                # policy "none" ARE the plain step (same key as a run
                # that never configured them)
                self.remat_policy, self.grad_accumulation,
                # pipeline schedule/stages and the MoE aux wiring also
                # change the program at identical shapes
                # (tests/test_pipeline_train.py pins the miss)
                self.pipeline_stages, self.pipeline_schedule,
                self.pipeline_virtual_stages, self.expert_parallel,
                self.expert_aux_weight if self.expert_parallel
                else None)

    def set_metrics_server(self, port: int = 0, host: str = "127.0.0.1",
                           *, liveness_deadline: float = 600.0):
        """Expose the live telemetry plane over HTTP for the duration
        of :meth:`optimize`: /metrics (Prometheus text), /metrics.json,
        /trace, /healthz, /readyz (docs/OBSERVABILITY.md). ``port=0``
        binds an ephemeral port — read it from
        ``self._metrics_server.port`` once training starts. A
        ``training_liveness`` health check reports failing when no step
        has progressed within ``liveness_deadline`` seconds (warming up
        before the first step counts as live). Returns self."""
        if liveness_deadline <= 0:
            raise ValueError(f"liveness_deadline must be > 0, got "
                             f"{liveness_deadline}")
        self._metrics_server_cfg = {"port": int(port), "host": host}
        self._liveness_deadline = float(liveness_deadline)
        return self

    def set_flight_recorder(self, recorder=None):
        """Replace the default crash flight recorder: pass a
        :class:`FlightRecorder`, a directory path (a recorder dumping
        there), or None to disable. On by default — an optimizer run
        that dies leaves a postmortem directory (registry JSON, trace
        JSON, last-N events, compile ledger, exception). Returns
        self."""
        if isinstance(recorder, str):
            recorder = FlightRecorder(dir=recorder)
        self.flight_recorder = recorder
        return self

    # -- telemetry plane lifecycle (docs/OBSERVABILITY.md) --
    def _liveness_check(self):
        last = self._last_step_mono
        if last is None:
            return True, "no step yet (warming up)"
        age = time.monotonic() - last
        return (age <= self._liveness_deadline,
                f"last step {age:.1f}s ago "
                f"(deadline {self._liveness_deadline:.0f}s)")

    def _telemetry_step(self) -> None:
        """Heartbeat: one monotonic read per iteration, feeding the
        training_liveness health check."""
        self._last_step_mono = time.monotonic()

    def _telemetry_start(self) -> None:
        self._last_step_mono = None
        if self.flight_recorder is not None:
            self.flight_recorder.install()
        from bigdl_tpu.observability.exporter import default_health
        default_health().register("training_liveness",
                                  self._liveness_check, kind="liveness")
        self._liveness_registered = True
        if self._metrics_server_cfg is not None:
            from bigdl_tpu.observability.exporter import MetricsServer
            cfg = self._metrics_server_cfg
            self._metrics_server = MetricsServer(cfg["port"],
                                                 cfg["host"]).start()
            logger.info("telemetry plane listening on %s "
                        "(/metrics /metrics.json /trace /healthz "
                        "/readyz)", self._metrics_server.url)

    def _telemetry_stop(self) -> None:
        if self._liveness_registered:
            from bigdl_tpu.observability.exporter import default_health
            default_health().unregister("training_liveness")
            self._liveness_registered = False
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None
        if self.flight_recorder is not None:
            self.flight_recorder.uninstall()

    def optimize(self):
        """Run the training loop with the telemetry plane armed: the
        metrics server (when configured) and the training-liveness
        check span the run, and ANY escaping exception leaves a
        postmortem directory before propagating — the loop may be
        wrapped in a driver that catches it, where ``sys.excepthook``
        would never fire."""
        self._telemetry_start()
        try:
            return self._optimize_impl()
        except BaseException as e:
            if self.flight_recorder is not None:
                self.flight_recorder.dump_postmortem(
                    e, reason="optimizer exception")
            raise
        finally:
            # failure path: drain/stop the async checkpoint writer
            # without masking the original exception (the success path
            # already shut it down, raising on background save errors)
            self._ckpt_shutdown(raise_errors=False)
            self._telemetry_stop()

    # -- shared helpers --
    def _header(self, epoch, count, total, neval, wallclock):
        """(reference Optimizer.header, Optimizer.scala:131-134)"""
        return f"[Epoch {epoch} {count}/{total}][Iteration {neval}]" \
               f"[Wall Clock {wallclock:.3f}s]"

    def _record_step(self, neval: int, loss: float, n: int,
                     step_time: float, data_time: float,
                     device_time: float) -> None:
        """Shared per-iteration observability: the honest host-side
        phase split into Metrics (-> registry histograms) plus the
        TrainSummary event log. Called at DRAIN time with the step's
        original ``neval`` stamp — ``loss`` is already a host float;
        everything here is host arithmetic."""
        self.metrics.record("device step time", device_time)
        self.metrics.record("host input time", data_time)
        self._epoch_wait_s += data_time
        self._epoch_total_s += step_time
        if self.train_summary is not None:
            s = self.train_summary
            s.add_scalar("Loss", loss, neval)
            s.add_scalar("Throughput", n / max(step_time, 1e-9), neval)
            s.add_scalar("HostInputTime", data_time, neval)
            s.add_scalar("DeviceStepTime", device_time, neval)

    def _emit_input_wait_fraction(self, neval: int) -> None:
        """Epoch-end roll-up of the per-step host-side span timers: what
        fraction of the epoch's wall time the consumer spent waiting on
        input. Pure host arithmetic over already-collected floats — no
        device sync — labeled per host by the shard-tagged starvation
        metrics it complements (dataset/prefetch.py)."""
        if self._epoch_total_s <= 0:
            return
        frac = min(1.0, self._epoch_wait_s / self._epoch_total_s)
        self.metrics.set("input wait fraction", frac)
        if self.train_summary is not None:
            self.train_summary.add_scalar("InputWaitFraction", frac, neval)
        self._epoch_wait_s = 0.0
        self._epoch_total_s = 0.0

    def _validate(self, apply_fn, params, mstate, driver_state, *,
                  fire: bool | None = None):
        """``fire``: pre-evaluated trigger decision from :meth:`_fires`;
        None (direct callers/tests) evaluates the trigger here."""
        if fire is None:
            if self.validation_trigger is None or \
                    self.validation_dataset is None:
                return None
            fire = self.validation_trigger(driver_state)
        if not fire:
            return None
        if jax.process_count() > 1:
            _require_process_sharded(self.validation_dataset,
                                     "validation dataset")
            # multi-host: gather params/state to host ONCE per validation
            # pass (a collective — safe: the fire decision is a
            # deterministic function of the shared driver state, and it
            # runs once per pass regardless of per-process batch counts);
            # apply_fn then evaluates on local devices
            from bigdl_tpu.utils.file import _to_host
            params, mstate = _to_host(params), _to_host(mstate)
        results = [None] * len(self.validation_methods)
        count = 0
        t0 = time.perf_counter()
        # in-training validation rides the same prefetch machinery as
        # the train loop: batch assembly (transforms, stacking) overlaps
        # eval dispatch on a worker thread (dataset/prefetch.py)
        from bigdl_tpu.dataset.prefetch import open_input_pipeline
        val_iter = open_input_pipeline(
            self.validation_dataset.data(train=False),
            depth=self.prefetch_depth, name="val",
            # validating ON the training set is legal: the train
            # pipeline already holds that dataset's worker guard
            dataset=(self.validation_dataset
                     if self.validation_dataset is not self.dataset
                     else None),
            shard=self.validation_dataset.process_shard_index())
        try:
            with trace.span("validation",
                            host_sync="per-batch metric eval"):
                for batch in val_iter:
                    data, labels = to_jax_batch(batch)
                    out = apply_fn(params, mstate, data)
                    count += data.shape[0]
                    for i, m in enumerate(self.validation_methods):
                        r = m(out, labels)
                        results[i] = r if results[i] is None \
                            else results[i] + r
        finally:
            val_iter.close()
        if jax.process_count() > 1:
            # each process validated its own shard; reduce to the global
            # result on every host (reference DistriValidator's driver
            # reduce). Safe as a collective: the trigger is a
            # deterministic function of the shared driver state
            from bigdl_tpu.optim.validation import aggregate_results
            from bigdl_tpu.parallel.collective import \
                process_allgather_pyobj
            results = aggregate_results(results)
            count = sum(process_allgather_pyobj(count))  # global records
        elapsed = time.perf_counter() - t0
        logger.info(f"validate model throughput is "
                    f"{count / max(elapsed, 1e-9):.2f} records/second")
        for m, r in zip(self.validation_methods, results):
            logger.info(f"{m!r} is {r!r}")
        if self.val_summary is not None:
            step = int(driver_state.get("neval", 0))
            for m, r in zip(self.validation_methods, results):
                self.val_summary.add_scalar(repr(m),
                                            float(r.result()[0]), step)
            self.val_summary.add_scalar(
                "ValidationThroughput",
                count / max(elapsed, 1e-9), step)
        return dict(zip([repr(m) for m in self.validation_methods], results))

    @staticmethod
    def _host_rng_snapshot() -> bytes:
        """Pickled host-RNG state. Captured at each training-iterator
        (re)creation: mid-epoch resume restores THIS state and replays the
        consumed batches, so the pipeline's random-augmentation draws land
        exactly where the uninterrupted run's did (restoring the
        checkpoint-time state would double-consume the replayed draws)."""
        import pickle
        from bigdl_tpu.utils.random import RandomGenerator
        return pickle.dumps(RandomGenerator.RNG()._rng.bit_generator.state)

    # -- async checkpoint writer lifecycle (bigdl_tpu/elastic/) --
    def _ckpt_writer_get(self):
        if self._ckpt_writer is None:
            from bigdl_tpu.elastic.checkpoint_writer import CheckpointWriter
            self._ckpt_writer = CheckpointWriter(name=type(self).__name__)
        return self._ckpt_writer

    def _ckpt_barrier(self):
        """Wait out every in-flight save (epoch end: the boundary
        shuffle and a new epoch's dispatch must not stack snapshots
        behind a slow filesystem)."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.barrier()

    def _ckpt_shutdown(self, *, raise_errors: bool):
        """Drain + stop the writer and publish the save-overhead receipt
        (``self.checkpoint_receipt``). ``raise_errors=False`` is the
        already-failing path: a background save error must not mask the
        original exception."""
        w, self._ckpt_writer = self._ckpt_writer, None
        if w is None:
            return
        try:
            w.close()
        except Exception:
            if raise_errors:
                self.checkpoint_receipt = w.receipt()
                raise
            logger.warning("async checkpoint writer shutdown failed "
                           "(training already unwinding)", exc_info=True)
        self.checkpoint_receipt = w.receipt()

    def _snapshot_module(self, host_params, host_mstate):
        """Detached module snapshot for the background writer: deep-copy
        the TOPOLOGY only (all runtime arrays unbound during the copy —
        cloning device gradients would mean per-leaf transfers), then
        bind the already-on-host param/state trees onto the clone. The
        snapshot shares no mutable state with the training loop."""
        from bigdl_tpu.utils.file import _strip_runtime
        model = self.model
        saved = []

        def unbind(m):
            saved.append((m, m.params, m.state, m.grad_params, m._rng))
            m.params = m.state = m.grad_params = m._rng = None
            for child in getattr(m, "modules", []):
                unbind(child)

        unbind(model)
        try:
            snap = model.clone_module()
        finally:
            for m, p, s, g, r in saved:
                m.params, m.state, m.grad_params, m._rng = p, s, g, r
        _strip_runtime(snap)
        snap.params = host_params
        snap.state = host_mstate
        if host_params is not None:
            snap.sync(host_params, host_mstate)
        return snap

    def _checkpoint(self, driver_state, opt_state=None, rng=None,
                    record_count=0, batches_this_epoch=0,
                    epoch_start_host_rng: bytes | None = None, *,
                    fire: bool | None = None):
        """Save the WHOLE training state on trigger (reference
        DistriOptimizer.scala:319-341 saves the full state Table): driver
        counters + optimizer state (momentum/accumulators) + device rng +
        data-pipeline position + host-rng state, so a resumed run is the
        run that was stopped. ``fire``: pre-evaluated trigger decision.

        Elastic rendering (bigdl_tpu/elastic/, docs/ELASTICITY.md): the
        critical path pays ONE packed ``jax.device_get`` over every
        device leaf — mandatory either way, the next step's donated
        buffers must not be rewritten under a pending readback — and
        serialization runs on the background writer (``checkpoint_async``,
        default). Write order is model → state → manifest: the manifest
        is the commit point ``latest_checkpoint`` trusts, so a crash at
        any point never exposes a torn snapshot."""
        if fire is None:
            if self.checkpoint_trigger is None or \
                    self.checkpoint_path is None:
                return
            fire = self.checkpoint_trigger(driver_state)
        if fire:
            with trace.span("checkpoint handoff",
                            step=driver_state["neval"]):
                self._checkpoint_handoff(
                    driver_state, opt_state, rng, record_count,
                    batches_this_epoch, epoch_start_host_rng)

    def _checkpoint_handoff(self, driver_state, opt_state, rng,
                            record_count, batches_this_epoch,
                            epoch_start_host_rng):
        """The fired checkpoint's critical path: the packed readback,
        the detached snapshot and the hand-off to the writer."""
        from bigdl_tpu.elastic.checkpoint_writer import snapshot_to_host
        from bigdl_tpu.elastic.manifest import (build_manifest,
                                                manifest_name,
                                                write_manifest)
        from bigdl_tpu.utils import file as _file
        neval = driver_state["neval"]
        suffix = "" if self.is_overwrite else f".{neval}"
        path = self.checkpoint_path
        t0 = time.perf_counter()
        host_params, host_mstate, host_opt, host_rng = snapshot_to_host(
            (self.model.params, self.model.state, opt_state, rng))
        module = self._snapshot_module(host_params, host_mstate)
        full_state = dict(driver_state)
        full_state["record_count"] = record_count
        full_state["batches_this_epoch"] = batches_this_epoch
        if host_opt is not None:
            full_state["opt_state"] = host_opt
        if host_rng is not None:
            full_state["rng"] = np.asarray(host_rng)
        # opaque bytes: the nested state dict (strings/ints/arrays) must
        # round-trip exactly, not through the array-flattening save path
        full_state["host_rng_state"] = (epoch_start_host_rng
                                        if epoch_start_host_rng is not None
                                        else self._host_rng_snapshot())
        # prefetch-era position state: the worker's read-ahead may have
        # advanced the LIVE state past the consumer (it can start the
        # next pass while the loop is still mid-epoch), so the loops
        # snapshot at pipeline creation and the snapshot is advanced by
        # the CONSUMER's progress — unconsumed prefetched batches fold
        # back into the saved position (dataset/prefetch.py)
        pos = self._epoch_position_state
        if pos is not None and batches_this_epoch > 0:
            pos = self.dataset.advance_position_state(pos)
        if pos is None:
            pos = self.dataset.get_position_state()
        if pos is not None:
            full_state["data_position"] = pos
        if self._pad_stage is not None and self._pad_stage.full_size:
            # the learned full batch shape: a resume whose first replayed
            # batch is the short one must still pad to the original size
            full_state["pad_full_size"] = int(self._pad_stage.full_size)
        # the saved mesh descriptor: resume redistributes onto whatever
        # mesh the new process initializes (elastic/redistribute.py)
        from bigdl_tpu.elastic.manifest import mesh_layout
        layout = mesh_layout(self._ckpt_mesh)
        if layout is not None:
            full_state["mesh_layout"] = layout
        manifest = build_manifest(
            neval=neval, epoch=int(driver_state["epoch"]),
            model_file=f"model{suffix}", state_file=f"state{suffix}",
            params=host_params, opt_state=host_opt, mesh=layout)
        model_path = f"{path}/model{suffix}"
        state_path = f"{path}/state{suffix}"
        manifest_path = f"{path}/{manifest_name(suffix)}"

        keep = None if self.is_overwrite else self.checkpoint_keep

        def write_job():
            _file.save_module(module, model_path, overwrite=True,
                              prepared=True)
            _file.save(full_state, state_path, overwrite=True)
            write_manifest(manifest, manifest_path)  # commit point
            if keep is not None:
                # retention GC strictly after the commit, on the single
                # writer thread — never concurrent with a write, and a
                # sweep failure must not fail the checkpoint
                from bigdl_tpu.elastic.manifest import sweep_checkpoints
                try:
                    sweep_checkpoints(path, keep)
                except Exception:
                    logger.warning("checkpoint GC failed for %s", path,
                                   exc_info=True)

        handoff_s = time.perf_counter() - t0
        if self.checkpoint_async:
            self._ckpt_writer_get().submit(
                write_job, label=f"neval={neval}", handoff_s=handoff_s)
            self.metrics.record("checkpoint handoff time", handoff_s)
            logger.info(f"Save model to {model_path} (async)")
        else:
            write_job()
            self.metrics.record("checkpoint handoff time",
                                time.perf_counter() - t0)
            logger.info(f"Save model to {model_path}")

    def set_profiler(self, trace_dir: str, start_iteration: int = 10,
                     num_iterations: int = 5):
        """Capture a ``jax.profiler`` trace of iterations
        [start, start+num) into ``trace_dir`` (SURVEY §7 step 7 — the
        XLA-native replacement for the reference's per-module
        forwardTime/backwardTime inspection; open with TensorBoard or
        Perfetto)."""
        self.profile_dir = trace_dir
        self.profile_start = start_iteration
        self.profile_iters = num_iterations
        return self

    def _profile_hook(self, neval: int):
        if self.profile_dir is None:
            return
        if not self._profiling and self.profile_iters > 0 and \
                self.profile_start <= neval < self.profile_start + \
                self.profile_iters:
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True
        elif self._profiling and neval >= self.profile_start + \
                self.profile_iters:
            jax.profiler.stop_trace()
            self._profiling = False
            logger.info("profiler trace written to %s", self.profile_dir)

    def _lookup_step(self, step_pipeline, key, args):
        """This iteration's executable from the ``StepCompiler``, and
        whether this was the first sight of ``key``. On first sight the
        step's instruction -> scope table is kept
        (``trace.ProgramScopes``): a profiler session starts long after
        compilation, and the TPU's trace names device operations by HLO
        instruction only."""
        first = key not in step_pipeline
        compiled, _ = step_pipeline.get(key, args)
        if first:
            self._step_scopes.add(compiled)
        return compiled, first

    def _stop_profiler(self):
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False

    def _fires(self, driver_state) -> tuple[bool, bool]:
        """Evaluate the validation and checkpoint triggers EXACTLY once per
        iteration (a stateful user trigger must not be consumed twice) and
        return (fire_validation, fire_checkpoint)."""
        fire_val = (self.validation_trigger is not None
                    and self.validation_dataset is not None
                    and self.validation_trigger(driver_state))
        fire_ckpt = (self.checkpoint_trigger is not None
                     and self.checkpoint_path is not None
                     and self.checkpoint_trigger(driver_state))
        return fire_val, fire_ckpt

    # -- async dispatch (docs/PERFORMANCE.md) --
    def _loss_sync_reason(self) -> str | None:
        """Which configured trigger (if any) reads the loss and therefore
        forces a readback every iteration — the stopping decision must
        see the true per-step value, so the dispatch window collapses to
        lockstep."""
        for what, t in (("end_when", self.end_when),
                        ("validation trigger", self.validation_trigger),
                        ("checkpoint trigger", self.checkpoint_trigger)):
            if t is not None and "loss" in getattr(t, "requires",
                                                   frozenset()):
                return f"{what} {t!r} reads loss"
        return None

    def _dispatch_window(self) -> tuple[int, str | None]:
        """Effective in-flight window for this run: ``max_in_flight``
        unless a loss-reading trigger forces lockstep."""
        reason = self._loss_sync_reason()
        if reason is not None:
            if self.max_in_flight > 1:
                logger.info(
                    "async dispatch disabled (%s) — draining loss every "
                    "iteration to preserve exact stopping semantics",
                    reason)
            return 1, reason
        return self.max_in_flight, None

    def _emit_step(self, e: dict, loss: float) -> None:
        """Emit one drained step's log line + observability records,
        stamped with the step's ORIGINAL counters (the drain may run up
        to ``max_in_flight`` iterations later). The f-string is only
        built when INFO is live — this runs once per iteration."""
        if logger.isEnabledFor(logging.INFO):
            logger.info(
                self._header(e["epoch"], e["count"], e["epoch_size"],
                             e["neval"], e["wallclock"])
                + f" loss is {loss:.6f}, iteration time is "
                f"{e['step_time']:.4f}s, host input time is "
                f"{e['data_time']:.4f}s, device step time is "
                f"{e['device_time']:.4f}s, throughput is "
                f"{e['n'] / max(e['step_time'], 1e-9):.2f} records/second")
        self._record_step(e["neval"], loss, e["n"], e["step_time"],
                          e["data_time"], e["device_time"])

    def _drain_pending(self, pending: list, driver_state: dict,
                       reason: str, mstate) -> None:
        """Drain the in-flight window: ONE packed ``jax.device_get`` for
        every pending loss (the sanctioned batched readback — the only
        host<-device sync in the steady-state loop), then emit each
        step's deferred log line / summary scalars under its original
        ``neval``. The readback wait cannot be attributed to a single
        step once dispatch runs ahead, so it is amortized evenly across
        the window (window-amortized device time, docs/PERFORMANCE.md).

        Inside a profiler session the experts' routing telemetry that
        ``mstate`` holds (the module state the window's last step
        returned: alive until the next dispatch donates it) rides the
        same readback and is stated as one ``bigdl:optim:expert_state``
        annotation: ``{"step": ..., "layers": {path: {stat: float}}}``.
        What the state holds decides, no flag; no program is compiled.
        """
        if not pending:
            return
        depth = len(pending)
        self.metrics.set("dispatch depth", depth)
        experts = {}
        if trace.in_profiler_session():
            from bigdl_tpu.parallel.expert import moe_state_stats
            experts = moe_state_stats(mstate)
        t0 = time.perf_counter()
        with trace.span("loss drain", host_sync="packed loss readback",
                        depth=depth, reason=reason,
                        first_step=pending[0]["neval"],
                        last_step=pending[-1]["neval"]):
            losses, experts = jax.device_get(
                ([e["loss"] for e in pending], experts))
        share = (time.perf_counter() - t0) / depth
        if experts:
            trace.state("expert state", "optim", {
                "step": pending[-1]["neval"],
                "layers": {layer: {k: float(v) for k, v in stats.items()}
                           for layer, stats in experts.items()}})
        # the user's summary callbacks run in here; releasing the drained
        # steps' loss arrays belongs to it (on the TPU the runtime then
        # walks the step's donated buffers: ~0.5 ms at 309 leaves)
        with trace.span("emit steps", depth=depth):
            for e, lv in zip(pending, losses):
                loss = float(lv)
                e["device_time"] += share
                e["step_time"] += share
                self._emit_step(e, loss)
                driver_state["loss"] = loss
            pending.clear()

    def _publish_expert_telemetry(self, mstate) -> None:
        """Publish the experts' routing telemetry the module state
        HOLDS (``MoE``'s keys, ``ExpertShare``'s, or none: the state
        decides, no flag) to the metric registry, at every epoch end and
        at exit: ONE batched ``jax.device_get`` over every layer's
        state leaves — the loop never pays a per-step sync for it."""
        from bigdl_tpu.parallel.expert import publish_moe_metrics
        try:
            stats = publish_moe_metrics(mstate)
        except Exception as e:    # telemetry must never break training
            logger.debug("moe telemetry publish failed: %s", e)
            return
        if logger.isEnabledFor(logging.INFO):
            for layer, vals in stats.items():
                logger.info("moe[%s]: %s", layer, ", ".join(
                    f"{key.removeprefix('moe_')} {val:.4g}"
                    for key, val in vals.items()))

    def _resume(self, optim, params):
        """Rebuild (opt_state, rng, count_this_epoch, batches_to_skip) from
        ``self.state`` — full-fidelity when the state came from a round-2
        checkpoint, best-effort (the reference's epoch/neval semantics)
        otherwise."""
        from bigdl_tpu.utils.random import RandomGenerator
        opt_state = optim.init_state(params)
        saved = self.state.get("opt_state")
        if saved is not None:
            opt_state = jax.tree.map(jnp.asarray, dict(saved))
        elif int(self.state.get("neval", 1)) > 1:
            # legacy states carry no optimizer state — at least restore the
            # LR-schedule counter so decay doesn't restart
            opt_state["neval"] = jnp.asarray(
                int(self.state["neval"]) - 1, jnp.int32)
        saved_rng = self.state.get("rng")
        rng = (jnp.asarray(saved_rng) if saved_rng is not None
               else jax.random.PRNGKey(int(self.state.get("seed", 0))))
        host_state = self.state.get("host_rng_state")
        if host_state is not None:
            import pickle
            if not isinstance(host_state, bytes):
                host_state = np.asarray(host_state).item()
            RandomGenerator.RNG()._rng.bit_generator.state = \
                pickle.loads(host_state)
        count = int(self.state.get("record_count", 0))
        skip = int(self.state.get("batches_this_epoch", 0))
        pos = self.state.get("data_position")
        if pos is not None:
            self.dataset.set_position_state(pos, mid_pass=skip > 0)
        self._init_pad_stage()
        return opt_state, rng, count, skip

    # -- overlapped input pipeline (dataset/prefetch.py) --
    def _init_pad_stage(self):
        """Per-run partial-batch pad stage; the checkpoint carries the
        learned full batch size so a resume whose first replayed batch
        is the short one still pads to the original shape."""
        if not self.pad_partial_batches:
            self._pad_stage = None
            return
        if jax.process_count() > 1:
            raise ValueError(
                "pad_partial_batches is single-controller only: each "
                "process pads its own block of the global batch, so the "
                "in-step validity mask (arange < valid) cannot describe "
                "the multi-host row layout — pad per-process batches in "
                "the dataset pipeline instead")
        from bigdl_tpu.dataset.prefetch import PadPartialBatches
        saved = int(self.state.get("pad_full_size", 0))
        self._pad_stage = PadPartialBatches(saved or None)

    def _open_train_pipeline(self, place, *, skip: int = 0,
                             consumed: int = 0, records_scale: int = 1):
        """Build one epoch's input pipeline: raw dataset iterator ->
        optional partial-batch padding -> device placement, overlapped
        on a prefetch worker at ``prefetch_depth`` >= 1 (synchronous at
        0). The worker is EPOCH-BOUNDED (``max_records``) so its pull
        sequence — and therefore every host-RNG draw and pass
        transition — is exactly the synchronous loop's; the position
        state is snapshotted here, before the fast-forward pulls, for
        :meth:`_checkpoint`. MUST be close()d before
        ``dataset.shuffle()`` (thread-safety contract,
        dataset/prefetch.py)."""
        from bigdl_tpu.dataset.prefetch import open_input_pipeline
        self._epoch_position_state = self.dataset.get_position_state()
        raw = self.dataset.data(train=True)
        for _ in range(skip):   # fast-forward to the resume point
            next(raw)
        pad = self._pad_stage
        if pad is not None and place is not None:
            def stage(b, _pad=pad, _place=place):
                return _place(_pad(b))
        else:
            stage = pad if place is None else place
        max_records = None
        if self.prefetch_depth > 0:
            max_records = max(int(self.dataset.size()) - int(consumed), 0)
        return open_input_pipeline(raw, depth=self.prefetch_depth,
                                   stage=stage, max_records=max_records,
                                   records_scale=records_scale,
                                   name="train", dataset=self.dataset,
                                   shard=self.dataset.process_shard_index())

    # -- the training loop: one, for both optimizers --
    def _prepare_run(self) -> _TrainRun:
        """A subclass's whole share of a run: check the configuration,
        place the training state, build the step."""
        raise NotImplementedError

    def _initial_state(self):
        """The module's training state and the resume point: ``(params,
        mstate, opt_state, rng, count_this_epoch, batches_to_skip)``
        (reference: epoch/neval live in the state Table,
        DistriOptimizer.scala:80-81; full opt_state/rng/data-position
        restore when the state came from a checkpoint)."""
        model = self.model
        model.materialize()
        model.training()
        return (model.params, model.state) \
            + self._resume(self.optim_method, model.params)

    def _masked_criterion(self):
        """The criterion under the in-step validity mask of padded
        partial batches; None where batches are not padded."""
        if self._pad_stage is None:
            return None
        from bigdl_tpu.nn.criterion import MaskedCriterion
        return MaskedCriterion(self.criterion)

    def _global_view_step(self, masked, update_fn):
        """The step over the whole batch, assembled from the memory
        knobs: the (possibly remat-wrapped) forward and the microbatched
        gradient-accumulation scan (optim/remat.py,
        optim/accumulation.py); policy "none" + k=1 is EXACTLY the plain
        step."""
        from bigdl_tpu.optim.accumulation import make_train_step
        from bigdl_tpu.optim.remat import remat_forward
        return make_train_step(
            fwd=remat_forward(self.model, self.remat_policy),
            criterion=self.criterion, masked=masked,
            input_transform=self.input_transform,
            grad_clip=self.grad_clip, update_fn=update_fn,
            num_microbatches=self.grad_accumulation,
            aux_loss=self._aux_loss_fn())

    def _eval_apply(self, params, mstate, data):
        if self.input_transform is not None:
            data = self.input_transform(data)
        out, _ = self.model.apply(params, mstate, data, training=False)
        return out

    def _optimize_impl(self):
        run = self._prepare_run()
        model = self.model
        params, mstate, opt_state, rng = \
            run.params, run.mstate, run.opt_state, run.rng
        step_pipeline = self.step_compiler = run.step_compiler
        driver_state = {"epoch": int(self.state.get("epoch", 1)),
                        "neval": int(self.state.get("neval", 1)),
                        "is_epoch_end": False, "loss": float("inf")}
        count_this_epoch = run.count_this_epoch
        batches_this_epoch = run.batches_to_skip
        epoch_start_host_rng = self._host_rng_snapshot()
        epoch_size = self.dataset.size()
        pipeline = self._open_train_pipeline(
            run.place, skip=run.batches_to_skip, consumed=count_this_epoch,
            records_scale=run.records_scale)
        window, lockstep = self._dispatch_window()
        pending: list[dict] = []
        wallclock_start = time.perf_counter()

        try:
            while True:
                # the profiler hook first, so that a set_profiler trace
                # holds the whole of its first iteration's span
                self._profile_hook(driver_state["neval"])
                with trace.span("train iteration",
                                step=driver_state["neval"]):
                    if self.end_when is not None and \
                            self.end_when(driver_state):
                        break
                    driver_state["is_epoch_end"] = False
                    self._step_scopes.annotate()
                    t0 = time.perf_counter()
                    with trace.span("input wait"):
                        # queue pop at depth >= 1: the batch was assembled,
                        # checked, and placed on the worker thread
                        # ("input produce")
                        batch = next(pipeline)
                    t1 = time.perf_counter()
                    data_time = t1 - t0
                    data, labels = batch.data, batch.labels
                    # records consumed across all hosts; of a padded
                    # batch the REAL rows (single controller —
                    # _init_pad_stage refuses multi-host)
                    n = int(batch.valid if batch.valid is not None
                            else data.shape[0])
                    with trace.span("step lookup"):
                        rng, step_rng = jax.random.split(rng)
                        epoch_arr = jnp.asarray(driver_state["epoch"],
                                                jnp.int32)
                        step_args = (step_rng, data, labels, epoch_arr)
                        if self._pad_stage is not None:   # n_valid
                            step_args += (jnp.asarray(n, jnp.int32),)
                        # lower/compile (or AOT-cache load) on first sight
                        # of a batch signature; compile counts, executable
                        # FLOPs and peak HBM land in the registry either
                        # way (observability/compile_watch.py). Only the
                        # batch varies between iterations (params/opt
                        # state keep their avals through donation): two
                        # leaves to hash, the full signature only on a
                        # miss inside the pipeline
                        compiled, compiled_this_iter = self._lookup_step(
                            step_pipeline,
                            compile_watch.signature_of((data, labels)),
                            (params, mstate, opt_state) + step_args)
                        if compiled_this_iter and len(step_pipeline) == 1:
                            run.on_first_compile(compiled)
                    with trace.span("device step"):
                        # dispatch only — loss stays on device; the packed
                        # readback happens at drain time (docs/PERFORMANCE.md).
                        # Honest phase metrics: the reference's get-weights/
                        # compute/aggregate phases fuse inside the jitted
                        # step, so what's measurable is input wait vs device
                        # step (see metrics.py)
                        params, mstate, opt_state, loss = compiled(
                            params, mstate, opt_state, *step_args)
                    t2 = time.perf_counter()
                    self._telemetry_step()
                    count_this_epoch += n
                    batches_this_epoch += 1
                    pending.append({"epoch": driver_state["epoch"],
                                    "count": count_this_epoch,
                                    "epoch_size": epoch_size,
                                    "neval": driver_state["neval"],
                                    "wallclock": time.perf_counter()
                                    - wallclock_start,
                                    "loss": loss, "n": n,
                                    "step_time": t2 - t0,
                                    "data_time": data_time,
                                    "device_time": t2 - t1,
                                    "compiled": compiled_this_iter})
                    if len(pending) >= window:
                        self._drain_pending(pending, driver_state,
                                            lockstep or "window full",
                                            mstate)
                    driver_state["neval"] += 1
                    if count_this_epoch >= epoch_size:
                        self._drain_pending(pending, driver_state,
                                            "epoch end", mstate)
                        self._emit_input_wait_fraction(driver_state["neval"])
                        # epoch-end checkpoint barrier: pending async saves
                        # commit before the next epoch dispatches (bounds
                        # queued snapshots; surfaces background save errors
                        # at the boundary)
                        self._ckpt_barrier()
                        driver_state["epoch"] += 1
                        driver_state["is_epoch_end"] = True
                        count_this_epoch = 0
                        batches_this_epoch = 0
                        # drain + join the worker BEFORE shuffle() touches
                        # the order it iterates (thread-safety contract,
                        # dataset/prefetch.py), then restart it on the fresh
                        # epoch's iterator
                        pipeline.close()
                        self.dataset.shuffle()
                        epoch_start_host_rng = self._host_rng_snapshot()
                        pipeline = self._open_train_pipeline(
                            run.place, records_scale=run.records_scale)
                        # once per epoch: one batched readback, never
                        # per-step
                        self._publish_expert_telemetry(mstate)
                    fire_val, fire_ckpt = self._fires(driver_state)
                    ptree, opt_export = params, opt_state
                    if fire_val or fire_ckpt:
                        # validation/checkpoint read host-visible state: flush
                        # the window first, then publish params (syncing the
                        # module tree every iteration is pure host overhead:
                        # a tree walk on deep models)
                        self._drain_pending(pending, driver_state,
                                            "validation/checkpoint trigger",
                                            mstate)
                        with trace.span("model sync"):
                            ptree, opt_export = run.export(
                                params, opt_state, fire_ckpt)
                            model.sync(ptree, mstate)
                    self._validate(run.eval_fn, ptree, mstate, driver_state,
                                   fire=fire_val)
                    self._checkpoint(driver_state, opt_export, rng,
                                     count_this_epoch, batches_this_epoch,
                                     epoch_start_host_rng, fire=fire_ckpt)
        finally:
            pipeline.close()

        self._drain_pending(pending, driver_state, "training end", mstate)
        # exit barrier: every handed-off checkpoint is committed (and any
        # background save error raised) before optimize() returns
        self._ckpt_shutdown(raise_errors=True)
        self._stop_profiler()
        self._publish_expert_telemetry(mstate)
        ptree, _ = run.export(params, opt_state, False)
        model.sync(ptree, mstate)
        model.evaluate()
        return model


class LocalOptimizer(Optimizer):
    """Single-host training (reference optim/LocalOptimizer.scala): one
    program on the default device, no mesh and no collectives."""

    def _prepare_run(self):
        if self.pipeline_stages > 1:
            raise ValueError(
                "pipeline_stages needs a device mesh to shard stages "
                "over — construct the optimizer with mesh= (or a "
                "sharded dataset) so the distributed path runs, with a "
                "'pipe' axis of that size")
        if self.shard_weight_update or self.wire_codec is not None:
            logger.info(
                "sharded update / wire codec configured, but the local "
                "optimizer is one program with no collectives — inert "
                "(DistriOptimizer runs the sharded path)")
        params, mstate, opt_state, rng, count_this_epoch, \
            batches_to_skip = self._initial_state()
        masked = self._masked_criterion()
        train_step = self._global_view_step(masked,
                                            self.optim_method.update)

        # explicit lower -> compile -> cache step construction
        # (tuning/aot_cache.py): executables are built per batch
        # signature OUTSIDE the hot loop's dispatch path, optionally
        # loaded from the persistent AOT cache (set_aot_cache /
        # $BIGDL_TPU_AOT_CACHE_DIR) so a restarting worker skips XLA;
        # per-call signature counting keeps compile_watch's
        # calls/compiles/storm accounting identical to the old
        # implicit-jit path
        from bigdl_tpu.tuning.aot_cache import StepCompiler
        step_pipeline = StepCompiler(
            jax.jit(train_step, donate_argnums=(0, 1, 2)),
            name="local_train_step", cache=self._aot_cache() or False,
            donate_argnums=(0, 1, 2), extra=self._step_key_extra(),
            count_calls=True)

        def place(b):
            # runs on the prefetch worker (depth >= 1): host->device
            # transfer overlaps the in-flight device steps
            if isinstance(b.data, jax.Array):
                return b   # a user pipeline already placed it
            from bigdl_tpu.dataset.sample import MiniBatch
            return MiniBatch(jnp.asarray(b.data), jnp.asarray(b.labels),
                             valid=b.valid)

        return _TrainRun(
            params, mstate, opt_state, rng, count_this_epoch,
            batches_to_skip, step_compiler=step_pipeline, place=place,
            eval_fn=jax.jit(self._eval_apply))

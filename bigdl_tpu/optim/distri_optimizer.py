"""DistriOptimizer — synchronous data-parallel training over a device mesh.

Reference parity: optim/DistriOptimizer.scala:34-573, the heart of the
reference (call stack SURVEY §3.1). Its per-iteration machinery:

  getWeights (all-gather FP16 slices) → per-core fwd/bwd → chunked gradient
  merge → putGradients (reduce-scatter slices through BlockManager) →
  per-slice SGD → sendWeightPartition

collapses into ONE pjit-compiled step: the batch is sharded along the
``data`` mesh axis, parameters are replicated, and XLA inserts the gradient
all-reduce over ICI during the backward pass — the BlockManager
reduce-scatter/all-gather pair (parameters/AllReduceParameter.scala:53-229)
becomes a single fused collective with no host round-trips. Per-slice
optimizer-state ownership (the reference keeps SGD state only for the local
partition, DistriOptimizer.scala:231-232) maps to optional optimizer-state
sharding along the same axis (``shard_optim_state=True``, cf. "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training").

Straggler dropping (invokeAndWait2 timeouts, :153-176) has no SPMD
equivalent — lockstep collectives can't drop members — so per-phase Metrics
are kept instead (SURVEY §7 translation table).

BatchNorm note: under global-array semantics batch statistics are computed
over the GLOBAL batch (XLA inserts the cross-device mean); the reference's
stats were per-core-replica. Documented difference, generally an accuracy
improvement.
"""
from __future__ import annotations

import logging

import jax
import numpy as np

from bigdl_tpu.optim.optimizer import Optimizer, _TrainRun
from bigdl_tpu.parallel.engine import (get_mesh, data_sharding, replicated)

logger = logging.getLogger("bigdl_tpu.optim")

__all__ = ["DistriOptimizer"]


class _UnderMesh:
    """A jitted function traced under jax's ambient mesh
    (``jax.set_mesh``): that is where a Pallas kernel dispatch learns
    it must run per shard (ops/pallas/per_shard.py). Only the trace
    needs it, so the context is entered around ``lower`` and around a
    direct call — never around the training loop, whose eager ops would
    each recompile under the changed trace context."""

    def __init__(self, jit_fn, mesh):
        self.jit_fn, self.mesh = jit_fn, mesh

    def lower(self, *args):
        with jax.set_mesh(self.mesh):
            return self.jit_fn.lower(*args)

    def __call__(self, *args):
        with jax.set_mesh(self.mesh):
            return self.jit_fn(*args)


class DistriOptimizer(Optimizer):
    """(reference optim/DistriOptimizer.scala)"""

    def __init__(self, model, dataset, criterion, batch_size=None, *,
                 mesh=None, shard_optim_state: bool = False,
                 shard_weight_update: bool = False, wire_codec=None,
                 bucket_mb: float | None = None,
                 tensor_parallel: bool | str = False,
                 sequence_parallel: bool | str = False, **kw):
        super().__init__(model, dataset, criterion, batch_size, **kw)
        self.mesh = mesh
        self.shard_optim_state = shard_optim_state
        # fully cross-replica-sharded update (optim/sharded_update.py):
        # reduce-scatter grads in buckets, 1/N update math + optimizer
        # state per replica, all-gather params; wire_codec None keeps
        # the bit-identical implicit construction, "fp32"/"bf16"/"int8"
        # run explicit (compressed) per-shard collectives
        # bucket_mb None = resolve at run time: the autotuned record for
        # this (param count, data-axis size), else the 4 MB default
        # (optim/sharded_update.py tuned_bucket_mb)
        if shard_weight_update or wire_codec is not None:
            self.set_sharded_update(True, wire_codec=wire_codec,
                                    bucket_mb=bucket_mb)
        elif bucket_mb is not None:
            self.bucket_mb = float(bucket_mb)
        # True / axis name: store params sharded over the mesh 'model'
        # axis and let XLA's SPMD partitioner split the math
        # (parallel/tensor_parallel.py)
        self.tensor_parallel = tensor_parallel
        # True / axis name: shard the batch's SEQUENCE dim (dim 1) over
        # the mesh 'seq' axis as well — pair with a model whose attention
        # runs ring/Ulysses over that axis (models/transformer/model.py
        # sequence_parallel=...). Composes with data and tensor
        # parallelism: one jitted step over a dp x tp x sp mesh.
        self.sequence_parallel = sequence_parallel
        # cached after _account_collectives — the hot loop must not
        # re-read the metrics dict every iteration
        self._wire_bytes = 0.0

    def _account_collectives(self, compiled, n_devices: int) -> None:
        """Static per-step collective-bytes accounting from the compiled
        HLO — the XLA-era equivalent of the reference's put/get-gradient
        phase instrumentation (AllReduceParameter.scala:134-228). Runs
        once per compile; read back via ``metrics.summary()``."""
        from bigdl_tpu.parallel.collective_bench import collective_bytes
        try:
            acct = collective_bytes(compiled.as_text(), n_devices)
        except Exception as e:   # accounting must never break training
            logger.debug(f"collective accounting unavailable: {e}")
            return
        self.metrics.set("collective ops per step", acct["ops"])
        self.metrics.set("collective logical bytes per step",
                         acct["logical_bytes"])
        self.metrics.set("collective wire bytes per chip per step",
                         acct["wire_bytes_per_chip"])
        self._wire_bytes = float(acct["wire_bytes_per_chip"])
        logger.info(
            "collectives per step: %d ops, %.1f MB logical, %.1f MB wire "
            "per chip (ring estimate)", acct["ops"],
            acct["logical_bytes"] / 1e6, acct["wire_bytes_per_chip"] / 1e6)

    def _init_pipeline(self, mesh):
        """Validate + build the PipelineParallel mechanics (None when
        pipeline_stages == 1). The pipeline path owns its own layouts,
        so the features that assume a replicated or data-only layout
        are refused loudly here."""
        if self.pipeline_stages <= 1:
            return None
        if self.tensor_parallel or self.sequence_parallel:
            raise ValueError(
                "pipeline_stages shards the layer stack over the "
                "'pipe' axis and does not compose with "
                "tensor_parallel/sequence_parallel yet — pick one "
                "model-sharding scheme")
        if self.shard_optim_state:
            raise ValueError(
                "pipeline_stages subsumes shard_optim_state: optimizer "
                "state is already stored per stage (and 1/N over the "
                "data axis under shard_weight_update) — drop "
                "shard_optim_state")
        if self.wire_codec is not None:
            raise ValueError(
                "pipeline_stages composes with the implicit sharded "
                "update only (wire_codec=None) — the explicit "
                "compressed-wire step is a whole-step shard_map that "
                "cannot nest the pipeline schedule")
        if self._pad_stage is not None:
            raise ValueError(
                "pipeline_stages does not compose with "
                "pad_partial_batches — pad in the dataset pipeline")
        if self.expert_parallel:
            raise ValueError(
                "pipeline_stages + expert_parallel in one stack is not "
                "supported yet: MoE layers carry per-step state the "
                "pipeline's stateless-block contract refuses")
        from bigdl_tpu.parallel.pipeline import PipelineParallel
        pp = PipelineParallel(
            mesh, self.model, self.criterion, self.optim_method,
            n_stages=self.pipeline_stages,
            num_microbatches=self.grad_accumulation,
            schedule=self.pipeline_schedule,
            virtual_stages=self.pipeline_virtual_stages,
            data_axis="data", remat_policy=self.remat_policy,
            sharded_update=(self.shard_weight_update
                            or self.wire_codec is not None),
            bucket_mb=self.bucket_mb)
        from bigdl_tpu.parallel.pipeline import pipeline_schedule_stats
        st = pipeline_schedule_stats(
            pp.m, pp.s, pp.schedule, virtual_stages=pp.v)
        logger.info(
            "pipeline: %d stages x %d virtual, %s schedule, M=%d "
            "microbatches — modeled bubble %.3f, stash %d microbatches",
            pp.s, pp.v, pp.schedule, pp.m, st["bubble_fraction"],
            st["peak_stash_microbatches"])
        return pp

    def _init_sharded_update(self, mesh, params):
        """Validate + build the ShardedWeightUpdate mechanics (None when
        the feature is off). Raises on configurations whose layouts
        conflict with the flat-bucket construction."""
        if not (self.shard_weight_update or self.wire_codec is not None):
            return None
        if self.tensor_parallel or self.sequence_parallel:
            raise ValueError(
                "shard_weight_update shards flat parameter buckets over "
                "the data axis and requires replicated parameters — it "
                "does not compose with tensor_parallel/sequence_parallel")
        if self.shard_optim_state:
            raise ValueError(
                "shard_weight_update subsumes shard_optim_state (ZeRO-1): "
                "optimizer state is already stored 1/N per replica in "
                "bucket slices — drop shard_optim_state")
        if "data" not in mesh.axis_names:
            raise ValueError(
                f"shard_weight_update needs a 'data' mesh axis, mesh has "
                f"{mesh.axis_names}")
        from bigdl_tpu.parameters.compression import get_codec
        codec = get_codec(self.wire_codec)
        if codec is not None and self._pad_stage is not None:
            raise ValueError(
                "pad_partial_batches does not compose with an explicit "
                "wire codec: the per-shard loss cannot see the global "
                "valid-row count — use wire_codec=None (implicit sharded "
                "update) or disable padding")
        optim = self.optim_method
        for what in ("learning_rates", "weight_decays"):
            spec = getattr(optim, what, None)
            if spec is not None and jax.tree.structure(spec) != \
                    jax.tree.structure(0):
                raise ValueError(
                    f"shard_weight_update flattens params into wire "
                    f"buckets, so a params-shaped {what} tree cannot be "
                    "matched leafwise — use scalar hyperparameters")
        from bigdl_tpu.optim.sharded_update import ShardedWeightUpdate
        su = ShardedWeightUpdate(mesh, optim, params, wire_codec=codec,
                                 bucket_mb=self.bucket_mb)
        logger.info(
            "sharded weight update: %d buckets over %d-way data axis, "
            "wire codec %s", len(su.buckets), su.n,
            codec.name if codec is not None else "implicit/fp32")
        return su

    def _shard_batch(self, data, labels, sharding,
                     label_sharding=None):
        """Lay a host batch out across the data axis.

        Multi-host: each process passes its local shard and the global
        array is assembled over ICI/DCN
        (``jax.make_array_from_process_local_data`` — the TPU equivalent of
        the reference's locality-zipped RDD partitions,
        ZippedPartitionsWithLocalityRDD.scala:27-118).
        """
        if label_sharding is None:
            # sequence-parallel: labels shard like data when they carry a
            # sequence dim, over 'data' alone when rank-1
            from jax.sharding import NamedSharding, PartitionSpec as P
            label_sharding = (sharding if np.ndim(labels) >= 2
                              else NamedSharding(sharding.mesh, P("data")))
        if jax.process_count() > 1:
            data = jax.make_array_from_process_local_data(sharding, data)
            labels = jax.make_array_from_process_local_data(label_sharding,
                                                            labels)
            return data, labels
        return (jax.device_put(data, sharding),
                jax.device_put(labels, label_sharding))

    def _emit_step(self, e: dict, loss: float) -> None:
        super()._emit_step(e, loss)
        if self._wire_bytes > 0 and not e["compiled"]:
            # device step time >= collective time, so this is a LOWER
            # bound on link bandwidth — the honest in-training readout
            # (the isolated figure comes from parallel/collective_bench);
            # compile iterations are excluded, their wall time is
            # compilation, not the link. Under async dispatch the device
            # time is window-amortized (docs/PERFORMANCE.md), so this
            # stays a per-window average rather than a per-step sample.
            self.metrics.record(
                "allreduce GB/s (wire bytes / device step, lower bound)",
                self._wire_bytes / max(e["device_time"], 1e-9) / 1e9)
        if logger.isEnabledFor(logging.DEBUG):
            logger.debug(self.metrics.summary())

    def _prepare_run(self):
        model, criterion, optim = self.model, self.criterion, \
            self.optim_method
        mesh = self.mesh or get_mesh()
        self._ckpt_mesh = mesh   # recorded in checkpoint manifests
        n_shards = int(np.prod(mesh.devices.shape))
        if self.tensor_parallel or self.shard_optim_state:
            # params/optimizer-state leaves carry mesh shardings on these
            # paths: the concat-grouped small-leaf update miscompiles
            # under GSPMD (values summed over the data axis — see
            # SGD.group_small_leaves); force the per-leaf form
            optim.group_small_leaves = False
        if self.expert_parallel and \
                self.expert_parallel not in mesh.axis_names:
            raise ValueError(
                f"expert_parallel={self.expert_parallel!r} needs that "
                f"mesh axis — build the mesh with Engine.init(axes="
                f"{{'data': N, {self.expert_parallel!r}: E}}) (mesh "
                f"has {mesh.axis_names})")
        if self.expert_parallel and self.wire_codec is not None:
            raise ValueError(
                "expert_parallel does not compose with an explicit "
                "wire codec: the per-shard compressed step cannot nest "
                "the MoE dispatch's own shard_map — use "
                "wire_codec=None")
        params, mstate, opt_state, rng, count_this_epoch, \
            batches_to_skip = self._initial_state()
        pp = self._init_pipeline(mesh)
        su = None if pp is not None \
            else self._init_sharded_update(mesh, params)
        if su is None and isinstance(opt_state, dict) \
                and "ef_residual" in opt_state:
            # resuming a compressed-collective checkpoint into a run
            # without error feedback: the residual is meaningless here
            opt_state = {k: v for k, v in opt_state.items()
                         if k != "ef_residual"}
            logger.info("dropping checkpointed error-feedback residual "
                        "(sharded update with int8 codec not active)")

        repl = replicated(mesh)
        # a pure-pipeline mesh (axes={'pipe': S}) has no data axis: the
        # batch replicates and every stage sees the full microbatches
        batch_shard = (repl if "data" not in mesh.axis_names
                       else data_sharding(mesh))
        label_shard = batch_shard
        sp_axis, sp_size = None, 1
        if self.sequence_parallel:
            from jax.sharding import NamedSharding, PartitionSpec as P
            sp_axis = (self.sequence_parallel
                       if isinstance(self.sequence_parallel, str)
                       else "seq")
            sp_size = int(mesh.shape[sp_axis])
            batch_shard = NamedSharding(mesh, P("data", sp_axis))
            # labels may be rank-1 (sequence classification) — their
            # placement is rank-derived per batch and the jitted step
            # inherits it (in_shardings=None for that arg)
            label_shard = None
        # the batch's dim 0 shards over the axes named in the spec's
        # first entry — a seq/model axis does not constrain batch size
        dim0 = batch_shard.spec[0] if batch_shard.spec else None
        if dim0 is None:
            batch_div = 1
        elif isinstance(dim0, (tuple, list)):
            batch_div = int(np.prod([mesh.shape[a] for a in dim0]))
        else:
            batch_div = int(mesh.shape[dim0])
        param_shard, opt_shard = repl, repl
        tp_tree = None
        if self.tensor_parallel:
            from bigdl_tpu.parallel.tensor_parallel import shard_params
            tp_axis = (self.tensor_parallel
                       if isinstance(self.tensor_parallel, str)
                       else "model")
            param_shard = tp_tree = shard_params(params, mesh, tp_axis)
        if self.shard_optim_state:
            # ZeRO-1 layout: each replica keeps 1/N of momentum/accums
            # (composes with TP — the TP layout wins where present)
            from bigdl_tpu.parallel.tensor_parallel import \
                shard_optim_state_zero1
            opt_shard = shard_optim_state_zero1(
                opt_state, params, mesh, param_shardings=tp_tree)
        elif tp_tree is not None:
            from bigdl_tpu.parallel.tensor_parallel import \
                sharding_for_tree_like
            opt_shard = sharding_for_tree_like(opt_state, params,
                                               tp_tree, repl)
        if pp is not None:
            # pipeline owns the layouts: device-major stacked layer
            # params over 'pipe', optimizer state in the matching
            # stacked (or per-stage bucket-slice) form
            # (parallel/pipeline.py)
            mstate = jax.device_put(mstate, repl)
            params = pp.import_params(params)
            opt_state = pp.import_opt_state(opt_state)
            param_shard = pp.params_sharding()
            opt_shard = pp.opt_state_sharding(opt_state)
        elif su is not None:
            # sharded update owns both layouts: flat bucket slices for
            # optimizer state, and (explicit codecs) master slices for
            # params (optim/sharded_update.py)
            mstate = jax.device_put(mstate, repl)
            opt_state = su.import_opt_state(opt_state, params)
            params = su.import_params(params)
            param_shard = su.params_sharding()
            opt_shard = su.opt_state_sharding(opt_state)
        else:
            # mesh-portable placement (elastic/redistribute.py): the
            # resumed host arrays land on THIS run's mesh whatever mesh
            # they were saved under — 8 devices -> 4 is a resize, not an
            # error (checkpoints hold host-global arrays, so this is
            # placement, never a data transform)
            from bigdl_tpu.elastic.redistribute import redistribute
            src_layout = self.state.get("mesh_layout")
            params = redistribute(params, src_layout, mesh,
                                  shardings=param_shard, what="params")
            mstate = redistribute(mstate, src_layout, mesh,
                                  shardings=repl, what="model state")
            opt_state = redistribute(opt_state, src_layout, mesh,
                                     shardings=opt_shard,
                                     what="optimizer state")

        masked = self._masked_criterion()

        if pp is not None:
            # combined forward/backward schedule in ONE compiled step:
            # remat applies per chunk inside the schedule's backward
            # recompute, the data-axis reduction (or the per-stage
            # bucketed reduce-scatter under shard_weight_update) and
            # the optimizer update fire once per accumulated step
            # (parallel/pipeline.py)
            train_step = pp.make_train_step(
                grad_clip=self.grad_clip,
                input_transform=self.input_transform)
        elif su is not None and su.codec is not None:
            # explicit construction: the whole step runs per-shard under
            # shard_map — local forward/backward (scanned k microbatches
            # at a time under grad accumulation, with the bucketed
            # compressed reduce-scatter + error feedback firing ONCE on
            # the accumulated grads), sharded update on f32 masters,
            # compressed param all-gather
            from bigdl_tpu.optim.remat import remat_forward
            fwd = remat_forward(model, self.remat_policy)

            def local_vag(p, mstate_in, data, labels, key):
                if self.input_transform is not None:
                    data = self.input_transform(data)

                def loss_fn(pp):
                    y, new_mstate = fwd(pp, mstate_in, data,
                                        training=True, rng=key)
                    return criterion.apply(y, labels), new_mstate

                return jax.value_and_grad(loss_fn, has_aux=True)(p)

            explicit_step = su.make_explicit_step(
                local_vag, grad_clip=self.grad_clip,
                num_microbatches=self.grad_accumulation)

            def train_step(params, mstate, opt_state, rng, data, labels,
                           epoch, n_valid=None):
                return explicit_step(params, mstate, opt_state, rng,
                                     data, labels, epoch)
        else:
            # global-view construction: mean over the GLOBAL batch — the
            # gradient allreduce this induces in backward IS the
            # reference's whole parameters/AllReduceParameter machinery;
            # under the implicit sharded update the update math and
            # optimizer state run 1/N per replica (su.apply_update), and
            # with grad accumulation the induced reduction fires once
            # per ACCUMULATED step (k x fewer collective bytes per
            # example)
            train_step = self._global_view_step(
                masked, su.apply_update if su is not None else optim.update)

        # label_shard is None under sequence_parallel (rank-derived at
        # placement, _shard_batch); jit then inherits the arg sharding
        in_shardings = (param_shard, repl, opt_shard, repl, batch_shard,
                        label_shard, None)
        if masked is not None:
            in_shardings += (None,)   # n_valid: replicated scalar
        jit_step = jax.jit(
            train_step,
            donate_argnums=(0, 1, 2),
            in_shardings=in_shardings,
            out_shardings=(param_shard, repl, opt_shard, repl))
        # explicit lower -> compile -> cache pipeline
        # (tuning/aot_cache.py): one executable per batch shape (partial
        # final batches recompile, like jit would), loaded from the
        # persistent AOT cache on a warm restart instead of recompiling;
        # collective accounting reads the first executable's HLO
        from bigdl_tpu.tuning.aot_cache import StepCompiler
        step_pipeline = StepCompiler(
            _UnderMesh(jit_step, mesh), name="distri_train_step",
            cache=self._aot_cache() or False, mesh=mesh,
            donate_argnums=(0, 1, 2), extra=self._step_key_extra())

        # sharded update / pipeline: evaluation/checkpoint see the
        # gathered params tree, so eval shardings are replicated
        eval_param_shard = (repl if su is not None or pp is not None
                            else param_shard)
        if jax.process_count() > 1:
            # multi-host in-training validation: per-process shards can't
            # be device_put onto the global mesh (round-5 review finding:
            # that raised before the cross-host reduce was ever reached).
            # Each process evaluates its own shard on its LOCAL devices
            # with the host-gathered params _validate provides, and
            # Optimizer._validate merges results across hosts.
            from bigdl_tpu.optim.validator import local_sharded_eval
            eval_fn = local_sharded_eval(self._eval_apply)
        else:
            from bigdl_tpu.optim.validator import _padded_eval
            jit_eval = _UnderMesh(
                jax.jit(self._eval_apply,
                        in_shardings=(eval_param_shard, repl, batch_shard),
                        out_shardings=batch_shard), mesh)
            # params stay in their training placement (param_shard may be
            # ZeRO-sharded) — only the batch is padded/placed/trimmed
            eval_fn = _padded_eval(jit_eval, batch_shard, n_shards)

        def place(batch):
            """Host batch -> mesh-sharded device batch, run on the
            prefetch worker (depth >= 1) so placement overlaps the
            in-flight device steps; also the depth-0 inline stage."""
            if isinstance(batch.data, jax.Array):
                # a user-pipeline DevicePrefetcher already placed it
                # (overlapped upstream) — don't round-trip it, but keep
                # the friendly divisibility error for sharding-less
                # prefetchers and user-placed arrays
                if batch.data.shape[0] % batch_div != 0:
                    raise ValueError(
                        f"global batch {batch.data.shape[0]} not "
                        f"divisible by the {batch_div} data-axis shards "
                        "(reference Utils.getBatchSize divisibility "
                        "requirement, dataset/Utils.scala:25-47)")
                return batch
            data = np.asarray(batch.data)
            labels = np.asarray(batch.labels)
            global_n = data.shape[0] * jax.process_count()
            if global_n % batch_div != 0:
                raise ValueError(
                    f"global batch {global_n} not divisible by the "
                    f"{batch_div} data-axis shards (reference "
                    "Utils.getBatchSize divisibility requirement, "
                    "dataset/Utils.scala:25-47)")
            if sp_size > 1 and data.shape[1] % sp_size != 0:
                raise ValueError(
                    f"sequence length {data.shape[1]} not divisible "
                    f"by the {sp_size}-way '{sp_axis}' mesh axis "
                    "(sequence_parallel shards batch dim 1)")
            data, labels = self._shard_batch(data, labels, batch_shard,
                                             label_shard)
            from bigdl_tpu.dataset.sample import MiniBatch
            return MiniBatch(data, labels, valid=batch.valid)

        # sharded update / pipeline: validation, a checkpoint and the
        # exit see the gathered f32 masters, and a checkpoint the
        # bucketed optimizer state re-shaped to the params-shaped
        # (ZeRO-1-compatible) layout
        owner = su if su is not None else pp

        def export(params, opt_state, with_opt):
            if owner is None:
                return params, opt_state
            return (owner.gather_params(params),
                    owner.export_opt_state(opt_state) if with_opt
                    else opt_state)

        return _TrainRun(
            params, mstate, opt_state, rng, count_this_epoch,
            batches_to_skip, step_compiler=step_pipeline, place=place,
            eval_fn=eval_fn,
            records_scale=jax.process_count(),
            on_first_compile=lambda compiled: self._account_collectives(
                compiled, n_shards),
            export=export)

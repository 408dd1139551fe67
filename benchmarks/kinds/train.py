"""Driver kind ``train``: a training job through the program's normal
path — ``Optimizer(...)`` -> ``DistriOptimizer`` on a ``data`` mesh, as
``chip_smoke._train`` builds it — fed fresh batches from a host iterator
and ended by a wall-clock trigger of the benchmark's own.

Traffic keys: ``batch_per_chip``, ``seq_len``, ``optimizer`` (name and
keyword arguments of a ``bigdl_tpu.optim`` method), ``warmup_steps``
(executed before the window opens; set-up), ``trace_after_steps`` and
``trace_steps`` (the traced run profiles that many whole steps).
"""
from __future__ import annotations

import gc
import math

from benchmarks import loadgen, model_setup, tracing
from benchmarks.manifest import plugin
from benchmarks.recorder import StepRecorder, clock

#: why these tolerances, and what each must catch — see check() below
TOL_LOSS_REL = 2e-5
TOL_GRAD_REL = 0.15
GRAD_FLOOR = 1e-2


class _Window:
    """Opens when the warm-up steps' losses have arrived, closes the
    trigger ``seconds`` later; in a traced run starts and stops the
    profiler at loss arrivals, which are drain points: every dispatched
    step has finished and nothing is in flight."""

    def __init__(self, seconds, warmup_steps, trace, trace_after,
                 trace_steps, compiles, group):
        def up(k):                 # losses arrive `group` to a drain
            return -(-int(k) // group) * group
        self.seconds = seconds
        self.group = group
        self.warmup = up(warmup_steps)
        self.trace = trace
        self.trace_from = self.warmup + up(trace_after)
        self.trace_to = self.trace_from + up(trace_steps)
        self.compiles = compiles
        self.t0 = None
        self.seen = 0
        self.traced_steps = 0

    def on_loss(self, row) -> None:
        self.seen += 1
        if self.seen == self.warmup:
            self.t0 = row["t"]
            self.compiles.open()
        if self.trace is not None:
            if self.seen == self.trace_from:
                self.trace.start()
            elif self.trace.active and self.seen >= self.trace_to \
                    and self.seen % self.group == 0:
                self.traced_steps = self.seen - self.trace_from
                self.trace.stop()

    def done(self, _state) -> bool:
        if self.t0 is None or clock() < self.t0 + self.seconds:
            return False
        # a traced run ends only after its trace has been taken
        return self.trace is None or self.trace.done


def params_dtype_ok(params, want: str) -> bool:
    """Every parameter leaf is held in the dtype the configuration's
    policy states: float32 silently served as bfloat16 moves a
    random-init loss by less than any loss tolerance could see, so the
    dtypes are compared directly."""
    import jax
    import jax.numpy as jnp
    return all(leaf.dtype == jnp.dtype(want)
               for leaf in jax.tree.leaves(params))


def run(ctx) -> dict:
    from bigdl_tpu import optim as optim_mod
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import Optimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel.engine import Engine

    cfg, traffic = ctx.config, ctx.traffic
    builder = plugin("builders", cfg["builder"])
    n = len(ctx.devices)
    batch = int(traffic["batch_per_chip"]) * n
    seq = int(traffic["seq_len"])
    model_setup.set_dtype_policy(cfg["policy"])

    mesh = Engine.init(axes={"data": n}, devices=ctx.devices)
    model = builder.build(cfg)
    model_setup.materialize_lean(model, ctx.seed, ctx.devices[0])
    ctx.log(f"train: {n} chip(s), global batch {batch} x {seq}, "
            f"parameters {model_setup.tree_bytes(model.params) / 1e9:.3f}"
            f" GB, live on device "
            f"{model_setup.live_device_bytes() / 1e9:.3f} GB")

    def batches():
        for data, labels in loadgen.train_batches(
                cfg["vocab_size"], batch, seq, ctx.seed):
            yield MiniBatch(data, labels)

    dataset = DataSet.iterator(batches, size=1 << 48)
    opt = Optimizer(model, dataset, builder.criterion(), mesh=mesh)
    spec = dict(traffic["optimizer"])
    opt.set_optim_method(getattr(optim_mod, spec.pop("name"))(**spec))

    trace = tracing.TraceSession(ctx.name) if ctx.trace else None
    window = _Window(ctx.seconds, int(traffic["warmup_steps"]), trace,
                     int(traffic.get("trace_after_steps", 4)),
                     int(traffic.get("trace_steps", 6)), ctx.compiles,
                     group=int(opt.max_in_flight))
    recorder = StepRecorder(on_loss=window.on_loss)
    opt.set_train_summary(recorder)
    opt.set_end_when(Trigger(window.done, "benchmark window closed"))
    opt.optimize()
    ctx.compiles.close()

    rows = [r for r in recorder.rows if "t" in r]
    in_window = [r for r in rows if r["t"] > window.t0]
    peak = model_setup.memory_peak_bytes(ctx.devices)
    ctx.log(f"train: memory_stats {ctx.devices[0].memory_stats()}")
    # free the job before the reference takes its place on the chip
    first_loss = rows[0]["loss"]
    dtype_ok = params_dtype_ok(model.params, cfg["policy"]["param_dtype"])
    del opt, dataset
    model_setup.unbind(model)
    gc.collect()

    record = {
        "kind": "train", "chips": n, "global_batch": batch, "seq": seq,
        "window": {"t0": window.t0,
                   "t1": in_window[-1]["t"] if in_window else window.t0},
        "steps": in_window, "warmup_steps": window.warmup,
        "attempted": len(in_window),
        "failed": sum(not math.isfinite(r["loss"]) for r in in_window),
        "memory_peak_bytes": peak,
        "trace_events": trace.events(ctx.keep_trace) if trace else None,
        "traced_steps": window.traced_steps,
    }
    record["checks"] = check(ctx, builder, model, first_loss, rows, batch,
                             seq)
    record["checks"]["param_dtype_ok"] = dtype_ok
    record["checks"]["ok"] = bool(record["checks"]["ok"] and dtype_ok)
    return record


def check(ctx, builder, model, first_loss, rows, batch, seq):
    """``correct`` for a training run, outside the window.

    1. every loss of the run is finite;
    2. the system's FIRST-step loss (from the program's own summary)
       equals the plain float32 reference's loss on the same batch and
       the same initial weights, within TOL_LOSS_REL = 2e-5. The system
       computes matmuls and activations in bf16 (the configuration's
       policy), but the rounding is zero-mean and the loss averages
       8192 tokens: measured on the chip the two differ by 1.1e-7 to 2.2e-6
       relative over 9 seeds (PERF.md section 6). A random-init loss sits near
       ln(vocab) whatever the network does, so a dropped layer or wrong
       positions move it by only a few 1e-4 — hence a tolerance this
       tight, and the gradient comparison below, which is the sharp one.
    (and, in run(): the trained parameters are still held in the
    policy's parameter dtype — params_dtype_ok.)
    3. the gradients of the system's own model.apply + criterion on a
       ONE-sequence sample agree with the reference's, leaf by leaf, in
       relative L2 norm within TOL_GRAD_REL = 0.15: bf16 activations
       and matmuls give 0.067-0.075 on the worst leaf (an FFN weight of the
       last layers; measured on the chip, 9 seeds, PERF.md section 6);
       a dropped layer, a wrong mask or a wrong backward of any layer
       gives a different gradient altogether, of order 1.
    """
    import jax
    import jax.numpy as jnp
    cfg = ctx.config
    ref = plugin("reference", cfg["reference"])
    heads = cfg["num_attention_heads"]
    out = {"finite": all(math.isfinite(r["loss"]) for r in rows)}
    dev = ctx.devices[0]
    params = model_setup.init_params(model, ctx.seed, dev)
    w = builder.reference_weights(params, cfg)
    data, labels = next(loadgen.train_batches(cfg["vocab_size"], batch,
                                              seq, ctx.seed))
    with jax.default_device(dev):
        ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
        ref_loss = ref.loss(w, ids, tgt, heads)
        out["first_loss"] = first_loss
        out["reference_loss"] = ref_loss
        out["loss_rel_err"] = abs(first_loss - ref_loss) / abs(ref_loss)
        out["loss_ok"] = out["loss_rel_err"] <= TOL_LOSS_REL

        crit = builder.criterion()
        state = model.init_state()

        def sys_loss(p, x, y):
            logits, _ = model.apply(p, state, x, training=True)
            return crit.apply(logits, y)

        d1 = jnp.asarray(data[:1])
        l1 = jnp.asarray(labels[:1])
        g_sys = jax.jit(jax.grad(sys_loss))(params, d1, l1)
        g_sys = builder.reference_weights(g_sys, cfg)
        _, g_ref = ref.loss_and_grads(w, ids[:1], tgt[:1], heads)
        flat_s = jax.tree_util.tree_flatten_with_path(g_sys)[0]
        flat_r = jax.tree.leaves(g_ref)
        norms = [float(jnp.linalg.norm(gr)) for gr in flat_r]
        # a leaf whose true gradient is zero (the key bias: softmax is
        # invariant to it) has only rounding noise on both sides; judge
        # every leaf against at least GRAD_FLOOR of the largest leaf norm
        floor = GRAD_FLOOR * max(norms)
        errs = []
        for (path, gs), gr, norm in zip(flat_s, flat_r, norms):
            diff = float(jnp.linalg.norm(gs.astype(jnp.float32) - gr))
            err = diff / max(norm, floor)
            errs.append((err if math.isfinite(err) else math.inf,
                         jax.tree_util.keystr(path)))
        errs.sort(reverse=True)
        worst, where = errs[0]
        out["grad_worst_leaves"] = errs[:3]
        out["grad_rel_err"] = worst
        out["grad_ok"] = math.isfinite(worst) and worst <= TOL_GRAD_REL
    out["ok"] = bool(out["finite"] and out["loss_ok"] and out["grad_ok"])
    return out

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that bigdl_tpu still starts on the chip.

Drives the two normal paths once, at the full width of the repo's flagship
LM and of Inception-v1, with random weights made from a seed:

  train-lm         TransformerLM d1024/12L through Optimizer -> DistriOptimizer
  serve-lm         the same width behind Router(ReplicaPool(ContinuousBatcher))
  train-inception  Inception-v1 (the LRN kernel) through the same Optimizer
  kernels          every live Pallas kernel, compiled, against its plain
                   jax.numpy reference on the same inputs

ONE process: it never sets the platform, it asserts it. No accelerator is an
immediate non-zero exit; any phase that raises, fails a check or overruns its
deadline is a non-zero exit and no result line. On success the LAST line of
standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

With more than one chip visible the training phases shard the batch over all
of them and additionally check that every chip holds only its shard, that the
Pallas kernels in the optimized per-device program take the PER-CHIP batch
with no all-gather anywhere in the step, and that the first losses agree with
a one-chip run of the same global batch (accumulated in microbatches).

``--rehearsal`` runs the same script at tiny widths on whatever backend is
there (the CPU; Pallas kernels in interpret mode) to rehearse the control
flow before spending chip time. It checks no program text, prints that it is
a rehearsal on its last line, and is never a pass.

Run it on the chip through the chip tool: ``chiprun -- python3 chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import faulthandler
import gc
import json
import re
import sys
import time

# whole-run budget under the 1200 s contract, compilation included
RUN_DEADLINE_S = 1140.0

CHIP = dict(
    vocab=32768, d_model=1024, heads=8, layers=12, seq=2048, lm_batch=4,
    lm_lr=0.05, steps=5, parity_steps=3,
    # Inception-v1 without aux heads starts on a plateau at ln(1000):
    # plain SGD needs this rate for five steps to move the loss past the
    # ~1e-3 step-to-step noise of its dropout (1.0 diverges at step 6)
    img_batch=256, img_size=224, classes=1000, img_lr=0.5,
    prompt_lens=(30, 28, 100, 120, 400, 500, 700), new_tokens=32,
    max_batch=8, page_size=16, num_pages=512, serve_deadline_s=420.0,
    # (B, S, H, D, causal): the looped causal schedule at both head
    # widths (D=64 is the benchmark's), a length no menu tile divides, a
    # head too long for one backward pass (dq and dk/dv kernels over the
    # grid, index maps clamped at the diagonal), and no diagonal at all
    flash=((2, 2048, 4, 128, True), (2, 2048, 8, 64, True),
           (2, 320, 4, 128, True), (1, 16384, 1, 128, True),
           (2, 2048, 4, 128, False)),
    lrn=(256, 64, 56, 56),
    paged=dict(batch=4, heads=8, head_dim=128, pages_per_seq=16,
               kv_heads=(1, 8), t=(1, 64), page_sizes=(16, 128)),
    ce=(8192, 1024, 32768),
)

REHEARSAL = dict(
    vocab=128, d_model=64, heads=2, layers=1, seq=32, lm_batch=2,
    lm_lr=0.05, steps=3, parity_steps=2,
    img_batch=4, img_size=32, classes=10, img_lr=0.01,
    prompt_lens=(5, 6, 12), new_tokens=4,
    max_batch=2, page_size=4, num_pages=64, serve_deadline_s=240.0,
    flash=((1, 128, 2, 64, True), (1, 320, 1, 64, True),
           (1, 256, 1, 64, False)),
    lrn=(64, 16, 4, 4),
    paged=dict(batch=2, heads=4, head_dim=32, pages_per_seq=4,
               kv_heads=(1, 4), t=(1, 8), page_sizes=(8,)),
    ce=(128, 128, 256),
)

# bf16 operands, f32 accumulation: a compiled kernel and its XLA reference
# round differently; errors are judged against the reference's largest value
TOL_BF16 = 3e-2
# parity of a multi-chip loss series with the one-chip run of the same
# global batch: same math, other reduction order and (Inception) other
# dropout draws per microbatch. Measured on the four-chip host (PR 21):
# 8e-7 and 1.2e-4.
TOL_PARITY_LM = 1e-3
TOL_PARITY_CONVNET = 1e-2

_MOSAIC = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# reading the optimized program text
# ---------------------------------------------------------------------------

def mosaic_calls(text: str, kernel: str) -> list:
    """Lines of an optimized per-device program text (``compiled.as_text()``)
    that are the Mosaic custom call of the Pallas kernel named ``kernel``:
    its ``pallas_call`` name sits in the line's ``op_name``, bare or under
    jvp(...)/transpose(...)."""
    named = re.compile(rf'op_name="[^"]*\b{re.escape(kernel)}\b[^"]*"')
    return [ln for ln in text.splitlines()
            if _MOSAIC in ln and named.search(ln)]


def first_operand_dims(line: str) -> tuple:
    """Dims of the first operand of a custom-call line, from its
    ``operand_layout_constraints={bf16[32,2048,128]{...}, ...}``."""
    m = re.search(r"operand_layout_constraints=\{\w+\[([\d,]*)\]", line)
    check(m is not None, f"cannot read operand shapes from: {line[:200]}")
    return tuple(int(d) for d in m.group(1).split(",") if d)


def require_kernels(text: str, kernels, what: str) -> dict:
    found = {k: mosaic_calls(text, k) for k in kernels}
    missing = [k for k, lines in found.items() if not lines]
    check(not missing,
          f"{what}: no Mosaic custom call for {missing} in the compiled "
          "program — the Pallas kernel is not on this path")
    return found


# ---------------------------------------------------------------------------
# training phases
# ---------------------------------------------------------------------------

class _Losses:
    """In-memory stand-in for a TrainSummary: keeps the Loss series."""

    def __init__(self):
        self.values: list = []

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.values.append(float(value))
        return self


def _train(build_model, criterion, lr, data, labels, steps, devices,
           **opt_kw):
    """``steps`` iterations of one repeated seeded batch through the normal
    path, built the way models/transformer/train.py builds it. Returns
    (optimizer, model, loss series)."""
    import jax

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import SGD, Optimizer, max_iteration
    from bigdl_tpu.parallel.engine import Engine

    mesh = Engine.init(axes={"data": len(devices)}, devices=devices)
    model = build_model()
    model.materialize(jax.random.PRNGKey(0))
    batch = MiniBatch(data, labels)
    dataset = DataSet.iterator(lambda: iter([batch]),
                               size=data.shape[0] * steps)
    opt = Optimizer(model, dataset, criterion, mesh=mesh, **opt_kw)
    opt.set_optim_method(SGD(learning_rate=lr))
    opt.set_end_when(max_iteration(steps))
    losses = _Losses()
    opt.set_train_summary(losses)
    opt.optimize()
    return opt, model, losses.values


def _check_losses(what: str, losses, steps: int) -> None:
    import math
    check(len(losses) == steps,
          f"{what}: {len(losses)} losses recorded for {steps} steps")
    check(all(math.isfinite(v) for v in losses),
          f"{what}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{what}: loss did not fall over {steps} steps: {losses}")


def _check_shards(what: str, compiled, model, shapes, per_chip: int,
                  n: int) -> None:
    """Every chip holds batch/n rows of the batch and a full replica of
    the parameters — nothing gathered onto one chip."""
    import jax
    arg_shardings = compiled.input_shardings[0]
    # step arguments: params, model state, optimizer state, rng, data,
    # labels, epoch
    for idx, name, shape in ((4, "data", shapes[0]),
                             (5, "labels", shapes[1])):
        sh = arg_shardings[idx]
        rows = sh.shard_shape(shape)[0]
        check(len(sh.device_set) == n and rows == per_chip,
              f"{what}: {name} is laid out as {rows} rows on "
              f"{len(sh.device_set)} chips, wanted {per_chip} on {n}")
    for leaf in jax.tree.leaves(model.params):
        check(len(leaf.sharding.device_set) == n
              and leaf.sharding.is_fully_replicated,
              f"{what}: a parameter leaf is not replicated over the "
              f"{n} chips: {leaf.sharding}")


def _check_parity(what: str, losses, ref, tol: float) -> str:
    worst = max(abs(a - b) / max(abs(b), 1e-6)
                for a, b in zip(losses, ref))
    check(worst <= tol,
          f"{what}: losses on all chips {losses[:len(ref)]} differ from "
          f"the one-chip run {ref} by {worst:.3g} > {tol}")
    return f"one-chip parity {worst:.2g} (tol {tol})"


def _train_phase(what, cfg, rehearsal, *, build_model, criterion, lr,
                 data, labels, per_chip, kernels, kernel_batch, tol):
    """Shared body of train-lm and train-inception. ``kernels`` maps each
    Pallas kernel name to the index of the operand dim that carries its
    batch; ``kernel_batch`` is that dim's per-chip value."""
    import jax
    devices = jax.devices()
    if rehearsal:
        devices = devices[:2]      # enough to rehearse the multi-chip path
    n = len(devices)
    steps = cfg["steps"]
    x, y = data(n), labels(n)      # one seeded global batch, repeated
    opt, model, losses = _train(build_model, criterion, lr, x, y, steps,
                                devices)
    _check_losses(what, losses, steps)
    notes = [f"{n} chip(s)",
             "loss " + " ".join(f"{v:.4f}" for v in losses)]
    (compiled,) = opt.step_compiler.executables().values()
    text = compiled.as_text()
    if not rehearsal:
        found = require_kernels(text, kernels, what)
        for name, dim in kernels.items():
            for line in found[name]:
                got = first_operand_dims(line)[dim]
                check(got == kernel_batch,
                      f"{what}: {name} runs on a batch dim of {got}, not "
                      f"the per-chip {kernel_batch} — the kernel was not "
                      f"split over the {n} chips")
        notes.append("Mosaic calls " + ", ".join(
            f"{k} x{len(v)}" for k, v in found.items()))
    if n > 1:
        _check_shards(what, compiled, model, (x.shape, y.shape),
                      per_chip, n)
        check(not re.search(r"\ball-gather(-start)?\(", text),
              f"{what}: the data-parallel step contains an all-gather")
        notes.append(f"batch split {per_chip}/chip, no all-gather")
        del opt, model, compiled, text
        gc.collect()
        # the same global batch on ONE chip, accumulated in n microbatches
        _, _, ref = _train(build_model, criterion, lr, x, y,
                           cfg["parity_steps"], devices[:1],
                           grad_accumulation=n)
        notes.append(_check_parity(what, losses, ref, tol))
    return "; ".join(notes)


def train_lm(cfg, rehearsal):
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.models import TransformerLM

    def build():
        return TransformerLM(cfg["vocab"], d_model=cfg["d_model"],
                             num_heads=cfg["heads"],
                             num_layers=cfg["layers"], max_len=cfg["seq"],
                             with_log_softmax=False)

    def tokens(seed):
        def make(n):
            rs = np.random.default_rng(seed)
            return rs.integers(1, cfg["vocab"] + 1,
                               size=(cfg["lm_batch"] * n, cfg["seq"])
                               ).astype(np.int32)
        return make

    return _train_phase(
        "train-lm", cfg, rehearsal, build_model=build,
        criterion=nn.CrossEntropyCriterion(), lr=cfg["lm_lr"],
        data=tokens(0), labels=tokens(1), per_chip=cfg["lm_batch"],
        # flash folds (B, S, H, D) to (B*H, S, D); a head of 2048 x 128
        # fits VMEM, so the backward is the one-pass kernel
        kernels={"flash_attention_fwd": 0, "flash_attention_dqdkdv": 0},
        kernel_batch=cfg["lm_batch"] * cfg["heads"], tol=TOL_PARITY_LM)


def train_inception(cfg, rehearsal):
    import numpy as np

    from bigdl_tpu import models, nn

    def build():
        if not rehearsal:
            return models.Inception_v1_NoAuxClassifier(cfg["classes"])
        # rehearsal: the real conv1..pool2 stem (both LRN layers) under a
        # pooled linear head — Inception's widths do not shrink
        from bigdl_tpu.models.inception.model import _v1_stem
        side = cfg["img_size"] // 8
        return (_v1_stem()
                .add(nn.SpatialAveragePooling(side, side, 1, 1))
                .add(nn.View(192))
                .add(nn.Linear(192, cfg["classes"]))
                .add(nn.LogSoftMax()))

    def images(n):
        rs = np.random.default_rng(2)
        return rs.standard_normal(
            (cfg["img_batch"] * n, 3, cfg["img_size"], cfg["img_size"])
        ).astype(np.float32)

    def labels(n):
        rs = np.random.default_rng(3)
        return rs.integers(1, cfg["classes"] + 1,
                           size=(cfg["img_batch"] * n,)).astype(np.int32)

    return _train_phase(
        "train-inception", cfg, rehearsal, build_model=build,
        criterion=nn.ClassNLLCriterion(), lr=cfg["img_lr"], data=images,
        labels=labels, per_chip=cfg["img_batch"],
        # the LRN kernel sees a (H*W, C, N) view: batch is the LAST dim
        kernels={"lrn_fwd": -1, "lrn_bwd": -1},
        kernel_batch=cfg["img_batch"], tol=TOL_PARITY_CONVNET)


# ---------------------------------------------------------------------------
# serving phase
# ---------------------------------------------------------------------------

def _serve(model, prompts, cfg, **pool_kw):
    """The prompts through Router(ReplicaPool(model, 1, ...)). Returns
    ({request id: tokens}, the pool's step compilers)."""
    from bigdl_tpu.models.transformer.serving import PagedStepCompilers
    from bigdl_tpu.observability.exporter import HealthRegistry
    from bigdl_tpu.observability.registry import MetricRegistry
    from bigdl_tpu.serving import ReplicaPool, Router, SLOConfig

    compilers = PagedStepCompilers()
    health = HealthRegistry()
    pool = ReplicaPool(model, 1, max_batch=cfg["max_batch"],
                       page_size=cfg["page_size"],
                       num_pages=cfg["num_pages"],
                       max_new_tokens=cfg["new_tokens"],
                       aot_cache=compilers, health=health, **pool_kw)
    # the first request of each shape pays a compile: latency targets that
    # would make the router shed load have no place in a smoke
    slo = SLOConfig(ttft_p99_s=cfg["serve_deadline_s"],
                    decode_token_p99_s=cfg["serve_deadline_s"],
                    max_queue_depth=len(prompts))
    router = Router(pool, slo=slo, registry=MetricRegistry(),
                    health=health)
    try:
        for i, prompt in enumerate(prompts):
            router.submit(i, prompt)
        # a failed step raises here with the replica's own exception
        router.wait_all(timeout=cfg["serve_deadline_s"])
        results = dict(router.finished())
    finally:
        router.close()
        pool.close()
    for rep in pool:
        check(rep.step_error is None,
              f"replica {rep.name} recorded a step exception: "
              f"{rep.step_error!r}")
    return results, compilers


def serve_lm(cfg, rehearsal):
    import jax
    import numpy as np

    from bigdl_tpu.models import TransformerLM

    model = TransformerLM(cfg["vocab"], d_model=cfg["d_model"],
                          num_heads=cfg["heads"], num_layers=cfg["layers"],
                          max_len=cfg["seq"], with_log_softmax=False)
    model.materialize(jax.random.PRNGKey(1))
    model.evaluate()
    rs = np.random.default_rng(4)
    prompts = [[int(t) for t in rs.integers(1, cfg["vocab"] + 1, size=(n,))]
               for n in cfg["prompt_lens"]]

    # on the chip the switch is left alone and must resolve to the compiled
    # kernel; the rehearsal has only the interpreter
    results, compilers = _serve(
        model, prompts, cfg,
        **({"paged_kernel": "interpret"} if rehearsal else {}))
    check(sorted(results) == list(range(len(prompts))),
          f"serve-lm: finished {sorted(results)} of {len(prompts)} requests")
    for i, toks in results.items():
        check(len(toks) == cfg["new_tokens"]
              and all(1 <= t <= cfg["vocab"] for t in toks),
              f"serve-lm: request {i} returned {len(toks)} tokens, or "
              f"tokens out of the vocabulary: {toks}")
    steps = compilers.executables()
    names = {name for name, _, _, _ in steps}
    check({"serving_prefill_step", "serving_decode_step"} <= names,
          f"serve-lm: compiled steps are {sorted(names)}")
    want = "interpret" if rehearsal else "pallas"
    for name, statics, quick, compiled in steps:
        check(statics["paged_kernel"] == want,
              f"serve-lm: {name} {quick} resolved paged_kernel="
              f"{statics['paged_kernel']!r}, wanted {want!r}")
        if not rehearsal:
            require_kernels(compiled.as_text(), ["paged_attention"],
                            f"serve-lm {name} {quick}")

    dense, _ = _serve(model, prompts, cfg, paged_kernel="dense")
    same = sum(a == b for i in results
               for a, b in zip(results[i], dense[i]))
    total = len(prompts) * cfg["new_tokens"]
    return (f"{len(prompts)} requests x {cfg['new_tokens']} tokens, "
            f"kernel {want!r} in {len(steps)} compiled steps, "
            f"token agreement with the dense view {same}/{total} "
            f"= {same / total:.3f}")


# ---------------------------------------------------------------------------
# kernels phase
# ---------------------------------------------------------------------------

def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape,
          f"kernel output {got.shape} vs reference {want.shape}")
    check(bool(np.isfinite(got).all()), "kernel output is not finite")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-6))


def _compare(what: str, got, want, tol: float) -> float:
    import jax
    errs = [_rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                           jax.tree.leaves(want))]
    worst = max(errs)
    check(worst <= tol,
          f"kernels: {what} differs from its reference by {worst:.3g} "
          f"(tolerance {tol}; per output {errs})")
    return worst


def kernels(cfg, rehearsal):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.transformer.serving import (_attend_grouped,
                                                      _paged_view)
    from bigdl_tpu.nn.normalization import _lrn
    from bigdl_tpu.ops.pallas import lrn as plrn
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention
    from bigdl_tpu.ops.pallas.fused_ce import linear_cross_entropy
    from bigdl_tpu.ops.pallas.paged_attention import paged_attention
    from bigdl_tpu.parallel.sequence import dot_product_attention

    interp = bool(rehearsal)
    dtype = jnp.bfloat16
    rs = np.random.default_rng(5)
    report = []

    def rand(shape, scale=1.0):
        return jnp.asarray(scale * rs.standard_normal(shape), dtype)

    def value_and_grads(fn, ct, *args):
        """(output, grads of <output, ct>) under one jit."""
        def scalar(*a):
            return jnp.vdot(fn(*a).astype(jnp.float32),
                            ct.astype(jnp.float32))
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            scalar, argnums=tuple(range(len(a))))(*a)))(*args)

    # flash attention, fwd + bwd (320 has no menu tile and takes a
    # generated divisor: one 320-row block)
    for b, s, h, d, causal in cfg["flash"]:
        q, k, v, ct = (rand((b, s, h, d), 0.5) for _ in range(4))
        got = value_and_grads(
            lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            interpret=interp), ct, q, k, v)
        want = value_and_grads(
            lambda q, k, v: dot_product_attention(q, k, v, causal=causal,
                                                  flash=False),
            ct, q, k, v)
        what = f"S{s} D{d}" + ("" if causal else " full")
        err = _compare(f"flash_attention {what}", got, want, TOL_BF16)
        report.append(f"flash {what} {err:.1e}")

    # LRN, fwd + bwd
    x, ct = rand(cfg["lrn"]), rand(cfg["lrn"])
    got = value_and_grads(
        lambda x: plrn.lrn(x, 5, 1e-4, 0.75, 1.0, interp), ct, x)
    want = value_and_grads(lambda x: _lrn(x, 5, 1e-4, 0.75, 1.0), ct, x)
    report.append(f"lrn {_compare('lrn', got, want, TOL_BF16):.1e}")

    # paged attention straight off the page pool vs the dense view
    pg = cfg["paged"]
    b, h, d, p = pg["batch"], pg["heads"], pg["head_dim"], \
        pg["pages_per_seq"]
    for kv in pg["kv_heads"]:
        for s in pg["page_sizes"]:
            kp, vp = (rand((b * p + 1, s, kv, d), 0.5) for _ in range(2))
            table = jnp.asarray(
                rs.permutation(b * p).reshape(b, p), jnp.int32)
            for t in pg["t"]:
                q = rand((b, t, h, d), 0.5)
                q_start = jnp.asarray(
                    rs.integers(0, p * s - t + 1, size=(b,)), jnp.int32)
                got = jax.jit(lambda *a: paged_attention(
                    *a, interpret=interp))(q, kp, vp, table, q_start)

                def dense(q, kp, vp, table, q_start):
                    cols = q_start[:, None] + jnp.arange(q.shape[1])[None]
                    return _attend_grouped(
                        q, _paged_view(kp, table), _paged_view(vp, table),
                        cols, q.shape[2], q.shape[-1] ** -0.5)
                want = jax.jit(dense)(q, kp, vp, table, q_start)
                err = _compare(f"paged_attention KV={kv} S={s} T={t}",
                               got, want, TOL_BF16)
                report.append(f"paged kv{kv} s{s} t{t} {err:.1e}")

    # fused LM-head cross-entropy, fwd + bwd (off the normal path, run
    # standalone)
    n, d_model, vocab = cfg["ce"]
    hid = rand((n, d_model), 0.5)
    w = rand((vocab, d_model), d_model ** -0.5)
    bias = rand((vocab,), 0.1)
    tgt = jnp.asarray(rs.integers(1, vocab + 1, size=(n,)), jnp.int32)

    def ce(use_kernel):
        def fn(hid, w, bias):
            return linear_cross_entropy(hid, w, bias, tgt,
                                        use_kernel=use_kernel,
                                        interpret=interp)
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2)))(
            hid, w, bias)
    err = _compare("fused_ce", ce(True), ce(False), TOL_BF16)
    report.append(f"fused_ce {err:.1e}")
    return (f"max error vs reference / tolerance {TOL_BF16}: "
            + ", ".join(report))


PHASES = (("train-lm", train_lm), ("serve-lm", serve_lm),
          ("train-inception", train_inception), ("kernels", kernels))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearsal", action="store_true",
                        help="tiny widths on any backend, kernels "
                             "interpreted; never a pass")
    args = parser.parse_args(argv)
    t_start = time.monotonic()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: platform={device['platform']} "
          f"kind={device['kind']!r} count={device['count']}", flush=True)
    if device["platform"] != "tpu" and not args.rehearsal:
        print("chip_smoke: jax found no TPU — this script proves the "
              "program on the chip and does not fall back "
              "(--rehearsal rehearses it elsewhere)", file=sys.stderr)
        return 2
    cfg = REHEARSAL if args.rehearsal else CHIP

    import jax.numpy as jnp

    from bigdl_tpu.tensor import DTypePolicy, set_policy
    from bigdl_tpu.utils import compile_cache

    # the bench dtype policy: f32 params, bf16 compute and activations
    set_policy(DTypePolicy(param_dtype=jnp.float32,
                           compute_dtype=jnp.bfloat16,
                           activation_dtype=jnp.bfloat16))
    print(f"compile cache: {compile_cache.cache_dir()}", flush=True)
    cache = compile_cache.CacheCounter()

    for name, phase in PHASES:
        left = RUN_DEADLINE_S - (time.monotonic() - t_start)
        check(left > 0, f"{name}: the run's {RUN_DEADLINE_S:.0f}s budget "
                        "was spent before the phase began")
        # a phase that hangs dumps every thread's stack and exits 1
        faulthandler.dump_traceback_later(left, exit=True,
                                          file=sys.__stderr__)
        t0 = time.monotonic()
        detail = phase(cfg, args.rehearsal)
        faulthandler.cancel_dump_traceback_later()
        gc.collect()
        c = cache.delta()
        print(f"PASS {name} [{time.monotonic() - t0:.1f}s; programs "
              f"compiled {c['compiled']}, read back from the cache "
              f"{c['read_back']}] {detail}", flush=True)

    if args.rehearsal:
        print("REHEARSAL ONLY: tiny widths, kernels interpreted, no chip "
              "— not a result")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

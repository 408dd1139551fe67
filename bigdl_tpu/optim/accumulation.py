"""On-device gradient accumulation: one compiled step, k microbatches
(ISSUE 10 tentpole).

The per-chip batch is capped by activation memory; gradient accumulation
runs an effectively k-times-larger batch at near-constant peak HBM by
``lax.scan``-ning k microbatches through forward/backward with the
gradient accumulated in the scan carry (donated buffers — XLA updates
the accumulator in place), then running the existing optimizer update
EXACTLY ONCE. Collectives amortize the same way: the gradient
reduce-scatter / all-reduce fires once per ACCUMULATED step, so wire
bytes per example drop by k (pinned statically in
tests/test_accumulation.py against the compiled HLO).

Microbatch layout is STRIDED — microbatch ``j`` takes global rows
``j, j+k, j+2k, ...`` via a free ``(B,) -> (B/k, k) -> (k, B/k)``
reshape/transpose. On a data-sharded mesh each device's contiguous
block splits locally (every microbatch holds ``local_rows/k`` rows from
EVERY device), so the scan never moves batch rows across chips. Which
rows form a microbatch is semantically irrelevant: the accumulated
gradient, the loss average, and the masked numerator/denominator are
sums over all rows regardless of grouping.

Semantics vs the single k×-batch step:

- **loss / gradients** — exact mean semantics are preserved (per-row
  cotangent scale, masked valid-count normalization: numerator and
  denominator accumulate separately and divide once). Results are
  bit-identical whenever the float additions involved are exact, and
  within partial-sum rounding (~1 ulp per reduction) otherwise —
  splitting a reduction into k partial sums is a re-association, which
  f32 addition does not commute with (docs/PERFORMANCE.md pins both:
  bitwise on an exactly-representable workload, tight tolerance on
  real models).
- **RNG** — each microbatch draws from ``fold_in(step_rng, j)``
  (deterministic, replayable); a dropout model's mask SEQUENCE therefore
  differs from the k×-batch step's single draw, by design.
- **batch statistics** — BN-style state is computed per microbatch and
  averaged across the k microbatches (inexact leaves; integer counters
  pass through), mirroring the per-shard ``pmean`` of the explicit
  sharded step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["split_microbatches", "microbatch_valid_mask",
           "validate_microbatches", "accumulated_value_and_grads",
           "finalize_accumulated", "make_train_step"]


def validate_microbatches(batch: int, k: int, *, what: str = "batch"):
    """Loud divisibility contract: the scan needs k equal microbatches."""
    k = int(k)
    if k < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {k}")
    if batch % k != 0:
        raise ValueError(
            f"grad accumulation: {what} {batch} is not divisible by "
            f"num_microbatches={k} — choose k | {what} (microbatches "
            "must be equal-sized for exact loss averaging)")
    return k


def split_microbatches(x, k: int):
    """``(B, ...) -> (k, B/k, ...)`` strided view: microbatch ``j`` is
    rows ``j::k``. Free on a dim-0-sharded array — each device's block
    reshapes locally, no cross-chip row movement."""
    b = x.shape[0]
    validate_microbatches(b, k)
    m = b // k
    return jnp.moveaxis(x.reshape((m, k) + x.shape[1:]), 1, 0)


def microbatch_valid_mask(j, m: int, k: int, n_valid):
    """Validity mask for microbatch ``j`` of a padded batch: row ``i``
    of the microbatch is global row ``i*k + j``, valid while below the
    batch's real row count (``MaskedCriterion`` contract)."""
    return (jnp.arange(m) * k + j) < n_valid


def accumulated_value_and_grads(mb_value_and_grad, k: int, params,
                                data, labels, rng):
    """Scan ``k`` microbatches through forward/backward, accumulating
    gradients (and the loss numerator/denominator) in the scan carry.

    ``mb_value_and_grad(params, j, data_mb, labels_mb, key) ->
    ((num, weight, new_mstate), grads)`` is one microbatch's
    value-and-grad: ``num``/``weight`` are the caller's loss numerator
    and denominator contributions (see :func:`finalize_accumulated`),
    ``new_mstate`` the microbatch's module-state update.

    Returns ``(num_sum, weight_sum, mstate, grads_sum)`` — gradients
    and loss UNNORMALIZED (the caller divides once), module state
    averaged across microbatches (inexact leaves; others take the last
    microbatch's value, which is identical across microbatches for
    step counters since every microbatch starts from the same state).
    """
    ds = split_microbatches(data, k)
    ls = split_microbatches(labels, k)
    js = jnp.arange(k, dtype=jnp.int32)

    def run_one(p, j, d, l):
        key = jax.random.fold_in(rng, j) if rng is not None else None
        return mb_value_and_grad(p, j, d, l, key)

    # trace-time shape probe: the carry needs zeros of the grads/state/
    # loss structure before the first microbatch runs (no unrolled
    # first iteration — the scan body is the WHOLE program, compile
    # time and code size stay flat in k)
    out_shapes = jax.eval_shape(run_one, params, js[0], ds[0], ls[0])
    (num_s, w_s, ms_s), g_s = out_shapes
    zeros = lambda tree: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), tree)

    def body(carry, xs):
        j, d, l = xs
        gacc, nacc, wacc, msacc = carry
        (num, w, ms), g = run_one(params, j, d, l)
        gacc = jax.tree.map(jnp.add, gacc, g)
        msacc = jax.tree.map(
            lambda acc, cur: acc + cur / k
            if jnp.issubdtype(cur.dtype, jnp.inexact) else cur,
            msacc, ms)
        return (gacc, nacc + num, wacc + w, msacc), None

    init = (zeros(g_s), zeros(num_s), zeros(w_s), zeros(ms_s))
    (grads, num, weight, mstate), _ = jax.lax.scan(body, init,
                                                   (js, ds, ls))
    return num, weight, mstate, grads


def finalize_accumulated(num, weight, grads, *, k: int,
                         size_average: bool, masked: bool):
    """Normalize the accumulated loss and gradients to the single
    k×-batch step's semantics.

    - unmasked, size-averaging criterion: each microbatch contributed
      its own normalized mean (``num`` = sum of k means, ``weight``
      unused) — divide by k; equal microbatches make this the exact
      full-batch mean.
    - unmasked, summing criterion: sums add; no normalization.
    - masked: each microbatch contributed the UNNORMALIZED masked sum
      and its valid count; one division by the total count reproduces
      the full batch's masked mean exactly (per-microbatch counts may
      differ — normalizing early would be wrong).
    """
    if masked and size_average:
        denom = jnp.maximum(weight, 1.0)
    elif not masked and size_average:
        denom = jnp.asarray(float(k), num.dtype)
    else:
        denom = None
    if denom is None:
        return num, grads
    return num / denom, jax.tree.map(lambda g: g / denom, grads)


def make_train_step(*, fwd, criterion, masked=None, input_transform=None,
                    grad_clip=None, update_fn, num_microbatches: int = 1,
                    aux_loss=None):
    """Construct the train step both optimizers compile:
    ``step(params, mstate, opt_state, rng, data, labels, epoch,
    n_valid=None) -> (params, mstate, opt_state, loss)``.

    ``fwd`` is the (possibly remat-wrapped) model forward
    (optim/remat.py), ``update_fn(grads, params, opt_state) ->
    (new_params, new_opt_state)`` the optimizer update (the sharded
    update's ``apply_update`` on that path), ``masked`` the
    ``MaskedCriterion`` when partial-batch padding is on. ``aux_loss``
    (``set_expert_parallel``) maps the forward's new module state to an
    auxiliary objective term — the MoE load-balancing loss riding the
    state — added to the criterion (and, under accumulation, averaged
    across microbatches with the rest of the loss).

    ``num_microbatches == 1`` builds EXACTLY the pre-accumulation
    program — same ops in the same order, so golden training fixtures
    and the AOT executable cache are untouched. ``> 1`` scans strided
    microbatches with the gradient accumulated in donated carry
    buffers and runs ``update_fn`` once.

    Both branches name the step's parts with ``jax.named_scope`` —
    ``model``, ``criterion``, ``grad_clip``, ``optimizer_update`` — so a
    profiler trace names every device operation by the part it belongs
    to: ``jvp(model)/...`` forward, ``transpose(jvp(model))/...``
    backward. A scope is debug metadata only: the lowered program is
    the same text with and without it
    (tests/test_program_spans.py).
    """
    from bigdl_tpu.optim.optimizer import _clip_gradients
    k = int(num_microbatches)
    use_mask = masked is not None
    if use_mask and aux_loss is not None:
        raise ValueError(
            "expert_parallel's aux loss does not compose with "
            "pad_partial_batches: the masked numerator/denominator "
            "normalization cannot carry the per-microbatch aux term — "
            "disable padding or the aux loss")
    size_avg = getattr(criterion, "size_average", True)

    if k == 1:
        def train_step(params, mstate, opt_state, rng, data, labels,
                       epoch, n_valid=None):
            if input_transform is not None:
                data = input_transform(data)

            def loss_fn(p):
                with jax.named_scope("model"):
                    y, new_mstate = fwd(p, mstate, data, training=True,
                                        rng=rng)
                with jax.named_scope("criterion"):
                    if use_mask:
                        # validity mask materialized in-step from the
                        # real row count: padded rows contribute exactly
                        # zero to loss and gradient (nn.MaskedCriterion)
                        mask = jnp.arange(data.shape[0]) < n_valid
                        return masked.apply(y, labels, mask), new_mstate
                    loss = criterion.apply(y, labels)
                    if aux_loss is not None:
                        loss = loss + aux_loss(new_mstate)
                return loss, new_mstate

            (loss, new_mstate), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            with jax.named_scope("grad_clip"):
                grads = _clip_gradients(grads, grad_clip)
            opt_state = dict(opt_state, epoch=epoch)
            with jax.named_scope("optimizer_update"):
                new_params, new_opt_state = update_fn(grads, params,
                                                      opt_state)
            return new_params, new_mstate, new_opt_state, loss

        return train_step

    def train_step(params, mstate, opt_state, rng, data, labels, epoch,
                   n_valid=None):
        def mb_vag(p, j, d, l, key):
            if input_transform is not None:
                # per-microbatch: the transformed (widened) batch is
                # never materialized whole — transforms are per-row
                # (the u8 normalize path), so the slice commutes
                d = input_transform(d)

            def loss_fn(pp):
                with jax.named_scope("model"):
                    y, new_mstate = fwd(pp, mstate, d, training=True,
                                        rng=key)
                with jax.named_scope("criterion"):
                    if use_mask:
                        mask = microbatch_valid_mask(j, d.shape[0], k,
                                                     n_valid)
                        num, cnt = masked.masked_sum(y, l, mask)
                    else:
                        num = criterion.apply(y, l)
                        if aux_loss is not None:
                            # per-microbatch aux joins the numerator;
                            # the final /k restores its mean like the
                            # loss
                            num = num + aux_loss(new_mstate)
                        cnt = jnp.ones((), num.dtype)
                return num, (cnt, new_mstate)

            (num, (cnt, new_mstate)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p)
            return (num, cnt, new_mstate), grads

        num, w, new_mstate, grads = accumulated_value_and_grads(
            mb_vag, k, params, data, labels, rng)
        loss, grads = finalize_accumulated(num, w, grads, k=k,
                                           size_average=size_avg,
                                           masked=use_mask)
        with jax.named_scope("grad_clip"):
            grads = _clip_gradients(grads, grad_clip)
        opt_state = dict(opt_state, epoch=epoch)
        with jax.named_scope("optimizer_update"):
            new_params, new_opt_state = update_fn(grads, params,
                                                  opt_state)
        return new_params, new_mstate, new_opt_state, loss

    return train_step

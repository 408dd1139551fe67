"""Remat policy registry: named activation-rematerialization policies
applied to the model forward at step-construction time (ISSUE 10
tentpole).

Training MFU is activation-memory-bound before it is compute-bound: the
per-chip batch is capped by the residuals XLA saves between forward and
backward, not by MXU throughput. ``jax.checkpoint`` trades those HBM
bytes for recompute FLOPs (usually idle in memory-bound steps); this
module names the useful points on that tradeoff so they are a training
KNOB (``remat_policy=`` on both optimizers, a ``tune()`` axis, an AOT
cache-key component) rather than a per-model wrapper decision:

- ``"none"``         — save every residual (the default; zero recompute)
- ``"dots_saveable"``— save matmul/conv outputs, recompute elementwise
                       chains (cheap recompute, moderate savings)
- ``"per_block"``    — checkpoint each top-level block of a
                       ``Sequential`` stack (transformer / inception):
                       block-boundary activations are saved, and inside
                       a block only what a module NAMES as made by an
                       attention kernel and small next to what it costs
                       to make again (``KEPT_NAMES``); the rest of one
                       block's interior is recomputed at a time — the
                       selective policy deep stacks want. A block whose
                       modules name nothing keeps nothing: what is in
                       the traced block decides, not a setting
- ``"nothing_saveable"`` — save only the checkpointed region's inputs;
                       maximum savings, one full forward of recompute

Policies are SEMANTICALLY INVISIBLE: the recomputed forward is the same
program, so outputs AND gradients are bit-identical to the unwrapped
model (tests/test_remat.py pins it). Only memory and recompute move.

Static receipt: :func:`saved_residual_bytes` counts the bytes the
backward actually saves via abstract ``jax.vjp`` partial-eval — no
compile, no execution, backend-independent. This is deliberately NOT
the compiled executable's ``memory_analysis()``: the CPU backend CSEs
rematerialized subgraphs away (no HBM pressure to respect), so only the
jaxpr-level accounting shows the policy effect everywhere; the TPU
buffer assignment honors it. ``train_memory_probe`` (bench
``train_peak_hbm_bytes`` row) reports both.
"""
from __future__ import annotations

import logging

logger = logging.getLogger("bigdl_tpu.optim")

__all__ = ["REMAT_POLICIES", "KEPT_NAMES", "known_remat_policies",
           "check_remat_policy", "remat_forward", "saved_residual_bytes",
           "train_memory_probe"]

#: the policy names; ``_checkpoint_policy`` gives each but "none" its
#: ``jax.checkpoint`` policy, and "per_block" is also a structure (a
#: region a block) in remat_forward.
REMAT_POLICIES = ("none", "dots_saveable", "per_block", "nothing_saveable")

#: what ``"per_block"`` keeps inside a block, by the name a module gives
#: the value (``jax.ad_checkpoint.checkpoint_name``): an attention
#: kernel's output, its row statistics, and an exact selection's
#: per-row results (ops/pallas/sparse_attention.py, eva_attention.py)
KEPT_NAMES = ("attention_out", "attention_stats", "attention_selection")


def known_remat_policies() -> tuple:
    return REMAT_POLICIES


def check_remat_policy(name):
    """Validate (and normalize) a policy name; None means "none"."""
    name = "none" if name is None else str(name)
    if name not in REMAT_POLICIES:
        raise ValueError(f"unknown remat policy {name!r} "
                         f"(known: {list(REMAT_POLICIES)})")
    return name


def _checkpoint_policy(name):
    """The ``jax.checkpoint`` policy of every name but ``"none"``."""
    import jax
    if name == "dots_saveable":
        return jax.checkpoint_policies.dots_saveable
    if name == "nothing_saveable":
        return jax.checkpoint_policies.nothing_saveable
    if name == "per_block":
        return jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)
    raise AssertionError(name)


def _state_kept(model, blocks):
    """One ``bigdl:optim:remat_kept`` instant: ``KEPT_NAMES`` and, for
    each ``(block, args)`` of ``blocks``, how many values and bytes the
    ``"per_block"`` policy holds for the block's backward over what a
    checkpoint that keeps nothing holds (the block's inputs): the named
    values it saved. Abstract ``jax.vjp`` of each block, as
    :func:`saved_residual_bytes`; python runs this where the forward is
    traced with tracing on, never in a step."""
    import jax

    from bigdl_tpu.observability import trace

    def held(block, args, name):
        fn = jax.checkpoint(block, policy=_checkpoint_policy(name))
        leaves = jax.tree.leaves(jax.eval_shape(
            lambda *a: jax.vjp(fn, *a)[1], *args))
        return len(leaves), _tree_bytes(leaves)

    per_block = [[a - b for a, b in zip(held(block, args, "per_block"),
                                        held(block, args,
                                             "nothing_saveable"))]
                 for block, args in blocks]
    trace.instant("remat_kept", cat="optim", model=type(model).__name__,
                  names=",".join(KEPT_NAMES), blocks=len(per_block),
                  values=sum(n for n, _ in per_block),
                  bytes=sum(b for _, b in per_block), per_block=per_block)


def remat_forward(model, policy):
    """The model forward the train step should differentiate through:
    ``fwd(params, state, x, training=..., rng=...) -> (y, new_state)``.

    ``"none"`` returns ``model.apply`` untouched — the plain step is
    EXACTLY the pre-remat construction (golden fixtures unaffected).
    ``"per_block"`` checkpoints each top-level child of a ``Sequential``
    with the child-index rng fold and name scope mirrored from
    ``Sequential.apply`` so dropout draws land identically and a trace
    names the same modules; non-Sequential models degrade to a
    whole-forward checkpoint (logged). Either way the checkpoint keeps
    the values named in ``KEPT_NAMES`` and says so where it is traced
    (:func:`_state_kept`).

    A model may carry a policy of its own (``Sequential.set_remat``: the
    recipe it was built with). Its ``apply`` is then this function's
    result for that policy, which is what ``"none"`` returns here: the
    model's own. Any other ``policy`` wins over it: this function wraps
    the model's PLAIN forward (``apply_plain``), so the model's policy is
    not applied a second time inside the caller's.
    """
    import jax

    from bigdl_tpu.nn.containers import Sequential
    from bigdl_tpu.nn.module import _fold
    from bigdl_tpu.observability import trace

    policy = check_remat_policy(policy)
    if policy == "none":
        return model.apply
    plain = getattr(model, "apply_plain", model.apply)
    chk_policy = _checkpoint_policy(policy)

    if policy == "per_block" and isinstance(model, Sequential):
        def per_block(params, state, x, *, training=False, rng=None):
            # mirrors Sequential.apply exactly (same rng folds, same
            # state tree) with each block its own checkpoint region:
            # the residual stream at block boundaries is saved, and what
            # a block's modules name (KEPT_NAMES)
            new_state, blocks = {}, []
            for i, m in enumerate(model.modules):
                def block(p, s, xx, r, _m=m):
                    return _m.apply(p, s, xx, training=training, rng=r)

                args = (params[str(i)], state[str(i)], x, _fold(rng, i))
                blocks.append((block, args))
                with jax.named_scope(m._name or f"{i}_{type(m).__name__}"):
                    x, s = jax.checkpoint(block, policy=chk_policy)(*args)
                new_state[str(i)] = s
            if trace.enabled():
                _state_kept(model, blocks)
            return x, new_state

        return per_block

    if policy == "per_block":
        logger.info(
            "remat_policy='per_block' on a %s (not a Sequential stack) — "
            "checkpointing the whole forward instead", type(model).__name__)

    def whole_forward(params, state, x, *, training=False, rng=None):
        def inner(p, s, xx, r):
            return plain(p, s, xx, training=training, rng=r)

        args = (params, state, x, rng)
        if policy == "per_block" and trace.enabled():
            _state_kept(model, [(inner, args)])
        return jax.checkpoint(inner, policy=chk_policy)(*args)

    return whole_forward


def saved_residual_bytes(loss_fn, *args) -> int:
    """Bytes of residuals the backward of ``loss_fn(*args)`` saves,
    counted by abstract ``jax.vjp`` partial-eval (the returned vjp
    closure is a pytree whose leaves ARE the saved residuals). Pure
    shape evaluation: nothing compiles, nothing executes — this is the
    activation-memory term a remat policy controls, measured the same
    on every backend."""
    import jax

    def capture(*a):
        _, vjp = jax.vjp(loss_fn, *a)
        return vjp

    return _tree_bytes(jax.eval_shape(capture, *args))


def _tree_bytes(tree) -> int:
    import numpy as np

    import jax
    return int(sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(tree)
                   if hasattr(l, "shape")))


def train_memory_probe(*, d_model: int = 256, num_layers: int = 4,
                       seq: int = 1024, batch: int = 8,
                       vocab: int = 8192,
                       policies=REMAT_POLICIES,
                       accum_k: int = 4,
                       compile_accum: bool = True) -> dict:
    """Static peak-HBM accounting for the transformer train step across
    remat policies at FIXED effective batch (the bench
    ``train_peak_hbm_bytes`` row; tests call it in-process at tiny
    geometry).

    Per policy: ``saved_residual_bytes`` of the step's loss (abstract —
    fast even at bench geometry) plus the persistent-state term (params,
    grads, optimizer state) that does not move with the policy; modeled
    ``peak_hbm_bytes = persistent + residuals``. ``reduction`` is
    peak(none) / peak(nothing_saveable) — the acceptance number.

    ``compile_accum=True`` additionally compiles the k=1 and k=accum_k
    steps and reports executable ``memory_analysis`` temp bytes: the
    microbatched scan bounds activation liveness in the BUFFER
    ASSIGNMENT itself, so this one shows on the CPU backend too (remat
    does not — CPU CSEs the recompute; see module docstring)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.observability.compile_watch import executable_stats
    from bigdl_tpu.optim.accumulation import split_microbatches
    from bigdl_tpu.optim.sgd import SGD

    model = TransformerLM(vocab, d_model=d_model,
                          num_heads=max(d_model // 64, 1),
                          num_layers=num_layers, max_len=seq,
                          with_log_softmax=False)
    model.materialize(jax.random.PRNGKey(0))
    model.training()
    criterion = nn.CrossEntropyCriterion()
    optim = SGD(learning_rate=0.01, momentum=0.9)
    params, mstate = model.params, model.state
    opt_state = optim.init_state(params)
    host = np.random.default_rng(0)
    data = jnp.asarray(host.integers(1, vocab + 1, size=(batch, seq)))
    labels = jnp.asarray(host.integers(1, vocab + 1, size=(batch, seq)))

    persistent = (_tree_bytes(params) * 2          # params + grads
                  + _tree_bytes(opt_state))
    resid, peak = {}, {}
    for pol in policies:
        fwd = remat_forward(model, pol)

        def loss_fn(p, _fwd=fwd):
            y, _ = _fwd(p, mstate, data, training=True,
                        rng=jax.random.PRNGKey(1))
            return criterion.apply(y, labels)

        rb = saved_residual_bytes(loss_fn, params)
        resid[pol] = rb
        peak[pol] = persistent + rb

    out = {
        "geometry": f"transformer d{d_model} L{num_layers} B{batch} "
                    f"S{seq} V{vocab}",
        "persistent_bytes": persistent,
        "saved_residual_bytes": resid,
        "peak_hbm_bytes": peak,
        "reduction": (peak["none"] / peak["nothing_saveable"]
                      if "none" in peak and "nothing_saveable" in peak
                      else None),
        "residual_reduction": {
            p: (resid["none"] / r if r else None)
            for p, r in resid.items()} if "none" in resid else {},
    }

    if compile_accum:
        def step(params, mstate, opt_state, rng, data, labels, k):
            def mb_loss(p, d, l):
                y, s = model.apply(p, mstate, d, training=True, rng=rng)
                return criterion.apply(y, l), s

            if k == 1:
                (loss, s2), g = jax.value_and_grad(
                    mb_loss, has_aux=True)(params, data, labels)
            else:
                ds = split_microbatches(data, k)
                ls = split_microbatches(labels, k)

                def body(carry, xs):
                    d, l = xs
                    (lv, _), g = jax.value_and_grad(
                        mb_loss, has_aux=True)(params, d, l)
                    gacc, lacc = carry
                    return (jax.tree.map(jnp.add, gacc, g),
                            lacc + lv), None

                zero = jax.tree.map(jnp.zeros_like, params)
                (g, lsum), _ = jax.lax.scan(body,
                                            (zero, jnp.zeros(())),
                                            (ds, ls))
                g = jax.tree.map(lambda a: a / k, g)
                loss = lsum / k
            p2, o2 = optim.update(g, params, opt_state)
            return p2, o2, loss

        accum = {}
        for k in (1, int(accum_k)):
            from functools import partial
            c = jax.jit(partial(step, k=k),
                        donate_argnums=(0, 1, 2)).lower(
                params, mstate, opt_state, jax.random.PRNGKey(0),
                data, labels).compile()
            accum[str(k)] = executable_stats(c)
        out["accum_executable_stats"] = accum
        t1 = accum["1"].get("temp_bytes")
        tk = accum[str(int(accum_k))].get("temp_bytes")
        out["accum_temp_reduction"] = (t1 / tk if t1 and tk else None)
        out["accum_k"] = int(accum_k)
    return out

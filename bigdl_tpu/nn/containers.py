"""Container modules.

Reference parity: Sequential (nn/Sequential.scala:28-52), Concat
(nn/Concat.scala:42-80), ConcatTable, ParallelTable, Bottle
(all in dl/.../bigdl/nn/). The reference threads output-copies through
``Engine.model.invoke``; here XLA fuses the concatenation — no manual
threading.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Container, Module, _fold

__all__ = ["Sequential", "Concat", "ConcatTable", "ParallelTable", "Bottle",
           "MapTable", "Remat"]


class Sequential(Container):
    """Chain children (reference nn/Sequential.scala:28-52). Each child
    runs under a ``jax.named_scope`` — its ``set_name`` name, else
    ``<index>_<ClassName>`` — so a profiler trace of a compiled step
    names device operations by module (``jvp(model)/block_3/...``):
    the reference's per-module forward/backward time in the form a
    compiled step can give it.

    ``set_remat(policy)`` makes a recomputation policy of optim/remat.py
    part of the model AS BUILT: ``apply`` then IS
    ``remat_forward(self, policy)``, for whoever differentiates it — the
    optimizers' step or a bare ``jax.grad``. An optimizer given a policy
    of its own differentiates ``apply_plain`` under that one instead, so
    nothing is recomputed twice."""

    remat_policy: str | None = None

    def set_remat(self, policy: str | None):
        from bigdl_tpu.optim.remat import check_remat_policy
        policy = check_remat_policy(policy)
        self.remat_policy = None if policy == "none" else policy
        return self

    def apply(self, params, state, x, *, training=False, rng=None):
        if self.remat_policy is not None:
            from bigdl_tpu.optim.remat import remat_forward
            return remat_forward(self, self.remat_policy)(
                params, state, x, training=training, rng=rng)
        return self.apply_plain(params, state, x, training=training,
                                rng=rng)

    def apply_plain(self, params, state, x, *, training=False, rng=None):
        new_state = {}
        for i, m in enumerate(self.modules):
            # never get_name()'s default: it holds id(self)
            with jax.named_scope(m._name or f"{i}_{type(m).__name__}"):
                x, s = m.apply(params[str(i)], state[str(i)], x,
                               training=training, rng=_fold(rng, i))
            new_state[str(i)] = s
        return x, new_state


class Concat(Container):
    """Run children on the same input, concat outputs along ``dimension``
    (reference nn/Concat.scala; 1-based dim in the reference, here 0-based
    with the batch at axis 0 — reference dim=2 on NCHW == axis=1 here)."""

    def __init__(self, dimension: int = 1):
        super().__init__()
        self.dimension = dimension

    def apply(self, params, state, x, *, training=False, rng=None):
        outs, new_state = [], {}
        for i, m in enumerate(self.modules):
            y, s = m.apply(params[str(i)], state[str(i)], x,
                           training=training, rng=_fold(rng, i))
            outs.append(y)
            new_state[str(i)] = s
        return jnp.concatenate(outs, axis=self.dimension), new_state


class ConcatTable(Container):
    """Run children on the same input, return tuple of outputs
    (reference nn/ConcatTable.scala)."""

    def apply(self, params, state, x, *, training=False, rng=None):
        outs, new_state = [], {}
        for i, m in enumerate(self.modules):
            y, s = m.apply(params[str(i)], state[str(i)], x,
                           training=training, rng=_fold(rng, i))
            outs.append(y)
            new_state[str(i)] = s
        return tuple(outs), new_state


class ParallelTable(Container):
    """i-th child consumes i-th element of the input table
    (reference nn/ParallelTable.scala)."""

    def apply(self, params, state, x, *, training=False, rng=None):
        outs, new_state = [], {}
        for i, m in enumerate(self.modules):
            y, s = m.apply(params[str(i)], state[str(i)], x[i],
                           training=training, rng=_fold(rng, i))
            outs.append(y)
            new_state[str(i)] = s
        return tuple(outs), new_state


class MapTable(Container):
    """Apply the single child to every element of the input table
    (reference nn/MapTable.scala). Parameters are shared across elements."""

    def __init__(self, module: Module | None = None):
        super().__init__()
        if module is not None:
            self.add(module)

    def init(self, rng):
        return {"0": self.modules[0].init(rng)}

    def init_state(self):
        return {"0": self.modules[0].init_state()}

    def apply(self, params, state, x, *, training=False, rng=None):
        m = self.modules[0]
        outs = []
        s = state["0"]
        for i, xi in enumerate(x):
            y, s = m.apply(params["0"], s, xi, training=training,
                           rng=_fold(rng, i))
            outs.append(y)
        return tuple(outs), {"0": s}


class Bottle(Container):
    """Collapse leading dims, apply child, restore (reference nn/Bottle.scala).

    ``n_input_dim`` is the child's expected input rank.
    """

    def __init__(self, module: Module, n_input_dim: int = 2,
                 n_output_dim: int | None = None):
        super().__init__(module)
        self.n_input_dim = n_input_dim
        self.n_output_dim = n_output_dim or n_input_dim

    def apply(self, params, state, x, *, training=False, rng=None):
        shape = x.shape
        lead = shape[:len(shape) - self.n_input_dim + 1]
        squashed = x.reshape((-1,) + shape[len(shape) - self.n_input_dim + 1:])
        y, s = self.modules[0].apply(params["0"], state["0"], squashed,
                                     training=training, rng=rng)
        y = y.reshape(lead + y.shape[1:])
        return y, {"0": s}


class Remat(Container):
    """Rematerialize the child in backward (``jax.checkpoint``).

    TPU-first memory lever with no reference counterpart: the reference
    caches every module's ``output``/``gradInput`` (AbstractModule.scala:48-53)
    because its backward consumes them; under autodiff those cached
    activations become XLA-saved residuals and, for bandwidth-bound models,
    HBM traffic. Wrapping a block in ``Remat`` saves only the block
    boundary (and, with no ``policy`` given, what optim/remat.py's
    ``"per_block"`` keeps inside one: the values a module names as made
    by an attention kernel) and recomputes the interior during backward —
    trading MXU FLOPs (usually idle in memory-bound steps) for HBM bytes.

    Transparent to the param/state pytree: the child's tree IS this
    module's tree, so wrapping changes no checkpoint layout, golden
    fixture, or Caffe/Torch name-matched import.
    """

    def __init__(self, module: Module, policy=None):
        super().__init__(module)
        self.policy = policy

    def init(self, rng):
        return self.modules[0].init(rng)

    def init_state(self):
        return self.modules[0].init_state()

    def apply(self, params, state, x, *, training=False, rng=None):
        child = self.modules[0]

        def inner(p, s, xx, r):
            return child.apply(p, s, xx, training=training, rng=r)

        policy = self.policy
        if policy is None:
            # the child is a block: keep what "per_block" keeps
            from bigdl_tpu.optim.remat import _checkpoint_policy
            policy = _checkpoint_policy("per_block")
        return jax.checkpoint(inner, policy=policy)(params, state, x, rng)

    def sync(self, params, state=None):
        Module.sync(self, params, state)
        self.modules[0].sync(params, state)
        return self

    def materialize(self, rng=None):
        if self.params is None:
            if rng is None:
                rng = jax.random.PRNGKey(0)
            self._rng = rng
            self.modules[0].materialize(rng)
            self.params = self.modules[0].params
            self.state = self.modules[0].state
            self.grad_params = jax.tree.map(jnp.zeros_like, self.params)
        return self

    def __repr__(self):
        return f"Remat({self.modules[0]!r})"

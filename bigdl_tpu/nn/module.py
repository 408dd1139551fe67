"""Core module protocol: pure init/apply with a Torch-style stateful facade.

Reference parity:
- ``AbstractModule[A,B,T]`` (nn/abstractnn/AbstractModule.scala:40-323):
  forward/backward, cached output/gradInput, parameters(), getParameters()
  flat view, train/eval mode, per-module forward/backward wall-clock.
- ``Activity`` = Tensor | Table (nn/abstractnn/Activity.scala:25-44): here any
  JAX pytree (array, tuple/list/dict) is a valid activity.
- ``Container`` (nn/Container.scala:29-138): recursive composite.

TPU-first design: the reference mutates per-module ``output``/``gradInput``
buffers and hand-writes every backward pass. Here every module is a *pure
function pair*::

    params          = module.init(rng)                  # parameter pytree
    state           = module.init_state()               # running stats etc.
    y, new_state    = module.apply(params, state, x, training=..., rng=...)

which is what ``jax.jit`` / ``jax.grad`` / ``pjit`` consume — backward passes
come from autodiff, op parallelism from XLA (the reference's intra-op
``Engine.model.invoke`` threading, SURVEY §2.3, intentionally has no
equivalent here). The Torch-style stateful API (``forward``/``backward``/
``zero_grad_parameters``/``update_parameters``) is a thin facade over the pure
core so reference users keep their mental model and layer-level tests can be
written exactly like the reference's nn specs.
"""
from __future__ import annotations

import copy
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.tensor import flatten_params

__all__ = ["Module", "Container", "Criterion", "Identity", "Echo"]


def _fold(rng, i: int):
    return None if rng is None else jax.random.fold_in(rng, i)


class Module:
    """Base class of all layers (reference AbstractModule.scala:40)."""

    def __init__(self):
        self.training_mode: bool = True
        # cached activities (reference AbstractModule.scala:48-53)
        self.output: Any = None
        self.grad_input: Any = None
        # materialized state for the stateful facade
        self.params: Any = None
        self.state: Any = None
        self.grad_params: Any = None
        # per-module timing (reference AbstractModule.scala:124-135)
        self.forward_time: float = 0.0
        self.backward_time: float = 0.0
        self._name: Optional[str] = None
        self._rng = None

    # ------------------------------------------------------------------
    # pure protocol — subclasses override
    # ------------------------------------------------------------------
    def init(self, rng) -> Any:
        """Create the parameter pytree (dict of arrays; {} when
        parameterless)."""
        return {}

    def init_state(self) -> Any:
        """Create the non-trainable state pytree (e.g. BN running stats)."""
        return {}

    def apply(self, params, state, x, *, training: bool = False, rng=None):
        """Pure forward. Returns ``(output, new_state)``."""
        raise NotImplementedError

    # ------------------------------------------------------------------
    # stateful Torch-style facade (reference AbstractModule forward/backward)
    # ------------------------------------------------------------------
    def materialize(self, rng=None):
        """Instantiate ``self.params`` / ``self.state`` (idempotent)."""
        if self.params is None:
            if rng is None:
                rng = jax.random.PRNGKey(0)
            self._rng = rng
            self.params = self.init(rng)
            self.state = self.init_state()
            self.grad_params = jax.tree.map(jnp.zeros_like, self.params)
        return self

    #: when True (default), ``forward``/``backward`` bracket their timers
    #: with ``jax.block_until_ready`` so ``get_times()`` reports true
    #: wall time like the reference's ``getTimes()``
    #: (AbstractModule.scala:124-135), not async dispatch time. Set False
    #: to keep the facade fully asynchronous (then the times are
    #: dispatch-only; use ``Optimizer.set_profiler`` for device truth).
    sync_times: bool = True

    def forward(self, x, rng=None):
        """Timed stateful forward (reference AbstractModule.scala:144-150)."""
        self.materialize()
        if Module.sync_times:
            jax.block_until_ready(x)   # charge upstream work upstream
        t0 = time.perf_counter()
        if rng is None and self._rng is not None:
            self._rng, rng = jax.random.split(self._rng)
        self._forward_rng = rng  # reused by backward for identical masks
        self.output, self.state = self.apply(
            self.params, self.state, x, training=self.training_mode, rng=rng)
        if Module.sync_times:
            jax.block_until_ready(self.output)
        self.forward_time += time.perf_counter() - t0
        return self.output

    __call__ = forward

    def backward(self, x, grad_output, rng=None):
        """Stateful backward via autodiff (reference
        AbstractModule.scala:162-169).

        Computes grad wrt input (returned, like ``updateGradInput``) and
        *accumulates* parameter grads (like ``accGradParameters``).
        Stochastic layers (Dropout/RReLU) replay the SAME rng the preceding
        ``forward`` consumed so masks match between passes.
        """
        self.materialize()
        if rng is None:
            rng = getattr(self, "_forward_rng", None)
        if Module.sync_times:
            jax.block_until_ready((x, grad_output))
        t0 = time.perf_counter()

        def f(params, inp):
            y, _ = self.apply(params, self.state, inp,
                              training=self.training_mode, rng=rng)
            return y

        _, vjp = jax.vjp(f, self.params, x)
        d_params, d_input = vjp(grad_output)
        self.grad_params = jax.tree.map(jnp.add, self.grad_params, d_params)
        self.grad_input = d_input
        if Module.sync_times:
            jax.block_until_ready((self.grad_params, d_input))
        self.backward_time += time.perf_counter() - t0
        return self.grad_input

    # ------------------------------------------------------------------
    # parameter access (reference AbstractModule.scala:216-242)
    # ------------------------------------------------------------------
    def parameters(self):
        """(params, grad_params) pytrees (reference ``parameters()``)."""
        self.materialize()
        return self.params, self.grad_params

    def get_parameters(self):
        """Flat (weights, grads) vectors (reference ``getParameters()`` /
        Module.flatten, nn/Module.scala:41-69)."""
        p, g = self.parameters()
        fp, _ = flatten_params(p)
        fg, _ = flatten_params(g)
        return fp, fg

    def get_parameters_table(self):
        """name -> {weight, bias, ...} mapping for Caffe/Torch import
        (reference AbstractModule.scala:242)."""
        name = self.get_name()
        p, _ = self.parameters()
        return {name: p} if p else {}

    def set_parameters(self, params):
        self.params = params
        if self.grad_params is None or jax.tree.structure(
                self.grad_params) != jax.tree.structure(params):
            self.grad_params = jax.tree.map(jnp.zeros_like, params)
        return self

    def sync(self, params, state=None):
        """Point this module (and any children) at new params/state trees.

        Training loops donate the old parameter buffers to the jitted step
        (XLA updates weights in place in HBM); this rebinds the module
        facade to the live arrays afterwards.
        """
        self.params = params
        if state is not None:
            self.state = state
        return self

    def zero_grad_parameters(self):
        self.materialize()
        self.grad_params = jax.tree.map(jnp.zeros_like, self.grad_params)

    def update_parameters(self, lr: float):
        self.params = jax.tree.map(lambda p, g: p - lr * g,
                                   self.params, self.grad_params)

    # ------------------------------------------------------------------
    # modes, naming, timing, cloning (reference AbstractModule.scala:247-323)
    # ------------------------------------------------------------------
    def training(self):
        self.training_mode = True
        return self

    def evaluate(self):
        self.training_mode = False
        return self

    def is_training(self) -> bool:
        return self.training_mode

    def set_name(self, name: str):
        self._name = name
        return self

    def set_init_method(self, method: str):
        """Chainable init-method override (reference ``setInitMethod``).

        Must be called before ``materialize`` — init_method is only read
        when parameters are created."""
        if self.params is not None:
            raise RuntimeError(
                "set_init_method after materialize has no effect; call it "
                "before the first forward/materialize")
        self.init_method = method
        return self

    def get_name(self) -> str:
        return self._name or f"{type(self).__name__}@{id(self):x}"

    def get_times(self):
        """[(module, forward_s, backward_s)] (reference ``getTimes()``,
        AbstractModule.scala:124-135).

        With ``Module.sync_times`` (default True) the facade
        ``forward``/``backward`` bracket their timers with
        ``block_until_ready``, so these are true wall times on standard
        backends. Children of a Container accumulate only when their own
        ``forward`` is invoked — the Container's pure ``apply`` chain is
        jit-compiled and cannot host per-child syncs; use
        ``Optimizer.set_profiler`` for per-op device truth under jit."""
        return [(self, self.forward_time, self.backward_time)]

    def reset_times(self):
        self.forward_time = 0.0
        self.backward_time = 0.0

    def clear_state(self):
        self.output = None
        self.grad_input = None
        return self

    def clone_module(self):
        """Deep copy (reference ``cloneModule()``, Java serialization)."""
        return copy.deepcopy(self)

    def save(self, path: str, overwrite: bool = False):
        from bigdl_tpu.utils import file as _file
        _file.save_module(self, path, overwrite=overwrite)
        return self

    def save_torch(self, path: str, overwrite: bool = False):
        """Export as a Torch .t7 file (reference AbstractModule.saveTorch,
        :311-315)."""
        from bigdl_tpu.utils import torchfile
        torchfile.save_torch(self, path, overwrite)
        return self

    @staticmethod
    def load_torch(path: str):
        """(reference Module.loadTorch, nn/Module.scala:31-33)"""
        from bigdl_tpu.utils import torchfile
        return torchfile.load_torch(path)

    @staticmethod
    def load_caffe(model, def_path: str, model_path: str,
                   match_all: bool = True):
        """(reference Module.loadCaffe, nn/Module.scala:35-39)"""
        from bigdl_tpu.utils.caffe import load_caffe
        return load_caffe(model, def_path, model_path, match_all)

    def __repr__(self):
        return f"{type(self).__name__}()"


class Container(Module):
    """Composite module (reference nn/Container.scala:29-138).

    Child params/state are pytrees keyed by the child's position:
    ``{"0": ...}``.
    """

    def __init__(self, *modules: Module):
        super().__init__()
        self.modules: list[Module] = list(modules)

    def add(self, module: Module):
        self.modules.append(module)
        return self

    def __len__(self):
        return len(self.modules)

    def __getitem__(self, i):
        return self.modules[i]

    def init(self, rng):
        return {str(i): m.init(_fold(rng, i))
                for i, m in enumerate(self.modules)}

    def init_state(self):
        return {str(i): m.init_state() for i, m in enumerate(self.modules)}

    # containers recurse (reference Container.scala:71-78)
    def training(self):
        super().training()
        for m in self.modules:
            m.training()
        return self

    def evaluate(self):
        super().evaluate()
        for m in self.modules:
            m.evaluate()
        return self

    def get_times(self):
        """Timing rows: the container's own row first, then children.

        DEVIATION from reference Container.getTimes (Container.scala:71-73,
        children only): under jit the container facade's forward time covers
        the whole compiled chain while children read zero, so the self row
        is the only signal in the common path. It is emitted only when
        nonzero, and a summing aggregator that also forwards children
        individually should filter rows with ``isinstance(m, Container)``
        to avoid double counting."""
        out = ([(self, self.forward_time, self.backward_time)]
               if (self.forward_time or self.backward_time) else [])
        for m in self.modules:
            out.extend(m.get_times())
        return out

    def reset_times(self):
        super().reset_times()
        for m in self.modules:
            m.reset_times()

    def get_parameters_table(self):
        out = {}
        for m in self.modules:
            out.update(m.get_parameters_table())
        return out

    def sync(self, params, state=None):
        super().sync(params, state)
        for i, m in enumerate(self.modules):
            m.sync(params[str(i)],
                   None if state is None else state[str(i)])
        return self

    def materialize(self, rng=None):
        # keep child facades usable on their own AND consistent with ours
        if self.params is None:
            if rng is None:
                rng = jax.random.PRNGKey(0)
            self._rng = rng
            for i, m in enumerate(self.modules):
                m.materialize(_fold(rng, i))
            self.params = {str(i): m.params
                           for i, m in enumerate(self.modules)}
            self.state = {str(i): m.state for i, m in enumerate(self.modules)}
            self.grad_params = jax.tree.map(jnp.zeros_like, self.params)
        return self

    def __repr__(self):
        inner = "\n".join(f"  ({i}): {m!r}"
                          for i, m in enumerate(self.modules))
        return f"{type(self).__name__}(\n{inner}\n)"


class Criterion:
    """Loss base (reference AbstractCriterion,
    nn/abstractnn/AbstractCriterion.scala:29-75).

    Pure protocol: ``loss = criterion.apply(input, target)`` (scalar).
    Stateful facade: ``forward`` caches output; ``backward`` returns
    d loss / d input via autodiff.
    """

    size_average: bool = True

    def __init__(self):
        self.output = None
        self.grad_input = None

    def apply(self, x, target):
        raise NotImplementedError

    def forward(self, x, target):
        self.output = self.apply(x, target)
        return self.output

    __call__ = forward

    def backward(self, x, target):
        self.grad_input = jax.grad(lambda inp: self.apply(inp, target))(x)
        return self.grad_input

    def clone_criterion(self):
        return copy.deepcopy(self)

    def __repr__(self):
        return f"{type(self).__name__}()"


class Identity(Module):
    """Pass-through (reference nn/Identity.scala)."""

    def apply(self, params, state, x, *, training=False, rng=None):
        return x, state


class Echo(Module):
    """Print activation shape then pass through (reference nn/Echo.scala)."""

    def apply(self, params, state, x, *, training=False, rng=None):
        jax.debug.print("Echo: shape={s}", s=jnp.shape(x))
        return x, state

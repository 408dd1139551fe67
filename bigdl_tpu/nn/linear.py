"""Linear / embedding family.

Reference parity: Linear (nn/Linear.scala, 218 LoC), Bilinear, LookupTable
(nn/LookupTable.scala:32-105), Cosine, Euclidean, Add, CAdd, CMul, Mul, MM, MV
(all in dl/.../bigdl/nn/).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn import init as init_mod
from bigdl_tpu.nn.module import Module
from bigdl_tpu.tensor import activation_dtype, compute_dtype, default_dtype

__all__ = ["Linear", "GatedFFN", "Bilinear", "LookupTable", "Cosine",
           "Euclidean",
           "Add", "CAdd", "CMul", "Mul", "MM", "MV"]


class Linear(Module):
    """y = x W^T + b (reference nn/Linear.scala; default init
    stdv = 1/sqrt(inputSize))."""

    def __init__(self, input_size: int, output_size: int,
                 with_bias: bool = True,
                 init_method: str = init_mod.Default,
                 output_dtype=None):
        super().__init__()
        self.input_size = input_size
        self.output_size = output_size
        self.with_bias = with_bias
        self.init_method = init_method
        # e.g. float32 logits from bf16 operands: the matmul's own f32
        # accumulator is the output, not a rounded copy of it widened
        self.output_dtype = output_dtype

    def init(self, rng):
        kw, kb = jax.random.split(rng)
        p = {"weight": init_mod.init_weight(
            self.init_method, kw, (self.output_size, self.input_size),
            fan_in=self.input_size, fan_out=self.output_size)}
        if self.with_bias:
            stdv = (1.0 / np.sqrt(self.input_size)
                    if self.init_method == init_mod.Default else 0.0)
            p["bias"] = (init_mod.uniform_reset(kb, (self.output_size,), stdv)
                         if stdv else jnp.zeros((self.output_size,),
                                                default_dtype()))
        return p

    def apply(self, params, state, x, *, training=False, rng=None):
        w = params["weight"].astype(compute_dtype())
        if self.output_dtype is not None:
            y = jnp.matmul(x.astype(compute_dtype()), w.T,
                           preferred_element_type=self.output_dtype)
            if self.with_bias:
                y = y + params["bias"].astype(self.output_dtype)
            return y, state
        y = jnp.matmul(x.astype(compute_dtype()), w.T)
        if self.with_bias:
            y = y + params["bias"].astype(compute_dtype())
        return y.astype(activation_dtype()), state

    def __repr__(self):
        return f"Linear({self.input_size} -> {self.output_size})"


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gated_ffn(act, h, w_gate, w_up, w_down):
    return _gated_ffn_fwd(act, h, w_gate, w_up, w_down)[0]


def _gated_ffn_fwd(act, h, w_gate, w_up, w_down):
    gate = jnp.matmul(h, w_gate.T)
    up = jnp.matmul(h, w_up.T)
    y = jnp.matmul(act(gate) * up, w_down.T)
    return y, (h, gate, up, w_gate, w_up, w_down)


def _gated_ffn_bwd(act, res, dy):
    from bigdl_tpu.observability import trace
    held = jax.lax.optimization_barrier
    h, gate, up, w_gate, w_up, w_down = res
    d_ff, d_model = w_gate.shape
    tokens = gate.size // d_ff
    # python runs this when the backward is traced for a compile, once a
    # layer and never in a step
    trace.instant("gated_ffn_backward", cat="nn", tokens=tokens,
                  d_model=d_model, d_ff=d_ff,
                  materialised_bytes=tokens * (4 * d_ff + 2 * d_model)
                  * gate.dtype.itemsize)
    # every token-side operand of the five matmuls is held as a tensor.
    # Left to itself XLA fuses each one's producer (act and act' for a,
    # d_gate and d_up; the cast of the residual stream's gradient for
    # dy; the norm's scale for h) into the operand of every matmul that
    # reads it, and evaluates it again as that matmul streams it. d_a
    # too: the elementwise pass then rides the recomputed gate matmul
    # and not the d_a matmul, which the v5e runs 3 ms a layer sooner
    # (PERF.md section 6, PR 28)
    dy, h = held((dy, h))
    d_a = held(jnp.matmul(dy, w_down))
    s, act_vjp = jax.vjp(act, gate)
    a, d_gate, d_up = held((s * up, act_vjp(d_a * up)[0], d_a * s))

    def over_tokens(x, y):
        return jnp.matmul(x.reshape(tokens, -1).T, y.reshape(tokens, -1))

    d_h = jnp.matmul(d_gate, w_gate) + jnp.matmul(d_up, w_up)
    return (d_h, over_tokens(d_gate, h), over_tokens(d_up, h),
            over_tokens(dy, a))


_gated_ffn.defvjp(_gated_ffn_fwd, _gated_ffn_bwd)


class GatedFFN(Module):
    """y = W_down( act(W_gate x) * (W_up x) ), three bias-free matrices
    (Shazeer, arXiv:2002.05202; ``act="silu"`` is SwiGLU). ``act`` is a
    callable or the name of one in ``jax.nn``.

    The module defines its own backward (``jax.custom_vjp``): the five
    matmuls autodiff writes, with ``act(gate) * up`` and the gradients
    of ``gate`` and ``up`` computed in one elementwise pass and every
    matmul operand kept as a tensor, because XLA otherwise evaluates
    the activation and its derivative again inside every matmul that
    reads them (PERF.md section 6, PR 28). So it is differentiable in
    reverse mode only:
    ``jax.jvp`` / ``jax.jacfwd`` (and ``jax.hessian``) through it raise;
    nothing here differentiates it twice."""

    def __init__(self, d_model: int, d_ff: int, act="silu"):
        super().__init__()
        self.d_model, self.d_ff = d_model, d_ff
        self.act = getattr(jax.nn, act) if isinstance(act, str) else act

    def init(self, rng):
        shapes = {"gate_weight": (self.d_ff, self.d_model),
                  "up_weight": (self.d_ff, self.d_model),
                  "down_weight": (self.d_model, self.d_ff)}
        return {name: init_mod.init_weight(
                    init_mod.Default, k, shape, fan_in=shape[1],
                    fan_out=shape[0])
                for (name, shape), k in zip(
                    shapes.items(), jax.random.split(rng, len(shapes)))}

    def apply(self, params, state, x, *, training=False, rng=None):
        cd = compute_dtype()
        y = _gated_ffn(self.act, x.astype(cd),
                       params["gate_weight"].astype(cd),
                       params["up_weight"].astype(cd),
                       params["down_weight"].astype(cd))
        return y.astype(activation_dtype()), state

    def __repr__(self):
        return f"GatedFFN({self.d_model} -> {self.d_ff} -> {self.d_model})"


class Bilinear(Module):
    """y_k = x1 W_k x2^T + b_k over a table input (x1, x2)
    (reference nn/Bilinear.scala)."""

    def __init__(self, input_size1: int, input_size2: int, output_size: int,
                 bias_res: bool = True):
        super().__init__()
        self.n1, self.n2, self.n_out = input_size1, input_size2, output_size
        self.bias_res = bias_res

    def init(self, rng):
        kw, kb = jax.random.split(rng)
        stdv = 1.0 / np.sqrt(self.n1)
        p = {"weight": init_mod.uniform_reset(
            kw, (self.n_out, self.n1, self.n2), stdv)}
        if self.bias_res:
            p["bias"] = init_mod.uniform_reset(kb, (self.n_out,), stdv)
        return p

    def apply(self, params, state, x, *, training=False, rng=None):
        x1, x2 = x
        y = jnp.einsum("bi,kij,bj->bk", x1, params["weight"], x2)
        if self.bias_res:
            y = y + params["bias"]
        return y, state


class LookupTable(Module):
    """Embedding lookup (reference nn/LookupTable.scala:32-105).

    Indices are 1-based like the reference. ``padding_value`` rows embed to
    whatever is stored (the reference zeroes their gradient — autodiff does
    that automatically since a stop-gradient mask is applied), ``max_norm``
    renormalizes looked-up rows.
    """

    def __init__(self, n_index: int, n_output: int, padding_value: float = 0,
                 max_norm: float | None = None, norm_type: float = 2.0):
        super().__init__()
        self.n_index, self.n_output = n_index, n_output
        self.padding_value = int(padding_value)
        self.max_norm, self.norm_type = max_norm, norm_type

    def init(self, rng):
        return {"weight": jax.random.normal(
            rng, (self.n_index, self.n_output), default_dtype())}

    def apply(self, params, state, x, *, training=False, rng=None):
        idx = x.astype(jnp.int32) - 1  # reference is 1-based
        w = params["weight"]
        if self.max_norm is not None:
            norms = jnp.linalg.norm(w, ord=self.norm_type, axis=1,
                                    keepdims=True)
            w = w * jnp.minimum(1.0, self.max_norm / (norms + 1e-7))
        y = jnp.take(w, jnp.clip(idx, 0, self.n_index - 1), axis=0)
        if self.padding_value:
            mask = (idx != self.padding_value - 1)[..., None]
            y = jnp.where(mask, y, jax.lax.stop_gradient(y))
        return y, state


class Cosine(Module):
    """Cosine similarity vs each weight row (reference nn/Cosine.scala)."""

    def __init__(self, input_size: int, output_size: int):
        super().__init__()
        self.input_size, self.output_size = input_size, output_size

    def init(self, rng):
        stdv = 1.0 / np.sqrt(self.input_size)
        return {"weight": init_mod.uniform_reset(
            rng, (self.output_size, self.input_size), stdv)}

    def apply(self, params, state, x, *, training=False, rng=None):
        w = params["weight"]
        xn = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
        wn = w / (jnp.linalg.norm(w, axis=-1, keepdims=True) + 1e-12)
        return jnp.matmul(xn, wn.T), state


class Euclidean(Module):
    """L2 distance to each weight column (reference nn/Euclidean.scala)."""

    def __init__(self, input_size: int, output_size: int):
        super().__init__()
        self.input_size, self.output_size = input_size, output_size

    def init(self, rng):
        stdv = 1.0 / np.sqrt(self.input_size)
        return {"weight": init_mod.uniform_reset(
            rng, (self.output_size, self.input_size), stdv)}

    def apply(self, params, state, x, *, training=False, rng=None):
        diff = x[..., None, :] - params["weight"]
        return jnp.linalg.norm(diff, axis=-1), state


class Add(Module):
    """Learned bias add (reference nn/Add.scala)."""

    def __init__(self, input_size: int):
        super().__init__()
        self.input_size = input_size

    def init(self, rng):
        stdv = 1.0 / np.sqrt(self.input_size)
        return {"bias": init_mod.uniform_reset(rng, (self.input_size,), stdv)}

    def apply(self, params, state, x, *, training=False, rng=None):
        return x + params["bias"], state


class CAdd(Module):
    """Learned elementwise bias of arbitrary broadcast shape
    (reference nn/CAdd.scala)."""

    def __init__(self, size):
        super().__init__()
        self.size = tuple(size)

    def init(self, rng):
        stdv = 1.0 / np.sqrt(int(np.prod(self.size)))
        return {"bias": init_mod.uniform_reset(rng, self.size, stdv)}

    def apply(self, params, state, x, *, training=False, rng=None):
        return x + params["bias"], state


class CMul(Module):
    """Learned elementwise scale (reference nn/CMul.scala)."""

    def __init__(self, size):
        super().__init__()
        self.size = tuple(size)

    def init(self, rng):
        stdv = 1.0 / np.sqrt(int(np.prod(self.size)))
        return {"weight": init_mod.uniform_reset(rng, self.size, stdv)}

    def apply(self, params, state, x, *, training=False, rng=None):
        return x * params["weight"], state


class Mul(Module):
    """Single learned scalar scale (reference nn/Mul.scala)."""

    def init(self, rng):
        return {"weight": init_mod.uniform_reset(rng, (1,), 1.0)}

    def apply(self, params, state, x, *, training=False, rng=None):
        return x * params["weight"][0], state


class MM(Module):
    """Batch matrix-matrix product of a table (a, b)
    (reference nn/MM.scala)."""

    def __init__(self, trans_a: bool = False, trans_b: bool = False):
        super().__init__()
        self.trans_a, self.trans_b = trans_a, trans_b

    def apply(self, params, state, x, *, training=False, rng=None):
        a, b = x
        if self.trans_a:
            a = jnp.swapaxes(a, -1, -2)
        if self.trans_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b), state


class MV(Module):
    """Batch matrix-vector product of a table (m, v)
    (reference nn/MV.scala)."""

    def __init__(self, trans: bool = False):
        super().__init__()
        self.trans = trans

    def apply(self, params, state, x, *, training=False, rng=None):
        m, v = x
        if self.trans:
            m = jnp.swapaxes(m, -1, -2)
        return jnp.einsum("...ij,...j->...i", m, v), state

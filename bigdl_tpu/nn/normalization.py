"""Normalization layers.

Reference parity: BatchNormalization (nn/BatchNormalization.scala:30-104 —
eps=1e-5, momentum=0.1, optional affine, runningMean/runningVar updated in
train and used in eval), SpatialBatchNormalization, SpatialCrossMapLRN,
SpatialContrastiveNormalization, SpatialDivisiveNormalization,
SpatialSubtractiveNormalization, Normalize.

BN under data parallelism: the reference's statistics are per-replica
(per-core model clone, SURVEY §7 "hard parts"). Here statistics are computed
over the device-local batch by default; pass ``axis_name`` to sync across a
mesh axis with ``lax.pmean`` (the idiomatic TPU upgrade).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.containers import Sequential as _Sequential
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops import pow_neg_beta as _pow_neg_beta
from bigdl_tpu.tensor import activation_dtype, default_dtype

__all__ = ["BatchNormalization", "SpatialBatchNormalization",
           "SpatialCrossMapLRN", "ReLUCrossMapLRN", "Normalize", "LayerNorm",
           "RMSNorm",
           "SpatialDivisiveNormalization", "SpatialSubtractiveNormalization",
           "SpatialContrastiveNormalization"]


class BatchNormalization(Module):
    """1-D batch norm over (N, C) (reference nn/BatchNormalization.scala)."""

    n_dim = 2
    _one_pass_stats = False   # exact two-pass variance (see apply)

    def __init__(self, n_output: int, eps: float = 1e-5,
                 momentum: float = 0.1, affine: bool = True,
                 axis_name: str | None = None):
        super().__init__()
        self.n_output = n_output
        self.eps, self.momentum, self.affine = eps, momentum, affine
        self.axis_name = axis_name

    def init(self, rng):
        if not self.affine:
            return {}
        # reference reset(): weight ~ U(0,1), bias = 0
        return {"weight": jax.random.uniform(rng, (self.n_output,),
                                             default_dtype()),
                "bias": jnp.zeros((self.n_output,), default_dtype())}

    def init_state(self):
        return {"running_mean": jnp.zeros((self.n_output,), default_dtype()),
                "running_var": jnp.ones((self.n_output,), default_dtype())}

    def _reduce_axes(self, x):
        return tuple(i for i in range(x.ndim) if i != 1)

    def apply(self, params, state, x, *, training=False, rng=None):
        squeeze = x.ndim == self.n_dim - 1  # unbatched input
        if squeeze:
            x = x[None]
        axes = self._reduce_axes(x)
        # statistics always accumulate in >= f32 even when activations flow
        # bf16 (the reference's MKL path is f32 throughout); running stats
        # stay at param precision
        stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
        if training:
            xs = x.astype(stat_dtype)
            mean = jnp.mean(xs, axis=axes)
            if self._one_pass_stats:
                # one fused pass: E[x] and E[x^2] reduce together, where
                # jnp.var's (x - mean)^2 form needs a SECOND sequential
                # read of the activation after the mean lands — profiled
                # at 33% of a ResNet-50 step (98 convert_reduce fusions,
                # 18.8 ms; docs/PERF.md round 3). Spatial variant only:
                # conv outputs are near-zero-mean, so the f32
                # cancellation the two-pass form guards against is
                # absent; the generic (N, C) module keeps the exact form
                # (raw feature columns can have mean/std ratios where
                # E[x^2]-E[x]^2 rounds to zero).
                mean2 = jnp.mean(jnp.square(xs), axis=axes)
                if self.axis_name is not None:
                    # pmean of per-device moments is EXACT for E[x]/E[x^2]
                    # (it was only approximate for per-device variances)
                    mean = jax.lax.pmean(mean, self.axis_name)
                    mean2 = jax.lax.pmean(mean2, self.axis_name)
                var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            else:
                var = jnp.var(xs, axis=axes)
                if self.axis_name is not None:
                    mean = jax.lax.pmean(mean, self.axis_name)
                    var = jax.lax.pmean(var, self.axis_name)
            n = np.prod([x.shape[a] for a in axes])
            if self.axis_name is not None and self._one_pass_stats:
                # the fused form's variance is GLOBAL over all devices'
                # samples; Bessel must use the global count too
                n = n * jax.lax.psum(1, self.axis_name)
            unbiased = var * n / jnp.maximum(n - 1, 1)
            m = self.momentum
            new_state = {
                "running_mean": (1 - m) * state["running_mean"] + m * mean,
                "running_var": (1 - m) * state["running_var"] + m * unbiased,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = state
        shape = [1] * x.ndim
        shape[1] = self.n_output
        scale = jax.lax.rsqrt(var.astype(stat_dtype) + self.eps)
        if self.affine:
            scale = scale * params["weight"].astype(stat_dtype)
        shift = -mean.astype(stat_dtype) * scale
        if self.affine:
            shift = shift + params["bias"].astype(stat_dtype)
        # one fused multiply-add; f32 in registers, output in the activation
        # dtype (XLA fuses the whole elementwise chain, nothing f32 hits HBM)
        y = (x.astype(stat_dtype) * scale.reshape(shape)
             + shift.reshape(shape)).astype(x.dtype)
        if squeeze:
            y = y[0]
        return y, new_state

    def __repr__(self):
        return f"{type(self).__name__}({self.n_output})"


class SpatialBatchNormalization(BatchNormalization):
    """4-D (N, C, H, W) wrapper (reference
    nn/SpatialBatchNormalization.scala).

    ``one_pass_stats=True`` (default) fuses E[x]/E[x^2] into one
    activation read — right for near-zero-mean conv outputs. A stem BN
    fed raw, non-centered inputs can lose precision to E[x^2]-E[x]^2
    cancellation in f32; pass ``one_pass_stats=False`` there to get the
    exact two-pass variance of the base class."""

    n_dim = 4

    def __init__(self, *args, one_pass_stats: bool = True, **kwargs):
        super().__init__(*args, **kwargs)
        self._one_pass_stats = one_pass_stats


def _lrn_window_sum(v, size, adjoint=False):
    """Sum over a size-wide window along the channel axis (NCHW axis 1).

    ``adjoint`` transposes the (asymmetric, for even sizes) padding: the
    forward window at j covers [j-half, j+size-1-half], so the backward
    sum over {j : i in win(j)} covers [i-(size-1-half), i+half].
    """
    half = (size - 1) // 2
    lo, hi = (size - 1 - half, half) if adjoint else (half, size - 1 - half)
    return jax.lax.reduce_window(
        v, 0.0, jax.lax.add,
        window_dimensions=(1, size, 1, 1),
        window_strides=(1, 1, 1, 1),
        padding=((0, 0), (lo, hi), (0, 0), (0, 0)))


def _lrn_impl(x, size, alpha, beta, k):
    f32 = jnp.promote_types(x.dtype, jnp.float32)
    s = k + (alpha / size) * _lrn_window_sum(jnp.square(x.astype(f32)), size)
    return (x.astype(f32) * _pow_neg_beta(s, beta)).astype(x.dtype)


def _lrn_fwd(x, size, alpha, beta, k):
    f32 = jnp.promote_types(x.dtype, jnp.float32)
    s = k + (alpha / size) * _lrn_window_sum(jnp.square(x.astype(f32)), size)
    sb = _pow_neg_beta(s, beta)
    y = (x.astype(f32) * sb).astype(x.dtype)
    # residuals at activation precision: autodiff through the naive graph
    # keeps ~5 full-size f32 buffers live; this saves x plus two factors
    # in the activation dtype
    return y, (x, sb.astype(x.dtype), (sb / s).astype(x.dtype))


def _lrn_bwd(size, alpha, beta, k, res, g):
    # dx_i = g_i*s_i^-b - (2ab/n) * x_i * sum_win(g_j * x_j * s_j^-(b+1))
    x, sb, sb1 = res
    f32 = jnp.promote_types(x.dtype, jnp.float32)
    acc = _lrn_window_sum(g.astype(f32) * x.astype(f32) * sb1.astype(f32),
                          size, adjoint=True)
    dx = g.astype(f32) * sb.astype(f32) \
        - (2.0 * alpha * beta / size) * x.astype(f32) * acc
    return (dx.astype(x.dtype),)


_lrn = jax.custom_vjp(_lrn_impl, nondiff_argnums=(1, 2, 3, 4))
_lrn.defvjp(_lrn_fwd, _lrn_bwd)


def _pallas_lrn(x, size, alpha, beta, k, relu=False):
    """The fused Pallas kernel (ops/pallas/lrn.py) where it applies,
    else None. Under a multi-device mesh the kernel runs once per batch
    shard (jax refuses to partition a Mosaic call itself), so the
    kernel's constraints are judged on the per-device shape."""
    from bigdl_tpu.ops.pallas import lrn as plrn
    from bigdl_tpu.ops.pallas.per_shard import kernel_shards
    shards = kernel_shards(x.shape) if x.ndim == 4 else None
    local = x if shards is None else jax.ShapeDtypeStruct(
        shards.local_shape, x.dtype)
    if not plrn.lrn_supported(local):
        return None

    def kernel(x):
        return plrn.lrn(x, size, alpha, beta, k, False, relu)

    return kernel(x) if shards is None else shards.run(kernel, x)


class SpatialCrossMapLRN(Module):
    """AlexNet/Inception local response normalization across channels
    (reference nn/SpatialCrossMapLRN.scala, threaded; here one
    reduce_window over the channel axis with an analytic custom VJP).

    y = x / (k + alpha/size * sum_{local} x^2)^beta

    The hand-written backward matters on TPU: autodiff of the naive graph
    materializes ~5 full-size f32 tensors per LRN (profiled №1 HBM consumer
    of an Inception train step); the analytic form needs one window-sum and
    keeps residuals in the activation dtype.
    """

    def __init__(self, size: int = 5, alpha: float = 1.0,
                 beta: float = 0.75, k: float = 1.0):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k

    def apply(self, params, state, x, *, training=False, rng=None):
        # fused single-HBM-pass kernel — profiled ~4x less LRN traffic
        # than the reduce_window path
        y = _pallas_lrn(x, self.size, self.alpha, self.beta, self.k)
        if y is None:
            y = _lrn(x, self.size, self.alpha, self.beta, self.k)
        return y, state


class ReLUCrossMapLRN(_Sequential):
    """TPU fusion of ReLU -> SpatialCrossMapLRN in ONE HBM pass.

    A Sequential of the two child modules — child names, the (name-keyed)
    parameter table, and .t7 export stay reference-faithful, and the
    fused forward is equivalent to running the children in order (both
    are parameter-free). Note: introducing the wrapper into a model DOES
    shift that model's index-keyed Sequential pytree (sibling indices
    change), like any structural edit — raw ``save``d checkpoints from
    before the edit don't line up, name-based flows (Caffe/Torch import,
    parameter table) do. On TPU the Pallas kernel applies the ReLU in
    VMEM, eliminating
    the standalone elementwise read+write of the activation (profiled on
    Inception-v1: the conv2/relu_3x3 pass alone moves ~620 MB/step at
    batch 256); elsewhere the Sequential fallback runs the children.
    """

    def __init__(self, relu, lrn):
        super().__init__(relu, lrn)

    def apply(self, params, state, x, *, training=False, rng=None):
        m = self.modules[1]
        y = _pallas_lrn(x, m.size, m.alpha, m.beta, m.k, relu=True)
        if y is not None:
            return y, state
        return super().apply(params, state, x, training=training, rng=rng)


class Normalize(Module):
    """Lp-normalize over the feature axis (reference nn/Normalize.scala)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p, self.eps = p, eps

    def apply(self, params, state, x, *, training=False, rng=None):
        if np.isinf(self.p):
            n = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        else:
            n = jnp.power(jnp.sum(jnp.power(jnp.abs(x), self.p), axis=-1,
                                  keepdims=True), 1.0 / self.p)
        return x / jnp.maximum(n, self.eps), state


def _gaussian_kernel(kernel_size: int) -> np.ndarray:
    """Default 2-D gaussian used by the reference's subtractive/divisive
    normalization (Torch image.gaussian semantics)."""
    sigma = 0.25 * kernel_size  # torch default sigma=0.25 relative
    ax = np.arange(kernel_size) - (kernel_size - 1) / 2.0
    g = np.exp(-(ax ** 2) / (2 * sigma ** 2))
    k = np.outer(g, g)
    return (k / k.sum()).astype(np.float32)


class SpatialSubtractiveNormalization(Module):
    """Subtract local weighted mean (reference
    nn/SpatialSubtractiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None):
        super().__init__()
        self.n_input_plane = n_input_plane
        k = np.asarray(kernel, np.float32) if kernel is not None \
            else _gaussian_kernel(9)
        self.kernel = k / (k.sum() * n_input_plane)

    def _local_mean(self, x):
        kh, kw = self.kernel.shape
        w = jnp.asarray(self.kernel)[None, None].repeat(
            self.n_input_plane, axis=1)
        mean = jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), (1, 1),
            padding=[((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # divide by local window mass (border correction, as Torch does via
        # convolving a ones image; the kernel is already normalized by
        # ksum * n_input_plane, so interior coef == 1 — dividing by
        # coef * n again would shrink the mean n-fold, caught by
        # test_subtractive_normalization_zeroes_constant_input)
        ones = jnp.ones((1, self.n_input_plane) + x.shape[2:], x.dtype)
        coef = jax.lax.conv_general_dilated(
            ones, w.astype(x.dtype), (1, 1),
            padding=[((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return mean / coef

    def apply(self, params, state, x, *, training=False, rng=None):
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        y = x - self._local_mean(x)
        if squeeze:
            y = y[0]
        return y, state


class SpatialDivisiveNormalization(SpatialSubtractiveNormalization):
    """Divide by local weighted std (reference
    nn/SpatialDivisiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__(n_input_plane, kernel)
        self.threshold, self.thresval = threshold, thresval

    def apply(self, params, state, x, *, training=False, rng=None):
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        local_std = jnp.sqrt(jnp.maximum(self._local_mean(jnp.square(x)),
                                         0.0))
        mean_std = jnp.mean(local_std, axis=(2, 3), keepdims=True)
        den = jnp.maximum(local_std, mean_std)
        den = jnp.where(den < self.threshold, self.thresval, den)
        y = x / den
        if squeeze:
            y = y[0]
        return y, state


class SpatialContrastiveNormalization(Module):
    """Subtractive then divisive normalization (reference
    nn/SpatialContrastiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel,
                                                threshold, thresval)

    def apply(self, params, state, x, *, training=False, rng=None):
        y, _ = self.sub.apply({}, {}, x, training=training)
        y, _ = self.div.apply({}, {}, y, training=training)
        return y, state


class LayerNorm(Module):
    """Per-sample normalization over the trailing feature axis.

    Not in the reference (its era normalized with BatchNorm only); carried
    as the TPU-era extension the transformer stack (nn/attention.py,
    models/transformer) requires. Statistics in f32 like BatchNorm."""

    def __init__(self, n_output: int, eps: float = 1e-5,
                 affine: bool = True):
        super().__init__()
        self.n_output, self.eps, self.affine = n_output, eps, affine

    def init(self, rng):
        if not self.affine:
            return {}
        return {"weight": jnp.ones((self.n_output,), default_dtype()),
                "bias": jnp.zeros((self.n_output,), default_dtype())}

    def apply(self, params, state, x, *, training=False, rng=None):
        f32 = jnp.promote_types(x.dtype, jnp.float32)
        xs = x.astype(f32)
        mean = jnp.mean(xs, axis=-1, keepdims=True)
        var = jnp.var(xs, axis=-1, keepdims=True)
        y = (xs - mean) * jax.lax.rsqrt(var + self.eps)
        if self.affine:
            y = y * params["weight"].astype(f32) \
                + params["bias"].astype(f32)
        return y.astype(x.dtype), state


class RMSNorm(Module):
    """y = x / sqrt(mean(x^2) + eps) * w over the trailing feature axis,
    no mean subtracted and no bias (Zhang & Sennrich, arXiv:1910.07467).

    ``unit_offset=True`` stores w - 1 (initialised to zero) and scales by
    1 + g, so weight decay pulls the scale towards one. The mean of
    squares and the division are float32 whatever x is; the result is
    rounded ONCE to the policy's activation dtype — a float32 residual
    stream comes out as activations — and scaled there, or stays float32
    throughout under ``fp32=True``."""

    def __init__(self, n_output: int, eps: float = 1e-5,
                 unit_offset: bool = False, fp32: bool = False):
        super().__init__()
        self.n_output, self.eps = n_output, eps
        self.unit_offset, self.fp32 = unit_offset, fp32

    def init(self, rng):
        fill = jnp.zeros if self.unit_offset else jnp.ones
        return {"weight": fill((self.n_output,), default_dtype())}

    def apply(self, params, state, x, *, training=False, rng=None):
        f32 = jnp.promote_types(x.dtype, jnp.float32)
        xs = x.astype(f32)
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(xs), axis=-1,
                                     keepdims=True) + self.eps)
        dt = f32 if self.fp32 else activation_dtype()
        w = params["weight"].astype(dt)
        if self.unit_offset:
            w = 1.0 + w
        return (xs * inv).astype(dt) * w, state

    def __repr__(self):
        return (f"RMSNorm({self.n_output}, eps={self.eps}, "
                f"unit_offset={self.unit_offset})")

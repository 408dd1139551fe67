"""Weight initialization methods.

Reference parity: nn/InitializationMethod.scala:24-47 — ``Default``,
``Xavier``, ``BilinearFiller``; the per-layer default stdv rules live in each
layer's ``reset()`` (e.g. Linear stdv = 1/sqrt(inputSize),
SpatialConvolution stdv = 1/sqrt(kW*kH*nInputPlane)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.tensor import default_dtype

__all__ = ["Default", "Xavier", "BilinearFiller", "uniform_reset"]

Default = "default"
Xavier = "xavier"
BilinearFiller = "bilinear_filler"


def uniform_reset(rng, shape, stdv, dtype=None):
    """Torch-style reset: uniform(-stdv, stdv)."""
    return jax.random.uniform(rng, shape, dtype or default_dtype(),
                              minval=-stdv, maxval=stdv)


def init_weight(method, rng, shape, fan_in, fan_out, dtype=None):
    """Dispatch on init method (reference InitializationMethod.scala);
    a callable ``method(rng, shape, dtype)`` is the initialiser itself."""
    dtype = dtype or default_dtype()
    if callable(method):
        return method(rng, shape, dtype)
    if method == Default:
        stdv = 1.0 / np.sqrt(fan_in)
        return uniform_reset(rng, shape, stdv, dtype)
    if method == Xavier:
        limit = np.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(rng, shape, dtype, -limit, limit)
    if method == BilinearFiller:
        # reference SpatialFullConvolution bilinear upsampling kernel init
        assert len(shape) == 4, "BilinearFiller expects OIHW conv weights"
        _, _, kh, kw = shape
        f = np.ceil(kw / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        w = np.zeros(shape, np.float32)
        for i in range(kh):
            for j in range(kw):
                w[:, :, i, j] = (1 - abs(i / f - c)) * (1 - abs(j / f - c))
        return jnp.asarray(w, dtype)
    raise ValueError(f"unknown init method: {method}")

"""Mamba-2's mixer (Dao & Gu, arXiv:2405.21060) as Nemotron-H uses it
(arXiv:2504.03624): a selective state-space layer of H heads of P, B and
C shared by the heads of each of G groups, a state of N a head, a causal
depthwise convolution in front and a gated RMS norm behind.

    [z | xBC | dt] = W_in h                       (no bias)
    xBC = silu(causal_conv_K(xBC) + b_conv)       (depthwise, zero history)
    x, B, C = split(xBC)                          (H P | G N | G N)
    Delta = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(Delta_t A) S_{t-1} + Delta_t B_t x_t^T,  y_t = C_t^T S_t + D x_t
    y = RMSNorm_grouped(y * silu(z)) * w          (G groups of H P / G)
    out = W_out y                                 (no bias)

docs/state_space.md has the equations, the chunked algorithm and its
backward pass. The scan is ``ops/pallas/ssd.py`` on the TPU and
``ssd_chunked_xla`` (the same chunked algorithm in ``jax.numpy``)
elsewhere; the per-position recurrence is the plain reference's
(benchmarks/reference/nemotron.py) and the tests'. The conv + silu and
the gated norm are ``ops/pallas/ssm_pointwise.py`` on the TPU (one pass
each way) and ``causal_conv1d`` / ``gated_rms_norm`` elsewhere.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import init as init_mod
from bigdl_tpu.nn.module import Module
from bigdl_tpu.tensor import activation_dtype, compute_dtype, default_dtype

__all__ = ["Mamba2Mixer", "ssd_chunked_xla", "causal_conv1d",
           "gated_rms_norm"]

F32 = jnp.float32
#: Mamba-2's range for A = exp(A_log) at initialisation
A_INIT = (1.0, 16.0)


def causal_conv1d(x, w, b):
    """Depthwise causal convolution over time: ``x`` (B, S, C), ``w``
    (C, K), ``b`` (C,); out[t] = b + sum_i w[:, i] x[t + i - K + 1], the
    positions before the sequence's start zero. float32."""
    k = w.shape[1]
    xs = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    w = w.astype(F32)
    return b.astype(F32) + sum(xs[:, i:i + s] * w[:, i] for i in range(k))


def gated_rms_norm(y, z, w, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal slices of the last axis of
    ``y * silu(z)``, times ``w``: the gate before the norm
    (``norm_before_gate=False``). float32."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    shape = g.shape
    g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return g.reshape(shape) * w.astype(F32)


def ssd_chunked_xla(x, dt, a, bm, cm, d, *, chunk: int):
    """The SSD scan of ``ops/pallas/ssd.ssd`` (same arguments, same
    result) in ``jax.numpy``: inside a chunk the masked product
    (L o C B^T o Delta_s) x, each chunk's own state, the states passed
    from chunk to chunk by a ``lax.scan`` over the chunks, and
    exp(Lambda_t) C_t^T S_in added. Products in x's dtype with float32
    accumulation; Lambda, the decays, the states and D x float32.
    Materialises (B, S / chunk, H, L, L) decays: the CPU's path and the
    tests' second witness."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    if s % chunk:
        raise ValueError(f"ssd: sequence length {s} is not a multiple of "
                         f"the chunk {chunk}")
    nc, r, cd = s // chunk, h // g, x.dtype

    def mm(spec, *ops):
        return jnp.einsum(spec, *ops, preferred_element_type=F32)

    xc = x.reshape(b, nc, chunk, g, r, p)
    bc = bm.reshape(b, nc, chunk, g, n).astype(cd)
    cc = cm.reshape(b, nc, chunk, g, n).astype(cd)
    dtc = dt.astype(F32).reshape(b, nc, chunk, g, r)
    lam = jnp.cumsum(dtc * a.astype(F32).reshape(g, r), axis=2)
    lam_t = jnp.moveaxis(lam, 2, -1)                        # (b nc g r L)
    dt_t = jnp.moveaxis(dtc, 2, -1)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, lam_t[..., :, None]
                              - lam_t[..., None, :], -jnp.inf))
    cb = mm("bctgn,bcsgn->bcgts", cc, bc)
    m = (cb[:, :, :, None] * decay * dt_t[..., None, :]).astype(cd)
    y = mm("bcgrts,bcsgrp->bctgrp", m, xc)
    last = lam_t[..., -1:]
    w = (jnp.exp(last - lam_t) * dt_t)                      # (b nc g r L)
    own = mm("bcgrs,bcsgn,bcsgrp->bcgrnp", w.astype(cd), bc, xc)

    def carry(state, inp):
        own_c, last_c = inp
        return jnp.exp(last_c)[..., None] * state + own_c, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((b, g, r, n, p), F32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(last, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # (b nc g r n p)
    y = y + jnp.exp(lam)[..., None] * mm(
        "bctgn,bcgrnp->bctgrp", cc, entering.astype(cd))
    y = y + d.astype(F32).reshape(g, r, 1) * xc.astype(F32)
    return y.reshape(b, s, h, p).astype(cd)


class Mamba2Mixer(Module):
    """One Mamba-2 mixer on (B, S, ``d_model``): the equations of the
    module docstring, ``heads`` x ``head_dim`` wide, ``groups`` groups
    of B and C of ``state`` each, a causal conv of ``conv_kernel`` over
    x, B and C, the scan in chunks of ``chunk``.

    Parameters float32: ``in_weight`` (2 H P + 2 G N + H, d), ``conv_weight``
    (H P + 2 G N, K), ``conv_bias``, ``dt_bias``, ``A_log``, ``D`` (H,),
    ``norm_weight`` (H P,), ``out_weight`` (d, H P). Initialised as
    Mamba-2 does (``A`` uniform in ``A_INIT``, Delta log-uniform in
    ``dt_init`` floored at ``dt_floor`` through the inverse softplus of
    ``dt_bias``, D one, the norm's weight one, the conv as torch's
    Conv1d), the projections as ``Linear``'s default.

    The projections and the scan's products run in the compute dtype
    with float32 accumulation; dt's columns of W_in leave the matmul in
    float32, and Delta, A, the conv, the scan's decays and state, D x
    and the gated norm are float32. On the TPU the scan is the Pallas
    kernel (``ops/pallas/ssd.py``) and shapes it does not take are an
    error, never another path; elsewhere ``ssd_chunked_xla``. So with
    the conv + silu and the gated norm: on the TPU one Pallas pass each
    way (``ops/pallas/ssm_pointwise.py``: compute dtype in and out,
    float32 inside, no float32 residual), elsewhere ``causal_conv1d``
    and ``gated_rms_norm``. Scopes: ``ssm_project`` (W_in, the conv under
    ``ssm_conv``, the gated norm under ``ssm_norm``, W_out) and
    ``ssm_scan``."""

    def __init__(self, d_model: int, heads: int, head_dim: int,
                 groups: int, state: int, conv_kernel: int = 4,
                 chunk: int = 128, eps: float = 1e-5,
                 dt_init=(1e-3, 0.1), dt_floor: float = 1e-4):
        super().__init__()
        if heads % groups:
            raise ValueError(f"{heads} heads do not split into {groups} "
                             "groups")
        self.d_model, self.heads, self.head_dim = d_model, heads, head_dim
        self.groups, self.state_size = groups, state
        self.conv_kernel = conv_kernel
        self.chunk, self.eps = chunk, eps
        self.dt_init, self.dt_floor = dt_init, dt_floor
        self.inner = heads * head_dim
        self.conv_dim = self.inner + 2 * groups * state

    def init(self, rng):
        d, h, inner = self.d_model, self.heads, self.inner
        k_in, k_out, k_cw, k_cb, k_dt, k_a = jax.random.split(rng, 6)
        proj = 2 * inner + 2 * self.groups * self.state_size + h
        dtype = default_dtype()
        lo, hi = self.dt_init
        dt = jnp.exp(jax.random.uniform(k_dt, (h,), F32, math.log(lo),
                                        math.log(hi)))
        dt = jnp.maximum(dt, self.dt_floor)
        bound = 1.0 / math.sqrt(self.conv_kernel)
        return {
            "in_weight": init_mod.init_weight(init_mod.Default, k_in,
                                              (proj, d), fan_in=d,
                                              fan_out=proj),
            "conv_weight": init_mod.uniform_reset(
                k_cw, (self.conv_dim, self.conv_kernel), bound, dtype),
            "conv_bias": init_mod.uniform_reset(k_cb, (self.conv_dim,),
                                                bound, dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(
                k_a, (h,), F32, *A_INIT)).astype(dtype),
            "D": jnp.ones((h,), dtype),
            "norm_weight": jnp.ones((inner,), dtype),
            "out_weight": init_mod.init_weight(init_mod.Default, k_out,
                                               (d, inner), fan_in=inner,
                                               fan_out=d),
        }

    def scan_inputs(self, params, x, *, fused: bool = False):
        """(z, x, Delta, A, B, C) of the scan from the layer's normed
        input (B, S, d): z and x (B, S, H, P) and B, C (B, S, G, N) in
        the compute dtype, Delta (B, S, H) and A (H,) float32. W_in is
        three products, z | xBC | dt, so that each pass reads its own
        columns whole. ``fused``: the conv + silu + split as ONE pass
        (``ops/pallas/ssm_pointwise.conv_silu_split``), else
        ``causal_conv1d``."""
        from bigdl_tpu.ops.pallas import ssm_pointwise as pointwise
        b, s, _ = x.shape
        cd = compute_dtype()
        h, inner, gn = self.heads, self.inner, self.groups * self.state_size
        w = params["in_weight"].astype(cd)
        hx = x.astype(cd)
        split = inner + self.conv_dim
        z = jnp.matmul(hx, w[:inner].T)
        xbc = jnp.matmul(hx, w[inner:split].T)
        dt = jnp.matmul(hx, w[split:].T, preferred_element_type=F32)
        with jax.named_scope("ssm_conv"):
            if fused:
                xs, bm, cm = pointwise.conv_silu_split(
                    xbc, params["conv_weight"], params["conv_bias"],
                    heads=h, head_dim=self.head_dim, groups=self.groups,
                    state=self.state_size)
            else:
                xbc = jax.nn.silu(causal_conv1d(
                    xbc, params["conv_weight"],
                    params["conv_bias"])).astype(cd)
                xs = xbc[..., :inner].reshape(b, s, h, self.head_dim)
                bm, cm = (xbc[..., inner + i * gn:inner + (i + 1) * gn]
                          .reshape(b, s, self.groups, self.state_size)
                          for i in (0, 1))
        delta = jax.nn.softplus(dt + params["dt_bias"].astype(F32))
        a = -jnp.exp(params["A_log"].astype(F32))
        return z, xs, delta, a, bm, cm

    def apply(self, params, state, x, *, training=False, rng=None):
        from bigdl_tpu.observability import trace
        from bigdl_tpu.ops.pallas import ssd as kernels
        from bigdl_tpu.ops.pallas import ssm_pointwise as pointwise
        b, s, _ = x.shape
        cd = compute_dtype()
        on_kernel = jax.default_backend() == "tpu"
        # python runs this when the layer is traced for a compile, never
        # in a step
        trace.instant("mamba_mixer", cat="nn", seq=s, heads=self.heads,
                      head_dim=self.head_dim, groups=self.groups,
                      state=self.state_size, chunk=self.chunk,
                      conv_kernel=self.conv_kernel,
                      scan="ssd_kernel" if on_kernel else "ssd_chunked_xla",
                      pointwise="fused_kernel" if on_kernel else "xla",
                      pointwise_bytes=pointwise.pointwise_bytes(
                          b * s, self.inner, self.conv_dim,
                          jnp.dtype(cd).itemsize))
        with jax.named_scope("ssm_project"):
            z, xs, delta, a, bm, cm = self.scan_inputs(params, x,
                                                       fused=on_kernel)
        with jax.named_scope("ssm_scan"):
            scan = kernels.ssd if on_kernel else ssd_chunked_xla
            y = scan(xs, delta, a, bm, cm, params["D"], chunk=self.chunk)
        with jax.named_scope("ssm_project"):
            with jax.named_scope("ssm_norm"):
                if on_kernel:
                    y = pointwise.gated_norm(y, z, params["norm_weight"],
                                             self.groups, self.eps)
                else:
                    y = gated_rms_norm(y.reshape(b, s, self.inner), z,
                                       params["norm_weight"], self.groups,
                                       self.eps).astype(cd)
            out = jnp.matmul(y, params["out_weight"].astype(cd).T)
        return out.astype(activation_dtype()), state

    def __repr__(self):
        return (f"Mamba2Mixer({self.d_model}, heads={self.heads}x"
                f"{self.head_dim}, groups={self.groups}, state="
                f"{self.state_size}, chunk={self.chunk})")

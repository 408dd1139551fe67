"""Fused LM-head + cross-entropy Pallas TPU kernel (chunked over vocab).

Why this kernel exists (round-3 LM trace, docs/PERF.md): at vocab 32k /
S=2048 / batch 8, the unfused loss path materializes the (B, S, V) logits
THREE times — the bf16 head-GEMM output (1 GB), an f32 convert the
softmax statistics read (2.15 GB — XLA materializes it because lse, max
and the target gather all consume it), and the bf16 dlogits cotangent
(1 GB) — ~10 ms of the 44.5 ms step. This kernel streams vocab tiles
through VMEM with an online logsumexp, exactly like flash attention
streams K/V tiles, so full logits never exist:

- forward:  read h (N, D), W (V, D), b — emit per-token nll and lse.
- backward: recompute the logits tile-by-tile from (h, W, lse) and
  accumulate dh (tokens outer, vocab inner) and dW/db (vocab outer,
  tokens inner) in two passes — one extra head-GEMM of FLOPs in exchange
  for ~4 GB less HBM traffic per step.

Matmuls run in the storage dtype (bf16 on the MXU, f32 accumulation);
softmax statistics are f32 in VMEM. Targets are 1-based, matching the
reference's ClassNLLCriterion convention (nn/ClassNLLCriterion.scala).

This is a training-path op for big-vocab LMs; the module-level
``CrossEntropyCriterion`` (nn/criterion.py) remains the general API.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["linear_cross_entropy", "linear_ce_supported"]

logger = logging.getLogger("bigdl_tpu.ops")

# token/vocab tiles: the (BT, BV) f32 logits tile plus double-buffered
# W tiles must fit the 16 MB VMEM budget — 512x1024 keeps the dh kernel
# at ~8 MB with bf16 W at D=512 (1024x2048 OOMed on v5e). The menu is
# the fallback — an autotuned record (bigdl_tpu/tuning) for this
# (tokens, vocab, device kind) wins when one exists and is legal.
_T_BLOCKS = (512, 256, 128)
_V_BLOCKS = (1024, 512, 256, 128)
# Mosaic's default scoped-VMEM limit (16 MiB of the v5e's 128) refuses
# the 512x1024 menu tile from D=1024 up (the dW kernel alone holds
# double-buffered W and dW tiles plus an f32 accumulator); with this
# limit the menu compiles up to _MAX_ROW_BYTES per feature row (bf16
# D=4096, f32 D=2048 — compiled for the v5e, PR 21)
_VMEM_LIMIT_BYTES = 64 * 2 ** 20
_MAX_ROW_BYTES = 8192


def _pick(n, menu):
    return next((b for b in menu if n % b == 0), None)


def _pick_tiles(n: int, v: int) -> tuple[int, int]:
    """(BT, BV) for (tokens, vocab): tuned record first, static menu
    otherwise. Used identically by forward and both backward kernels so
    a tuning record retiles the whole op."""
    from bigdl_tpu.tuning.records import default_records
    cfg = default_records().lookup("fused_ce", {"n": n, "v": v})
    if cfg:
        try:
            bt, bv = int(cfg["bt"]), int(cfg["bv"])
        except (KeyError, TypeError, ValueError):
            bt = bv = 0
        if bt >= 8 and bv >= 128 and n % bt == 0 and v % bv == 0:
            return bt, bv
        logger.warning("ignoring illegal fused_ce tuning record %s "
                       "for n=%d v=%d", cfg, n, v)
    return _pick(n, _T_BLOCKS), _pick(v, _V_BLOCKS)


def _tiles_ok(h, w) -> bool:
    return (h.shape[0] % _T_BLOCKS[-1] == 0
            and w.shape[0] % _V_BLOCKS[-1] == 0
            and h.shape[1] % 128 == 0
            and h.shape[1] * jnp.dtype(h.dtype).itemsize <= _MAX_ROW_BYTES)


def linear_ce_supported(h, w) -> bool:
    """TPU backend with tile-divisible token count / vocab and a
    lane-tileable feature dim narrow enough for the tiles to fit VMEM."""
    return jax.default_backend() == "tpu" and _tiles_ok(h, w)


def _logits_tile(h_ref, w_ref, b_ref):
    s = jax.lax.dot_general(h_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s + b_ref[...]


def _onehot_tile(t_ref, vi, bt, bv):
    """(BT, BV) one-hot of the (1-based) targets within vocab tile vi."""
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1) + vi * bv
    return (col == t_ref[...] - 1).astype(jnp.float32)


def _fwd_kernel(h_ref, w_ref, b_ref, t_ref, nll_ref, lse_ref,
                m_scr, l_scr, tl_scr, *, nv, bt, bv):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        tl_scr[:] = jnp.zeros_like(tl_scr)

    s = _logits_tile(h_ref, w_ref, b_ref)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_scr[:] = l_scr[:] * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True)
    m_scr[:] = m_new
    tl_scr[:] = tl_scr[:] + jnp.sum(
        s * _onehot_tile(t_ref, vi, bt, bv), axis=1, keepdims=True)

    @pl.when(vi == nv - 1)
    def _finalize():
        lse = m_scr[:] + jnp.log(l_scr[:])
        lse_ref[...] = lse
        nll_ref[...] = lse - tl_scr[:]


def _dh_kernel(h_ref, w_ref, b_ref, t_ref, lse_ref, g_ref, dh_ref,
               dh_scr, *, nv, bt, bv):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    s = _logits_tile(h_ref, w_ref, b_ref)
    dlogits = (jnp.exp(s - lse_ref[...])
               - _onehot_tile(t_ref, vi, bt, bv)) * g_ref[...]
    dh_scr[:] = dh_scr[:] + jax.lax.dot_general(
        dlogits.astype(w_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vi == nv - 1)
    def _finalize():
        dh_ref[...] = dh_scr[:].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, b_ref, t_ref, lse_ref, g_ref,
               dw_ref, db_ref, dw_scr, db_scr, *, nt, bt, bv):
    ti = pl.program_id(1)
    vi = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    s = _logits_tile(h_ref, w_ref, b_ref)
    dlogits = (jnp.exp(s - lse_ref[...])
               - _onehot_tile(t_ref, vi, bt, bv)) * g_ref[...]
    dw_scr[:] = dw_scr[:] + jax.lax.dot_general(
        dlogits.astype(h_ref.dtype), h_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_scr[:] = db_scr[:] + jnp.sum(dlogits, axis=0, keepdims=True)

    @pl.when(ti == nt - 1)
    def _finalize():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)
        db_ref[...] = db_scr[:].astype(db_ref.dtype)


def _specs(bt, bv, d):
    h_spec = pl.BlockSpec((bt, d), lambda t, v: (t, 0))
    w_spec = pl.BlockSpec((bv, d), lambda t, v: (v, 0))
    b_spec = pl.BlockSpec((1, bv), lambda t, v: (0, v))
    t_spec = pl.BlockSpec((bt, 1), lambda t, v: (t, 0))
    return h_spec, w_spec, b_spec, t_spec


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _linear_ce(h, w, b, targets, interpret):
    nll, _ = _forward(h, w, b, targets, interpret)
    return nll


def _forward(h, w, b, targets, interpret):
    from jax.experimental.pallas import tpu as pltpu
    n, d = h.shape
    v = w.shape[0]
    bt, bv = _pick_tiles(n, v)
    nt, nv = n // bt, v // bv
    h_spec, w_spec, b_spec, t_spec = _specs(bt, bv, d)
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, nv=nv, bt=bt, bv=bv),
        grid=(nt, nv),
        in_specs=[h_spec, w_spec, b_spec, t_spec],
        out_specs=[t_spec, t_spec],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)] * 3,
        interpret=interpret,
        name="fused_ce_fwd",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(h, w, b.reshape(1, v), targets.reshape(n, 1).astype(jnp.int32))
    return nll[:, 0], lse


def _linear_ce_fwd(h, w, b, targets, interpret):
    nll, lse = _forward(h, w, b, targets, interpret)
    return nll, (h, w, b, targets, lse)


def _linear_ce_bwd(interpret, res, g):
    from jax.experimental.pallas import tpu as pltpu
    h, w, b, targets, lse = res
    n, d = h.shape
    v = w.shape[0]
    bt, bv = _pick_tiles(n, v)
    nt, nv = n // bt, v // bv
    h_spec, w_spec, b_spec, t_spec = _specs(bt, bv, d)
    g2 = g.reshape(n, 1).astype(jnp.float32)
    t2 = targets.reshape(n, 1).astype(jnp.int32)
    b2 = b.reshape(1, v)
    params = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)

    dh = pl.pallas_call(
        functools.partial(_dh_kernel, nv=nv, bt=bt, bv=bv),
        grid=(nt, nv),
        in_specs=[h_spec, w_spec, b_spec, t_spec, t_spec, t_spec],
        out_specs=h_spec,
        out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
        name="fused_ce_dh",
        compiler_params=params,
    )(h, w, b2, t2, lse, g2)

    # vocab tiles outer, token tiles inner — dW/db accumulate over tokens
    h_spec_w = pl.BlockSpec((bt, d), lambda v_, t: (t, 0))
    w_spec_w = pl.BlockSpec((bv, d), lambda v_, t: (v_, 0))
    b_spec_w = pl.BlockSpec((1, bv), lambda v_, t: (0, v_))
    t_spec_w = pl.BlockSpec((bt, 1), lambda v_, t: (t, 0))
    db_spec = pl.BlockSpec((1, bv), lambda v_, t: (0, v_))
    dw, db = pl.pallas_call(
        functools.partial(_dw_kernel, nt=nt, bt=bt, bv=bv),
        grid=(nv, nt),
        in_specs=[h_spec_w, w_spec_w, b_spec_w, t_spec_w, t_spec_w,
                  t_spec_w],
        out_specs=[w_spec_w, db_spec],
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct((1, v), b.dtype)],
        scratch_shapes=[pltpu.VMEM((bv, d), jnp.float32),
                        pltpu.VMEM((1, bv), jnp.float32)],
        interpret=interpret,
        name="fused_ce_dw",
        compiler_params=params,
    )(h, w, b2, t2, lse, g2)
    return dh, dw, db.reshape(v), None


_linear_ce.defvjp(_linear_ce_fwd, _linear_ce_bwd)


def linear_cross_entropy(h, w, b, targets, *, reduction: str = "mean",
                         use_kernel: str | bool = "auto",
                         interpret: bool = False):
    """Cross-entropy over ``logits = h @ w.T + b`` WITHOUT materializing
    the logits (kernel path), for (N, D) activations, (V, D) torch-layout
    weight, (V,) bias (or None) and 1-based integer ``targets`` (N,).

    ``use_kernel``: "auto" picks the Pallas path on TPU when shapes tile
    (``linear_ce_supported``); True forces it (raises otherwise); False
    uses the XLA fallback (identical math, materialized logits).
    Returns the scalar mean (or summed) negative log-likelihood.

    Contract: targets must lie in ``[1, V]`` (1-based, reference
    ClassNLLCriterion convention). An out-of-contract target — e.g. a
    0 padding label — contributes ``nll = lse`` (its one-hot matches no
    class) on BOTH paths; mask padding out before calling if that is
    not the intent.
    """
    n = h.shape[0]
    bias = b if b is not None else jnp.zeros((w.shape[0],), h.dtype)
    # interpret substitutes for the TPU backend, never for the tiling
    supported = _tiles_ok(h, w) and (interpret
                                     or jax.default_backend() == "tpu")
    if use_kernel is True and not supported:
        raise ValueError(
            f"use_kernel=True but the fused CE kernel does not support "
            f"this call: backend={jax.default_backend()}, h{h.shape} "
            f"w{w.shape} (need TPU, tokens % {_T_BLOCKS[-1]} == 0, vocab "
            f"% {_V_BLOCKS[-1]} == 0, features % 128 == 0)")
    if use_kernel is not False and supported:
        nll = _linear_ce(h, w, bias, targets, interpret)
    else:
        logits = (h @ w.T.astype(h.dtype)).astype(jnp.float32) + bias
        lse = jax.nn.logsumexp(logits, axis=-1)
        t0 = targets.astype(jnp.int32) - 1
        tl = jnp.take_along_axis(
            logits, jnp.clip(t0, 0, w.shape[0] - 1)[:, None], axis=-1)[:, 0]
        # out-of-contract targets match no class — same as the kernel's
        # one-hot semantics (instead of take_along_axis index wrap-around)
        in_contract = (t0 >= 0) & (t0 < w.shape[0])
        nll = lse - jnp.where(in_contract, tl, 0.0)
    total = jnp.sum(nll)
    return total / n if reduction == "mean" else total

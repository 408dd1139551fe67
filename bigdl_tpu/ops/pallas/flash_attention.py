"""Fused Pallas TPU flash attention (forward + FlashAttention-2 backward).

Why this kernel exists: the stack's naive attention core
(parallel/sequence.py `dot_product_attention`) materializes the full
(B, H, S, S) score matrix in f32 — at S=4096, H=8, B=1 that is 512 MB of
HBM traffic per direction per layer, and O(S^2) memory caps the sequence
length a chip can hold. This kernel streams K/V blocks through VMEM with
an online softmax, so HBM traffic is O(S·D) and live memory is one
(BLOCK_Q, BLOCK_K) tile per program:

- forward:  read q/k/v, write o and the per-row logsumexp — the softmax
  normalizer is the only residual beyond the layer's own inputs/outputs.
- backward: two kernels (dq; dk+dv fused) recompute probabilities from
  q/k/lse instead of loading an S×S matrix; plus an elementwise
  delta = rowsum(dO ∘ O) precomputed on the XLA path.

The construction follows the public FlashAttention/FlashAttention-2
algorithm (see PAPERS.md); causal masking skips fully-masked tiles at
the grid level. All arithmetic is f32 in VMEM; q/k/v/o touch HBM in
their own (typically bf16) dtype.

Reference scope note: the reference predates transformers (SURVEY §5.7)
— attention itself is already beyond parity; this kernel is the TPU-hot
path for the framework's long-context story (ring/Ulysses sequence
parallelism compose with it: each shard's local attention is this
kernel whenever shapes allow).
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_supported"]

logger = logging.getLogger("bigdl_tpu.ops")

# block-size menu: largest tile dividing the sequence wins — bigger tiles
# amortize grid overhead and keep the MXU busy (512x1024 measured 2.7x the
# 128x128 fwd at S=4096 on v5e); VMEM peak stays ~4 MB (s+p f32 tiles).
# The menu is the FALLBACK: a measured winner in the tuning record store
# (bigdl_tpu/tuning/) for this (sq, skv, device kind) takes precedence,
# and sequences no menu entry divides fall back to generated divisors
# (_divisor_fallback) before giving up.
_Q_BLOCKS = (512, 256, 128)
_K_BLOCKS = (1024, 512, 256, 128)


def _tuned_blocks(sq: int, skv: int) -> tuple[int, int] | None:
    """Autotuned (BQ, BK) for this geometry on this device kind, if a
    record exists and is still legal for the shapes (a stale record —
    e.g. tuned for a different sequence — is ignored with a warning,
    never an error)."""
    from bigdl_tpu.tuning.records import default_records
    cfg = default_records().lookup("flash_attention",
                                   {"sq": sq, "skv": skv})
    if not cfg:
        return None
    try:
        bq, bk = int(cfg["bq"]), int(cfg["bk"])
    except (KeyError, TypeError, ValueError):
        bq = bk = 0
    if bq >= 8 and bk >= 8 and sq % bq == 0 and skv % bk == 0:
        return bq, bk
    logger.warning("ignoring illegal flash_attention tuning record "
                   "%s for sq=%d skv=%d", cfg, sq, skv)
    return None


def _divisor_fallback(s: int, cap: int) -> int | None:
    """Largest tile legally dividing ``s`` when no menu entry does:
    multiples of 16 (the bf16 sublane tile — legal for f32 too) from
    ``cap`` down to 128. E.g. s=320 -> 160, s=384 -> 384."""
    top = min(cap, s)
    for b in range(top - top % 16, 127, -16):
        if s % b == 0:
            return b
    return None


def _blocks_or_none(sq: int, skv: int) -> tuple[int, int] | None:
    tuned = _tuned_blocks(sq, skv)
    if tuned is not None:
        return tuned
    bq = next((b for b in _Q_BLOCKS if sq % b == 0), None) \
        or _divisor_fallback(sq, _Q_BLOCKS[0])
    bk = next((b for b in _K_BLOCKS if skv % b == 0), None) \
        or _divisor_fallback(skv, _K_BLOCKS[0])
    if bq is None or bk is None:
        return None
    return bq, bk


def _pick_blocks(sq: int, skv: int) -> tuple[int, int]:
    picked = _blocks_or_none(sq, skv)
    if picked is None:
        raise ValueError(
            f"flash_attention needs sequence lengths with a tile "
            f"divisor >= 128 (multiple of 16); got q_seq={sq}, "
            f"kv_seq={skv} "
            f"(use dot_product_attention's XLA path for ragged shapes)")
    return picked

_NEG = -1e9  # finite mask value, matches parallel/sequence.py


def flash_supported(q, k) -> bool:
    """Kernel constraints: TPU backend, sequence lengths ``_pick_blocks``
    can tile (menu, tuned record, or generated divisor — this predicate
    and the picker share ``_blocks_or_none``, so supported == will not
    raise), and a head dim Mosaic tiles cleanly. D=64 — the most common
    transformer geometry — engages the kernel (round 3: Mosaic pads the
    64-lane minor dim internally; measured faster than the XLA fallback,
    which the old ``d % 128`` guard silently forced)."""
    return (jax.default_backend() == "tpu"
            and _blocks_or_none(q.shape[1], k.shape[1]) is not None
            and q.shape[-1] % 64 == 0)


def _causal_mask(s, qi, ki, bq, bk):
    """Mask s (BQ, BK) for tile (qi, ki): kpos > qpos -> _NEG."""
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(kpos > qpos, _NEG, s)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, nk, bq, bk):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: tiles entirely above the diagonal contribute exactly zero
    # (exp(_NEG - m) underflows); skip their FLOPs at the grid level
    def _compute():
        # matmuls stay in the storage dtype (bf16 on the MXU at full rate,
        # f32 accumulation via preferred_element_type) — converting inputs
        # to f32 first runs the MXU at its 1/4-1/8 f32 rate and was the
        # round-2 kernel's S=2048 parity problem (docs/PERF.md round 3)
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = m_new

    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _fwd(q, k, v, scale, causal, interpret):
    from jax.experimental.pallas import tpu as pltpu
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq, bk = _pick_blocks(sq, skv)
    nq, nk = sq // bq, skv // bk
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal, nk=nk,
                             bq=bq, bk=bk)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------
# backward (FlashAttention-2): dq in one kernel, dk/dv fused in another
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, nk, bq, bk):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, nq, bq, bk):
    qi = pl.program_id(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk)
        p = jnp.exp(s - lse_ref[0])                     # (BQ, BK)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # dk accumulates ds^T q * scale (ds here carries no scale; fold it
        # at the end would change dq too — apply to the addend directly)
        ds = p * (dp - delta_ref[0]) * scale            # (BQ, BK)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, interpret, res, g):
    """VJP for (o, lse) outputs.

    The lse cotangent folds into the existing kernels: with lse an
    output, ds_ij gains + g_lse_i * p_ij (d lse_i / d s_ij = p_ij), so
    ds = p * (dp - (delta - g_lse)) — pass delta' = delta - g_lse and
    the dq/dkdv kernels are unchanged. dv has no direct lse term.
    """
    from jax.experimental.pallas import tpu as pltpu
    q, k, v, o, lse = res
    g, g_lse = g
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq, bk = _pick_blocks(sq, skv)
    nq, nk = sq // bq, skv // bk
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = delta - g_lse.astype(jnp.float32)

    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    kv_spec_q = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, nk=nk,
                          bq=bq, bk=bk),
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, g, lse, delta)

    # dk/dv: grid walks q blocks innermost for each k block
    q_spec_k = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))
    row_spec_k = pl.BlockSpec((1, bq, 1),
                              lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, causal=causal, nq=nq,
                          bq=bq, bk=bk),
        grid=(bh, nk, nq),
        in_specs=[q_spec_k, kv_spec, kv_spec, q_spec_k, row_spec_k,
                  row_spec_k],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dkdv",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public entry: (B, S, H, D) api matching parallel/sequence.py
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd(q, k, v, scale, causal, interpret):
    return _fwd(q, k, v, scale, causal, interpret)


def _flash_fwd(q, k, v, scale, causal, interpret):
    o, lse = _fwd(q, k, v, scale, causal, interpret)
    return (o, lse), (q, k, v, o, lse)


_flash_bhsd.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, interpret: bool = False):
    """Tiled online-softmax attention over (B, S, H, D).

    Drop-in for ``dot_product_attention`` (zero offsets); differentiable
    via the fused FlashAttention-2 backward. Requires sequence lengths
    ``_pick_blocks`` can tile (a divisor >= 128 that is a multiple of
    16) and a head_dim multiple of 64 (``flash_supported``); tile sizes
    scale up with S from the static menu unless an autotuned record
    (``bigdl_tpu/tuning``) overrides them.
    """
    o, _ = flash_attention_with_lse(q, k, v, causal=causal, scale=scale,
                                    interpret=interpret)
    return o


def flash_attention_with_lse(q, k, v, *, causal: bool = False,
                             scale: float | None = None,
                             interpret: bool = False):
    """``flash_attention`` that also returns the per-row logsumexp
    (B, S, H) of the scaled scores — the statistic blockwise/ring
    attention needs to merge partial results across sequence shards
    (parallel/sequence.py). Both outputs are differentiable.
    """
    b, sq, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    o, lse = _flash_bhsd(fold(q), fold(k), fold(v), scale, causal,
                         interpret)
    o = o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, sq).transpose(0, 2, 1)
    return o, lse

"""Latent attention's core (DeepSeek-V2/V3's MLA, as Kimi-VL-A3B's
language model trains it), one Pallas TPU kernel each way: causal
softmax attention whose score is the SUM OF TWO PRODUCTS — a per-head
content part and a rotary part whose key is ONE vector a position,
shared by every head — and whose values have a width of their own:

    s[t, j, h] = qN[t, h] . kN[j, h] + qR[t, h] . kR[j]        (j <= t)
    o[t, h]    = sum_j softmax_j(s[t, ., h]) v[j, h]

``qN, kN`` (B, S, H, DN), ``qR`` (B, S, H, DR), ``kR`` (B, S, DR), ``v``
(B, S, H, DV); Kimi-VL: 128 / 64 / 128, 16 heads. The softmax scale is
the caller's: it rides q (``nn.LatentAttention`` folds it into W_q), so
no score tile is ever multiplied by it.

A second file beside ``flash_attention.py``, sharing its tile
conventions and whatever of its bodies does not read a key: the walk to
the diagonal (``_walk_to_diagonal``: wide steps wholly below the
diagonal in a loop, then one step with what is left and the block on
the diagonal, cut in ``block``-sided squares of which only those on it
build a mask), the online softmax's init and finish, the tile sizes
(``_schedule`` of a causal call of this length at width DN) and the
lane-dense row statistics. The softmax step and the backward's piece are
this file's own, because a score tile is ``qN kN^T + qR kR^T`` — two MXU
products into one float32 tile — and dk leaves in two parts. That file
is not edited: what its callers lower to cannot have changed.

What is resident a grid step (grid: batch x heads, S / bq): the head's
``kN`` and ``v`` (fetched once a head) and the batch row's ``kR``, whose
block index is ``b // H`` — it changes once a BATCH ROW, so the shared
key is fetched once for all H heads and no (B, S, H, DN + DR) key with
the rotary part copied H times exists anywhere. The backward is ONE
kernel of the same walk with tiles transposed (k rows by q columns, as
flash's): it recomputes P once and makes the seven products (two for
the score, dV, dP, two for dK's parts, two for dQ's). dkN and dv
accumulate in VMEM over a head's q blocks and leave with its last one;
``dkR`` accumulates over ALL H heads of a batch row — the sum over
heads the shared key's gradient needs — and leaves once, with the last
head's last q block, so the grid's first axis is sequential too.

What the forward made is NAMED (``attention_out``, ``attention_stats``):
a recomputed block keeps it and runs this kernel once
(``optim/remat.py``). What a call runs is stated where it is traced, as
a ``bigdl:kernels:latent_schedule`` instant (never in a step).
``latent_attention_xla`` is the same mathematics in ``jax.numpy``: the
path off the TPU, and what the tests hold the kernels to.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas.flash_attention import (
    _NEG, _NN, _NT, _TN, _RESIDENT_BUDGET, _dot, _schedule, _softmax_finish,
    _softmax_init, _walk_to_diagonal)

__all__ = ["latent_attention", "latent_attention_xla", "latent_schedule",
           "LatentSchedule"]

# the one-pass backward holds, beside what the forward holds, three
# outputs the size of their inputs and three float32 accumulators: at
# 8192 x (128 | 64 | 128) bf16 that is 36 MiB of the v5e's 128, so it
# asks Mosaic for more than flash's 64
_BWD_RESIDENT_BUDGET = 48 * 2 ** 20
_VMEM_LIMIT = 100 * 2 ** 20


class LatentSchedule(NamedTuple):
    """What one call runs: q rows a grid step (``bq``), K rows a loop
    step forward and backward, the side of the squares the block on the
    diagonal is cut into, what one head holds resident in VMEM each way
    (bytes, lanes padded to 128, operands double-buffered), and the
    forward's ``block``-sided score squares a head against those the
    mask leaves any weight in."""

    bq: int
    bk: int
    bwd_bk: int
    block: int
    fwd_resident_bytes: int
    bwd_resident_bytes: int
    tiles_computed: int
    tiles_causal: float


def latent_schedule(sq: int, dn: int, dr: int, dv: int,
                    itemsize: int) -> LatentSchedule:
    """Tiles from the shapes alone; raises by name what the kernels do
    not take (there is no other path on the TPU)."""
    local = _schedule(True, sq, sq, dn, itemsize)

    def rows(d, size):            # (sq, d) in VMEM, lanes padded
        return sq * -(-d // 128) * 128 * size

    fwd = 2 * sum(rows(d, itemsize) for d in (dn, dr, dv))
    bwd = 2 * fwd + sum(rows(d, 4) for d in (dn, dr, dv))
    if not (local.kv_resident and local.one_pass_backward) \
            or fwd > _RESIDENT_BUDGET or bwd > _BWD_RESIDENT_BUDGET:
        raise ValueError(
            f"latent_attention: a head of {sq} x ({dn} | {dr} | {dv}) "
            f"does not fit VMEM (resident {fwd} bytes forward, {bwd} "
            "backward)")
    return LatentSchedule(local.bq, local.bk, local.bwd_bk, local.block,
                          fwd, bwd, local.tiles_computed, local.tiles_visible)


def _score_tile(an, ar, bn, br, diag):
    """``an bn^T + ar br^T`` in float32: rows a against rows b, content
    part and rotary part. ``diag`` is None for a tile wholly below the
    diagonal, else (dim of the q rows, position of q row 0 less position
    of k row 0), as ``flash_attention._scores`` takes it."""
    s = _dot(an, bn, _NT) + _dot(ar, br, _NT)
    if diag is not None:
        q_dim, q0_less_k0 = diag
        ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_dim)
                 - jax.lax.broadcasted_iota(jnp.int32, s.shape, q_dim))
        s = jnp.where(ahead > q0_less_k0, _NEG, s)       # kpos > qpos
    return s


def _softmax_step(qn, qr, pieces, m_scr, l_scr, acc_scr):
    """``flash_attention._softmax_step`` over two-part scores: one
    online-softmax step of the q block against ``pieces`` = [((lo, hi),
    kN, kR, v, diag)], ONE running-max update and ONE rescale of the
    accumulators for all of them."""
    cuts = sorted({0, qn.shape[0]} | {c for rows, *_ in pieces for c in rows})
    groups = list(zip(cuts, cuts[1:]))

    def fold(op, whole, parts):
        out = []
        for g0, g1 in groups:
            rows = whole[g0:g1]
            for lo, hi, x in parts:
                if lo <= g0 and g1 <= hi:
                    rows = op(rows, x[g0 - lo:g1 - lo])
            out.append(rows)
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)

    scores = [(lo, hi, _score_tile(qn[lo:hi], qr[lo:hi], kn, kr, diag))
              for (lo, hi), kn, kr, _, diag in pieces]
    m_prev = m_scr[:]
    m_new = fold(jnp.maximum, m_prev,
                 [(lo, hi, jnp.max(s, axis=1, keepdims=True))
                  for lo, hi, s in scores])
    corr = jnp.exp(m_prev - m_new)
    probs = [(lo, hi, jnp.exp(s - m_new[lo:hi])) for lo, hi, s in scores]
    l_scr[:] = fold(jnp.add, l_scr[:] * corr,
                    [(lo, hi, jnp.sum(p, axis=1, keepdims=True))
                     for lo, hi, p in probs])
    acc_scr[:] = fold(jnp.add, acc_scr[:] * corr,
                      [(lo, hi, _dot(p.astype(piece[3].dtype), piece[3], _NN))
                       for (lo, hi, p), piece in zip(probs, pieces)])
    m_scr[:] = m_new


def _fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, bq, bk, block):
    """One q block against the head's kN and v and the batch row's kR,
    all in VMEM."""
    _softmax_init(m_scr, l_scr, acc_scr)
    qn, qr = qn_ref[0], qr_ref[0]

    def rows(at, n):
        at = pl.ds(pl.multiple_of(at, block), n)
        return kn_ref[0, at, :], kr_ref[0, at, :], v_ref[0, at, :]

    def step(pieces):
        split = []
        for at, n, masked in pieces:
            if not masked:
                split.append(((0, bq), *rows(at, n), None))
                continue
            for lo in range(0, bq, block):
                # q rows lo.. sit lo rows below the piece's first K row
                split.append(((lo, lo + block), *rows(at, lo + block),
                              (0, lo)))
        _softmax_step(qn, qr, split, m_scr, l_scr, acc_scr)

    _walk_to_diagonal(pl.program_id(1), bq, bk, step)
    _softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                delta_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                dqn_scr, dqr_scr, dkn_scr, dkr_scr, dv_scr, *, heads, nq,
                bq, bk, block):
    """One q block, all of the backward in one pass, tiles transposed
    (k rows by q columns: ``flash_attention._backward_piece``). dkN and
    dv accumulate over a head's q blocks, dkR over a batch row's
    ``heads`` heads."""
    head, qi = pl.program_id(0) % heads, pl.program_id(1)

    @pl.when(qi == 0)
    def _new_head():
        dkn_scr[:] = jnp.zeros_like(dkn_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(jnp.logical_and(qi == 0, head == 0))
    def _new_batch_row():
        dkr_scr[:] = jnp.zeros_like(dkr_scr)

    dqn_scr[:] = jnp.zeros_like(dqn_scr)
    dqr_scr[:] = jnp.zeros_like(dqr_scr)
    qn, qr, do = qn_ref[0], qr_ref[0], do_ref[0]

    def piece(at, n, lo, masked):
        rows = pl.ds(pl.multiple_of(at, block), n)
        kn, kr, v = kn_ref[0, rows, :], kr_ref[0, rows, :], v_ref[0, rows, :]
        st = _score_tile(kn, kr, qn[lo:], qr[lo:],
                         (1, 0) if masked else None)
        pt = jnp.exp(st - lse_ref[0, :, lo:])               # (n, bq - lo)
        dv_scr[rows, :] = dv_scr[rows, :] + _dot(
            pt.astype(do.dtype), do[lo:], _NN)
        dst = (pt * (_dot(v, do[lo:], _NT) - delta_ref[0, :, lo:])
               ).astype(qn.dtype)
        dkn_scr[rows, :] = dkn_scr[rows, :] + _dot(dst, qn[lo:], _NN)
        dkr_scr[rows, :] = dkr_scr[rows, :] + _dot(dst, qr[lo:], _NN)
        dqn_scr[lo:, :] = dqn_scr[lo:, :] + _dot(dst, kn, _TN)
        dqr_scr[lo:, :] = dqr_scr[lo:, :] + _dot(dst, kr, _TN)

    def step(pieces):
        for at, n, masked in pieces:
            if not masked:
                piece(at, n, 0, False)
                continue
            for lo in range(0, bq, block):
                piece(at + lo, block, lo, True)

    _walk_to_diagonal(qi, bq, bk, step)
    dqn_ref[0] = dqn_scr[:].astype(dqn_ref.dtype)
    dqr_ref[0] = dqr_scr[:].astype(dqr_ref.dtype)

    @pl.when(qi == nq - 1)
    def _write_head():
        dkn_ref[0] = dkn_scr[:].astype(dkn_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(qi == nq - 1, head == heads - 1))
    def _write_batch_row():
        dkr_ref[0] = dkr_scr[:].astype(dkr_ref.dtype)


def _specs(heads, sq, bq, dn, dr, dv):
    """(q block's content, rotary and value-wide specs; the head's kN,
    v; the batch row's kR; a q block's row statistics)."""
    nq = sq // bq

    def block(d):
        return pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0))

    def head(d):
        return pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0))

    shared = pl.BlockSpec((1, sq, dr), lambda b, i: (b // heads, 0, 0))
    # row statistics lane-dense: a (1, bq) row a q block
    stats = pl.BlockSpec((1, 1, bq), lambda b, i: (b * nq + i, 0, 0))
    return block(dn), block(dr), block(dv), head(dn), head(dv), shared, stats


@functools.lru_cache(maxsize=64)
def _fwd_call(bh, heads, sq, dn, dr, dv, dtype, interpret, sched):
    """(qN, qR, kN, kR, v) -> (o, lse); cached as flash's is: the layers
    of a model share ONE traced kernel."""
    from jax.experimental.pallas import tpu as pltpu
    bq = sched.bq
    qn_s, qr_s, o_s, kn_s, v_s, kr_s, stat_s = _specs(heads, sq, bq, dn, dr,
                                                      dv)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=sched.bk,
                          block=sched.block),
        grid=(bh, sq // bq),
        in_specs=[qn_s, qr_s, kn_s, kr_s, v_s],
        out_specs=[o_s, stat_s],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, dv), dtype),
                   jax.ShapeDtypeStruct((bh * sq // bq, 1, bq),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="latent_attention_fwd",
    )


@functools.lru_cache(maxsize=64)
def _bwd_call(bh, heads, sq, dn, dr, dv, dtype, interpret, sched):
    """(qN, qR, kN, kR, v, dO, lse, delta) -> (dqN, dqR, dkN, dkR, dv),
    dkR (B, S, DR) already summed over the heads."""
    from jax.experimental.pallas import tpu as pltpu
    bq = sched.bq
    qn_s, qr_s, o_s, kn_s, v_s, kr_s, stat_s = _specs(heads, sq, bq, dn, dr,
                                                      dv)

    def like(d, lead=bh):
        return jax.ShapeDtypeStruct((lead, sq, d), dtype)

    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, nq=sq // bq, bq=bq,
                          bk=sched.bwd_bk, block=sched.block),
        grid=(bh, sq // bq),
        in_specs=[qn_s, qr_s, kn_s, kr_s, v_s, o_s, stat_s, stat_s],
        out_specs=[qn_s, qr_s, kn_s, kr_s, v_s],
        out_shape=[like(dn), like(dr), like(dn), like(dr, bh // heads),
                   like(dv)],
        scratch_shapes=[pltpu.VMEM((bq, dn), jnp.float32),
                        pltpu.VMEM((bq, dr), jnp.float32),
                        pltpu.VMEM((sq, dn), jnp.float32),
                        pltpu.VMEM((sq, dr), jnp.float32),
                        pltpu.VMEM((sq, dv), jnp.float32)],
        # dkR's accumulator runs over the heads of a batch row: the
        # first axis is sequential as well
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="latent_attention_dqdkdv",
    )


def _sizes(qn, qr, v, heads):
    bh, sq, dn = qn.shape
    return bh, heads, sq, dn, qr.shape[-1], v.shape[-1]


def _fwd(qn, qr, kn, kr, v, heads, interpret):
    from bigdl_tpu.observability import trace
    bh, _, sq, dn, dr, dv = sizes = _sizes(qn, qr, v, heads)
    sched = latent_schedule(sq, dn, dr, dv, qn.dtype.itemsize)
    # python runs this when the kernel is traced for a compile, never in
    # a step: the schedule is static
    trace.instant("latent_schedule", cat="kernels", sq=sq, heads=heads,
                  qk_nope=dn, qk_rope=dr, v_dim=dv, **sched._asdict())
    o, lse = _fwd_call(*sizes, qn.dtype, interpret, sched)(qn, qr, kn, kr, v)
    return o, lse.reshape(bh, sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _latent_bhsd(qn, qr, kn, kr, v, heads, interpret):
    return _fwd(qn, qr, kn, kr, v, heads, interpret)[0]


def _latent_fwd(qn, qr, kn, kr, v, heads, interpret):
    """What the kernel made is NAMED: a recomputed block keeps it
    (``optim/remat.py``, ``"per_block"``) and runs the kernel once."""
    o, lse = _fwd(qn, qr, kn, kr, v, heads, interpret)
    o = checkpoint_name(o, "attention_out")
    lse = checkpoint_name(lse, "attention_stats")
    return o, (qn, qr, kn, kr, v, o, lse)


def _latent_bwd(heads, interpret, res, g):
    qn, qr, kn, kr, v, o, lse = res
    bh, _, sq, dn, dr, dv = sizes = _sizes(qn, qr, v, heads)
    sched = latent_schedule(sq, dn, dr, dv, qn.dtype.itemsize)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    stats = (bh * sq // sched.bq, 1, sched.bq)
    return tuple(_bwd_call(*sizes, qn.dtype, interpret, sched)(
        qn, qr, kn, kr, v, g, lse.reshape(stats), delta.reshape(stats)))


_latent_bhsd.defvjp(_latent_fwd, _latent_bwd)


def latent_attention(qn, qr, kn, kr, v, *, interpret: bool = False):
    """Causal latent attention over ``qn, kn`` (B, S, H, DN), ``qr``
    (B, S, H, DR), ``kr`` (B, S, DR) — the ONE rotary key all heads
    share — and ``v`` (B, S, H, DV), all of one dtype; (B, S, H, DV).
    Differentiable in all five; ``kr``'s gradient is summed over the
    heads inside the kernel. No scale: the caller's q carries it.
    Shapes the kernels do not take raise a ``ValueError`` that names
    them (``latent_schedule``)."""
    b, sq, h, _ = qn.shape

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, sq, x.shape[-1])

    o = _latent_bhsd(fold(qn), fold(qr), fold(kn), kr, fold(v), h,
                     interpret)
    return o.reshape(b, h, sq, -1).transpose(0, 2, 1, 3)


def latent_attention_xla(qn, qr, kn, kr, v):
    """The same in plain ``jax.numpy``, float32 inside: the path off the
    TPU and the kernels' reference. Materialises (B, H, S, S): small
    sizes only."""
    f32 = jnp.float32
    s = qn.shape[1]
    scores = (jnp.einsum("bthd,bjhd->bhtj", qn.astype(f32), kn.astype(f32))
              + jnp.einsum("bthd,bjd->bhtj", qr.astype(f32), kr.astype(f32)))
    pos = jnp.arange(s)
    p = jax.nn.softmax(jnp.where(pos[None, :] <= pos[:, None], scores,
                                 -jnp.inf), axis=-1)
    return jnp.einsum("bhtj,bjhd->bthd", p, v.astype(f32)).astype(qn.dtype)

"""Fused Pallas TPU flash attention (forward + FlashAttention-2 backward).

Why this kernel exists: the stack's naive attention core
(parallel/sequence.py `dot_product_attention`) materializes the full
(B, H, S, S) score matrix in f32, and O(S^2) memory caps the sequence
length a chip can hold. This kernel walks K/V blocks with an online
softmax, so HBM traffic is O(S·D) and the score matrix only ever exists
as one (BQ, BK) tile in VMEM. The softmax normalizer (per-row logsumexp)
is the only residual beyond the layer's own inputs and outputs; the
backward recomputes probabilities from q/k/lse, with the elementwise
delta = rowsum(dO ∘ O) precomputed on the XLA path.

The construction follows the public FlashAttention/FlashAttention-2
algorithm (see PAPERS.md). Matmul operands stay in the storage dtype
(bf16 on the MXU, f32 accumulation); scores, exp, running max/sum, lse
and every accumulator are f32. What a call runs — its SCHEDULE — follows
from (causal, sq, skv, d, dtype) alone (``_schedule``):

- Causal self-attention whose K/V of one head fit VMEM runs LOOPED
  kernels: grid (bh, nq), the head's K/V resident (fetched once a head),
  and the walk over K INSIDE the kernel (``_walk_to_diagonal``): wide
  steps (forward 2048 rows, backward 1024) while they lie wholly below
  the diagonal, then one step with what is left below it and the block
  on it. Rows above the diagonal cost no FLOPs, no DMA and no grid step;
  the block on the diagonal is cut into ``block``-sided squares of which
  only those ON it build a mask, so the waste is (n + 1) / n of the
  causal half for n = sq / block. The per-row bookkeeping of the online
  softmax (running max, rescale) is paid once a STEP: on the v5e a step's
  fixed cost, not the masked half-tiles, is what small tiles lose to.
- Its backward, when the head's f32 dk/dv accumulators fit too, is ONE
  kernel of the same walk that recomputes P once and makes the
  algorithm's five matmuls (S, dV, dP, dK, dQ) with tiles transposed, k
  rows by q columns: the row statistics are lane-dense (1, bq) rows, the
  q block and its dO stay the stationary operand while K streams past.
- Everything else (non-causal, sq != skv, heads too long for VMEM — ring
  attention's long shards): rectangular tiles streamed over a
  (bh, nq, nk) grid, a dq kernel and a fused dk/dv kernel in the
  backward; causal calls clamp the index maps so tiles above the
  diagonal are not fetched, and mask only where the diagonal cuts.

The softmax scale never rides the score tiles when it is a power of two
(D=64: 0.125): it is folded, exactly, into the kernel's outer operand;
dq and dk take it once, in their accumulators. Measured on the v5e at
the benchmark's shape (128 heads of 2048 x 64, bf16, causal; PERF.md,
PR 26): forward 2.74 -> 2.08 ms a call, backward 6.15 -> 3.03 ms, against
the three grid kernels (512 x 1024 tiles) this replaced.

Reference scope note: the reference predates transformers (SURVEY §5.7)
— attention itself is already beyond parity; this kernel is the TPU-hot
path for the framework's long-context story (ring/Ulysses sequence
parallelism compose with it: each shard's local attention is this
kernel whenever shapes allow).
"""
from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_supported"]

logger = logging.getLogger("bigdl_tpu.ops")

# rectangular menu (non-causal, cross-attention, streamed causal):
# largest tile dividing the sequence wins. The menu is the FALLBACK: a
# measured winner in the tuning record store (bigdl_tpu/tuning/) for this
# (sq, skv, device kind) takes precedence, and sequences no menu entry
# divides fall back to generated divisors (_divisor_fallback) before
# giving up.
_Q_BLOCKS = (512, 256, 128)
_K_BLOCKS = (1024, 512, 256, 128)
# causal self-attention, looped kernels: the q rows a grid step takes (the
# block on the diagonal), how many such blocks of K a loop step walks,
# forward and backward, and into how many squares a side the block on the
# diagonal is cut (PERF.md PR 26 has the v5e sweep behind all four)
_CAUSAL_BLOCKS = (512, 256, 128)
_FWD_BLOCKS_A_STEP = 4
_BWD_BLOCKS_A_STEP = 2
_DIAGONAL_CUTS = 2

# what the resident kernels may hold of ONE head in VMEM (double-buffered
# operands + scratch, lanes padded to 128), and the scoped limit they ask
# Mosaic for: the rest is the tiles' own temporaries
_RESIDENT_BUDGET = 24 * 2 ** 20
_VMEM_LIMIT = 64 * 2 ** 20


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


class Schedule(NamedTuple):
    """What one call runs: the forward's tiles, the backward's, and which
    kernels. Looped kernels (``kv_resident``, ``one_pass_backward``) take
    bq (bwd_bq) q rows a grid step and walk K bk (bwd_bk) rows a loop
    step, the bq x bq block on the diagonal in ``block``-sided squares;
    the others run bq x bk tiles over the grid (``block`` 0).
    ``tiles_computed`` counts the score tiles (looped: squares) the
    forward computes a head, ``tiles_causal`` the tiles' worth of scores
    the mask leaves any weight in: their ratio is the schedule's waste
    (1.0 without a mask)."""

    bq: int
    bk: int
    bwd_bq: int
    bwd_bk: int
    block: int
    kv_resident: bool
    one_pass_backward: bool
    tiles_computed: int
    tiles_causal: float


def _looped_tiles(sq: int) -> tuple[int, int, int] | None:
    """(q rows a grid step, K rows a forward step, K rows a backward
    step) of the looped kernels; None where a tuning record's pair does
    not nest."""
    tuned = _tuned_blocks(sq, sq)
    if tuned is not None:
        side, step = sorted(tuned)
        return (side, step, step) if step % side == 0 else None
    side = next((b for b in _CAUSAL_BLOCKS if sq % b == 0), None) \
        or _divisor_fallback(sq, _CAUSAL_BLOCKS[0])
    return (side, side * min(sq // side, _FWD_BLOCKS_A_STEP),
            side * min(sq // side, _BWD_BLOCKS_A_STEP))


def _schedule(causal: bool, sq: int, skv: int, d: int,
              itemsize: int) -> Schedule:
    """Tile sizes, residency and the number of backward passes, from the
    shapes alone (a tuning record keeps its precedence over the menus)."""
    rect = _pick_blocks(sq, skv)
    looped = _looped_tiles(sq) if causal and sq == skv else None
    lanes = -(-d // 128) * 128
    head = 2 * sq * lanes * itemsize           # one operand, two buffers
    fwd_bytes = 2 * head                       # k, v
    bwd_bytes = 4 * head + 2 * sq * lanes * 4  # k, v, dk, dv; f32 dk, dv
    kv_resident = looped is not None and fwd_bytes <= _RESIDENT_BUDGET
    one_pass = looped is not None and bwd_bytes <= _RESIDENT_BUDGET
    if causal:
        m = min(sq, skv)
        visible = m * (m + 1) / 2 + (sq - m) * skv
    else:
        visible = sq * skv
    side, fwd_step, bwd_step = looped or (0, 0, 0)
    block = 0
    if kv_resident:
        bq, bk = side, fwd_step
        # cut the block on the diagonal while the squares stay whole
        # (128-lane) tiles
        block = bq // _DIAGONAL_CUTS if bq % (128 * _DIAGONAL_CUTS) == 0 \
            else bq
        tile = block * block
        computed = (sq // block) * (sq // block + 1) // 2
    else:
        (bq, bk), tile = rect, rect[0] * rect[1]
        nk = skv // bk
        computed = sum(min(nk, (qi * bq + bq - 1) // bk + 1) if causal
                       else nk for qi in range(sq // bq))
    return Schedule(bq, bk, *((side, bwd_step) if one_pass else rect),
                    block=block, kv_resident=kv_resident,
                    one_pass_backward=one_pass, tiles_computed=computed,
                    tiles_causal=visible / tile)


_NEG = -1e9  # finite mask value, matches parallel/sequence.py


def flash_supported(q, k) -> bool:
    """Kernel constraints: TPU backend, sequence lengths ``_pick_blocks``
    can tile (menu, tuned record, or generated divisor — this predicate
    and the picker share ``_blocks_or_none``, so supported == will not
    raise), and a head dim Mosaic tiles cleanly. D=64 — the most common
    transformer geometry — engages the kernel (Mosaic pads the 64-lane
    minor dim internally)."""
    return (jax.default_backend() == "tpu"
            and _blocks_or_none(q.shape[1], k.shape[1]) is not None
            and q.shape[-1] % 64 == 0)


def _fold_scale(x, scale):
    """(operand carrying the scale, what is left to multiply each score
    by). A power of two is exact in every float dtype, so it moves into
    the (rows, d) operand; anything else stays on the f32 scores."""
    if math.frexp(scale)[0] != 0.5:
        return x, scale
    return (x.astype(jnp.float32) * scale).astype(x.dtype), 1.0


def _dot(a, b, contract):
    """Storage-dtype operands on the MXU, f32 accumulation (converting
    the inputs to f32 first would run it at a fraction of its rate)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b


def _scores(a, b, s_scale, diag):
    """Scaled scores of rows ``a`` against rows ``b``. ``diag`` is None
    for a tile wholly below the diagonal, else (dim of the q rows,
    position of q row 0 less position of k row 0): only such a tile
    builds the iotas and the select."""
    s = _dot(a, b, _NT)
    if s_scale != 1.0:
        s = s * s_scale
    if diag is not None:
        q_dim, q0_less_k0 = diag
        ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_dim)
                 - jax.lax.broadcasted_iota(jnp.int32, s.shape, q_dim))
        s = jnp.where(ahead > q0_less_k0, _NEG, s)   # kpos > qpos
    return s


def _when_visible(compute, qi, ki, bq, bk):
    """Streamed causal grid step: skip a tile above the diagonal, mask
    one the diagonal cuts, run the rest bare."""
    below = ki * bk + bk - 1 <= qi * bq
    pl.when(below)(lambda: compute(False))
    pl.when(jnp.logical_and(jnp.logical_not(below),
                            ki * bk <= qi * bq + bq - 1))(
        lambda: compute(True))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _softmax_step(q, pieces, m_scr, l_scr, acc_scr, s_scale):
    """One online-softmax step of q against ``pieces`` = [((lo, hi), k,
    v, diag)]: rows [lo, hi) of q against a row range of K/V. The pieces
    share ONE running-max update and ONE rescale of the accumulators, so
    the per-row bookkeeping is paid once a step, however many rows of K
    the step takes. Where the diagonal cuts the block, groups of q rows
    see pieces of their own; the statistics are then put together group
    by group (whole-tile slices, no data moves)."""
    cuts = sorted({0, q.shape[0]} | {c for rows, *_ in pieces for c in rows})
    groups = list(zip(cuts, cuts[1:]))

    def fold(op, whole, parts):
        """``whole`` (every q row) combined by ``op`` with each (lo, hi,
        x) of ``parts``, x holding rows [lo, hi)."""
        out = []
        for g0, g1 in groups:
            rows = whole[g0:g1]
            for lo, hi, x in parts:
                if lo <= g0 and g1 <= hi:
                    rows = op(rows, x[g0 - lo:g1 - lo])
            out.append(rows)
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)

    scores = [(lo, hi, _scores(q[lo:hi], k, s_scale, diag))
              for (lo, hi), k, _, diag in pieces]
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
                      [(lo, hi, _dot(p.astype(v.dtype), v, _NN))
                       for (lo, hi, p), (_, _, v, _) in zip(probs, pieces)])
    m_scr[:] = m_new


def _softmax_init(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    l = l_scr[:]
    o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
    lse = m_scr[:] + jnp.log(l)                              # (bq, 1)
    # a (1, bq) block takes the statistics lane-dense
    lse_ref[0] = lse if lse_ref.shape[-1] == 1 else jnp.transpose(lse)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, nk, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)
    pl.when(ki == 0)(lambda: _softmax_init(m_scr, l_scr, acc_scr))

    def compute(masked):
        q, s_scale = _fold_scale(q_ref[0], scale)
        diag = (0, qi * bq - ki * bk) if masked else None
        _softmax_step(q, [((0, bq), k_ref[0], v_ref[0], diag)], m_scr,
                      l_scr, acc_scr, s_scale)

    if causal:
        _when_visible(compute, qi, ki, bq, bk)
    else:
        compute(False)
    pl.when(ki == nk - 1)(
        lambda: _softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr))


def _one_of(index, n, fn):
    """``fn(c)`` for the c in range(n) that ``index`` equals: n static
    bodies, one of which runs."""
    if n == 1:
        return fn(0)
    for c in range(n):
        pl.when(index == c)(functools.partial(fn, c))


def _walk_to_diagonal(qi, bq, bk, step):
    """The K rows q block ``qi`` (bq rows) of causal self-attention sees,
    as calls of ``step([(first row, rows, masked), ...])``: bk rows a
    step (a multiple of bq) in a loop while a step lies wholly below the
    diagonal; then ONE step with what is left below it and the bq x bq
    block on it, which alone is masked. What is left is 0 .. bk/bq - 1
    blocks: one static body each, so every slice has a static size."""
    wide = bk // bq
    full = qi // wide
    jax.lax.fori_loop(
        0, full, lambda t, c: (step([(t * bk, bk, False)]), c)[1], 0)
    _one_of(qi - full * wide, wide, lambda left: step(
        [(full * bk, left * bq, False)] * (left > 0)
        + [(qi * bq, bq, True)]))


def _walk_forward(q, s_scale, k_ref, v_ref, qi, m_scr, l_scr, acc_scr, *,
                  bq, bk, block):
    """The online-softmax steps of q block ``qi`` (bq rows, counted from
    row 0 of ``k_ref``) of causal self-attention against K/V in VMEM
    (``_walk_to_diagonal``). The bq x bq block on the diagonal is cut
    into ``block``-sided squares: each group of ``block`` q rows takes
    the K rows up to its own square, which alone is masked."""

    def rows(at, n):
        at = pl.multiple_of(at, block)
        return k_ref[0, pl.ds(at, n), :], v_ref[0, pl.ds(at, n), :]

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
        _softmax_step(q, split, m_scr, l_scr, acc_scr, s_scale)

    _walk_to_diagonal(qi, bq, bk, step)


def _fwd_looped_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_scr, l_scr, acc_scr, *, scale, bq, bk, block):
    """One q block of causal self-attention against the head's K/V in
    VMEM (``_walk_forward``)."""
    _softmax_init(m_scr, l_scr, acc_scr)
    q, s_scale = _fold_scale(q_ref[0], scale)
    _walk_forward(q, s_scale, k_ref, v_ref, pl.program_id(1), m_scr, l_scr,
                  acc_scr, bq=bq, bk=bk, block=block)
    _softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr)


@functools.lru_cache(maxsize=64)
def _fwd_call(bh, sq, skv, d, dtype, scale, causal, interpret, sched):
    """The forward ``pallas_call`` of a geometry, (q, k, v) -> (o, lse).
    Cached: a ``pallas_call`` is a jitted callable, so the layers of a
    model that share a geometry share ONE traced kernel, where a new
    callable a layer traces the kernel body again for each (0.1-0.2 s a
    trace of the looped kernels' static bodies, twelve traces a step of
    the benchmark's six layers)."""
    from jax.experimental.pallas import tpu as pltpu
    bq, bk = sched.bq, sched.bk
    nq, nk = sq // bq, skv // bk
    out_shape = [jax.ShapeDtypeStruct((bh, sq, d), dtype)]
    scratch = [pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, d), jnp.float32)]
    if sched.kv_resident:
        q_spec = pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0))
        kv_spec = pl.BlockSpec((1, skv, d), lambda b, i: (b, 0, 0))
        # the row statistics leave lane-dense, a (1, bq) row a q block: a
        # (sq, 1) column pads every value to a 128-lane row in HBM
        return pl.pallas_call(
            functools.partial(_fwd_looped_kernel, scale=scale, bq=bq,
                              bk=bk, block=sched.block),
            grid=(bh, nq),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, pl.BlockSpec((1, 1, bq),
                                            lambda b, i: (b * nq + i, 0, 0))],
            out_shape=out_shape + [jax.ShapeDtypeStruct((bh * nq, 1, bq),
                                                        jnp.float32)],
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="flash_attention_fwd",
        )
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    if causal:
        # a tile above the diagonal re-names the last one below it: the
        # block index does not change, so nothing is fetched
        kv_spec = pl.BlockSpec(
            (1, bk, d),
            lambda b, i, j: (b, jnp.minimum(j, (i * bq + bq - 1) // bk), 0))
    else:
        kv_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, nk=nk,
                          bq=bq, bk=bk),
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))],
        out_shape=out_shape + [jax.ShapeDtypeStruct((bh, sq, 1),
                                                    jnp.float32)],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attention_fwd",
    )


def _fwd(q, k, v, scale, causal, interpret):
    from bigdl_tpu.observability import trace
    bh, sq, d = q.shape
    skv = k.shape[1]
    sched = _schedule(causal, sq, skv, d, q.dtype.itemsize)
    # python runs this when the kernel is traced for a compile, never in
    # a step: the schedule is static
    trace.instant("flash_schedule", cat="kernels", sq=sq, skv=skv, d=d,
                  causal=causal, **sched._asdict())
    o, lse = _fwd_call(bh, sq, skv, d, q.dtype, scale, causal, interpret,
                       sched)(q, k, v)
    return o, lse.reshape(bh, sq)


# --------------------------------------------------------------------------
# backward (FlashAttention-2). One pass where the head's dk/dv
# accumulators fit VMEM; else dq in one kernel, dk/dv fused in another.
# --------------------------------------------------------------------------

def _backward_piece(q, do, s_scale, k_ref, v_ref, lse_ref, delta_ref,
                    dq_scr, dk_scr, dv_scr, rows, lo, masked):
    """Rows ``rows`` of K/V against q rows lo.. of the block, all of the
    backward: the tile is computed TRANSPOSED, k rows by q columns, so
    the q block's row statistics broadcast along sublanes from
    lane-dense (1, bq) rows, the q block and its dO are the stationary
    operand of four of the five matmuls however many K rows stream past
    them, dv and dk are plain matmuls and only dq contracts the tile's
    first dim."""
    k, v = k_ref[0, rows, :], v_ref[0, rows, :]
    st = _scores(k, q[lo:], s_scale, (1, 0) if masked else None)
    pt = jnp.exp(st - lse_ref[0, :, lo:])                   # (n, bq - lo)
    dv_scr[rows, :] = dv_scr[rows, :] + _dot(
        pt.astype(do.dtype), do[lo:], _NN)
    dst = (pt * (_dot(v, do[lo:], _NT) - delta_ref[0, :, lo:])
           ).astype(q.dtype)
    dk_scr[rows, :] = dk_scr[rows, :] + _dot(dst, q[lo:], _NN)
    dq_scr[lo:, :] = dq_scr[lo:, :] + _dot(dst, k, _TN)


def _walk_backward(q, do, s_scale, k_ref, v_ref, lse_ref, delta_ref,
                   dq_scr, dk_scr, dv_scr, qi, *, bq, bk, block):
    """The backward of q block ``qi`` (counted from row 0 of ``k_ref``)
    of causal self-attention against K/V in VMEM (``_walk_to_diagonal``,
    ``_backward_piece``). The block on the diagonal goes a ``block`` of
    K rows at a time, each against the q rows from its own square on."""

    def piece(at, n, lo, masked):
        _backward_piece(q, do, s_scale, k_ref, v_ref, lse_ref, delta_ref,
                        dq_scr, dk_scr, dv_scr,
                        pl.ds(pl.multiple_of(at, block), n), lo, masked)

    def step(pieces):
        for at, n, masked in pieces:
            if not masked:
                piece(at, n, 0, False)
                continue
            for lo in range(0, bq, block):
                piece(at + lo, block, lo, True)

    _walk_to_diagonal(qi, bq, bk, step)


def _dqdkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                   *, scale, nq, bq, bk, block):
    """One q block of causal self-attention against the head's K/V in
    VMEM, all of the backward in one pass (``_walk_backward``). dk and
    dv accumulate over the q blocks of a head in ``dk_scr`` /
    ``dv_scr``."""
    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _new_head():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    dq_scr[:] = jnp.zeros_like(dq_scr)
    q, s_scale = _fold_scale(q_ref[0], scale)
    _walk_backward(q, do_ref[0], s_scale, k_ref, v_ref, lse_ref, delta_ref,
                   dq_scr, dk_scr, dv_scr, qi, bq=bq, bk=bk, block=block)
    dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)

    # dk saw q * scale when the scale folded; else it takes it here, once
    @pl.when(qi == nq - 1)
    def _write_dkdv():
        dk_ref[0] = (dk_scr[:] * (scale if s_scale != 1.0 else 1.0)
                     ).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, nk, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(masked):
        q, s_scale = _fold_scale(q_ref[0], scale)
        k = k_ref[0]
        s = _scores(q, k, s_scale,
                    (0, qi * bq - ki * bk) if masked else None)
        p = jnp.exp(s - lse_ref[0])
        dp = _dot(do_ref[0], v_ref[0], _NT)
        ds = (p * (dp - delta_ref[0])).astype(k.dtype)
        dq_scr[:] = dq_scr[:] + _dot(ds, k, _NN)

    if causal:
        _when_visible(compute, qi, ki, bq, bk)
    else:
        compute(False)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, nq, bq, bk):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute(masked):
        k, s_scale = _fold_scale(k_ref[0], scale)
        q, do = q_ref[0], do_ref[0]
        s = _scores(q, k, s_scale,
                    (0, qi * bq - ki * bk) if masked else None)
        p = jnp.exp(s - lse_ref[0])                     # (BQ, BK)
        dv_scr[:] = dv_scr[:] + _dot(p.astype(do.dtype), do, _TN)
        dp = _dot(do, v_ref[0], _NT)
        ds = (p * (dp - delta_ref[0])).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + _dot(ds, q, _TN)

    if causal:
        _when_visible(compute, qi, ki, bq, bk)
    else:
        compute(False)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=64)
def _bwd_calls(bh, sq, skv, d, dtype, scale, causal, interpret, sched):
    """The backward ``pallas_call``s of a geometry (cached as
    ``_fwd_call`` is): one, (q, k, v, dO, lse, delta) -> (dq, dk, dv), or
    the dq call and the dk/dv call."""
    from jax.experimental.pallas import tpu as pltpu
    bq, bk = sched.bwd_bq, sched.bwd_bk
    nq, nk = sq // bq, skv // bk
    q_shape = jax.ShapeDtypeStruct((bh, sq, d), dtype)
    kv_shape = jax.ShapeDtypeStruct((bh, skv, d), dtype)
    if sched.one_pass_backward:
        q_spec = pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0))
        head_spec = pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0))
        # row statistics lane-dense: a (1, bq) row a q block
        stat_spec = pl.BlockSpec((1, 1, bq), lambda b, i: (b * nq + i, 0, 0))
        return (pl.pallas_call(
            functools.partial(_dqdkdv_kernel, scale=scale, nq=nq,
                              bq=bq, bk=bk, block=sched.block),
            grid=(bh, nq),
            in_specs=[q_spec, head_spec, head_spec, q_spec, stat_spec,
                      stat_spec],
            out_specs=[q_spec, head_spec, head_spec],
            out_shape=[q_shape, kv_shape, kv_shape],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((sq, d), jnp.float32),
                            pltpu.VMEM((sq, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="flash_attention_dqdkdv",
        ),)

    # streamed; causal index maps re-name the nearest needed block where
    # a tile lies above the diagonal, so it is not fetched
    if causal:
        def kv_of(i, j):
            return jnp.minimum(j, (i * bq + bq - 1) // bk)

        def q_of(i, j):
            return jnp.maximum(j, (i * bk) // bq)
    else:
        kv_of = q_of = lambda i, j: j
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    kv_spec_q = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, kv_of(i, j), 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, nk=nk,
                          bq=bq, bk=bk),
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=q_shape,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )
    # dk/dv: grid walks q blocks innermost for each k block
    q_spec_k = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, q_of(i, j), 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))
    row_spec_k = pl.BlockSpec((1, bq, 1),
                              lambda b, i, j: (b, q_of(i, j), 0))
    dkdv_call = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, causal=causal, nq=nq,
                          bq=bq, bk=bk),
        grid=(bh, nk, nq),
        in_specs=[q_spec_k, kv_spec, kv_spec, q_spec_k, row_spec_k,
                  row_spec_k],
        out_specs=[kv_spec, kv_spec],
        out_shape=[kv_shape, kv_shape],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dkdv",
    )
    return dq_call, dkdv_call


def _bwd(scale, causal, interpret, res, g):
    """VJP for (o, lse) outputs.

    The lse cotangent folds into the existing kernels: with lse an
    output, ds_ij gains + g_lse_i * p_ij (d lse_i / d s_ij = p_ij), so
    ds = p * (dp - (delta - g_lse)) — pass delta' = delta - g_lse and
    the kernels are unchanged. dv has no direct lse term.
    """
    q, k, v, o, lse = res
    g, g_lse = g
    bh, sq, d = q.shape
    skv = k.shape[1]
    sched = _schedule(causal, sq, skv, d, q.dtype.itemsize)
    calls = _bwd_calls(bh, sq, skv, d, q.dtype, scale, causal, interpret,
                       sched)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta - g_lse.astype(jnp.float32)                   # (bh, sq)
    if sched.one_pass_backward:
        stats = (bh * sq // sched.bwd_bq, 1, sched.bwd_bq)
        return calls[0](q, k, v, g, lse.reshape(stats), delta.reshape(stats))
    # the streamed kernels take the row statistics as (bq, 1) columns
    args = (q, k, v, g, lse[..., None], delta[..., None])
    return (calls[0](*args), *calls[1](*args))


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
    16) and a head_dim multiple of 64 (``flash_supported``); the
    schedule follows from the shapes (``_schedule``) unless an autotuned
    record (``bigdl_tpu/tuning``) overrides the tile sizes.
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

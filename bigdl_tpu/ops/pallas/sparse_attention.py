"""Learned sparse attention (DeepSeek-V3.2-Exp's DSA, as the block of
Keye-VL-2.0-30B-A3B uses it) as Pallas TPU kernels: a small INDEXER
scores every causal (query, key) pair, each query keeps its ``topk``
best keys EXACTLY, grouped-query attention runs over the kept keys
alone, and the indexer is trained towards the attention's own
probabilities. docs/sparse_attention.md has the equations.

    I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])             (float32)
    S_t     = the topk largest I[t, s] over s <= t; lower s wins a tie
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s] scale) v[s]
    L_I     = mean_t KL( p_t || softmax_{s in S_t} I[t, s] ),
              p_t = sum_h P[t, h, :] / H, detached

Six kernels, all on flash attention's tile conventions
(``flash_attention.py``: storage-dtype operands on the MXU, float32
scores and statistics, row statistics lane-dense, the backward's tiles
transposed, k rows by q columns) and causal: a tile wholly in a query's
future costs no FLOPs, no DMA and no write.

- ``index_scores``: I, a (bq, bk) tile at a time, the J heads' ReLU'd
  float32 products (bfloat16 halves, all four partial products)
  weighted and summed in VMEM — the (S, J, S) products never exist.
- ``select_rows``: a block of whole rows of I in VMEM; the topk-th
  largest value of each row by bisection over the float's 32 bits on
  order-preserving integer keys (33 counting passes, all in VMEM), ties
  cut by a second bisection over the bits of the column index (15 more
  passes at 16384, run ALWAYS: the kernel's time does not follow the
  data); writes I back IN PLACE with -inf on every pair not kept, and
  each row's logsumexp over the kept, threshold key and tie column.
  That one (S, S) float32 array is the selection for everything after
  it: kept <=> finite. The two numbers a row ARE the selection: the
  backward pass writes the array again from I and them, as the scores
  kernel's epilogue (``index_scores(keep=...)``), and finds nothing.
- ``attend``: flash attention's online softmax over (bq, bk) tiles with
  the tile of kept pairs as its mask; one grid step takes ALL the query
  heads of a key/value group, so K/V and the mask are read once a group
  (K/V are never repeated).
- its backward, ONE pass: the five matmuls of FlashAttention-2 a tile,
  dq in VMEM over a q block's walk, dk/dv of the whole key/value head in
  VMEM over the head (the group's query heads add into them).
- ``kept_probs``: sum_h P[t, h, s] for all H heads of a tile from q, k
  and the row statistics, and from it d L_I / d I = (softmax_kept(I) -
  p) / rows, a tile at a time.
- ``index_backward``: d L_I / d (qI, kI, w) from d L_I / d I, the J
  heads' products recomputed a tile at a time (1 + 2 matmuls).

No (S, S) array leaves the call, as an output or as a residual: the
masked scores live from the selection to the end of the forward's
attention, and in the backward pass of ONE layer from the scores written
again to the indexer's gradients. What the backward needs that is costly
to make again and small is NAMED (``jax.ad_checkpoint.checkpoint_name``:
``attention_out`` o, ``attention_stats`` its row logsumexp,
``attention_selection`` a row's threshold, tie column and index
logsumexp), so that a recomputed block (``optim/remat.py``,
``"per_block"``) keeps it and runs neither the selection nor the
attention's forward a second time.

The attention is DENSE-MASKED: every causal tile is computed and the
unkept pairs are masked, because a query's kept keys are scattered
(random weights keep ~topk / t of every tile) — gathering K/V rows per
query would move H x topk x D bytes a query. What a call runs is stated
where it is traced, as a ``bigdl:kernels:sparse_schedule`` instant
(never in a step): tiles computed against the tiles' worth of kept
pairs.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas.flash_attention import (
    _NEG, _NN, _NT, _TN, _VMEM_LIMIT, _dot, _fold_scale)

__all__ = ["sparse_select_attention", "sparse_schedule", "SparseSchedule",
           "index_scores", "select_rows", "attend"]

_INT_MIN = -2 ** 31
_KEPT = -1e30            # a kept pair's masked score is above this


class SparseSchedule(NamedTuple):
    """What one call runs. The attention and its backward take ``bq`` x
    ``bk`` tiles, the indexer's kernels ``index_bq`` x ``index_bk``
    (fewer q rows where all heads share a step); the selection takes
    ``rows`` whole rows a step, scanned ``chunk`` columns at a time.
    ``tiles_computed`` counts the causal bq x bk tiles a (batch, group),
    ``tiles_needed`` the tiles' worth of kept pairs: their ratio is what
    masking costs over a kernel that could skip."""

    bq: int
    bk: int
    index_bq: int
    index_bk: int
    rows: int
    chunk: int
    tiles_computed: int
    tiles_needed: float


def _divisor(s: int, menu) -> int:
    return next((b for b in menu if s % b == 0), 0)


def sparse_schedule(s: int, topk: int | None = None) -> SparseSchedule:
    """Tiles from the shapes alone (``topk`` only counts the tiles
    needed; None: every causal pair); raises by name what the kernels do
    not take (there is no other path on the TPU)."""
    bq = _divisor(s, (512, 256, 128))
    if not bq:
        raise ValueError(f"sparse_select_attention: sequence length {s} "
                         "is not a multiple of 128")
    # a wide key step pays the online softmax's per-step bookkeeping
    # (running max, rescaling the accumulator) once for twice the keys
    bk = 2 * bq if s % (2 * bq) == 0 else bq
    kept = s if topk is None else min(topk, s)
    pairs = kept * (kept + 1) // 2 + (s - kept) * kept
    return SparseSchedule(
        bq, bk, index_bq=min(bq, 256), index_bk=bq,
        rows=_divisor(s, (128, 64, 32, 16, 8)),
        chunk=_divisor(s, (2048, 1024, 512, 256, 128)),
        tiles_computed=sum(_last(i, bq, bk) + 1 for i in range(s // bq)),
        tiles_needed=pairs / (bq * bk))


def _visible(i, j, bq, bk):
    """Tile (i, j) has a key some query of the block may see."""
    return j * bk <= i * bq + bq - 1


def _last(i, bq, bk):
    """The last key block q block ``i`` sees: index maps clamp to it, so
    a step beyond it re-names the block it has and fetches nothing."""
    return (i * bq + bq - 1) // bk


def _scaled(q, scale):
    """``_fold_scale``, but a caller that scaled q itself (scale 1.0, as
    ``nn.SparseSelectAttention`` does in its q norm) pays nothing."""
    return (q, 1.0) if scale == 1.0 else _fold_scale(q, scale)


def _halves(x):
    """float32 -> (high, low) bfloat16 with high + low == x to 2^-17:
    what the MXU multiplies when float32 is wanted of it."""
    high = x.astype(jnp.bfloat16)
    return high, (x - high.astype(jnp.float32)).astype(jnp.bfloat16)


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


# --------------------------------------------------------------------------
# the selection's rule, for the kernel that finds it and the one that
# applies it again
# --------------------------------------------------------------------------

def _order_keys(x):
    """Order-preserving int32 keys of float32 scores; -0.0 == +0.0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = bits ^ ((bits >> 31) & 0x7FFFFFFF)
    return jnp.where(x == 0.0, 0, key)


def _among_kept(key, col, row, t, m):
    """Pair (row, col) is kept: its key above the row's threshold ``t``,
    or equal to it left of the row's tie column ``m``; never a future
    pair."""
    return ((key > t) | ((key == t) & (col < m))) & (col <= row)


# --------------------------------------------------------------------------
# the indexer's scores
# --------------------------------------------------------------------------

def _scores_kernel(qi_ref, kh_ref, kl_ref, wi_ref, *rest, heads, bq, bk,
                   reach):
    """One tile of I. ``qi_ref`` holds each query [high | low], the two
    key blocks [high | high] and [low | low]: two bf16 matmuls of twice
    the depth give all four partial products of the float32 product.
    With ``reach`` (the backward's call) two more inputs, each row's
    threshold key and tie column, come before the output, and the tile
    leaves as ``select_rows`` left it: -inf on every pair not kept."""
    *keep_refs, out_ref = rest
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(_visible(i, j, bq, bk))
    def _tile():
        kh, kl, w = kh_ref[0], kl_ref[0], wi_ref[0]
        acc = jnp.zeros((bq, bk), jnp.float32)     # +0.0: never a -0.0
        for h in range(heads):
            q = qi_ref[0, h]
            r = _dot(q, kh, _NT) + _dot(q, kl, _NT)
            acc = acc + w[:, h:h + 1] * jnp.maximum(r, 0.0)
        if reach:
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            t_ref, m_ref = keep_refs
            keep = _among_kept(_order_keys(acc), col, row, t_ref[0],
                               m_ref[0])
            acc = jnp.where(keep, acc, -jnp.inf)
        out_ref[0] = acc

    if reach:
        # a kernel that walks key steps ``reach`` wide reads past the
        # diagonal's tile, where ``select_rows`` wrote -inf
        @pl.when(jnp.logical_not(_visible(i, j, bq, bk))
                 & (j <= _read_last(i, bq, bk, reach)))
        def _future():
            out_ref[0] = jnp.full((bq, bk), -jnp.inf, jnp.float32)


def _read_last(i, bq, bk, reach):
    """The last (bq, bk) tile of q block ``i`` that lies in a visible key
    step of ``reach`` columns (a multiple of bk); with no ``reach``, the
    last visible tile."""
    if not reach:
        return _last(i, bq, bk)
    return (_last(i, bq, reach) + 1) * (reach // bk) - 1


@functools.lru_cache(maxsize=16)
def _scores_call(b, s, heads, di, bq, bk, reach, interpret):
    nq, nk = s // bq, s // bk
    key_spec = pl.BlockSpec((1, bk, 2 * di), lambda n, i, j: (
        n, jnp.minimum(j, _last(i, bq, bk)), 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda n, i, j: (n, i, 0))
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads, bq=bq, bk=bk,
                          reach=reach),
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, heads, bq, 2 * di),
                         lambda n, i, j: (n, 0, i, 0)),
            key_spec, key_spec,
            pl.BlockSpec((1, bq, heads), lambda n, i, j: (n, i, 0)),
            *([row_spec, row_spec] if reach else [])],
        out_specs=pl.BlockSpec((1, bq, bk), lambda n, i, j: (
            n, i, jnp.minimum(j, _read_last(i, bq, bk, reach)))),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_index_scores",
    )


def index_scores(qi, ki, wi, *, keep=None, interpret: bool = False):
    """I (B, S, S) float32 from ``qi`` (B, J, S, DI), ``ki`` (B, S, DI),
    ``wi`` (B, S, J), all float32. The products are 2 x bfloat16, about
    16 mantissa bits: each operand goes to the MXU as two bfloat16
    halves and all four partial products are summed in float32, within
    2^-16 of the float32 product (Mosaic offers one bf16 pass or six;
    the halves side by side fill the MXU's depth at DI = 64, so this
    costs two passes). Only the causal tiles are written: what lies in a
    query block's future is left as it was allocated (``select_rows``
    never reads a pair with s > t).

    ``keep`` = (threshold keys, tie columns), two (B, S, 1) int32 of
    ``select_rows``: the scores leave MASKED, bit for bit the array
    ``select_rows`` wrote over them (the same products, the same
    comparison), on every tile that ``attend``'s key steps reach; the
    backward pass's way to the selection without finding it again."""
    b, heads, s, di = qi.shape
    sched = sparse_schedule(s)
    q_high, q_low = _halves(qi)
    k_high, k_low = _halves(ki)
    return _scores_call(b, s, heads, di, sched.index_bk, sched.index_bk,
                        sched.bk if keep else 0, interpret)(
        jnp.concatenate([q_high, q_low], axis=-1),
        jnp.concatenate([k_high, k_high], axis=-1),
        jnp.concatenate([k_low, k_low], axis=-1), wi, *(keep or ()))


# --------------------------------------------------------------------------
# the selection
# --------------------------------------------------------------------------

def _select_kernel(x_ref, out_ref, lse_ref, t_ref, m_ref, key_scr, t_scr,
                   m_scr, *, topk, rows, chunk, s):
    """One block of ``rows`` whole rows: keys, threshold, ties, write."""
    row0 = pl.program_id(1) * rows
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    used = (row0 + rows + chunk - 1) // chunk      # chunks with s <= t
    i32 = jnp.int32
    bits = s.bit_length()                          # a column is < 2^bits

    def cols(c):
        at = pl.multiple_of(c * chunk, chunk)
        col = at + jax.lax.broadcasted_iota(i32, (1, chunk), 1)
        return pl.ds(at, chunk), col

    def each_chunk(fn, init):
        return jax.lax.fori_loop(0, used, fn, init)

    def count(pred):
        """(rows, 1) number of columns where ``pred(keys, col)``."""
        def body(c, acc):
            at, col = cols(c)
            return acc + jnp.sum(pred(key_scr[:, at], col).astype(i32),
                                 axis=1, keepdims=True)
        return each_chunk(body, jnp.zeros((rows, 1), i32))

    def to_keys(c, carry):
        # order-preserving int32 keys of the float32 scores; a pair in
        # the future sorts below everything
        at, col = cols(c)
        key_scr[:, at] = jnp.where(col <= row, _order_keys(x_ref[0, :, at]),
                                   _INT_MIN)
        return carry

    each_chunk(to_keys, 0)
    t_scr[:] = jnp.full((rows, 1), _INT_MIN, i32)
    m_scr[:] = jnp.full((rows, 1), s, i32)

    @pl.when(row0 + rows > topk)       # else every row keeps all s <= t
    def _threshold():
        # the largest T with |{key >= T}| >= topk, bit by bit from the
        # sign; a row with fewer than topk causal keys stays at INT_MIN
        at_least = count(lambda key, col: key >= 0)
        t0 = jnp.where(at_least >= topk, 0, _INT_MIN).astype(i32)

        def refine(n, t):
            cand = t | (jnp.int32(1) << (30 - n))
            return jnp.where(count(lambda key, col: key >= cand) >= topk,
                             cand, t)

        t = jax.lax.fori_loop(0, 31, refine, t0)
        t_scr[:] = t
        above = count(lambda key, col: key > t)
        equal = count(lambda key, col: (key == t) & (col <= row))
        room = topk - above                        # equals that still fit
        tied = (equal > room) & (t != _INT_MIN)

        # the largest M with |{key == T, s < M}| <= room, over the bits
        # of a column number. ALWAYS, tied rows or not: index scores of
        # hidden states that have grown alike tie at float32's
        # resolution in more blocks with every training step, and the
        # step's time must not follow the model's state (PERF.md
        # section 6, PR 31)
        def refine_m(n, m):
            cand = m | (jnp.int32(1) << (bits - 1 - n))
            fits = count(lambda key, col: (key == t) & (col < cand)
                         & (col <= row)) <= room
            return jnp.where(fits, cand, m)

        m_scr[:] = jnp.where(
            tied, jax.lax.fori_loop(0, bits, refine_m,
                                    jnp.zeros((rows, 1), i32)), s)

    t, m = t_scr[:], m_scr[:]
    t_ref[0], m_ref[0] = t, m

    def kept(c):
        at, col = cols(c)
        keep = _among_kept(key_scr[:, at], col, row, t, m)
        return at, jnp.where(keep, x_ref[0, :, at], -jnp.inf)

    def write(c, top):
        at, x = kept(c)
        out_ref[0, :, at] = x
        return jnp.maximum(top, jnp.max(x, axis=1, keepdims=True))

    top = each_chunk(write, jnp.full((rows, 1), -jnp.inf, jnp.float32))

    def blank(c, carry):
        out_ref[0, :, cols(c)[0]] = jnp.full((rows, chunk), -jnp.inf,
                                             jnp.float32)
        return carry

    jax.lax.fori_loop(used, s // chunk, blank, 0)

    def sum_exp(c, acc):
        return acc + jnp.sum(jnp.exp(kept(c)[1] - top), axis=1,
                             keepdims=True)

    lse_ref[0] = top + jnp.log(
        each_chunk(sum_exp, jnp.zeros((rows, 1), jnp.float32)))


@functools.lru_cache(maxsize=16)
def _select_call(b, s, topk, rows, chunk, interpret):
    from jax.experimental.pallas import tpu as pltpu
    row_spec = pl.BlockSpec((1, rows, s), lambda n, i: (n, i, 0))
    one_spec = pl.BlockSpec((1, rows, 1), lambda n, i: (n, i, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, rows=rows,
                          chunk=chunk, s=s),
        grid=(b, s // rows),
        in_specs=[row_spec],
        out_specs=[row_spec, one_spec, one_spec, one_spec],
        out_shape=[jax.ShapeDtypeStruct((b, s, s), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, 1), jnp.int32),
                   jax.ShapeDtypeStruct((b, s, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.int32)],
        input_output_aliases={0: 0},
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
        name="sparse_select_rows",
    )


def select_rows(scores, topk: int, *, interpret: bool = False):
    """(kept, lse, threshold, tie): ``scores`` (B, S, S) float32 with
    -inf on every pair (t, s) that is NOT among query t's ``topk``
    largest over s <= t (every s <= t while t < topk; the lower s wins
    among equal scores), written over the input; the logsumexp (B, S, 1)
    of each row's kept scores; and the two int32 (B, S, 1) that ARE the
    row's selection (``index_scores(keep=...)`` applies them again): the
    order-preserving key of its topk-th largest score and the column
    left of which a score equal to it is kept (S: all of them). Exact."""
    b, s, _ = scores.shape
    sched = sparse_schedule(s, topk)
    return _select_call(b, s, topk, sched.rows, sched.chunk,
                        interpret)(scores)


# --------------------------------------------------------------------------
# attention over the kept pairs: forward
# --------------------------------------------------------------------------

def _attend_kernel(q_ref, k_ref, v_ref, x_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, scale, per, bq, bk, nk):
    """One (bq, bk) tile for the ``per`` query heads of a key/value
    group: one online-softmax step a head, one mask for all."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_visible(i, j, bq, bk))
    def _tile():
        keep = x_ref[0] > _KEPT
        k, v = k_ref[0], v_ref[0]
        for h in range(per):
            q, s_scale = _scaled(q_ref[0, h], scale)
            sc = _dot(q, k, _NT)
            if s_scale != 1.0:
                sc = sc * s_scale
            sc = jnp.where(keep, sc, _NEG)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + _dot(p.astype(v.dtype), v, _NN)
            m_scr[h] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        for h in range(per):
            l = l_scr[h]
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)
            # the row statistics leave lane-dense, a (1, bq) row a head
            lse_ref[0, h:h + 1, :] = jnp.transpose(m_scr[h] + jnp.log(l))


def _attend_specs(per, d, bq, bk, groups):
    q_spec = pl.BlockSpec((1, per, bq, d), lambda n, i, j: (n, 0, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda n, i, j: (
        n, jnp.minimum(j, _last(i, bq, bk)), 0))
    x_spec = pl.BlockSpec((1, bq, bk), lambda n, i, j: (
        n // groups, i, jnp.minimum(j, _last(i, bq, bk))))
    stat_spec = pl.BlockSpec((1, per, bq), lambda n, i, j: (n, 0, i))
    return q_spec, kv_spec, x_spec, stat_spec


@functools.lru_cache(maxsize=16)
def _attend_call(bg, groups, per, s, d, dtype, scale, bq, bk, interpret):
    from jax.experimental.pallas import tpu as pltpu
    q_spec, kv_spec, x_spec, stat_spec = _attend_specs(per, d, bq, bk,
                                                       groups)
    return pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale, per=per, bq=bq,
                          bk=bk, nk=s // bk),
        grid=(bg, s // bq, s // bk),
        in_specs=[q_spec, kv_spec, kv_spec, x_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct((bg, per, s, d), dtype),
                   jax.ShapeDtypeStruct((bg, per, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((per, bq, 1), jnp.float32),
                        pltpu.VMEM((per, bq, 1), jnp.float32),
                        pltpu.VMEM((per, bq, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_attention_fwd",
    )


def attend(q, k, v, kept, *, scale: float, interpret: bool = False):
    """(o, lse) of grouped-query attention over the kept pairs: ``q``
    (B G, P, S, D), ``k, v`` (B G, S, D), ``kept`` (B, S, S) float32
    (``select_rows``); o as q, lse (B G, P, S) float32."""
    bg, per, s, d = q.shape
    sched = sparse_schedule(s)
    return _attend_call(bg, bg // kept.shape[0], per, s, d, q.dtype, scale,
                        sched.bq, sched.bk, interpret)(q, k, v, kept)


# --------------------------------------------------------------------------
# attention over the kept pairs: backward, one pass
# --------------------------------------------------------------------------

def _attend_bwd_kernel(q_ref, k_ref, v_ref, x_ref, do_ref, lse_ref,
                       delta_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr,
                       dv_scr, *, scale, per, bq, bk, nq, nk):
    """One tile, all of the backward, TRANSPOSED (k rows by q columns,
    as flash attention's ``_backward_piece``): the row statistics
    broadcast along sublanes from lane-dense rows, dv and dk are plain
    matmuls and add up over the group's heads, only dq contracts the
    tile's first dim."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _new_head():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(j == 0)
    def _new_block():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_visible(i, j, bq, bk))
    def _tile():
        keep = jnp.transpose(x_ref[0]) > _KEPT             # (bk, bq)
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k, v = k_ref[0], v_ref[0]
        dk = jnp.zeros(k.shape, jnp.float32)
        dv = jnp.zeros(v.shape, jnp.float32)
        for h in range(per):
            q, s_scale = _scaled(q_ref[0, h], scale)
            do = do_ref[0, h]
            st = _dot(k, q, _NT)
            if s_scale != 1.0:
                st = st * s_scale
            pt = jnp.exp(jnp.where(keep, st, _NEG) - lse_ref[0, h:h + 1, :])
            dv = dv + _dot(pt.astype(do.dtype), do, _NN)
            dst = (pt * (_dot(v, do, _NT) - delta_ref[0, h:h + 1, :])
                   ).astype(q.dtype)
            dk = dk + _dot(dst, q, _NN)
            dq_scr[h] = dq_scr[h] + _dot(dst, k, _TN)
        dk_scr[rows, :] = dk_scr[rows, :] + dk
        dv_scr[rows, :] = dv_scr[rows, :] + dv

    # dk saw q * scale when the scale folded (a power of two); else it
    # takes it here, once
    k_scale = 1.0 if math.frexp(scale)[0] == 0.5 else scale

    @pl.when(j == nk - 1)
    def _write_dq():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)

    @pl.when((i == nq - 1) & (j == nk - 1))
    def _write_dkdv():
        dk_ref[0] = (dk_scr[:] * k_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=16)
def _attend_bwd_call(bg, groups, per, s, d, dtype, scale, bq, bk,
                     interpret):
    from jax.experimental.pallas import tpu as pltpu
    q_spec, kv_spec, x_spec, stat_spec = _attend_specs(per, d, bq, bk,
                                                       groups)
    head_spec = pl.BlockSpec((1, s, d), lambda n, i, j: (n, 0, 0))
    return pl.pallas_call(
        functools.partial(_attend_bwd_kernel, scale=scale, per=per, bq=bq,
                          bk=bk, nq=s // bq, nk=s // bk),
        grid=(bg, s // bq, s // bk),
        in_specs=[q_spec, kv_spec, kv_spec, x_spec, q_spec, stat_spec,
                  stat_spec],
        out_specs=[q_spec, head_spec, head_spec],
        out_shape=[jax.ShapeDtypeStruct((bg, per, s, d), dtype),
                   jax.ShapeDtypeStruct((bg, s, d), dtype),
                   jax.ShapeDtypeStruct((bg, s, d), dtype)],
        scratch_shapes=[pltpu.VMEM((per, bq, d), jnp.float32),
                        pltpu.VMEM((s, d), jnp.float32),
                        pltpu.VMEM((s, d), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="sparse_attention_dqdkdv",
    )


# --------------------------------------------------------------------------
# the indexer's loss: d L_I / d I, then d L_I / d (qI, kI, w)
# --------------------------------------------------------------------------

def _probs_kernel(q_ref, k_ref, x_ref, lse_ref, lsei_ref, out_ref, *,
                  scale, groups, per, bq, bk, weight):
    """d L_I / d I of one tile: (softmax_kept(I) - sum_h P_h / H) *
    ``weight``, P recomputed for all H = groups x per heads."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(_visible(i, j, bq, bk))
    def _tile():
        x = x_ref[0]
        keep = jnp.transpose(x) > _KEPT                    # (bk, bq)
        total = jnp.zeros((bk, bq), jnp.float32)
        for g in range(groups):
            k = k_ref[g]
            for h in range(per):
                q, s_scale = _scaled(q_ref[g, h], scale)
                st = _dot(k, q, _NT)
                if s_scale != 1.0:
                    st = st * s_scale
                total = total + jnp.exp(jnp.where(keep, st, _NEG)
                                        - lse_ref[g, h:h + 1, :])
        target = jnp.transpose(total) * (1.0 / (groups * per))
        out_ref[0] = (jnp.exp(x - lsei_ref[0]) - target) * weight


@functools.lru_cache(maxsize=16)
def _probs_call(b, groups, per, s, d, scale, bq, bk, weight, interpret):
    def clamp(i, j):
        return jnp.minimum(j, _last(i, bq, bk))

    tile = pl.BlockSpec((1, bq, bk), lambda n, i, j: (n, i, clamp(i, j)))
    return pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, groups=groups,
                          per=per, bq=bq, bk=bk, weight=weight),
        grid=(b, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((groups, per, bq, d),
                         lambda n, i, j: (n, 0, i, 0)),
            pl.BlockSpec((groups, bk, d),
                         lambda n, i, j: (n, clamp(i, j), 0)),
            tile,
            pl.BlockSpec((groups, per, bq), lambda n, i, j: (n, 0, i)),
            pl.BlockSpec((1, bq, 1), lambda n, i, j: (n, i, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        # over the masked scores, which nothing reads after this
        input_output_aliases={2: 0},
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_kept_probs",
    )


def _index_bwd_kernel(qi_ref, ki_ref, wi_ref, g_ref, dqi_ref, dki_ref,
                      dwi_ref, dqi_scr, dki_scr, dwi_scr, *, heads, bq, bk,
                      nq, nk, dtype):
    """One tile of the indexer's backward: the J heads' products again,
    then d w (a row sum), d qI and d kI (two matmuls a head). All three
    matmuls take operands in ``dtype`` — the attention's own, as every
    gradient matmul of the step: a product recomputed to that precision
    moves ReLU's gate only where the product, and so its weight in the
    gradient, is next to zero."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _new_batch():
        dki_scr[:] = jnp.zeros_like(dki_scr)

    @pl.when(j == 0)
    def _new_block():
        dqi_scr[:] = jnp.zeros_like(dqi_scr)
        dwi_scr[:] = jnp.zeros_like(dwi_scr)

    @pl.when(_visible(i, j, bq, bk))
    def _tile():
        g, ki, w = g_ref[0], ki_ref[0], wi_ref[0]
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        ki = ki.astype(dtype)
        dki = jnp.zeros(ki.shape, jnp.float32)
        for h in range(heads):
            qi = qi_ref[0, h].astype(dtype)
            r = _dot(qi, ki, _NT)
            live = r > 0.0
            dwi_scr[:, h:h + 1] = dwi_scr[:, h:h + 1] + jnp.sum(
                jnp.where(live, g * r, 0.0), axis=1, keepdims=True)
            dr = jnp.where(live, g * w[:, h:h + 1], 0.0).astype(dtype)
            dqi_scr[h] = dqi_scr[h] + _dot(dr, ki, _NN)
            dki = dki + _dot(dr, qi, _TN)
        dki_scr[rows, :] = dki_scr[rows, :] + dki

    @pl.when(j == nk - 1)
    def _write_block():
        dqi_ref[0] = dqi_scr[:]
        dwi_ref[0] = dwi_scr[:]

    @pl.when((i == nq - 1) & (j == nk - 1))
    def _write_batch():
        dki_ref[0] = dki_scr[:]


@functools.lru_cache(maxsize=16)
def _index_bwd_call(b, s, heads, di, bq, bk, dtype, interpret):
    from jax.experimental.pallas import tpu as pltpu

    def clamp(i, j):
        return jnp.minimum(j, _last(i, bq, bk))

    qi_spec = pl.BlockSpec((1, heads, bq, di), lambda n, i, j: (n, 0, i, 0))
    wi_spec = pl.BlockSpec((1, bq, heads), lambda n, i, j: (n, i, 0))
    all_keys = pl.BlockSpec((1, s, di), lambda n, i, j: (n, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_index_bwd_kernel, heads=heads, bq=bq, bk=bk,
                          nq=s // bq, nk=s // bk, dtype=dtype),
        grid=(b, s // bq, s // bk),
        in_specs=[qi_spec,
                  pl.BlockSpec((1, bk, di),
                               lambda n, i, j: (n, clamp(i, j), 0)),
                  wi_spec,
                  pl.BlockSpec((1, bq, bk),
                               lambda n, i, j: (n, i, clamp(i, j)))],
        out_specs=[qi_spec, all_keys, wi_spec],
        out_shape=[jax.ShapeDtypeStruct((b, heads, s, di), f32),
                   jax.ShapeDtypeStruct((b, s, di), f32),
                   jax.ShapeDtypeStruct((b, s, heads), f32)],
        scratch_shapes=[pltpu.VMEM((heads, bq, di), f32),
                        pltpu.VMEM((s, di), f32),
                        pltpu.VMEM((bq, heads), f32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="sparse_index_backward",
    )


# --------------------------------------------------------------------------
# the whole layer's core, one custom_vjp
# --------------------------------------------------------------------------

def _forward(q, k, v, qi, ki, wi, topk, scale, weight, interpret):
    """The three forward kernels; the residuals hold what they made that
    is small, by the names ``optim/remat.py``'s ``"per_block"`` keeps,
    and not the (S, S) masked scores."""
    with jax.named_scope("indexer"):
        scores = index_scores(qi, ki, wi, interpret=interpret)
    with jax.named_scope("select_topk"):
        kept, *rows = select_rows(scores, topk, interpret=interpret)
        lse_i, t, m = (checkpoint_name(a, "attention_selection")
                       for a in rows)
    with jax.named_scope("sparse_attention"):
        o, lse = attend(q, k, v, kept, scale=scale, interpret=interpret)
        o = checkpoint_name(o, "attention_out")
        lse = checkpoint_name(lse, "attention_stats")
    return o, (q, k, v, qi, ki, wi, lse_i, t, m, o, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _core(q, k, v, qi, ki, wi, topk, scale, weight, interpret):
    """o (B G, P, S, D) on the kernels' layouts; ``weight`` is what one
    query's KL weighs in L_I (one over the queries of the whole batch)."""
    return _forward(q, k, v, qi, ki, wi, topk, scale, weight,
                    interpret)[0]


def _core_bwd(topk, scale, weight, interpret, res, g):
    q, k, v, qi, ki, wi, lse_i, t, m, o, lse = res
    bg, per, s, d = q.shape
    b, heads, _, di = qi.shape
    groups = bg // b
    sched = sparse_schedule(s, topk)
    with jax.named_scope("indexer"), jax.named_scope("recompute"):
        # the forward's masked scores again, from a row's two numbers
        kept = index_scores(qi, ki, wi, keep=(t, m), interpret=interpret)
    with jax.named_scope("sparse_attention"):
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), -1)
        dq, dk, dv = _attend_bwd_call(
            bg, groups, per, s, d, q.dtype, scale, sched.bq, sched.bk,
            interpret)(q, k, v, kept, g, lse, delta)
    with jax.named_scope("indexer_loss"):
        d_scores = _probs_call(
            b, groups, per, s, d, scale, sched.index_bq, sched.index_bk,
            weight, interpret)(q, k, kept, lse, lse_i)
        dqi, dki, dwi = _index_bwd_call(
            b, s, heads, di, sched.index_bq, sched.index_bk, q.dtype,
            interpret)(qi, ki, wi, d_scores)
    return dq, dk, dv, dqi, dki, dwi


_core.defvjp(_forward, _core_bwd)


def sparse_select_attention(q, k, v, qi, ki, wi, *, topk: int,
                            scale: float | None = None,
                            interpret: bool = False):
    """Learned sparse attention over ``q`` (B, S, H, D), ``k, v``
    (B, S, G, D) — query head j reads key/value head j // (H / G) — of
    one dtype, steered by the indexer's float32 ``qi`` (B, S, J, DI),
    ``ki`` (B, S, DI), ``wi`` (B, S, J): o (B, S, H, D) as
    ``nn.attention.sparse_select_xla`` defines it. Differentiable in all
    six: q, k, v get the caller's gradient with the selection held
    constant; qi, ki, wi get the gradient of L_I at weight one,
    whatever the caller's cotangent (the selection passes none). S must
    be a multiple of 128; shapes the kernels do not take raise a
    ``ValueError`` that names them (``sparse_schedule``)."""
    from bigdl_tpu.observability import trace
    b, s, h, d = q.shape
    groups = k.shape[2]
    per = h // groups
    scale = scale if scale is not None else d ** -0.5
    sched = sparse_schedule(s, topk)
    # python runs this when the kernels are traced for a compile, never
    # in a step: the schedule is static
    trace.instant("sparse_schedule", cat="kernels", seq=s, topk=topk,
                  heads=h, kv_heads=groups, d=d, **sched._asdict())

    def local(q, k, v, qi, ki, wi):
        n = q.shape[0]
        o = _core(
            q.reshape(n, s, groups, per, d).transpose(0, 2, 3, 1, 4)
            .reshape(n * groups, per, s, d),
            k.transpose(0, 2, 1, 3).reshape(n * groups, s, d),
            v.transpose(0, 2, 1, 3).reshape(n * groups, s, d),
            qi.transpose(0, 2, 1, 3), ki, wi, topk, scale, 1.0 / (b * s),
            interpret)
        return o.reshape(n, groups, per, s, d).transpose(0, 3, 1, 2, 4) \
            .reshape(n, s, h, d)

    from bigdl_tpu.ops.pallas.per_shard import kernel_shards
    shards = kernel_shards(q.shape)
    if shards is None or shards.spec[0] is None:
        return local(q, k, v, qi, ki, wi)
    # one call a data shard: each sees its own sequences whole; L_I's
    # weight counts the queries of the whole batch, so the shards'
    # gradients add up to the mean's
    from jax.sharding import PartitionSpec as P
    spec = P(shards.spec[0])
    return jax.shard_map(local, in_specs=(spec,) * 6, out_specs=spec,
                         check_vma=False)(q, k, v, qi, ki, wi)

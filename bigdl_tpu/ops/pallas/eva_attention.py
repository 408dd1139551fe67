"""EVA attention (Zheng et al., arXiv:2302.04542) as EvaByte uses it, one
Pallas TPU kernel each way: exact causal softmax attention inside a
window of W tokens, every EARLIER window seen through one summary key
and value per chunk of C tokens, both under ONE softmax normalisation.

    local(i)  = { j in window(i), j <= i }
    remote(i) = { c : chunk c lies in a window before window(i) }
    o_i = [ sum_local exp(s q_i.k_j) v_j + sum_remote exp(s q_i.k~_c) v~_c ]
          / [ sum_local exp(s q_i.k_j) + sum_remote exp(s q_i.k~_c) ]

The summaries k~, v~ (S / C rows a head) are made once a layer by the
caller (``nn.EvaAttention``: a softmax pooling of each chunk's keys),
on the XLA path, and arrive here as two more operands; their gradients
leave as two more outputs. No S x S and no S x S/C score matrix exists
in HBM: work and traffic are linear in S.

One path with ``flash_attention.py``, adapting: a window's causal tiles
ARE a causal self-attention call of length W, so the forward kernel is
``_walk_forward`` (flash's looped kernel: the window's K/V resident in
VMEM, wide steps below the diagonal, the block on the diagonal cut in
squares) on the q block's position INSIDE its window, and then ONE more
``_softmax_step`` against the summaries of the windows before it, under
the same running max; the backward is ``_walk_backward`` and one more
``_backward_piece``. Tile sizes come from ``_schedule(causal, W, W, d)``
— what a flash call of one window would run. Grid (batch x heads, S /
bq): the window's K/V block index changes once a window, so it is
fetched once a window; the head's summaries (S / C x d) stay resident.
dk/dv accumulate in VMEM over a window's q blocks, dk~/dv~ over a
head's. A tile that lies wholly in a query's future, or in its own
window's summaries, is never computed: the summaries a q block takes
are a static slice chosen by its window's index (``_one_of``).

What a call runs is stated where it is traced, as a
``bigdl:kernels:eva_schedule`` instant (never in a step).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas.flash_attention import (
    _VMEM_LIMIT, Schedule, _backward_piece, _fold_scale, _one_of, _schedule,
    _softmax_finish, _softmax_init, _softmax_step, _walk_backward,
    _walk_forward)

__all__ = ["eva_attention", "eva_schedule", "EvaSchedule"]


class EvaSchedule(NamedTuple):
    """What one call runs. ``local`` is the flash schedule of ONE window
    (its ``tiles_computed`` / ``tiles_visible`` count ``block``-sided
    squares a window). ``tiles_*`` here count a head: local squares over
    all windows; remote tiles are bq x (W / C) — one q block against one
    earlier window's summaries — of which none is masked, so needed ==
    computed."""

    local: Schedule
    windows: int
    q_per_window: int
    per_window: int               # summaries a window: W / C
    tiles_computed: int
    tiles_needed: float
    remote_tiles_computed: int
    remote_tiles_needed: int


def eva_schedule(sq: int, window: int, chunk: int, d: int,
                 itemsize: int) -> EvaSchedule:
    """Tiles from the shapes alone; raises by name what the kernel does
    not support (there is no other path on the TPU)."""
    if window % chunk:
        raise ValueError(f"eva_attention: window {window} is not a "
                         f"multiple of chunk {chunk}")
    if sq % window:
        raise ValueError(f"eva_attention: sequence length {sq} is not a "
                         f"multiple of window {window}")
    per_window = window // chunk
    tile = 32 // itemsize          # sublane tile: bf16 16 rows, f32 8
    if per_window % tile:
        raise ValueError(
            f"eva_attention: {per_window} summaries a window (window "
            f"{window} / chunk {chunk}) is not a multiple of the {tile}-row "
            "sublane tile")
    local = _schedule(True, window, window, d, itemsize)
    if not (local.kv_resident and local.one_pass_backward):
        raise ValueError(
            f"eva_attention: a window of {window} x {d} does not fit "
            "VMEM (K/V resident, one backward pass)")
    windows, qpw = sq // window, window // local.bq
    remote = qpw * windows * (windows - 1) // 2
    return EvaSchedule(local, windows, qpw, per_window,
                       tiles_computed=local.tiles_computed * windows,
                       tiles_needed=local.tiles_visible * windows,
                       remote_tiles_computed=remote,
                       remote_tiles_needed=remote)


def _fwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, sched):
    """One q block: its window's causal tiles, then the summaries of the
    windows before it, one running max."""
    loc, qi = sched.local, pl.program_id(1)
    _softmax_init(m_scr, l_scr, acc_scr)
    q, s_scale = _fold_scale(q_ref[0], scale)
    _walk_forward(q, s_scale, k_ref, v_ref, qi % sched.q_per_window,
                  m_scr, l_scr, acc_scr, bq=loc.bq, bk=loc.bk,
                  block=loc.block)

    def remote(w):
        n = w * sched.per_window
        if n:
            _softmax_step(q, [((0, loc.bq), ks_ref[0, :n, :],
                               vs_ref[0, :n, :], None)],
                          m_scr, l_scr, acc_scr, s_scale)

    _one_of(qi // sched.q_per_window, sched.windows, remote)
    _softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _bwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, dks_ref, dvs_ref,
                dq_scr, dk_scr, dv_scr, dks_scr, dvs_scr, *, scale, nq,
                sched):
    """One q block, all of the backward in one pass. dk/dv accumulate
    over the q blocks of a WINDOW and leave with its last one; dk~/dv~
    over the q blocks of a head."""
    loc, qi = sched.local, pl.program_id(1)
    at = qi % sched.q_per_window

    @pl.when(qi == 0)
    def _new_head():
        dks_scr[:] = jnp.zeros_like(dks_scr)
        dvs_scr[:] = jnp.zeros_like(dvs_scr)

    @pl.when(at == 0)
    def _new_window():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    dq_scr[:] = jnp.zeros_like(dq_scr)
    q, s_scale = _fold_scale(q_ref[0], scale)
    do = do_ref[0]
    _walk_backward(q, do, s_scale, k_ref, v_ref, lse_ref, delta_ref,
                   dq_scr, dk_scr, dv_scr, at, bq=loc.bwd_bq,
                   bk=loc.bwd_bk, block=loc.block)

    def remote(w):
        n = w * sched.per_window
        if n:
            _backward_piece(q, do, s_scale, ks_ref, vs_ref, lse_ref,
                            delta_ref, dq_scr, dks_scr, dvs_scr,
                            pl.ds(0, n), 0, False)

    _one_of(qi // sched.q_per_window, sched.windows, remote)
    dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)
    # the keys saw q * scale when the scale folded; else they take it here
    k_scale = scale if s_scale != 1.0 else 1.0

    @pl.when(at == sched.q_per_window - 1)
    def _write_window():
        dk_ref[0] = (dk_scr[:] * k_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(qi == nq - 1)
    def _write_head():
        dks_ref[0] = (dks_scr[:] * k_scale).astype(dks_ref.dtype)
        dvs_ref[0] = dvs_scr[:].astype(dvs_ref.dtype)


def _specs(sched, sq, window, d, bq):
    qpw, n_sum = window // bq, sched.windows * sched.per_window
    nq = sq // bq
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0))
    win_spec = pl.BlockSpec((1, window, d), lambda b, i: (b, i // qpw, 0))
    sum_spec = pl.BlockSpec((1, n_sum, d), lambda b, i: (b, 0, 0))
    # row statistics lane-dense: a (1, bq) row a q block
    stat_spec = pl.BlockSpec((1, 1, bq), lambda b, i: (b * nq + i, 0, 0))
    return q_spec, win_spec, sum_spec, stat_spec


@functools.lru_cache(maxsize=16)
def _fwd_call(bh, sq, window, d, dtype, scale, interpret, sched):
    """(q, k, v, k~, v~) -> (o, lse); built once a geometry, as flash's."""
    from jax.experimental.pallas import tpu as pltpu
    bq = sched.local.bq
    q_spec, win_spec, sum_spec, stat_spec = _specs(sched, sq, window, d, bq)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, sched=sched),
        grid=(bh, sq // bq),
        in_specs=[q_spec, win_spec, win_spec, sum_spec, sum_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), dtype),
                   jax.ShapeDtypeStruct((bh * sq // bq, 1, bq),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="eva_attention_fwd",
    )


@functools.lru_cache(maxsize=16)
def _bwd_call(bh, sq, window, d, dtype, scale, interpret, sched):
    """(q, k, v, k~, v~, dO, lse, delta) -> (dq, dk, dv, dk~, dv~)."""
    from jax.experimental.pallas import tpu as pltpu
    bq = sched.local.bwd_bq
    n_sum = sched.windows * sched.per_window
    q_spec, win_spec, sum_spec, stat_spec = _specs(sched, sq, window, d, bq)
    seq = jax.ShapeDtypeStruct((bh, sq, d), dtype)
    summ = jax.ShapeDtypeStruct((bh, n_sum, d), dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, nq=sq // bq,
                          sched=sched),
        grid=(bh, sq // bq),
        in_specs=[q_spec, win_spec, win_spec, sum_spec, sum_spec, q_spec,
                  stat_spec, stat_spec],
        out_specs=[q_spec, win_spec, win_spec, sum_spec, sum_spec],
        out_shape=[seq, seq, seq, summ, summ],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((window, d), jnp.float32),
                        pltpu.VMEM((window, d), jnp.float32),
                        pltpu.VMEM((n_sum, d), jnp.float32),
                        pltpu.VMEM((n_sum, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="eva_attention_dqdkdv",
    )


def _fwd(q, k, v, ks, vs, window, chunk, scale, interpret):
    from bigdl_tpu.observability import trace
    bh, sq, d = q.shape
    sched = eva_schedule(sq, window, chunk, d, q.dtype.itemsize)
    # python runs this when the kernel is traced for a compile, never in
    # a step: the schedule is static
    loc = sched.local
    trace.instant("eva_schedule", cat="kernels", sq=sq, window=window,
                  chunk=chunk, d=d, bq=loc.bq, bk=loc.bk,
                  bwd_bq=loc.bwd_bq, bwd_bk=loc.bwd_bk, block=loc.block,
                  tiles_computed=sched.tiles_computed,
                  tiles_needed=sched.tiles_needed,
                  remote_tiles_computed=sched.remote_tiles_computed,
                  remote_tiles_needed=sched.remote_tiles_needed)
    o, lse = _fwd_call(bh, sq, window, d, q.dtype, scale, interpret,
                       sched)(q, k, v, ks, vs)
    return o, lse.reshape(bh, sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _eva_bhsd(q, k, v, ks, vs, window, chunk, scale, interpret):
    return _fwd(q, k, v, ks, vs, window, chunk, scale, interpret)[0]


def _eva_fwd(q, k, v, ks, vs, window, chunk, scale, interpret):
    """What the kernel made is NAMED: a recomputed block keeps it
    (``optim/remat.py``, ``"per_block"``) and runs the kernel once."""
    o, lse = _fwd(q, k, v, ks, vs, window, chunk, scale, interpret)
    o = checkpoint_name(o, "attention_out")
    lse = checkpoint_name(lse, "attention_stats")
    return o, (q, k, v, ks, vs, o, lse)


def _eva_bwd(window, chunk, scale, interpret, res, g):
    q, k, v, ks, vs, o, lse = res
    bh, sq, d = q.shape
    sched = eva_schedule(sq, window, chunk, d, q.dtype.itemsize)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    stats = (bh * sq // sched.local.bwd_bq, 1, sched.local.bwd_bq)
    return tuple(_bwd_call(bh, sq, window, d, q.dtype, scale, interpret,
                           sched)(q, k, v, ks, vs, g, lse.reshape(stats),
                                  delta.reshape(stats)))


_eva_bhsd.defvjp(_eva_fwd, _eva_bwd)


def eva_attention(q, k, v, ks, vs, *, window: int, chunk: int,
                  scale: float | None = None, interpret: bool = False):
    """EVA attention over ``q, k, v`` (B, S, H, D) and the chunk
    summaries ``ks, vs`` (B, S / chunk, H, D), all of one dtype; S a
    multiple of ``window``, ``window`` of ``chunk``. Differentiable in
    all five. Shapes the kernel does not take raise a ``ValueError``
    that names them (``eva_schedule``)."""
    b, sq, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    o = _eva_bhsd(fold(q), fold(k), fold(v), fold(ks), fold(vs), window,
                  chunk, scale, interpret)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

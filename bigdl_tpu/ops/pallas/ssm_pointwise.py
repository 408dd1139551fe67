"""Mamba-2's pointwise work around the scan as one Pallas TPU pass each
way (``nn.Mamba2Mixer``; docs/state_space.md):

    conv_silu_split:  x | B | C = silu(b + sum_i w[:, i] xBC[t + i - K + 1])
    gated_norm:       out = RMSNorm_group(y * silu(z)) * w

Each reads its inputs once and writes its outputs once in HBM, forward
and backward; everything between is float32 in VMEM, and what leaves is
in the inputs' dtype (the compute dtype: bf16 in the cell), where
``nn.state_space`` stored it before. Neither saves a float32 residual:
the backward passes recompute the pre-activation (conv) or the gated
product and its statistics (norm) from the inputs.

The passes write and read the layouts the scan's kernels use
(``ops/pallas/ssd.py``): x and y as (B, H, S, P), B and C as (B, G, S,
N). The functions take and give the mixer's logical (B, S, H, P) and
(B, S, G, N); the transposes between cancel against the scan wrapper's,
so no copy of x, B, C, y or their cotangents is made between the passes
and the scan.

conv_silu_split: grid (batch, token tile), every channel a grid step,
in column chunks of 128 k. A step reads its (tokens, channels) tile and
the ``HALO`` rows before it (zero before the sequence's start, so the
window never crosses a batch row). The backward reads the ``HALO`` rows
after it as well, applies silu' to the incoming cotangents, runs the
transposed conv and adds the taps' and the bias's gradients of its rows
into a float32 block that stays in VMEM across the token axis
(sequential, ``"arbitrary"``): one per batch row, summed over the batch
on the XLA path.

gated_norm: grid (batch, group, row tile); a block is one group's heads
(its columns of z and of the output), so the statistics never leave the
grid step. The backward's weight gradient is carried across the batch
and row axes the same way.

Token counts need not be a multiple of the tile: rows past the end are
masked out of every sum. Shapes the TPU kernels do not take raise a
``ValueError`` that names them (``conv_schedule``, ``norm_schedule``);
there is no other path on the TPU.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["conv_silu_split", "gated_norm", "conv_schedule",
           "norm_schedule", "Widths", "ConvSchedule", "NormSchedule",
           "pointwise_bytes"]

_VMEM_LIMIT = 64 * 2 ** 20
F32 = jnp.float32
#: rows of a neighbouring token tile a conv grid step reads: bf16's
#: sublane tile, and at least K - 1
HALO = 16
BLOCK_TOKENS, BLOCK_ROWS, CHUNK = 256, 512, 512


class Widths(NamedTuple):
    """The mixer's split of xBC: H heads of P, G groups of N."""

    heads: int
    head_dim: int
    groups: int
    state: int

    @property
    def inner(self):
        return self.heads * self.head_dim

    @property
    def gn(self):
        return self.groups * self.state


class ConvSchedule(NamedTuple):
    block_tokens: int
    token_blocks: int
    chunk_x: int        # columns of x a step computes at once
    chunk_g: int        # columns of B (or C) a step computes at once


class NormSchedule(NamedTuple):
    block_rows: int
    row_blocks: int
    width: int          # one group's columns


def _round_up(n, m):
    return -(-n // m) * m


def _tile(n, block):
    """A tile of ``block`` positions (fewer where the sequence is
    shorter, rounded up to the halo's rows) and the tiles to cover n."""
    ts = min(block, _round_up(n, HALO))
    return ts, -(-n // ts)


def _chunk(width, piece, interpret, what):
    """The widest column chunk of at most ``CHUNK`` that holds whole
    pieces (heads, groups) and, on the TPU, whole lane tiles."""
    for c in (CHUNK, 256, 128):
        if width % c == 0 and c % piece == 0:
            return c
    if interpret:
        return width
    raise ValueError(f"conv_silu_split: the TPU kernel takes {what} in "
                     f"column chunks of 128 k holding whole pieces of "
                     f"{piece}; got {width}")


def conv_schedule(seq: int, widths: Widths, kernel: int, *,
                  interpret: bool = False) -> ConvSchedule:
    """The conv pass's grid from the shapes; raises by name what the
    kernels do not take."""
    if not 1 <= kernel - 1 <= HALO:
        raise ValueError(f"conv_silu_split: a kernel of {kernel} taps "
                         f"reaches past the {HALO} rows a grid step reads")
    ts, nt = _tile(seq, BLOCK_TOKENS)
    return ConvSchedule(
        ts, nt, _chunk(widths.inner, widths.head_dim, interpret, "x"),
        _chunk(widths.gn, widths.state, interpret, "B and C"))


def norm_schedule(rows: int, widths: Widths, *,
                  interpret: bool = False) -> NormSchedule:
    """The gated norm's grid; raises by name what the kernels do not
    take."""
    if widths.heads % widths.groups:
        raise ValueError(f"gated_norm: {widths.heads} heads do not split "
                         f"into {widths.groups} groups")
    gw = widths.inner // widths.groups
    if gw % 128 and not interpret:
        raise ValueError(f"gated_norm: the TPU kernel takes groups of a "
                         f"multiple of 128 columns; got {gw}")
    tr, nr = _tile(rows, BLOCK_ROWS)
    return NormSchedule(tr, nr, gw)


def pointwise_bytes(tokens: int, inner: int, channels: int,
                    itemsize: int) -> int:
    """HBM bytes the two passes move a layer-step at their bound, every
    tensor read or written once: each forward twice (a block recomputed
    under ``per_block`` runs it again) and each backward once. conv:
    forward 2 T C (in + out), backward 3 T C (xBC, the cotangent, its
    gradient); norm: forward 3 T I (y, z, out), backward 5 T I (y, z,
    the cotangent, dy, dz)."""
    return itemsize * tokens * (2 * 2 * channels + 3 * channels
                                + 2 * 3 * inner + 5 * inner)


def _silu_grad(u):
    s = jax.nn.sigmoid(u)
    return s * (1.0 + u * (1.0 - s))


def _masked(v, start, limit):
    """``v`` (rows, ...) as float32 with the rows whose global position
    start + row lies outside [0, limit) zero (a select: what lies past
    the array's end is unspecified, NaN included)."""
    v = v.astype(F32)
    at = start + jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)
    return jnp.where((at >= 0) & (at < limit), v, 0.0)


def _taps(src, w_ref, cols, at, rows, k):
    """sum_i w[i] src[at + i - k + 1 .. + rows] plus the bias (row k),
    over the columns ``cols``."""
    out = w_ref[k:k + 1, cols]
    for i in range(k):
        lo = at + i - k + 1
        out = out + w_ref[i:i + 1, cols] * src[lo:lo + rows, cols]
    return out


def _segments(widths, sched):
    """(first column, chunk width, output index, piece width, first
    piece) of every column chunk: x's heads, then B's and C's groups."""
    inner, gn = widths.inner, widths.gn
    for c0 in range(0, inner, sched.chunk_x):
        yield c0, sched.chunk_x, 0, widths.head_dim, c0 // widths.head_dim
    for i, base in ((1, inner), (2, inner + gn)):
        for c0 in range(0, gn, sched.chunk_g):
            yield base + c0, sched.chunk_g, i, widths.state, \
                c0 // widths.state


# -- the causal conv + silu + split --------------------------------------

def _conv_fwd_kernel(x_ref, prev_ref, w_ref, ox_ref, ob_ref, oc_ref,
                     ext_scr, *, seq, k, widths, sched):
    ts = x_ref.shape[1]
    start = pl.program_id(1) * ts
    ext_scr[0:HALO] = _masked(prev_ref[0], start - HALO, seq)
    ext_scr[HALO:] = x_ref[0].astype(F32)
    outs = (ox_ref, ob_ref, oc_ref)
    for c0, width, which, piece, first in _segments(widths, sched):
        u = _taps(ext_scr, w_ref, slice(c0, c0 + width), HALO, ts, k)
        a = (u * jax.nn.sigmoid(u)).astype(outs[which].dtype)
        for j in range(width // piece):
            outs[which][0, first + j] = a[:, j * piece:(j + 1) * piece]


def _conv_bwd_kernel(x_ref, prev_ref, next_ref, dx_ref, dxn_ref, db_ref,
                     dbn_ref, dc_ref, dcn_ref, w_ref, dxbc_ref, dw_ref,
                     xs_scr, dy_scr, *, seq, k, widths, sched):
    """Rows start .. start + ts: the cotangents of x, B and C laid side
    by side as xBC's columns (``dy_scr``, with the HALO rows after), the
    pre-activation u again over the same rows, du = dy silu'(u) (zero
    past the sequence) in place, dxBC[t] = sum_i w[i] du[t + k - 1 - i],
    and the taps' and bias's sums of this tile's rows added into
    ``dw_ref`` (rows 0 .. k - 1 the taps, row k the bias)."""
    t = pl.program_id(1)
    ts = x_ref.shape[1]
    start = t * ts
    xs_scr[0:HALO] = _masked(prev_ref[0], start - HALO, seq)
    xs_scr[HALO:HALO + ts] = _masked(x_ref[0], start, seq)
    xs_scr[HALO + ts:] = _masked(next_ref[0], start + ts, seq)
    tiles = ((dx_ref, dxn_ref), (db_ref, dbn_ref), (dc_ref, dcn_ref))

    @pl.when(t == 0)
    def _new_row():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for c0, width, which, piece, first in _segments(widths, sched):
        cols = slice(c0, c0 + width)
        tile, after = tiles[which]
        for j in range(width // piece):
            at = slice(c0 + j * piece, c0 + (j + 1) * piece)
            dy_scr[0:ts, at] = _masked(tile[0, first + j], start, seq)
            dy_scr[ts:, at] = _masked(after[0, first + j], start + ts, seq)
        # the window's taps, read once: u's terms and dw's factors
        taps = [xs_scr[HALO + i - k + 1:HALO + i - k + 1 + ts + HALO, cols]
                for i in range(k)]
        u = w_ref[k:k + 1, cols]
        for i in range(k):
            u = u + w_ref[i:i + 1, cols] * taps[i]
        du = dy_scr[:, cols] * _silu_grad(u)
        dy_scr[:, cols] = du
        dxbc = w_ref[k - 1:k, cols] * du[:ts]
        for i in range(k - 1):
            dxbc = dxbc + w_ref[i:i + 1, cols] \
                * dy_scr[k - 1 - i:k - 1 - i + ts, cols]
        dxbc_ref[0, :, cols] = dxbc.astype(dxbc_ref.dtype)
        du = du[:ts]
        for i in range(k):
            dw_ref[0, i:i + 1, cols] += jnp.sum(du * taps[i][:ts], axis=0,
                                                keepdims=True)
        dw_ref[0, k:k + 1, cols] += jnp.sum(du, axis=0, keepdims=True)


def _conv_specs(sched, seq, c, widths):
    ts = sched.block_tokens
    per, last = ts // HALO, -(-seq // HALO) - 1

    def after(t):           # the HALO rows after tile t, in HALO blocks
        return jnp.minimum((t + 1) * per, last)

    h, p, g, n = widths
    rows = pl.BlockSpec((1, ts, c), lambda b, t: (b, t, 0))
    prev = pl.BlockSpec((1, HALO, c),
                        lambda b, t: (b, jnp.maximum(t * per - 1, 0), 0))
    nxt = pl.BlockSpec((1, HALO, c), lambda b, t: (b, after(t), 0))
    heads = pl.BlockSpec((1, h, ts, p), lambda b, t: (b, 0, t, 0))
    heads_next = pl.BlockSpec((1, h, HALO, p),
                              lambda b, t: (b, 0, after(t), 0))
    groups = pl.BlockSpec((1, g, ts, n), lambda b, t: (b, 0, t, 0))
    groups_next = pl.BlockSpec((1, g, HALO, n),
                               lambda b, t: (b, 0, after(t), 0))
    return rows, prev, nxt, heads, heads_next, groups, groups_next


def _params(interpret, semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu, dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


@functools.lru_cache(maxsize=16)
def _conv_fwd_call(b, s, k, widths, dtype, sched, interpret):
    pltpu, kw = _params(interpret, ("parallel", "parallel"))
    c = widths.inner + 2 * widths.gn
    rows, prev, _, heads, _, groups, _ = _conv_specs(sched, s, c, widths)
    h, p, g, n = widths
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, seq=s, k=k, widths=widths,
                          sched=sched),
        grid=(b, sched.token_blocks),
        in_specs=[rows, prev,
                  pl.BlockSpec((_round_up(k + 1, 8), c), lambda b, t: (0, 0))],
        out_specs=[heads, groups, groups],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, p), dtype),
                   jax.ShapeDtypeStruct((b, g, s, n), dtype),
                   jax.ShapeDtypeStruct((b, g, s, n), dtype)],
        scratch_shapes=[pltpu.VMEM((HALO + sched.block_tokens, c), F32)],
        name="ssm_conv_fwd", **kw)


@functools.lru_cache(maxsize=16)
def _conv_bwd_call(b, s, k, widths, dtype, sched, interpret):
    pltpu, kw = _params(interpret, ("parallel", "arbitrary"))
    c = widths.inner + 2 * widths.gn
    rows, prev, nxt, heads, heads_next, groups, groups_next = _conv_specs(
        sched, s, c, widths)
    kp, ts = _round_up(k + 1, 8), sched.block_tokens
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, seq=s, k=k, widths=widths,
                          sched=sched),
        grid=(b, sched.token_blocks),
        in_specs=[rows, prev, nxt, heads, heads_next, groups, groups_next,
                  groups, groups_next,
                  pl.BlockSpec((kp, c), lambda b, t: (0, 0))],
        out_specs=[rows, pl.BlockSpec((1, kp, c), lambda b, t: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, c), dtype),
                   jax.ShapeDtypeStruct((b, kp, c), F32)],
        scratch_shapes=[pltpu.VMEM((ts + 2 * HALO, c), F32),
                        pltpu.VMEM((ts + HALO, c), F32)],
        name="ssm_conv_bwd", **kw)


def _packed(w, b):
    """(C, K) taps and (C,) bias as ONE (8 k, C) float32 block: rows
    0 .. K - 1 the taps, row K the bias, the rest zero."""
    c, k = w.shape
    kp = _round_up(k + 1, 8)
    return jnp.concatenate([w.astype(F32).T, b.astype(F32)[None],
                            jnp.zeros((kp - k - 1, c), F32)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_core(x, w, b, widths, sched, interpret):
    return _conv_fwd(x, w, b, widths, sched, interpret)[0]


def _conv_fwd(x, w, b, widths, sched, interpret):
    bs, s, _ = x.shape
    out = _conv_fwd_call(bs, s, w.shape[1], widths, x.dtype, sched,
                         interpret)(x, x, _packed(w, b))
    return tuple(out), (x, w, b)


def _conv_bwd(widths, sched, interpret, res, cts):
    x, w, b = res
    bs, s, _ = x.shape
    k = w.shape[1]
    dx, db_, dc = (ct.astype(x.dtype) for ct in cts)
    dxbc, dwb = _conv_bwd_call(bs, s, k, widths, x.dtype, sched, interpret)(
        x, x, x, dx, dx, db_, db_, dc, dc, _packed(w, b))
    dwb = jnp.sum(dwb, axis=0)
    return dxbc, dwb[:k].T.astype(w.dtype), dwb[k].astype(b.dtype)


_conv_core.defvjp(_conv_fwd, _conv_bwd)


def conv_silu_split(xbc, w, b, *, heads: int, head_dim: int, groups: int,
                    state: int, interpret: bool = False):
    """``silu(causal_conv1d(xbc, w, b))`` (``nn.state_space``) over
    ``xbc`` (B, S, C) with taps ``w`` (C, K) and bias ``b`` (C,), split
    as the mixer splits it: x (B, S, heads, head_dim), B and C (B, S,
    groups, state), in xbc's dtype; float32 inside, zero history at each
    sequence's start. Differentiable in all three: dxbc in xbc's dtype,
    dw and db summed in float32."""
    widths = Widths(heads, head_dim, groups, state)
    if xbc.shape[2] != widths.inner + 2 * widths.gn:
        raise ValueError(f"conv_silu_split: {xbc.shape[2]} channels are not "
                         f"{heads} x {head_dim} + 2 x {groups} x {state}")
    sched = conv_schedule(xbc.shape[1], widths, w.shape[1],
                          interpret=interpret)
    x, bm, cm = _conv_core(xbc, w, b, widths, sched, interpret)
    return x.swapaxes(1, 2), bm.swapaxes(1, 2), cm.swapaxes(1, 2)


# -- the gated group norm ------------------------------------------------

def _gather_heads(y_ref, y_scr, per, p):
    """One group's heads of the (1, per, rows, P) block side by side:
    (rows, per P) float32."""
    for j in range(per):
        y_scr[:, j * p:(j + 1) * p] = y_ref[0, j].astype(F32)
    return y_scr[...]


def _gated(y, z_ref, eps):
    z = z_ref[0].astype(F32)
    sig = jax.nn.sigmoid(z)
    g = y * z * sig
    r = jax.lax.rsqrt(jnp.mean(g * g, axis=1, keepdims=True) + eps)
    return z, sig, g, r


def _norm_fwd_kernel(y_ref, z_ref, w_ref, o_ref, y_scr, *, eps, per):
    y = _gather_heads(y_ref, y_scr, per, y_ref.shape[3])
    _, _, g, r = _gated(y, z_ref, eps)
    o_ref[0] = (g * r * w_ref[...]).astype(o_ref.dtype)


def _norm_bwd_kernel(y_ref, z_ref, do_ref, w_ref, dy_ref, dz_ref, dw_ref,
                     y_scr, *, eps, per, seq):
    """dn = do w, dg = r (dn - g r^2 mean(dn g)), dy = dg silu(z), dz =
    dg y silu'(z); dw += the column sums of do g r over this tile's
    rows."""
    b, t = pl.program_id(1), pl.program_id(2)
    p = y_ref.shape[3]
    tr = y_ref.shape[2]
    y = _gather_heads(y_ref, y_scr, per, p)
    z, sig, g, r = _gated(y, z_ref, eps)
    do = do_ref[0].astype(F32)
    dn = do * w_ref[...]
    dg = r * (dn - g * (r * r) * jnp.mean(dn * g, axis=1, keepdims=True))
    dy = dg * z * sig
    for j in range(per):
        dy_ref[0, j] = dy[:, j * p:(j + 1) * p].astype(dy_ref.dtype)
    dz_ref[0] = (dg * y * sig * (1.0 + z * (1.0 - sig))).astype(
        dz_ref.dtype)

    @pl.when((b == 0) & (t == 0))
    def _first():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jnp.sum(_masked(do * g * r, t * tr, seq), axis=0,
                           keepdims=True)


def _norm_specs(sched, widths, bgt):
    """Blocks of one group's heads (B, H, S, P), its columns (B, S, H P)
    and of the weight; ``bgt`` maps the grid's indices to (batch, group,
    row tile)."""
    tr, gw, per = sched.block_rows, sched.width, widths.heads // widths.groups

    def heads_at(*i):
        b, g, t = bgt(*i)
        return b, g, t, 0

    def cols_at(*i):
        b, g, t = bgt(*i)
        return b, t, g

    return (pl.BlockSpec((1, per, tr, widths.head_dim), heads_at),
            pl.BlockSpec((1, tr, gw), cols_at),
            pl.BlockSpec((1, gw), lambda *i: (0, bgt(*i)[1])))


@functools.lru_cache(maxsize=16)
def _norm_fwd_call(b, s, widths, dtype, eps, sched, interpret):
    pltpu, kw = _params(interpret, ("parallel",) * 3)
    heads, cols, lane = _norm_specs(sched, widths, lambda b, g, t: (b, g, t))
    per = widths.heads // widths.groups
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, eps=eps, per=per),
        grid=(b, widths.groups, sched.row_blocks),
        in_specs=[heads, cols, lane], out_specs=cols,
        out_shape=jax.ShapeDtypeStruct((b, s, widths.inner), dtype),
        scratch_shapes=[pltpu.VMEM((sched.block_rows, sched.width), F32)],
        name="ssm_norm_fwd", **kw)


@functools.lru_cache(maxsize=16)
def _norm_bwd_call(b, s, widths, dtype, eps, sched, interpret):
    pltpu, kw = _params(interpret, ("parallel", "arbitrary", "arbitrary"))
    heads, cols, lane = _norm_specs(sched, widths, lambda g, b, t: (b, g, t))
    per = widths.heads // widths.groups
    h, p = widths.heads, widths.head_dim
    return pl.pallas_call(
        functools.partial(_norm_bwd_kernel, eps=eps, per=per, seq=s),
        grid=(widths.groups, b, sched.row_blocks),
        in_specs=[heads, cols, cols, lane], out_specs=[heads, cols, lane],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, p), dtype),
                   jax.ShapeDtypeStruct((b, s, widths.inner), dtype),
                   jax.ShapeDtypeStruct((1, widths.inner), F32)],
        scratch_shapes=[pltpu.VMEM((sched.block_rows, sched.width), F32)],
        name="ssm_norm_bwd", **kw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm_core(y, z, w, widths, eps, sched, interpret):
    return _norm_fwd(y, z, w, widths, eps, sched, interpret)[0]


def _norm_fwd(y, z, w, widths, eps, sched, interpret):
    b, _, s, _ = y.shape
    out = _norm_fwd_call(b, s, widths, y.dtype, eps, sched, interpret)(
        y, z, w.astype(F32)[None])
    return out, (y, z, w)


def _norm_bwd(widths, eps, sched, interpret, res, do):
    y, z, w = res
    b, _, s, _ = y.shape
    dy, dz, dw = _norm_bwd_call(b, s, widths, y.dtype, eps, sched,
                                interpret)(y, z, do.astype(y.dtype),
                                           w.astype(F32)[None])
    return dy, dz.astype(z.dtype), dw[0].astype(w.dtype)


_norm_core.defvjp(_norm_fwd, _norm_bwd)


def gated_norm(y, z, w, groups: int, eps: float, *,
               interpret: bool = False):
    """``gated_rms_norm(y, z, w, groups, eps)`` (``nn.state_space``) with
    ``y`` the scan's (B, S, H, P) and ``z`` (B, S, H P), the weight ``w``
    (H P,): (B, S, H P) in y's dtype, float32 inside. A group is H / G
    whole heads. Differentiable in all three: dy and dz in their dtypes,
    dw summed in float32."""
    b, s, h, p = y.shape
    widths = Widths(h, p, groups, 1)
    sched = norm_schedule(s, widths, interpret=interpret)
    return _norm_core(y.swapaxes(1, 2), z, w, widths, float(eps), sched,
                      interpret)

"""Pallas paged-attention decode kernel: attend straight off the KV
page pool (ISSUE 9 tentpole).

Why this kernel exists: the serving stack's paged decode used to
consume the page pool through ``_paged_view`` — a gather of the ENTIRE
(B, P) block table into a dense (B, P*S, KV, D) cache copy per layer,
per decode step — and then run grouped attention over that copy. That
is an O(B·P·S·KV·D) HBM materialization (gather write + attention
re-read) to score ONE new token per row. This kernel walks each row's
block table page-by-page with an online softmax instead:

- grid ``(B*KV, T_blocks, P)``: each program loads one PHYSICAL page
  ``(S, D)`` for one (row, kv-head) pair — the page index comes from
  the scalar-prefetched block table, so the logical->physical hop
  happens in the BlockSpec index map and no dense view ever exists;
- scratch carries the flash-style running (max, sum, acc) across the
  page walk; per-row length/causal masking uses the scalar-prefetched
  ``q_start`` (query column t sits at absolute position q_start+t and
  may attend keys at positions <= its own);
- pages entirely past a block's last query position are skipped at the
  grid level (``pl.when``), so a 20-token row in a 4096-token table
  touches 2 pages, not 256;
- GQA/MQA head grouping rides the q block: the G query heads sharing a
  kv head fold into the matmul's row dimension, padded to ``gp`` rows
  (sublane alignment; padded rows are sliced off host-side).

Tile picking follows the house idiom (flash/fused_ce/lrn/maxpool): an
autotuned record in ``bigdl_tpu/tuning`` for this (t, g, s, d, device
kind) wins when legal; the static default otherwise. ``interpret=True``
runs the identical program on CPU — tier-1 pins numeric parity against
the dense ``_paged_view`` + ``_attend_grouped`` reference there
(tests/test_paged_attention.py).

The same kernel also serves DENSE per-row caches (the ragged /
speculative machinery): a (B, M, KV, D) cache is a page pool of
``M // page`` contiguous pages per row with an identity block table —
``dense_cache_attention`` builds that view (a free reshape, no copy)
so the speculative verify/decode steps ride the same switch.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["paged_attention", "dense_cache_attention", "paged_supported",
           "dense_cache_supported", "dense_cache_page_size"]

logger = logging.getLogger("bigdl_tpu.ops")

_NEG = -1e9  # finite mask value, matches serving.py's _attend_grouped

#: static query-block menu: largest divisor of T wins (T is 1 for
#: decode, the prompt bucket for prefill, gamma+1 for speculative
#: verify) — bt*gp rows per program tile keep the score tile small.
_BT_CAP = 8
#: group rows are padded to a multiple of the f32 sublane tile so the
#: (bt*gp, S) score tile is Mosaic-aligned; padded rows cost VPU lanes,
#: not correctness (their outputs are sliced off).
_GP_ALIGN = 8


def _static_tiles(t: int, g: int) -> tuple[int, int]:
    bt = next((b for b in range(min(_BT_CAP, t), 0, -1) if t % b == 0), 1)
    gp = -(-g // _GP_ALIGN) * _GP_ALIGN
    return bt, gp


def _pick_tiles(t: int, g: int, s: int, d: int) -> tuple[int, int]:
    """(bt, gp) for this geometry: tuned record first (kernel
    ``paged_attention``, signature {t, g, s, d}), static default
    otherwise. An illegal record — bt not dividing T, gp below the real
    group count — is ignored with a warning, never an error."""
    from bigdl_tpu.tuning.records import default_records
    cfg = default_records().lookup("paged_attention",
                                   {"t": t, "g": g, "s": s, "d": d})
    if cfg:
        try:
            bt, gp = int(cfg["bt"]), int(cfg["gp"])
        except (KeyError, TypeError, ValueError):
            bt = gp = 0
        if 1 <= bt <= t and t % bt == 0 and gp >= g:
            return bt, gp
        logger.warning("ignoring illegal paged_attention tuning record "
                       "%s for t=%d g=%d s=%d d=%d", cfg, t, g, s, d)
    return _static_tiles(t, g)


def paged_supported(head_dim: int, page_size: int, kv_heads: int,
                    dtype) -> bool:
    """Whether the compiled kernel accepts this pool geometry — the
    auto switch picks "pallas" exactly where this is true and the
    dense view everywhere else. What Mosaic on the v5e accepts
    (compiled and checked against the dense view on the chip, PR 21):

    - bf16 and f32 pools (it compiles no float16);
    - a kv head is a ``head_dim``-wide lane block of the
      (num_pages, S, KV*D) pool view, so more than one kv head needs
      ``head_dim % 128 == 0`` — a single head IS the whole lane dim and
      any width passes;
    - any page that fills at least one 32-bit sublane row: one row of
      f32, two of bf16. Pages need NOT be whole (8|16, 128) tiles.

    ``interpret=True`` has no such constraints (the CPU parity path)."""
    dtype = jnp.dtype(dtype)
    return (jax.default_backend() == "tpu"
            and dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and (kv_heads == 1 or head_dim % 128 == 0)
            and page_size * dtype.itemsize >= 4)


def dense_cache_page_size(max_len: int, cap: int = 128,
                          floor: int = 8) -> int:
    """Page size the dense-cache view splits a (B, M, KV, D) cache
    into: the largest divisor of M in [floor, cap] — below the floor
    the per-page program overhead beats the skipping win, so an
    awkward M (e.g. prime) degrades to one M-wide page per row instead
    (still no copy, just no page skipping)."""
    return next((s for s in range(min(cap, max_len), floor - 1, -1)
                 if max_len % s == 0), max_len)


def dense_cache_supported(head_dim: int, max_len: int, kv_heads: int,
                          dtype) -> bool:
    """Auto-switch legality for the dense-cache (ragged/speculative)
    view on the compiled path."""
    return paged_supported(head_dim, dense_cache_page_size(max_len),
                           kv_heads, dtype)


def _kernel(table_ref, qstart_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, kv, n_pages, bt, gp, s):
    j = pl.program_id(2)        # logical page within the row's table
    ti = pl.program_id(1)       # query time-block
    b = pl.program_id(0) // kv  # batch row

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qs = qstart_ref[b]

    def _compute():
        d = q_ref.shape[-1]
        q = q_ref[0].reshape(bt * gp, d)            # (R, D)
        k = k_ref[0]                                # (S, D)
        v = v_ref[0]
        # matmuls stay in the pool dtype (bf16 full-rate on the MXU),
        # f32 accumulation — the flash kernel's round-3 lesson
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32
                                 ) * scale
        rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        kpos = j * s + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        qpos = qs + ti * bt + rows // gp
        sc = jnp.where(kpos > qpos, _NEG, sc)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = m_new

    # a page whose first slot sits past the block's LAST query position
    # contributes exactly zero (every key masked) — skip its DMA+FLOPs
    pl.when(j * s <= qs + ti * bt + bt - 1)(_compute)

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:]).reshape(bt, gp,
                                                   o_ref.shape[-1])


def paged_attention(q, kp, vp, table, q_start, *, scale=None,
                    bt: int | None = None, gp: int | None = None,
                    interpret: bool = False):
    """Grouped causal attention of ``q`` (B, T, H, D) directly against
    the page pool — no dense per-row cache view is materialized.

    ``kp``/``vp``: (num_pages, S, KV, D) physical pools; ``table``:
    (B, P) logical->physical page ids (every entry must be a legal pool
    index — the serving layer's tables are); ``q_start``: (B,) absolute
    position of each row's FIRST query column — column t sits at
    q_start+t and attends key positions <= q_start+t (exactly
    ``_attend_grouped``'s ``upto`` mask for the serving layer's
    column layouts). Returns (B, T, H, D) f32.

    Tiles come from the autotuned record store unless ``bt``/``gp``
    override them. ``interpret=True`` runs the interpreter (the CPU
    parity path tier-1 pins).
    """
    b, t, h, d = q.shape
    n_pool, s, kv, _ = kp.shape
    if h % kv:
        raise ValueError(f"{h} query heads not divisible by {kv} kv "
                         "heads")
    g = h // kv
    p = table.shape[1]
    scale = d ** -0.5 if scale is None else scale
    pbt, pgp = _pick_tiles(t, g, s, d)
    bt = pbt if bt is None else bt
    gp = pgp if gp is None else gp
    if t % bt or gp < g:
        raise ValueError(f"illegal tiles bt={bt} gp={gp} for t={t} "
                         f"g={g}")
    from jax.experimental.pallas import tpu as pltpu
    qg = q.astype(kp.dtype).reshape(b, t, kv, g, d)
    if gp > g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, gp - g), (0, 0)))
    qf = qg.transpose(0, 2, 1, 3, 4).reshape(b * kv, t, gp, d)

    def qmap(bk, ti, j, table_ref, qstart_ref):
        return (bk, ti, 0, 0)

    def kvmap(bk, ti, j, table_ref, qstart_ref):
        # the logical->physical hop: one scalar-prefetched table probe
        # per block, never a gathered view; the kv head is a D-wide
        # lane block of the (num_pages, S, KV*D) pool view
        return (table_ref[bk // kv, j], 0, bk % kv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * kv, t // bt, p),
        in_specs=[pl.BlockSpec((1, bt, gp, d), qmap),
                  pl.BlockSpec((1, s, d), kvmap),
                  pl.BlockSpec((1, s, d), kvmap)],
        out_specs=pl.BlockSpec((1, bt, gp, d), qmap),
        scratch_shapes=[pltpu.VMEM((bt * gp, 1), jnp.float32),
                        pltpu.VMEM((bt * gp, 1), jnp.float32),
                        pltpu.VMEM((bt * gp, d), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, kv=kv, n_pages=p, bt=bt,
                          gp=gp, s=s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kv, t, gp, d), jnp.float32),
        interpret=interpret,
        name="paged_attention",
    )(table.astype(jnp.int32), q_start.astype(jnp.int32), qf,
      kp.reshape(n_pool, s, kv * d), vp.reshape(n_pool, s, kv * d))
    return (out.reshape(b, kv, t, gp, d)[:, :, :, :g]
            .transpose(0, 2, 1, 3, 4).reshape(b, t, h, d))


def dense_cache_attention(q, ck, cv, q_start, *, scale=None,
                          interpret: bool = False):
    """The kernel over a DENSE per-row cache (B, M, KV, D) — the
    ragged/speculative layout. The cache IS a page pool of ``M // S``
    contiguous pages per row (a reshape, not a copy) with the identity
    block table, so the same online-softmax walk applies and short rows
    still skip their empty tail pages."""
    b, m, kv, d = ck.shape
    s = dense_cache_page_size(m)
    n = m // s
    pool_k = ck.reshape(b * n, s, kv, d)
    pool_v = cv.reshape(b * n, s, kv, d)
    table = (jnp.arange(b, dtype=jnp.int32)[:, None] * n
             + jnp.arange(n, dtype=jnp.int32)[None, :])
    return paged_attention(q, pool_k, pool_v, table, q_start,
                           scale=scale, interpret=interpret)

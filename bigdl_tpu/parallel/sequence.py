"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference handles sequences functionally (scan-unrolled RNNs, padded
batching — SURVEY §5.7) and has no sequence parallelism; on TPU, long
contexts are first-class, so this module provides the two standard schemes
over the mesh's ``seq`` axis (parallel/engine.py reserves it):

- ``ring_attention``: q/k/v stay sequence-sharded; K/V blocks rotate
  around the ring via ``ppermute`` while each shard folds them into a
  numerically-stable online softmax (the Blockwise/RingAttention
  construction — see PAPERS.md "Ring Attention with Blockwise
  Transformers"). Peak memory per chip is O(seq/N), communication rides
  ICI neighbor links, and the result is bit-equivalent to full attention
  up to float summation order.
- ``ulysses_attention``: two ``all_to_all``s re-shard sequence->heads,
  run full local attention per head group, and shard back (the
  DeepSpeed-Ulysses construction). Cheaper collectives for models with
  enough heads; requires heads % mesh[seq] == 0.

Both are pure functions differentiable end-to-end (the ring loop is a
Python unroll over the static mesh size, so autodiff just works), usable
eagerly or inside jit/pjit.

Shapes follow (batch, seq, heads, head_dim) throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.parallel.collective import shard_map
from bigdl_tpu.parallel.engine import get_mesh

__all__ = ["dot_product_attention", "ring_attention", "ulysses_attention"]

_NEG = -1e9  # finite mask value: keeps exp(s - m) well-defined everywhere


def _qkv_spec(mesh, axis, batch_axis):
    """Partition spec for (B, S, H, D): sequence on ``axis``, batch on
    ``batch_axis`` ("auto" = the mesh's data axis when present, so a
    dp x sp mesh keeps its batch shards instead of all-gathering them)."""
    if batch_axis == "auto":
        batch_axis = ("data" if "data" in mesh.axis_names
                      and axis != "data" else None)
    return P(batch_axis, axis)


def dot_product_attention(q, k, v, *, causal: bool = False,
                          scale: float | None = None,
                          q_offset: int = 0, kv_offset: int = 0,
                          flash: str | bool = "auto"):
    """Attention over (B, S, H, D).

    ``q_offset``/``kv_offset`` are the global positions of element 0 —
    how causal masking stays correct on sequence shards.

    ``flash="auto"`` routes to the fused Pallas kernel
    (ops/pallas/flash_attention.py) on TPU whenever shapes allow —
    O(S·D) memory instead of the (B,H,S,S) score matrix; what it runs
    (tiles, K/V in VMEM or streamed, one backward pass or two) follows
    from the shapes there, and its measured times are in PERF.md.
    The XLA fallback below is the reference semantics (and the CPU/test
    path); both share bf16-operand matmul rounding, so they agree to
    ~1e-3 under a temperate softmax.
    """
    if flash:
        from bigdl_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          flash_supported)
        offsets_ok = not causal or (q_offset == 0 and kv_offset == 0)
        supported = offsets_ok and flash_supported(q, k)
        if flash is True and not supported:
            raise ValueError(
                f"flash=True but the kernel does not support this call: "
                f"backend={jax.default_backend()}, q{q.shape} k{k.shape}, "
                f"q_offset={q_offset} kv_offset={kv_offset} (need TPU, "
                f"seq % 128 == 0, head_dim % 64 == 0, zero offsets when "
                f"causal)")
        if supported:
            from bigdl_tpu.ops.pallas.per_shard import kernel_shards
            kernel = functools.partial(flash_attention, causal=causal,
                                       scale=scale)
            # under a multi-device mesh the kernel runs once per
            # (batch, head) shard — jax refuses to partition it itself
            shards = kernel_shards(q.shape, head_dim=2)
            if shards is None:
                return kernel(q, k, v)
            return shards.run(kernel, q, k, v)
    f32 = jnp.float32
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), k.astype(f32)) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])[:, None]
        kpos = kv_offset + jnp.arange(k.shape[1])[None, :]
        s = jnp.where((kpos > qpos)[None, None], _NEG, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(f32)).astype(q.dtype)


def _merge_blocks(o, lse, o_t, lse_t):
    """Fold a block's (o_t, lse_t) into the running (o, lse) — the
    standard blockwise-softmax merge (numerically safe when either side
    is -inf, i.e. empty)."""
    m = jnp.maximum(lse, lse_t)
    # guard fully-empty rows (both -inf): keep weights at 0
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w, w_t = jnp.exp(lse - m_safe), jnp.exp(lse_t - m_safe)
    denom = w + w_t
    d_safe = jnp.where(denom == 0.0, 1.0, denom)
    o_new = (o * w[..., None] + o_t * w_t[..., None]) / d_safe[..., None]
    return o_new, m_safe + jnp.log(d_safe)


def _ring_body_flash(q, k, v, *, axis, n, causal, scale, interpret,
                     kv_groups=1):
    """Ring attention whose per-step local attention is the fused Pallas
    flash kernel: each rotating K/V block contributes (o_t, lse_t) and the
    shards merge by logsumexp. Per-chip live memory is O(S_local * D) —
    the (S_local, S_local) score tile never exists outside VMEM.

    Causal masking needs no traced offsets inside the kernel: a block is
    fully-visible (source shard before mine), diagonal (same shard —
    plain local causal mask), or fully-masked (skipped via lax.switch).
    """
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention_with_lse
    f32 = jnp.float32
    b, sq, h, d = q.shape
    idx = jax.lax.axis_index(axis)
    o = jnp.zeros((b, sq, h, d), f32)
    lse = jnp.full((b, sq, h), -jnp.inf, f32)
    perm = [(j, (j - 1) % n) for j in range(n)]  # receive from the right

    def full_fn(q, k, v):
        o_t, l_t = flash_attention_with_lse(q, k, v, causal=False,
                                            scale=scale, interpret=interpret)
        return o_t.astype(f32), l_t

    def diag_fn(q, k, v):
        o_t, l_t = flash_attention_with_lse(q, k, v, causal=True,
                                            scale=scale, interpret=interpret)
        return o_t.astype(f32), l_t

    def skip_fn(q, k, v):
        return jnp.zeros((b, sq, h, d), f32), jnp.full((b, sq, h), -jnp.inf,
                                                       f32)

    for t in range(n):
        src = (idx + t) % n                      # global block id of k/v
        # GQA: narrow (kv-head) blocks ride the ring; widen to the query
        # head count only for the local attention math (review finding:
        # a pre-ring repeat multiplied ring bytes by the group factor)
        ke = jnp.repeat(k, kv_groups, axis=2) if kv_groups > 1 else k
        ve = jnp.repeat(v, kv_groups, axis=2) if kv_groups > 1 else v
        if causal:
            case = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
            o_t, lse_t = jax.lax.switch(case, (full_fn, diag_fn, skip_fn),
                                        q, ke, ve)
        else:
            o_t, lse_t = full_fn(q, ke, ve)
        o, lse = _merge_blocks(o, lse, o_t, lse_t)
        if t != n - 1:
            k = jax.lax.ppermute(k, axis, perm)
            v = jax.lax.ppermute(v, axis, perm)
    return o.astype(q.dtype)


def _ring_body(q, k, v, *, axis, n, causal, scale, kv_groups=1):
    """Per-shard ring attention: local q block, rotating k/v blocks."""
    f32 = jnp.float32
    b, sq, h, d = q.shape
    skv = k.shape[1]
    idx = jax.lax.axis_index(axis)
    qf = q.astype(f32) * scale

    m = jnp.full((b, h, sq), -jnp.inf, f32)     # running row max
    l = jnp.zeros((b, h, sq), f32)              # running denominator
    o = jnp.zeros((b, sq, h, d), f32)           # running numerator
    perm = [(j, (j - 1) % n) for j in range(n)]  # receive from the right

    for t in range(n):
        src = (idx + t) % n                      # global block id of k/v
        ke = jnp.repeat(k, kv_groups, axis=2) if kv_groups > 1 else k
        v_use = jnp.repeat(v, kv_groups, axis=2) if kv_groups > 1 else v
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, ke.astype(f32))
        if causal:
            qpos = idx * sq + jnp.arange(sq)[:, None]
            kpos = src * skv + jnp.arange(skv)[None, :]
            s = jnp.where((kpos > qpos)[None, None], _NEG, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # exp(-inf - -inf) can't arise: s is finite (mask is finite)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * jnp.moveaxis(corr, 1, 2)[..., None] \
            + jnp.einsum("bhqk,bkhd->bqhd", p, v_use.astype(f32))
        m = m_new
        if t != n - 1:
            k = jax.lax.ppermute(k, axis, perm)
            v = jax.lax.ppermute(v, axis, perm)

    out = o / jnp.moveaxis(l, 1, 2)[..., None]
    return out.astype(q.dtype)


def _flash_ring_ok(q, k, q_local, kv_local, causal, flash,
                   interpret=False):
    """Whether the per-shard flash path applies (mirrors flash_supported,
    but on the LOCAL shard lengths). ``flash=True`` raises when the
    kernel cannot serve the call — same contract as
    ``dot_product_attention``; "auto" quietly falls back.

    Causal additionally requires equal q/kv shard lengths: the ring
    block classification (src < idx fully visible, src == idx local
    causal) only matches global-position masking when the shards are the
    same length (_ring_body masks on idx*sq vs src*skv and stays correct
    for cross-length causal calls).
    """
    if flash is False:
        return False
    from bigdl_tpu.ops.pallas.flash_attention import _Q_BLOCKS
    shapes_ok = (q_local % _Q_BLOCKS[-1] == 0
                 and kv_local % _Q_BLOCKS[-1] == 0
                 and k.shape[-1] % 64 == 0
                 and not (causal and q_local != kv_local))
    if flash is True and not shapes_ok:
        raise ValueError(
            f"flash=True but the ring flash path does not support this "
            f"call: local shards q={q_local} kv={kv_local}, "
            f"head_dim={k.shape[-1]}, causal={causal} (need shard "
            f"lengths % 128 == 0, head_dim % 64 == 0, and equal q/kv "
            f"shard lengths when causal)")
    if flash is True and not interpret and jax.default_backend() != "tpu":
        # advisor r2: without this the compiled Pallas lowering fails
        # deep inside Mosaic with an obscure error on CPU/GPU
        raise ValueError(
            "flash=True requires the TPU backend (or interpret=True for "
            "CPU testing); this process is running on "
            f"'{jax.default_backend()}'")
    if flash == "auto":
        return shapes_ok and jax.default_backend() == "tpu"
    return shapes_ok


def ring_attention(q, k, v, *, causal: bool = False,
                   scale: float | None = None, axis: str = "seq",
                   mesh: Mesh | None = None, batch_axis="auto",
                   flash: str | bool = "auto", interpret: bool = False,
                   kv_groups: int = 1):
    """Sequence-parallel attention; q/k/v sharded on dim 1 over ``axis``.

    Call eagerly with global arrays (this wrapper shards them) or use
    ``ring_attention_sharded`` inside an existing shard_map/pjit region.

    ``flash="auto"`` runs each shard's local block attention through the
    fused Pallas kernel on TPU when the local shard length divides the
    kernel tiles (O(S_local*D) live memory); ``flash=False`` keeps the
    XLA online-softmax body; ``flash=True`` forces the kernel
    (``interpret=True`` for CPU testing).
    """
    mesh = mesh or get_mesh()
    n = mesh.shape[axis]
    if q.shape[1] % n or k.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]}/{k.shape[1]} not divisible by "
            f"mesh axis '{axis}' size {n}")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    use_flash = _flash_ring_ok(q, k, q.shape[1] // n, k.shape[1] // n,
                               causal, flash, interpret)

    def body(qb, kb, vb):
        if use_flash:
            return _ring_body_flash(qb, kb, vb, axis=axis, n=n,
                                    causal=causal, scale=scale,
                                    interpret=interpret,
                                    kv_groups=kv_groups)
        return _ring_body(qb, kb, vb, axis=axis, n=n, causal=causal,
                          scale=scale, kv_groups=kv_groups)

    spec = _qkv_spec(mesh, axis, batch_axis)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_rep=False)(q, k, v)


def ring_attention_sharded(q, k, v, *, causal: bool = False,
                           scale: float | None = None, axis: str = "seq",
                           axis_size: int | None = None,
                           flash: str | bool = "auto",
                           interpret: bool = False, kv_groups: int = 1):
    """The per-shard ring computation, for use INSIDE shard_map/pjit where
    ``q``/``k``/``v`` are already the local sequence blocks."""
    n = axis_size if axis_size is not None else jax.lax.axis_size(axis)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if _flash_ring_ok(q, k, q.shape[1], k.shape[1], causal, flash,
                      interpret):
        return _ring_body_flash(q, k, v, axis=axis, n=n, causal=causal,
                                scale=scale, interpret=interpret,
                                kv_groups=kv_groups)
    return _ring_body(q, k, v, axis=axis, n=n, causal=causal, scale=scale,
                      kv_groups=kv_groups)


def ulysses_attention(q, k, v, *, causal: bool = False,
                      scale: float | None = None, axis: str = "seq",
                      mesh: Mesh | None = None, batch_axis="auto",
                      kv_groups: int = 1):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses scheme).

    Re-shards (B, S/N, H, D) -> (B, S, H/N, D) with one all_to_all, runs
    exact local attention over the full sequence for its head group, and
    re-shards back. Requires H % N == 0.

    ``kv_groups`` > 1 (GQA): pass k/v at their NARROW kv-head width —
    they cross the all_to_all at kv width (kv_groups-times less wire
    traffic than pre-widened) and widen locally after the re-shard.
    Alignment holds because head chunks are contiguous: widened
    chunk-local head t maps to chunk-local kv head t // kv_groups,
    which is the global h // kv_groups grouping restricted to the
    chunk. Falls back to pre-widening when the kv heads don't divide
    the axis (e.g. MQA on a mesh wider than the kv-head count).
    """
    mesh = mesh or get_mesh()
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(f"heads {q.shape[2]} not divisible by mesh axis "
                         f"'{axis}' size {n}")
    if q.shape[1] % n or k.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]}/{k.shape[1]} not divisible by "
            f"mesh axis '{axis}' size {n}")
    if kv_groups > 1:
        if kv_groups * k.shape[2] != q.shape[2]:
            raise ValueError(
                f"kv_groups={kv_groups} x {k.shape[2]} kv heads != "
                f"{q.shape[2]} query heads — pass k/v at their narrow "
                "kv-head width (or kv_groups=1 for pre-widened)")
        if k.shape[2] % n:
            k = jnp.repeat(k, kv_groups, axis=2)
            v = jnp.repeat(v, kv_groups, axis=2)
            kv_groups = 1

    def body(qb, kb, vb):
        # seq-sharded -> head-sharded: split heads, gather sequence
        def to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)
        qh, kh, vh = to_heads(qb), to_heads(kb), to_heads(vb)
        if kv_groups > 1:      # widen AFTER the wire (GQA)
            kh = jnp.repeat(kh, kv_groups, axis=2)
            vh = jnp.repeat(vh, kv_groups, axis=2)
        out = dot_product_attention(qh, kh, vh, causal=causal, scale=scale)
        # head-sharded -> seq-sharded
        return jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    spec = _qkv_spec(mesh, axis, batch_axis)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_rep=False)(q, k, v)

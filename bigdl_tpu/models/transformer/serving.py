"""Serving-depth decode paths (VERDICT r4 item 6 — beyond reference).

Three building blocks on top of ``generate.py``'s static-cache decode:

- **Ragged batches** (``generate_ragged``): one compiled program decodes a
  batch of prompts with DIFFERENT lengths. Prompts are right-padded to the
  batch max; each row carries its own absolute position, cache writes are
  per-row scatters, and attention masks per-row — so no retrace per length
  mix and no cross-row leakage (pinned against per-row ``generate`` in
  tests/test_serving.py).
- **Paged KV cache** (``PagedKVCache``): a vLLM-style block-table pool —
  (num_pages, page_size, kv_heads, head_dim) physical pages shared by all
  sequences, a (B, pages_per_seq) logical->physical table per row, and
  alloc/free for continuous batching. All shapes static; reads gather
  pages per row, writes scatter one slot. The TPU story is memory: a
  mixed-length batch holds pages for its ACTUAL lengths instead of
  B x max_len dense rows.
- **Speculative decoding** (``speculative_generate``): greedy
  draft-and-verify — a small draft model proposes ``gamma`` tokens, the
  target scores all of them in ONE parallel forward (the same T>1 cache
  step prefill uses), and the longest agreeing prefix (+1 correction
  token from the target) is accepted. Greedy acceptance is exact: output
  is BITWISE the target model's own greedy decode, only cheaper per
  token. Per-row accept counts ride the ragged machinery (rows advance
  at different rates). Reports the measured acceptance rate.

The reference has no serving story at all (SURVEY §5.7: its RNN era
predates LLM inference); this file is where the perf frontier of the
GQA/MQA decode path (BASELINE.md round-4: 190k tok/s) moves next.
"""
from __future__ import annotations

import functools
import logging
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.models.transformer.generate import (
    GenerationConfig, _embed, _ffn, _linear, _ln, _logits, _model_parts,
    _proj, _sample, _split_heads)
from bigdl_tpu.models.transformer.model import decode_meta
from bigdl_tpu.observability import compile_watch as _compile_watch
from bigdl_tpu.observability import trace
from bigdl_tpu.observability.registry import default_registry
from bigdl_tpu.tensor import activation_dtype, compute_dtype

__all__ = ["generate_ragged", "PagedKVCache", "paged_prefill",
           "paged_suffix_prefill", "paged_decode",
           "paged_decode_step_stats", "decode_hbm_probe",
           "speculative_generate", "ContinuousBatcher", "KVSnapshot",
           "PAGED_KERNEL_ENV", "PagedStepCompilers"]


def _rope_rows(x, positions, theta: float = 10000.0):
    """Rotary embedding with PER-ROW positions: ``x`` (B, T, H, D),
    ``positions`` (B, T) absolute token positions (rows of a ragged batch
    sit at different offsets). Same split-half convention and f32 angle
    math as ``nn.attention.apply_rope``."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (B, T, hf)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)        # (B,T,1,hf)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def _qkv(bp, x, num_heads, num_kv_heads):
    """LN + q/k/v projections split to heads (shared by the ragged and
    paged steps)."""
    mha_p = bp["0"]["1"]
    kv = num_kv_heads or num_heads
    h = _ln(bp["0"]["0"], x)
    q = _split_heads(_proj(mha_p, "q", h), num_heads)
    k = _split_heads(_proj(mha_p, "k", h), kv)
    v = _split_heads(_proj(mha_p, "v", h), kv)
    return q, k, v


def _attend_grouped(q, ck, cv, upto, num_heads, scale):
    """Grouped causal attention of q (B,T,H,D) against a cached view
    (B, M, KV, D), masked to key positions <= ``upto`` (B, T) per row.
    Cache-dtype operands, f32 accumulation (docs/PERF.md)."""
    b, t, _, hd = q.shape
    kv = ck.shape[2]
    g = num_heads // kv
    qg = q.reshape(b, t, kv, g, hd)
    s = jnp.einsum("btkgd,bmkd->bkgtm", qg.astype(ck.dtype), ck,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(ck.shape[1])[None, None, None, None, :]
    s = jnp.where(kpos > upto[:, None, None, :, None], -1e9, s)
    o = jnp.einsum("bkgtm,bmkd->btkgd",
                   jax.nn.softmax(s, axis=-1).astype(cv.dtype), cv,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, t, num_heads, hd)


def _ragged_block_step(bp, x, ck, cv, pos, num_heads, max_len,
                       rope=False, num_kv_heads=None,
                       paged_kernel=None):
    """One TransformerBlock on a (B, T, E) slice whose LAST column sits at
    per-row absolute position ``pos`` (B,). T==1 decode or T==gamma+1
    speculative verify. Cache writes are per-row scatters; attention
    masks per-row. ``paged_kernel`` in ("pallas", "interpret") routes
    the attention through the Pallas page-walk kernel, viewing the
    dense (B, M, KV, D) cache as contiguous pages (free reshape) so
    short rows skip their empty tail — the speculative path's half of
    the decode-kernel switch. Returns (x, ck, cv)."""
    b, t, e = x.shape
    scale = (e // num_heads) ** -0.5
    q, k, v = _qkv(bp, x, num_heads, num_kv_heads)
    # column j sits at per-row position pos - (T-1) + j
    cols = pos[:, None] - (t - 1) + jnp.arange(t)[None, :]      # (B, T)
    if rope:
        q = _rope_rows(q, cols)
        k = _rope_rows(k, cols)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
    ck = ck.at[rows, cols].set(k.astype(ck.dtype), mode="drop")
    cv = cv.at[rows, cols].set(v.astype(cv.dtype), mode="drop")
    if paged_kernel in ("pallas", "interpret"):
        from bigdl_tpu.ops.pallas.paged_attention import \
            dense_cache_attention
        o = dense_cache_attention(q, ck, cv, pos - (t - 1), scale=scale,
                                  interpret=(paged_kernel == "interpret"))
    else:
        o = _attend_grouped(q, ck, cv, cols, num_heads, scale)
    o = o.reshape(b, t, e).astype(x.dtype)
    x = x + _proj(bp["0"]["1"], "out", o).astype(activation_dtype())
    x = x + _ffn(bp["1"]["1"], _ln(bp["1"]["0"], x))
    return x, ck, cv


def _embed_rows(ep, tokens, cols):
    """Token+position embedding with per-row positions ``cols`` (B, T)."""
    idx = tokens.astype(jnp.int32) - 1
    vocab = ep["tok"].shape[0]
    y = jnp.take(ep["tok"], jnp.clip(idx, 0, vocab - 1), axis=0)
    if "pos" in ep:          # learned positions; absent under RoPE
        y = y + jnp.take(ep["pos"], jnp.clip(cols, 0, ep["pos"].shape[0]
                                             - 1), axis=0)
    return y


def _row_logits(params, num_layers, x, col):
    """LM-head logits of per-row column ``col`` (B,) of x (B, T, E)."""
    _, _, norm, head = _model_parts(params, num_layers)
    b = x.shape[0]
    last = x[jnp.arange(b), col]
    return _linear(head, _ln(norm, last))


def _ragged_prefill(params, prompt, num_layers, num_heads,
                    max_len, rope, num_kv_heads):
    """Right-padded (B, Pmax) prompt -> caches + per-row last position.

    Padding columns (j >= lengths[i]) write junk cache slots, but decode
    overwrites slot ``lengths[i]`` first and masks everything beyond the
    per-row position, so the junk is never read (test-pinned)."""
    embed, blocks, _, _ = _model_parts(params, num_layers)
    head_dim = embed["tok"].shape[1] // num_heads
    dtype = activation_dtype()
    b, pmax = prompt.shape
    kv = num_kv_heads or num_heads
    x = _embed(embed, prompt, 0).astype(dtype)
    # prefill positions are row-uniform (0..Pmax-1): padding rows' junk is
    # overwritten/masked later, so the shared-position fast path is safe
    pos_last = jnp.full((b,), pmax - 1, jnp.int32)
    ck, cv = [], []
    for li in range(num_layers):
        c_k = jnp.zeros((b, max_len, kv, head_dim), dtype)
        c_v = jnp.zeros((b, max_len, kv, head_dim), dtype)
        x, c_k, c_v = _ragged_block_step(blocks[li], x, c_k, c_v,
                                         pos_last, num_heads, max_len,
                                         rope, num_kv_heads)
        ck.append(c_k)
        cv.append(c_v)
    return tuple(ck), tuple(cv), x


@functools.partial(jax.jit, static_argnames=(
    "num_layers", "num_heads", "max_len", "n_new", "temperature",
    "top_k", "policy_key", "rope", "num_kv_heads"))
def _generate_ragged_impl(params, prompt, lengths, rng, *, num_layers,
                          num_heads, max_len, n_new, temperature, top_k,
                          policy_key, rope=False, num_kv_heads=None):
    embed, blocks, _, _ = _model_parts(params, num_layers)
    dtype = activation_dtype()
    ck, cv, x = _ragged_prefill(params, prompt, num_layers,
                                num_heads, max_len, rope, num_kv_heads)
    logits = _row_logits(params, num_layers, x, lengths - 1)
    rng, key0 = jax.random.split(rng)
    first = _sample(logits, key0, temperature, top_k)
    pos0 = lengths - 1                                    # (B,)

    def step(carry, key):
        tok, ck, cv, pos = carry                          # pos (B,)
        cols = (pos + 1)[:, None]
        x = _embed_rows(embed, tok[:, None], cols).astype(dtype)
        new_ck, new_cv = list(ck), list(cv)
        for li in range(num_layers):
            x, new_ck[li], new_cv[li] = _ragged_block_step(
                blocks[li], x, ck[li], cv[li], pos + 1, num_heads,
                max_len, rope, num_kv_heads)
        logits = _row_logits(params, num_layers, x,
                             jnp.zeros_like(pos))
        nxt = _sample(logits, key, temperature, top_k)
        return (nxt, tuple(new_ck), tuple(new_cv), pos + 1), nxt

    keys = jax.random.split(rng, max(n_new - 1, 1))
    (_, _, _, _), rest = jax.lax.scan(
        step, (first, ck, cv, pos0), keys[:n_new - 1])
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def generate_ragged(model, prompts, config: GenerationConfig | None = None,
                    *, rng=None, params=None):
    """Decode a MIXED-LENGTH batch in one compiled program.

    ``prompts``: list of 1-based id sequences (or a (B, Pmax) array +
    right-padding with any id, in which case pass per-row ``lengths`` via
    a (B, Pmax) array attribute is not needed — lists carry lengths).
    Returns (B, max_new_tokens) ids; row i's continuation is identical to
    ``generate(model, prompts[i:i+1])`` (pinned by tests/test_serving.py).
    """
    config = config or GenerationConfig()
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    pmax = int(lengths.max())
    batch = np.zeros((len(prompts), pmax), np.int32)
    for i, p in enumerate(prompts):
        batch[i, :len(p)] = np.asarray(p, np.int32)
        batch[i, len(p):] = 1                    # in-vocab padding id
    params = model.params if params is None else params
    meta = decode_meta(model)
    if pmax + config.max_new_tokens > meta["max_len"]:
        raise ValueError(f"longest prompt {pmax} + new "
                         f"{config.max_new_tokens} exceeds max_len "
                         f"{meta['max_len']}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    policy_key = (str(activation_dtype()), str(compute_dtype()))
    return _generate_ragged_impl(
        params, jnp.asarray(batch), jnp.asarray(lengths), rng,
        num_layers=meta["num_layers"], num_heads=meta["num_heads"],
        max_len=meta["max_len"], n_new=config.max_new_tokens,
        temperature=config.temperature, top_k=config.top_k,
        policy_key=policy_key,
        rope=meta.get("pos_encoding", "learned") == "rope",
        num_kv_heads=meta.get("num_kv_heads"))


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------

class PagedKVCache:
    """Block-table KV pool for continuous batching (vLLM-style, TPU-
    static).

    Physical storage: per layer, (num_pages, page_size, kv_heads,
    head_dim) k/v pools shared by ALL sequences. Logical view: each row
    owns ``pages_per_seq`` table slots mapping logical page -> physical
    page. ``alloc``/``free`` manage the pool host-side between decode
    bursts (admission control); the decode step itself is fully
    compiled.

    Memory: a 100-row batch whose rows average 1/8 of max_len holds
    ~1/8 of the dense cache's HBM. Throughput: reads gather pages per
    row — on TPU the gather is an XLA dynamic-gather over the pool;
    for peak decode rate at uniform lengths the dense cache stays the
    faster path (documented trade-off, bench row reports both).
    """

    def __init__(self, num_layers, num_pages, page_size, kv_heads,
                 head_dim, dtype=None):
        dtype = dtype or activation_dtype()
        self.num_pages, self.page_size = num_pages, page_size
        self.kv_heads, self.head_dim = kv_heads, head_dim
        self.num_layers = num_layers
        self.dtype = jnp.dtype(dtype)
        shape = (num_pages, page_size, kv_heads, head_dim)
        self.kp = tuple(jnp.zeros(shape, dtype) for _ in range(num_layers))
        self.vp = tuple(jnp.zeros(shape, dtype) for _ in range(num_layers))
        self._free = list(range(num_pages - 1, -1, -1))   # host-side stack

    def alloc(self, n_tokens: int) -> list[int]:
        """Reserve enough physical pages for ``n_tokens`` more tokens."""
        n = -(-n_tokens // self.page_size)
        if n > len(self._free):
            raise RuntimeError(f"paged cache exhausted: want {n} pages, "
                               f"{len(self._free)} free")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages) -> None:
        """Return a finished sequence's pages to the pool."""
        self._free.extend(int(p) for p in pages)

    @property
    def pages_free(self) -> int:
        return len(self._free)


def _paged_view(pool, table):
    """(num_pages, S, KV, D) pool + (B, P) table -> (B, P*S, KV, D)
    gathered per-row cache view (the logical dense cache). The
    FALLBACK consumption of the pool: an O(B*P*S*KV*D) HBM
    materialization per call — the Pallas paged kernel
    (ops/pallas/paged_attention.py) replaces it on the decode hot
    path; this stays as the off-TPU / explicitly-requested dense
    path."""
    b, p = table.shape
    g = pool[table.reshape(-1)]                  # (B*P, S, KV, D)
    s, kv, d = pool.shape[1:]
    return g.reshape(b, p * s, kv, d)


#: env override for the decode-kernel switch: "dense" | "pallas" |
#: "interpret" | "auto" (auto = Pallas on TPU when the geometry is
#: supported, dense-view otherwise)
PAGED_KERNEL_ENV = "BIGDL_TPU_PAGED_KERNEL"

_PAGED_KERNEL_MODES = ("auto", "dense", "pallas", "interpret")


def _resolve_paged_kernel(mode, supported) -> str:
    """Host-side resolution of the ``paged_kernel=`` switch to the
    static trace-time choice: ``None``/"auto" consults
    ``$BIGDL_TPU_PAGED_KERNEL`` then falls back to "pallas" iff
    ``supported()`` says the compiled kernel is legal here (TPU
    backend, tileable geometry), "dense" otherwise. Explicit modes are
    respected as given — "interpret" is the CPU parity path the tests
    pin."""
    if mode is None:
        mode = os.environ.get(PAGED_KERNEL_ENV) or "auto"
    if mode not in _PAGED_KERNEL_MODES:
        raise ValueError(f"paged_kernel must be one of "
                         f"{_PAGED_KERNEL_MODES}, got {mode!r}")
    if mode == "auto":
        return "pallas" if supported() else "dense"
    return mode


def _pool_kernel_supported(cache) -> bool:
    """auto-switch legality for this pool's geometry on the compiled
    TPU path (the interpret path has no constraints)."""
    from bigdl_tpu.ops.pallas.paged_attention import paged_supported
    return paged_supported(cache.head_dim, cache.page_size,
                           cache.kv_heads, cache.dtype)


def _attend_paged(q, kp, vp, table, q_start, upto, num_heads, scale,
                  kernel: str):
    """One attention consumption of the page pool, switched: the
    Pallas kernel walks the block table page-by-page (no dense view);
    the dense path gathers ``_paged_view`` and reuses
    ``_attend_grouped``. Both return (B, T, H, D) f32."""
    if kernel in ("pallas", "interpret"):
        from bigdl_tpu.ops.pallas.paged_attention import paged_attention
        return paged_attention(q, kp, vp, table, q_start, scale=scale,
                               interpret=(kernel == "interpret"))
    ckv = _paged_view(kp, table)
    cvv = _paged_view(vp, table)
    return _attend_grouped(q, ckv, cvv, upto, num_heads, scale)


@functools.partial(jax.jit, donate_argnums=(1, 2), static_argnames=(
    "num_layers", "num_heads", "page_size", "policy_key", "rope",
    "num_kv_heads", "paged_kernel"))
def _paged_prefill_impl(params, kp, vp, table, prompt, lengths, *,
                        num_layers, num_heads, page_size, policy_key,
                        rope=False, num_kv_heads=None,
                        paged_kernel="dense"):
    """Prefill right-padded prompts (B, Pmax) INTO the page pool.

    Column j of row i writes physical slot (table[i, j//S], j%S); padding
    columns (j >= lengths[i]) scatter to an out-of-range page id and are
    dropped, so they can never corrupt pages the table maps for other
    rows. Returns (greedy first token (B,), kp, vp)."""
    embed, blocks, _, _ = _model_parts(params, num_layers)
    dtype = activation_dtype()
    b, pmax = prompt.shape
    num_pages = kp[0].shape[0]
    x = _embed(embed, prompt, 0).astype(dtype)
    cols = jnp.broadcast_to(jnp.arange(pmax)[None, :], (b, pmax))
    valid = cols < lengths[:, None]
    log_page = table[jnp.arange(b)[:, None], cols // page_size]
    phys = jnp.where(valid, log_page, num_pages)     # OOB -> drop
    slot = cols % page_size
    new_kp, new_vp = list(kp), list(vp)
    scale = (x.shape[-1] // num_heads) ** -0.5
    for li in range(num_layers):
        q, k, v = _qkv(blocks[li], x, num_heads, num_kv_heads)
        if rope:
            q = _rope_rows(q, cols)
            k = _rope_rows(k, cols)
        new_kp[li] = new_kp[li].at[phys, slot].set(
            k.astype(kp[li].dtype), mode="drop")
        new_vp[li] = new_vp[li].at[phys, slot].set(
            v.astype(vp[li].dtype), mode="drop")
        # prefill query columns are row-uniform (0..Pmax-1), so the
        # kernel's q_start is zero for every row; padding columns
        # produce junk either way (never read — see docstring)
        o = _attend_paged(q, new_kp[li], new_vp[li], table,
                          jnp.zeros((b,), jnp.int32), cols, num_heads,
                          scale, paged_kernel)
        o = o.reshape(x.shape).astype(x.dtype)
        x = x + _proj(blocks[li]["0"]["1"], "out",
                      o).astype(activation_dtype())
        x = x + _ffn(blocks[li]["1"]["1"], _ln(blocks[li]["1"]["0"], x))
    logits = _row_logits(params, num_layers, x, lengths - 1)
    first = jnp.argmax(logits.astype(jnp.float32), axis=-1) + 1
    return first, tuple(new_kp), tuple(new_vp)


def paged_prefill(model, cache: PagedKVCache, table, prompts, *,
                  lengths=None, params=None, paged_kernel=None,
                  compilers: "PagedStepCompilers | None" = None,
                  warm_only: bool = False):
    """Prefill a mixed-length prompt batch into the paged pool.

    ``table``: (B, pages_per_seq) physical-page ids covering at least
    each row's prompt AND the tokens to be decoded after it.
    ``prompts``: list of 1-based id sequences — or, with ``lengths``, an
    already right-padded (B, Pmax) array whose per-row true lengths are
    given explicitly (bucketed serving pads Pmax past the longest
    prompt so compilation count stays bounded; padding columns never
    write pages or logits). ``paged_kernel``: the decode-kernel switch
    ("auto"/None consults $BIGDL_TPU_PAGED_KERNEL, then picks the
    Pallas page-walk kernel on TPU when legal and the dense
    ``_paged_view`` path otherwise; "interpret" is the CPU parity
    mode). Returns (greedy first tokens (B,), lengths (B,)) — feed
    both straight into :func:`paged_decode`; pool arrays inside
    ``cache`` are rebound."""
    params = model.params if params is None else params
    meta = decode_meta(model)
    if lengths is None:
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        pmax = int(lengths.max())
        batch = np.ones((len(prompts), pmax), np.int32)
        for i, p in enumerate(prompts):
            batch[i, :len(p)] = np.asarray(p, np.int32)
    else:
        batch = np.asarray(prompts, np.int32)
        lengths = np.asarray(lengths, np.int32)
        if batch.ndim != 2 or lengths.shape != (batch.shape[0],):
            raise ValueError("explicit-lengths prefill needs a (B, Pmax) "
                             "array and (B,) lengths")
        if int(lengths.max()) > batch.shape[1]:
            raise ValueError(f"lengths {lengths.tolist()} exceed the "
                             f"padded width {batch.shape[1]}")
    table = np.asarray(table, np.int32)
    capacity = table.shape[1] * cache.page_size
    if int(lengths.max()) > capacity:
        # without this the cols//page_size gather clamps to the last
        # table column and valid tokens silently overwrite one page
        # (round-5 review finding)
        raise ValueError(
            f"prompt of {int(lengths.max())} tokens exceeds the table's "
            f"{table.shape[1]} pages x {cache.page_size} slots "
            f"= {capacity}-token capacity")
    policy_key = (str(activation_dtype()), str(compute_dtype()))
    kernel = _resolve_paged_kernel(
        paged_kernel, lambda: _pool_kernel_supported(cache))
    statics = dict(
        num_layers=meta["num_layers"], num_heads=meta["num_heads"],
        page_size=cache.page_size, policy_key=policy_key,
        rope=meta.get("pos_encoding", "learned") == "rope",
        num_kv_heads=meta.get("num_kv_heads"), paged_kernel=kernel)
    if compilers is not None:
        # AOT path: execute the compiled executable directly (jit
        # dispatch would recompile — .lower().compile() does not
        # populate the jit cache)
        args = (params, cache.kp, cache.vp,
                jnp.asarray(table, jnp.int32), jnp.asarray(batch),
                jnp.asarray(lengths))
        quick = ("prefill", batch.shape, np.asarray(table).shape)
        if warm_only:
            compilers.prepare("serving_prefill_step", _paged_prefill_impl,
                              (1, 2), statics, quick, args)
            return None
        first, kp, vp = compilers.run(
            "serving_prefill_step", _paged_prefill_impl, (1, 2), statics,
            quick, args)
    elif warm_only:
        raise ValueError("warm_only prefill needs compilers=")
    else:
        first, kp, vp = _paged_prefill_impl(
            params, cache.kp, cache.vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(batch), jnp.asarray(lengths), **statics)
    cache.kp, cache.vp = kp, vp
    return first, lengths


@functools.partial(jax.jit, donate_argnums=(1, 2), static_argnames=(
    "num_layers", "num_heads", "page_size", "policy_key", "rope",
    "num_kv_heads", "paged_kernel"))
def _paged_suffix_prefill_impl(params, kp, vp, table, suffix, start,
                               lengths, *, num_layers, num_heads,
                               page_size, policy_key, rope=False,
                               num_kv_heads=None, paged_kernel="dense"):
    """Prefill only the SUFFIX of each row: column j of ``suffix``
    (B, Smax) sits at absolute position ``start[i] + j`` — the first
    ``start[i]`` tokens are already cached in the pages ``table`` maps
    (an adopted prefix snapshot). Writes scatter to the page/slot of
    the absolute position; attention runs with per-row ``q_start`` so
    each query column attends every cached prefix key plus the suffix
    keys at/before its own position — exactly what the full prefill
    computed for those columns, which is what makes adopt-prefix +
    prefill-suffix bitwise-equivalent to prefilling the whole prompt
    (causality: the KV of token j depends on tokens <= j only).
    ``lengths`` (B,) are ABSOLUTE total prompt lengths; padding columns
    (start + j >= lengths) scatter out-of-range and are dropped.
    Returns (greedy first token (B,), kp, vp)."""
    embed, blocks, _, _ = _model_parts(params, num_layers)
    dtype = activation_dtype()
    b, smax = suffix.shape
    num_pages = kp[0].shape[0]
    # absolute position of every suffix column, per row
    cols = start[:, None] + jnp.broadcast_to(jnp.arange(smax)[None, :],
                                             (b, smax))
    x = _embed_rows(embed, suffix, cols).astype(dtype)
    valid = cols < lengths[:, None]
    # clamp the table gather for padding columns past the row's page
    # allocation; their writes are dropped via the OOB page id anyway
    log_page = table[jnp.arange(b)[:, None],
                     jnp.minimum(cols // page_size,
                                 table.shape[1] - 1)]
    phys = jnp.where(valid, log_page, num_pages)     # OOB -> drop
    slot = cols % page_size
    new_kp, new_vp = list(kp), list(vp)
    scale = (x.shape[-1] // num_heads) ** -0.5
    for li in range(num_layers):
        q, k, v = _qkv(blocks[li], x, num_heads, num_kv_heads)
        if rope:
            q = _rope_rows(q, cols)
            k = _rope_rows(k, cols)
        new_kp[li] = new_kp[li].at[phys, slot].set(
            k.astype(kp[li].dtype), mode="drop")
        new_vp[li] = new_vp[li].at[phys, slot].set(
            v.astype(vp[li].dtype), mode="drop")
        # the kernel's per-row q_start IS the suffix offset; the dense
        # path masks to absolute key positions <= cols per query column
        o = _attend_paged(q, new_kp[li], new_vp[li], table, start,
                          cols, num_heads, scale, paged_kernel)
        o = o.reshape(x.shape).astype(x.dtype)
        x = x + _proj(blocks[li]["0"]["1"], "out",
                      o).astype(activation_dtype())
        x = x + _ffn(blocks[li]["1"]["1"], _ln(blocks[li]["1"]["0"], x))
    logits = _row_logits(params, num_layers, x, lengths - start - 1)
    first = jnp.argmax(logits.astype(jnp.float32), axis=-1) + 1
    return first, tuple(new_kp), tuple(new_vp)


def paged_suffix_prefill(model, cache: PagedKVCache, table, suffixes, *,
                         start, lengths, params=None, paged_kernel=None,
                         compilers: "PagedStepCompilers | None" = None,
                         warm_only: bool = False):
    """Prefill only the suffix of each row into the paged pool — the
    prefix-reuse fast path: the caller has already scattered a
    prefix-clean :class:`KVSnapshot`'s pages into ``table``'s rows and
    runs prefill for tokens ``start..lengths`` only.

    ``suffixes``: list of 1-based id sequences (row i holds tokens
    ``start[i]..lengths[i]`` of its prompt) — or, with 2-D input, an
    already right-padded (B, Smax) array. ``start`` (B,): tokens
    already cached per row (page-aligned on the batcher path);
    ``lengths`` (B,): ABSOLUTE total prompt lengths. Returns (greedy
    first tokens (B,), lengths (B,)) exactly like :func:`paged_prefill`
    — and BITWISE the same tokens full prefill would have produced,
    on the dense and kernel paths alike (test-pinned)."""
    params = model.params if params is None else params
    meta = decode_meta(model)
    start = np.asarray(start, np.int32)
    lengths = np.asarray(lengths, np.int32)
    batch = np.asarray(suffixes, np.int32) \
        if not isinstance(suffixes, (list, tuple)) else None
    if batch is None:
        smax = max(len(s) for s in suffixes)
        batch = np.ones((len(suffixes), smax), np.int32)
        for i, s in enumerate(suffixes):
            batch[i, :len(s)] = np.asarray(s, np.int32)
    if batch.ndim != 2 or start.shape != (batch.shape[0],) \
            or lengths.shape != (batch.shape[0],):
        raise ValueError("suffix prefill needs a (B, Smax) array with "
                         "(B,) start and lengths")
    if bool(np.any(lengths - start < 1)):
        raise ValueError(f"empty suffix: start {start.tolist()} must "
                         f"leave >= 1 token of lengths "
                         f"{lengths.tolist()} to prefill")
    if bool(np.any(lengths - start > batch.shape[1])):
        raise ValueError(f"suffixes of {(lengths - start).tolist()} "
                         f"tokens exceed the padded width "
                         f"{batch.shape[1]}")
    table = np.asarray(table, np.int32)
    capacity = table.shape[1] * cache.page_size
    if int(lengths.max()) > capacity:
        raise ValueError(
            f"prompt of {int(lengths.max())} tokens exceeds the table's "
            f"{table.shape[1]} pages x {cache.page_size} slots "
            f"= {capacity}-token capacity")
    policy_key = (str(activation_dtype()), str(compute_dtype()))
    kernel = _resolve_paged_kernel(
        paged_kernel, lambda: _pool_kernel_supported(cache))
    statics = dict(
        num_layers=meta["num_layers"], num_heads=meta["num_heads"],
        page_size=cache.page_size, policy_key=policy_key,
        rope=meta.get("pos_encoding", "learned") == "rope",
        num_kv_heads=meta.get("num_kv_heads"), paged_kernel=kernel)
    if compilers is not None:
        args = (params, cache.kp, cache.vp,
                jnp.asarray(table, jnp.int32), jnp.asarray(batch),
                jnp.asarray(start), jnp.asarray(lengths))
        quick = ("suffix_prefill", batch.shape, np.asarray(table).shape)
        if warm_only:
            compilers.prepare("serving_suffix_prefill_step",
                              _paged_suffix_prefill_impl, (1, 2),
                              statics, quick, args)
            return None
        first, kp, vp = compilers.run(
            "serving_suffix_prefill_step", _paged_suffix_prefill_impl,
            (1, 2), statics, quick, args)
    elif warm_only:
        raise ValueError("warm_only suffix prefill needs compilers=")
    else:
        first, kp, vp = _paged_suffix_prefill_impl(
            params, cache.kp, cache.vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(batch), jnp.asarray(start),
            jnp.asarray(lengths), **statics)
    cache.kp, cache.vp = kp, vp
    return first, lengths


@functools.partial(jax.jit, donate_argnums=(1, 2), static_argnames=(
    "num_layers", "num_heads", "n_new", "page_size", "temperature",
    "top_k", "policy_key", "rope", "num_kv_heads", "paged_kernel"))
def _paged_decode_impl(params, kp, vp, table, lengths, tok0, rng, *,
                       num_layers, num_heads, n_new, page_size,
                       temperature, top_k, policy_key, rope=False,
                       num_kv_heads=None, paged_kernel="dense"):
    """Scan ``n_new`` single-token steps through the paged pools.

    ``table`` (B, P) logical->physical page map, ``lengths`` (B,) tokens
    already cached per row, ``tok0`` (B,) the last sampled token."""
    embed, blocks, _, _ = _model_parts(params, num_layers)
    dtype = activation_dtype()

    def step(carry, key):
        tok, kp, vp, lengths = carry
        b = tok.shape[0]
        cols = lengths[:, None]                   # (B, 1) write position
        x = _embed_rows(embed, tok[:, None], cols).astype(dtype)
        scale = (x.shape[-1] // num_heads) ** -0.5
        new_kp, new_vp = list(kp), list(vp)
        # physical slot of this token: page table[b, len//S], row len%S
        log_page = lengths // page_size
        phys = table[jnp.arange(b), log_page]     # (B,)
        slot = lengths % page_size
        for li in range(num_layers):
            q, k, v = _qkv(blocks[li], x, num_heads, num_kv_heads)
            if rope:
                q = _rope_rows(q, cols)
                k = _rope_rows(k, cols)
            new_kp[li] = kp[li].at[phys, slot].set(
                k[:, 0].astype(kp[li].dtype))
            new_vp[li] = vp[li].at[phys, slot].set(
                v[:, 0].astype(vp[li].dtype))
            # the single query column sits at per-row position
            # ``lengths`` — the slot just written above
            o = _attend_paged(q, new_kp[li], new_vp[li], table,
                              lengths, cols, num_heads, scale,
                              paged_kernel)
            o = o.reshape(x.shape).astype(x.dtype)
            x = x + _proj(blocks[li]["0"]["1"], "out",
                          o).astype(activation_dtype())
            x = x + _ffn(blocks[li]["1"]["1"], _ln(blocks[li]["1"]["0"],
                                                   x))
        logits = _row_logits(params, num_layers, x,
                             jnp.zeros_like(lengths))
        nxt = _sample(logits, key, temperature, top_k)
        return (nxt, tuple(new_kp), tuple(new_vp), lengths + 1), nxt

    keys = jax.random.split(rng, n_new)
    (_, kp, vp, lengths), toks = jax.lax.scan(
        step, (tok0, kp, vp, lengths), keys)
    return toks.T, kp, vp, lengths


def paged_decode(model, cache: PagedKVCache, table, lengths, last_tokens,
                 n_new: int, *, config: GenerationConfig | None = None,
                 rng=None, params=None, paged_kernel=None,
                 compilers: "PagedStepCompilers | None" = None,
                 warm_only: bool = False):
    """Decode ``n_new`` tokens for every row through the paged pool.

    ``table``: (B, pages_per_seq) int32 physical-page ids from
    ``cache.alloc``; ``lengths``: (B,) tokens already cached (0 for a
    fresh row — its first "last token" is the prompt's last id after a
    ragged/dense prefill copied in, or the BOS id for from-scratch rows).
    ``paged_kernel``: "auto"/None (env-overridable) picks the Pallas
    page-walk kernel on TPU when legal, the dense ``_paged_view`` path
    otherwise; "dense"/"pallas"/"interpret" force a path. Returns
    (tokens (B, n_new), updated lengths); pool arrays inside ``cache``
    are replaced with the updated ones (functional update, rebinding —
    old arrays are donated garbage)."""
    config = config or GenerationConfig(max_new_tokens=n_new)
    params = model.params if params is None else params
    meta = decode_meta(model)
    table = np.asarray(table, np.int32)
    lengths = np.asarray(lengths, np.int32)
    capacity = table.shape[1] * cache.page_size
    if int(lengths.max()) + n_new > capacity:
        raise ValueError(
            f"decoding {n_new} tokens past length {int(lengths.max())} "
            f"exceeds the table's {capacity}-token capacity "
            f"({table.shape[1]} pages x {cache.page_size} slots)")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    policy_key = (str(activation_dtype()), str(compute_dtype()))
    kernel = _resolve_paged_kernel(
        paged_kernel, lambda: _pool_kernel_supported(cache))
    statics = dict(
        num_layers=meta["num_layers"], num_heads=meta["num_heads"],
        n_new=n_new, page_size=cache.page_size,
        temperature=config.temperature, top_k=config.top_k,
        policy_key=policy_key,
        rope=meta.get("pos_encoding", "learned") == "rope",
        num_kv_heads=meta.get("num_kv_heads"), paged_kernel=kernel)
    if compilers is not None:
        args = (params, cache.kp, cache.vp,
                jnp.asarray(table, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(last_tokens, jnp.int32), rng)
        quick = ("decode", n_new, table.shape)
        if warm_only:
            compilers.prepare("serving_decode_step", _paged_decode_impl,
                              (1, 2), statics, quick, args)
            return None
        toks, kp, vp, new_len = compilers.run(
            "serving_decode_step", _paged_decode_impl, (1, 2), statics,
            quick, args)
    elif warm_only:
        raise ValueError("warm_only decode needs compilers=")
    else:
        toks, kp, vp, new_len = _paged_decode_impl(
            params, cache.kp, cache.vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(last_tokens, jnp.int32), rng, **statics)
    cache.kp, cache.vp = kp, vp
    return toks, new_len


class _StaticKwargLowerer:
    """Adapter giving ``StepCompiler`` the positional ``.lower(*args)``
    it calls, over a jitted fn that also needs static kwargs (the paged
    impls key compilation on ``n_new``/``page_size``/... keywords)."""

    def __init__(self, jit_fn, statics: dict):
        self.jit_fn = jit_fn
        self._statics = dict(statics)

    def lower(self, *args):
        return self.jit_fn.lower(*args, **self._statics)


class PagedStepCompilers:
    """Shared AOT ``lower -> compile -> cache`` front end for the paged
    prefill/decode steps (ROADMAP 3: warm replica spin-up).

    One instance per :class:`~bigdl_tpu.serving.replica_pool.ReplicaPool`,
    shared by its batchers: the first replica compiles each
    (signature, statics) step and stores the executable in the
    :class:`~bigdl_tpu.tuning.aot_cache.AOTCache`; every later replica of
    identical geometry either probes the in-process table (same pool) or
    — a fresh pool/process over the same cache directory — deserializes
    the stored executable in ~10 ms instead of recompiling. That is the
    measured 7.4x warm cold-start (PR 8) turned into time-to-capacity
    under a traffic spike: the Nth replica compiles nothing.

    Decode/prefill then EXECUTE through the compiled executables
    directly (``compiled(*args)``) rather than through jit dispatch —
    ``.lower().compile()`` does not populate the jit cache, so routing
    execution back through the jitted fn would recompile anyway.

    Thread contract: replica drivers may race on first sight of a new
    signature; the worst case is a duplicate compile whose cache store
    is atomic (last writer wins with an identical payload). Steady
    state is a single dict probe per call.
    """

    def __init__(self, cache=None, *, watch=None):
        from bigdl_tpu.tuning.aot_cache import AOTCache, env_cache
        if cache is None:
            # follow $BIGDL_TPU_AOT_CACHE_DIR; absent -> in-process
            # executable table only (still no jit dispatch recompiles)
            cache = env_cache()
        elif isinstance(cache, (str, os.PathLike)):
            cache = AOTCache(str(cache))
        self.cache = cache
        self._watch = watch
        self._lock = threading.Lock()
        self._compilers: dict = {}

    def _compiler(self, name, jit_fn, donate, statics):
        skey = tuple(sorted(statics.items(), key=lambda kv: kv[0]))
        with self._lock:
            sc = self._compilers.get((name, skey))
            if sc is None:
                from bigdl_tpu.tuning.aot_cache import StepCompiler
                sc = StepCompiler(_StaticKwargLowerer(jit_fn, statics),
                                  name=name,
                                  cache=(self.cache if self.cache
                                         is not None else False),
                                  donate_argnums=donate,
                                  extra=("paged_step", skey),
                                  watch=self._watch)
                self._compilers[(name, skey)] = sc
        return sc

    def prepare(self, name, jit_fn, donate, statics, quick, args):
        """Build (compile or cache-load) the executable for this
        signature WITHOUT executing it — warm-up is shape-only."""
        sc = self._compiler(name, jit_fn, donate, statics)
        return sc.get(quick, args)

    def run(self, name, jit_fn, donate, statics, quick, args):
        compiled, _ = self.prepare(name, jit_fn, donate, statics, quick,
                                   args)
        return compiled(*args)

    def executables(self) -> list:
        """``[(step name, statics dict, quick key, compiled)]`` for
        every step built so far — the statics carry the RESOLVED
        ``paged_kernel``, the compiled program its text."""
        with self._lock:
            compilers = dict(self._compilers)
        return [(name, dict(skey), quick, compiled)
                for (name, skey), sc in compilers.items()
                for quick, compiled in sc.executables().items()]

    @property
    def hits(self) -> int:
        return self.cache.hits if self.cache is not None else 0

    @property
    def misses(self) -> int:
        return self.cache.misses if self.cache is not None else 0

    def __len__(self):
        return sum(len(sc) for sc in self._compilers.values())


def _compile_decode_step(model, cache: PagedKVCache, table, lengths,
                         last_tokens, *, paged_kernel=None, params=None):
    """Lower + AOT-compile ONE single-token decode step (no execution);
    returns ``(compiled, resolved_kernel)`` and records the executable
    into the process compile-watch table as
    ``paged_decode_step[<kernel>]`` — the routing that lets its
    cost/memory analysis prove what the step materializes."""
    params = model.params if params is None else params
    meta = decode_meta(model)
    kernel = _resolve_paged_kernel(
        paged_kernel, lambda: _pool_kernel_supported(cache))
    policy_key = (str(activation_dtype()), str(compute_dtype()))
    compiled = _paged_decode_impl.lower(
        params, cache.kp, cache.vp, jnp.asarray(table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(last_tokens, jnp.int32), jax.random.PRNGKey(0),
        num_layers=meta["num_layers"], num_heads=meta["num_heads"],
        n_new=1, page_size=cache.page_size, temperature=0.0, top_k=None,
        policy_key=policy_key,
        rope=meta.get("pos_encoding", "learned") == "rope",
        num_kv_heads=meta.get("num_kv_heads"),
        paged_kernel=kernel).compile()
    _compile_watch.record_executable(f"paged_decode_step[{kernel}]",
                                     compiled)
    return compiled, kernel


def paged_decode_step_stats(model, cache: PagedKVCache, table, lengths,
                            last_tokens, *, paged_kernel=None,
                            params=None):
    """:func:`compile_watch.executable_stats` of ONE compiled
    single-token decode step — FLOPs, bytes accessed, and the memory
    analysis (arg/output/temp/peak-HBM bytes). At
    ``paged_kernel="dense"`` the table includes the per-layer
    (B, P*S, KV, D) ``_paged_view`` materialization; with the Pallas
    kernel that temp is gone."""
    compiled, _ = _compile_decode_step(model, cache, table, lengths,
                                       last_tokens,
                                       paged_kernel=paged_kernel,
                                       params=params)
    return _compile_watch.executable_stats(compiled)


_HLO_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                    "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                    "pred": 1}


def _hlo_gather_bytes(hlo_text: str, min_bytes: int) -> tuple[int, int]:
    """(count, total output bytes) of gather ops at/above ``min_bytes``
    in an HLO module — the dense-view materializations. The same
    text-level accounting idiom as ``collective_bench.collective_bytes``
    (wire probe): static, backend-independent, no execution."""
    import re
    count, total = 0, 0
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(\w+)\[([\d,]*)\][^=]*?\bgather\(",
                      line.strip())
        if not m:
            continue
        dt = _HLO_DTYPE_BYTES.get(m.group(1))
        if dt is None or not m.group(2):
            continue
        n = dt
        for d in m.group(2).split(","):
            n *= int(d)
        if n >= min_bytes:
            count += 1
            total += n
    return count, total


def decode_hbm_probe(*, b: int = 8, pages_per_seq: int = 16,
                     page_size: int = 16, d_model: int = 256,
                     num_heads: int = 4, num_kv_heads: int = 1,
                     num_layers: int = 2, vocab: int = 512) -> dict:
    """Static per-decode-step HBM accounting, dense view vs paged
    kernel (the tentpole's measured receipt, ISSUE 9). Lowers ONE
    single-token decode step both ways — no execution, so it runs on
    any backend — and reports:

    - ``materialized_gather_{ops,bytes}``: gather instructions at/above
      the (B, P*S, KV, D) view size in each compiled HLO. The dense
      path carries exactly ``2 * num_layers`` of them (k and v view per
      layer); the kernel path carries ZERO — the materialization is
      gone, statically provable.
    - ``attn_hbm_bytes``: the static attention-traffic model per step —
      dense = 3x the view per consumption (pool gather read + view
      write + attention re-read); paged = each row's LIVE pages read
      once (rows skip their unallocated/out-of-length tail).
    - ``executable``: cost/memory analysis of both compiled steps
      (``compile_watch.executable_stats``). Off-TPU the paged step
      compiles in interpreter mode, so its executable numbers describe
      the emulation, not the kernel — the static rows above are the
      backend-independent receipt.
    """
    import jax as _jax

    from bigdl_tpu.models import TransformerLM
    model = TransformerLM(vocab, d_model=d_model, num_heads=num_heads,
                          num_layers=num_layers,
                          max_len=2 * pages_per_seq * page_size,
                          with_log_softmax=False,
                          num_kv_heads=num_kv_heads)
    model.materialize(_jax.random.PRNGKey(0))
    model.evaluate()
    kv = num_kv_heads or num_heads
    head_dim = d_model // num_heads
    cache = PagedKVCache(num_layers, num_pages=b * pages_per_seq + 1,
                         page_size=page_size, kv_heads=kv,
                         head_dim=head_dim)
    table = np.arange(b * pages_per_seq, dtype=np.int32).reshape(
        b, pages_per_seq)
    rs = np.random.default_rng(0)
    cap = pages_per_seq * page_size
    lengths = rs.integers(1, cap - 2, size=(b,)).astype(np.int32)
    last = np.ones((b,), np.int32)
    itemsize = jnp.dtype(cache.kp[0].dtype).itemsize
    view_bytes = b * pages_per_seq * page_size * kv * head_dim * itemsize
    consumptions = 2 * num_layers                      # k and v, per layer
    live_pages = int(np.sum(-(-(lengths + 1) // page_size)))
    paged_bytes = live_pages * page_size * kv * head_dim * itemsize \
        * consumptions
    dense_bytes = 3 * view_bytes * consumptions
    out = {"geometry": f"B{b} P{pages_per_seq} S{page_size} d{d_model} "
                       f"L{num_layers} kv{kv} hd{head_dim}",
           "view_shape": [b, pages_per_seq * page_size, kv, head_dim],
           "view_bytes": int(view_bytes),
           "attn_hbm_bytes": {"dense": int(dense_bytes),
                              "paged": int(paged_bytes)},
           "reduction": dense_bytes / max(paged_bytes, 1),
           "peak_view_bytes_per_layer": int(2 * view_bytes),
           "executable": {}, "materialized_gathers": {}}
    kernels = {"dense": "dense",
               "paged": "pallas" if _pool_kernel_supported(cache)
               else "interpret"}
    for label, kernel in kernels.items():
        compiled, _ = _compile_decode_step(model, cache, table, lengths,
                                           last, paged_kernel=kernel)
        ops, byts = _hlo_gather_bytes(compiled.as_text(), view_bytes)
        out["materialized_gathers"][label] = {"ops": ops, "bytes": byts}
        out["executable"][label] = _compile_watch.executable_stats(
            compiled)
    out["paged_compiled_as"] = kernels["paged"]
    # int8 quantized serving (serving/quantized.py): static accounting
    # of the decode step's resident weight + KV-pool arguments after
    # quantization — the bytes a replica parks in HBM between bursts
    from bigdl_tpu.serving.quantized import quantized_byte_report
    out["int8"] = quantized_byte_report(model, cache)
    return out


# ---------------------------------------------------------------------------
# Speculative decoding (greedy draft-and-verify)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "t_layers", "t_heads", "t_kv", "t_rope", "d_layers", "d_heads",
    "d_kv", "d_rope", "max_len", "n_new", "gamma", "temperature",
    "policy_key", "paged_kernel"))
def _speculative_impl(t_params, d_params, prompt, lengths, rng, *,
                      t_layers, t_heads, t_kv, t_rope, d_layers, d_heads,
                      d_kv, d_rope, max_len, n_new, gamma,
                      temperature, policy_key, paged_kernel="dense"):
    """Speculative loop. Per outer round: draft proposes gamma tokens
    one-by-one, target verifies all gamma+1 positions in ONE T=gamma+1
    cache step, rows accept a prefix plus one correction/bonus token.
    Rows advance at different rates, so positions/caches are the ragged
    machinery.

    ``temperature == 0``: greedy draft-and-verify — accept the longest
    prefix where draft argmax == target argmax; output is BITWISE the
    target's greedy decode. ``temperature > 0``: Leviathan-style
    rejection sampling — draft token x_j accepted with probability
    min(1, p_t(x_j)/p_d(x_j)); on rejection the replacement is drawn
    from the normalized residual max(p_t - p_d, 0), and after a fully
    accepted window the bonus is drawn from p_t at the next position.
    Either way the output distribution IS the target model's (the
    distribution-exactness statistical test lives in
    tests/test_serving.py). Returns (tokens (B, n_new),
    accepted_draft_total, rounds)."""
    embed_t, blocks_t, _, _ = _model_parts(t_params, t_layers)
    embed_d, blocks_d, _, _ = _model_parts(d_params, d_layers)
    dtype = activation_dtype()
    b = prompt.shape[0]

    tck, tcv, tx = _ragged_prefill(t_params, prompt, t_layers,
                                   t_heads, max_len, t_rope, t_kv)
    dck, dcv, dx = _ragged_prefill(d_params, prompt, d_layers,
                                   d_heads, max_len, d_rope, d_kv)
    t_logits = _row_logits(t_params, t_layers, tx, lengths - 1)
    rng, key0 = jax.random.split(rng)
    if temperature == 0.0:
        first = jnp.argmax(t_logits.astype(jnp.float32), axis=-1) + 1
    else:
        first = jax.random.categorical(
            key0, t_logits.astype(jnp.float32) / temperature, axis=-1) + 1

    out = jnp.zeros((b, n_new), jnp.int32).at[:, 0].set(first)
    # n_done counts emitted tokens per row; pos = position of the last
    # CACHED token (the prompt end); `first` is emitted but not yet cached
    n_done = jnp.ones((b,), jnp.int32)
    pos = lengths - 1
    vocab = embed_t["tok"].shape[0]

    def d_step(tok, dck, dcv, p, key):
        """One draft step at per-row position p+1: greedy token when
        temperature==0, else a sample plus the full draft distribution
        (needed for the acceptance ratio and the residual)."""
        x = _embed_rows(embed_d, tok[:, None], (p + 1)[:, None]
                        ).astype(dtype)
        nck, ncv = list(dck), list(dcv)
        for li in range(d_layers):
            x, nck[li], ncv[li] = _ragged_block_step(
                blocks_d[li], x, dck[li], dcv[li], p + 1, d_heads,
                max_len, d_rope, d_kv, paged_kernel)
        lg = _row_logits(d_params, d_layers, x,
                         jnp.zeros_like(p)).astype(jnp.float32)
        if temperature == 0.0:
            return (jnp.argmax(lg, axis=-1) + 1, None,
                    tuple(nck), tuple(ncv))
        probs = jax.nn.softmax(lg / temperature, axis=-1)
        tok = jax.random.categorical(key, lg / temperature, axis=-1) + 1
        return tok, probs, tuple(nck), tuple(ncv)

    def round_body(carry):
        (out, n_done, pos, tck, tcv, dck, dcv, acc, proposed, rounds,
         rng) = carry
        rng, r_draft, r_acc, r_bonus = jax.random.split(rng, 4)
        # proposals only count for rows still filling their budget —
        # finished rows keep riding the lockstep loop but their masked
        # proposals must not deflate the acceptance rate (ADVICE.md)
        proposed = proposed + gamma * jnp.sum(
            (n_done < n_new).astype(jnp.int32))
        # rows already finished keep proposing into masked positions;
        # their writes land beyond max_len-1? No: clamp via mode="drop"
        # in the scatter and the emit mask below.
        last = jnp.take_along_axis(out, (n_done - 1)[:, None],
                                   axis=1)[:, 0]
        # --- draft: gamma proposals, PLUS one extra step whose only job
        # is caching props[gamma-1] (its proposal is discarded) —
        # without it a fully-accepted round would leave the next round's
        # draft attending a hole at that position
        proposals, d_probs = [], []
        dtok = last
        dp = pos
        dkeys = jax.random.split(r_draft, gamma + 1)
        for gi in range(gamma + 1):
            dtok, dprob, dck, dcv = d_step(dtok, dck, dcv, dp, dkeys[gi])
            if gi < gamma:
                proposals.append(dtok)
                d_probs.append(dprob)
            dp = dp + 1
        props = jnp.stack(proposals, axis=1)              # (B, gamma)
        # --- target: ONE T=gamma+1 cache step over [last, props] scores
        # every draft position AND the bonus position past them
        seq = jnp.concatenate([last[:, None], props], axis=1)
        cols_last = pos + gamma + 1                       # (B,)
        x = _embed_rows(
            embed_t, seq,
            pos[:, None] + 1
            + jnp.arange(gamma + 1)[None, :]).astype(dtype)
        ntck, ntcv = list(tck), list(tcv)
        for li in range(t_layers):
            x, ntck[li], ntcv[li] = _ragged_block_step(
                blocks_t[li], x, tck[li], tcv[li], cols_last, t_heads,
                max_len, t_rope, t_kv, paged_kernel)
        _, _, norm_p, head_p = _model_parts(t_params, t_layers)
        tg = _linear(head_p, _ln(norm_p, x)).astype(jnp.float32)
        if temperature == 0.0:
            t_choice = jnp.argmax(tg, axis=-1) + 1        # (B, gamma+1)
            # --- accept longest agreeing prefix ----------------------
            a = (props == t_choice[:, :gamma])            # (B, gamma)
            acc_len = jnp.sum(jnp.cumprod(a, axis=1), axis=1)   # (B,)
            bonus = t_choice[jnp.arange(b), acc_len]
        else:
            # --- Leviathan rejection sampling ------------------------
            pt = jax.nn.softmax(tg / temperature, axis=-1)  # (B,γ+1,V)
            pd = jnp.stack(d_probs, axis=1)                 # (B,γ,V)
            pidx = (props - 1)[..., None]                   # 0-based
            pt_x = jnp.take_along_axis(pt[:, :gamma], pidx,
                                       axis=-1)[..., 0]     # (B,γ)
            pd_x = jnp.take_along_axis(pd, pidx, axis=-1)[..., 0]
            u = jax.random.uniform(r_acc, (b, gamma))
            # u < min(1, pt/pd)  <=>  u*pd < pt (division-free)
            a = u * pd_x < pt_x
            acc_len = jnp.sum(jnp.cumprod(a, axis=1), axis=1)
            # replacement at the reject position: residual
            # max(pt - pd, 0) normalized; after a fully accepted window
            # (acc_len==gamma) pd is zero-padded there, so the residual
            # IS pt[gamma] — one uniform rule covers both cases
            pd_pad = jnp.concatenate(
                [pd, jnp.zeros((b, 1, vocab), pd.dtype)], axis=1)
            pt_at = pt[jnp.arange(b), acc_len]              # (B, V)
            pd_at = pd_pad[jnp.arange(b), acc_len]
            resid = jnp.maximum(pt_at - pd_at, 0.0)
            z = jnp.sum(resid, axis=-1, keepdims=True)
            resid = jnp.where(z > 0, resid / jnp.maximum(z, 1e-20),
                              pt_at)
            bonus = jax.random.categorical(
                r_bonus, jnp.log(jnp.maximum(resid, 1e-37)),
                axis=-1) + 1
        # emitted this round = accepted drafts + 1 correction/bonus
        # token (column gamma exists because verify is T=γ+1)
        emit_n = acc_len + 1
        # ragged emit into `out`: row b writes tokens at n_done..+emit_n
        cols = n_done[:, None] + jnp.arange(gamma + 1)[None, :]
        vals = jnp.concatenate([props, bonus[:, None]], axis=1)
        # the accepted drafts then the bonus: position j<acc_len ->
        # props[j]; j==acc_len -> bonus
        vals = jnp.where(jnp.arange(gamma + 1)[None, :]
                         < acc_len[:, None], vals,
                         jnp.where(jnp.arange(gamma + 1)[None, :]
                                   == acc_len[:, None],
                                   bonus[:, None], 0))
        keep = (jnp.arange(gamma + 1)[None, :] <= acc_len[:, None]) \
            & (cols < n_new)
        rows_ix = jnp.broadcast_to(jnp.arange(b)[:, None], cols.shape)
        out = out.at[rows_ix, jnp.where(keep, cols, n_new)].set(
            jnp.where(keep, vals, 0), mode="drop")
        # accepted-draft count, clipped to what fit in the output budget
        acc = acc + jnp.sum(jnp.minimum(
            acc_len, jnp.maximum(n_new - n_done, 0)))
        n_done = jnp.minimum(n_done + emit_n, n_new)
        # --- caches: target cached all gamma verify positions; the per
        # -row valid prefix is pos + 1 + acc_len (last+accepted drafts);
        # junk beyond is overwritten next round (masked meanwhile).
        # Draft cached gamma proposals; valid prefix pos + 1 + acc_len
        # too (the draft's own tokens up to the disagreement point).
        pos = pos + 1 + acc_len
        return (out, n_done, pos, tuple(ntck), tuple(ntcv), dck, dcv,
                acc, proposed, rounds + 1, rng)

    def cond(carry):
        n_done = carry[1]
        return jnp.any(n_done < n_new)

    zero_acc = jnp.zeros((), jnp.int32)
    carry = (out, n_done, pos, tck, tcv, dck, dcv, zero_acc,
             jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32), rng)
    (out, n_done, pos, _, _, _, _, acc, proposed, rounds,
     _) = jax.lax.while_loop(cond, round_body, carry)
    return out, acc, proposed, rounds


def speculative_generate(model, draft_model, prompts, *,
                         max_new_tokens: int = 32, gamma: int = 4,
                         temperature: float = 0.0, rng=None,
                         params=None, draft_params=None,
                         paged_kernel=None):
    """Speculative decoding with ~1 target forward per ``accepted+1``
    tokens instead of per token.

    ``temperature == 0`` (default): greedy draft-and-verify — output is
    EXACTLY the target model's greedy continuation, whatever the draft
    proposes. ``temperature > 0``: Leviathan rejection sampling — the
    output DISTRIBUTION is exactly the target model's sampling
    distribution at that temperature (both pinned by
    tests/test_serving.py).

    ``prompts``: list of 1-based id sequences (mixed lengths ride the
    ragged path). Returns ``(tokens (B, max_new_tokens), stats)`` where
    stats reports ``accepted`` / ``proposed`` / ``rounds`` and
    ``acceptance_rate`` = accepted / proposed. Proposals are counted
    only for rows still short of their token budget at each round's
    start (rows that finished early keep riding the lockstep loop but
    their masked proposals no longer deflate the rate — ADVICE.md,
    mixed-progress batches).

    ``paged_kernel``: the same decode-kernel switch as
    :func:`paged_decode` — the draft's per-token steps and the
    target's T=gamma+1 verify step attend through the Pallas
    page-walk kernel (dense caches viewed as contiguous pages) instead
    of the masked full-cache einsum, so the speculative path does not
    silently keep paying the dense gather. "auto"/None engages it on
    TPU when BOTH models' geometries are legal."""
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    t_meta, d_meta = decode_meta(model), draft_model.lm_meta
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    pmax = int(lengths.max())
    if pmax + max_new_tokens + gamma > min(t_meta["max_len"],
                                           d_meta["max_len"]):
        raise ValueError("prompt + new tokens + gamma exceeds max_len")
    batch = np.ones((len(prompts), pmax), np.int32)
    for i, p in enumerate(prompts):
        batch[i, :len(p)] = np.asarray(p, np.int32)
    t_params = model.params if params is None else params
    d_params = draft_model.params if draft_params is None else draft_params
    policy_key = (str(activation_dtype()), str(compute_dtype()))
    if rng is None:
        rng = jax.random.PRNGKey(0)
    max_len_eff = min(t_meta["max_len"], d_meta["max_len"])

    def _both_supported():
        from bigdl_tpu.ops.pallas.paged_attention import \
            dense_cache_supported
        return all(
            dense_cache_supported(
                p["0"]["tok"].shape[1] // m["num_heads"], max_len_eff,
                m.get("num_kv_heads") or m["num_heads"],
                activation_dtype())
            for p, m in ((t_params, t_meta), (d_params, d_meta)))

    kernel = _resolve_paged_kernel(paged_kernel, _both_supported)
    out, acc, proposed, rounds = _speculative_impl(
        t_params, d_params, jnp.asarray(batch), jnp.asarray(lengths),
        rng,
        t_layers=t_meta["num_layers"], t_heads=t_meta["num_heads"],
        t_kv=t_meta.get("num_kv_heads"),
        t_rope=t_meta.get("pos_encoding", "learned") == "rope",
        d_layers=d_meta["num_layers"], d_heads=d_meta["num_heads"],
        d_kv=d_meta.get("num_kv_heads"),
        d_rope=d_meta.get("pos_encoding", "learned") == "rope",
        max_len=max_len_eff,
        n_new=max_new_tokens, gamma=gamma,
        temperature=float(temperature), policy_key=policy_key,
        paged_kernel=kernel)
    rounds_i = max(int(rounds), 1)
    proposed_i = int(proposed)
    stats = {"acceptance_rate": float(int(acc)) / max(proposed_i, 1),
             "accepted": int(acc), "proposed": proposed_i,
             "rounds": rounds_i}
    return out, stats


# ---------------------------------------------------------------------------
# KV handoff
# ---------------------------------------------------------------------------

class KVSnapshot:
    """Host-side export of one request's KV state — the handoff unit
    for prefix-cache reuse, prefill/decode disaggregation, and drain
    migration (the serving router, ``bigdl_tpu/serving/``).

    ``kv`` is a per-layer list of ``(k, v)`` numpy arrays shaped
    ``(n_pages, page_size, kv_heads, head_dim)``: the request's pages
    gathered off the pool in one packed ``jax.device_get``. The first
    ``n_cached`` token positions are valid; ``emitted`` tokens (always
    starting with the prefill's first sampled token) have already been
    produced; ``last_token`` is the next decode step's input. Adopting
    a snapshot re-allocates pages and scatters the data back in —
    greedy decode then continues bitwise identically to the exporting
    batcher, because the continuation is a pure function of
    (params, KV state, last token) (test-pinned in
    tests/test_serving_router.py).

    ``weight_version`` stamps WHICH params the KV was computed under
    (the deploy plane's rolling weight publishes,
    ``bigdl_tpu/deploy/``): adoption validates it against the target
    batcher's version, because continuing a sequence under different
    weights would silently mix versions mid-answer. ``None`` means
    unversioned (a fleet that never published) and matches anything."""

    __slots__ = ("prompt", "n_cached", "kv", "last_token", "emitted",
                 "page_size", "weight_version")

    def __init__(self, prompt, n_cached, kv, last_token, emitted,
                 page_size, weight_version=None):
        self.prompt = list(prompt)
        self.n_cached = int(n_cached)
        self.kv = kv
        self.last_token = int(last_token)
        self.emitted = list(emitted)
        self.page_size = int(page_size)
        self.weight_version = weight_version

    @property
    def n_pages(self) -> int:
        return int(self.kv[0][0].shape[0]) if self.kv else 0

    @property
    def nbytes(self) -> int:
        return sum(int(k.nbytes) + int(v.nbytes) for k, v in self.kv)

    @property
    def is_prefix_only(self) -> bool:
        """True for a truncated prefix snapshot: it carries cached KV
        pages but no sampled token, so it can only enter a batcher
        through ``submit(..., snapshot=, prefill_from=)`` — the suffix
        prefill produces the first token."""
        return not self.emitted

    def truncate(self, n_tokens: int) -> "KVSnapshot":
        """A page-boundary prefix of this snapshot: keep the full pages
        covering at most ``n_tokens`` PROMPT tokens (the partial page is
        dropped — its slots would mix in tokens past the boundary) and
        return a new prefix-only snapshot whose ``prompt``/``n_cached``/
        page list are mutually consistent. Causality makes the kept
        pages exact: the KV of token j is a function of tokens <= j
        only, so the prefix pages of a longer prefill ARE the prefill
        of the prefix. Raises ``ValueError`` when no full page fits."""
        limit = min(int(n_tokens), self.n_cached, len(self.prompt))
        p = (limit // self.page_size) * self.page_size
        if p <= 0:
            raise ValueError(
                f"cannot truncate to {n_tokens} tokens: no full "
                f"{self.page_size}-slot page fits (n_cached="
                f"{self.n_cached}, prompt_len={len(self.prompt)})")
        n_pages = p // self.page_size
        # real copies, not views: the point of truncation is that the
        # retained entry's bytes actually shrink
        kv = [(np.ascontiguousarray(k[:n_pages]),
               np.ascontiguousarray(v[:n_pages])) for k, v in self.kv]
        return KVSnapshot(self.prompt[:p], p, kv,
                          last_token=self.prompt[p - 1], emitted=[],
                          page_size=self.page_size,
                          weight_version=self.weight_version)

    def __repr__(self):
        return (f"KVSnapshot(prompt_len={len(self.prompt)}, "
                f"n_cached={self.n_cached}, n_pages={self.n_pages}, "
                f"emitted={len(self.emitted)}, "
                f"weight_version={self.weight_version!r})")


class _SuffixJob:
    """Queued adopt-prefix + prefill-suffix admission: the full prompt,
    the page-aligned prefix snapshot to adopt, and the token offset the
    suffix prefill starts at (``start == snapshot.n_cached``)."""

    __slots__ = ("prompt", "snapshot", "start")

    def __init__(self, prompt, snapshot, start):
        self.prompt = list(prompt)
        self.snapshot = snapshot
        self.start = int(start)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(pool, idx, data):
    """Adopt-side scatter: write snapshot pages ``data`` into pool rows
    ``idx``. Donated so adoption does not copy the whole pool; compiles
    once per (pool geometry, page count) — counts are bucketed by the
    export side, so signatures stay O(log max_len)."""
    return pool.at[idx].set(data.astype(pool.dtype))


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

class ContinuousBatcher:
    """Host-side continuous-batching loop over the paged cache.

    The orchestration layer that turns the paged primitives into a
    server: ``submit()`` queues requests, each ``step()`` admits queued
    requests into free slots (prompt prefilled into freshly allocated
    pages, lengths bucketed to powers of two so compilations stay
    O(log max_len)), decodes one fixed-shape burst for ALL slots in one
    compiled program, retires rows that hit ``eos_id`` or their token
    budget (pages returned to the pool), and ``finished()`` hands back
    completed generations. Greedy decode: each result equals the
    model's own per-prompt greedy continuation (test-pinned).

    Fixed shapes are the TPU contract: the slot batch is always
    ``max_batch`` rows — free slots decode into a dedicated scratch page
    and their outputs are discarded (documented demo trade-off; a
    production server would compact instead). vLLM's scheduler plays
    this role on GPU stacks; the reference has no serving story at all.

    Observability (bigdl_tpu.observability): every session records into
    a metric registry (``registry=`` — the process default unless
    given) — ``serving_ttft_seconds`` (submit -> first token),
    ``serving_decode_token_seconds`` (burst wall clock / burst),
    ``serving_queue_depth`` / ``serving_active_slots`` /
    ``serving_kv_page_utilization`` gauges, and admission / retirement
    / token counters. ``summary=`` (any Summary) adds a per-``step()``
    scalar event log (QueueDepth / ActiveSlots / KVPageUtilization /
    DecodeTokensPerSec). All instrumentation is host-side around the
    compiled programs — it adds no dispatches and no device syncs
    beyond the token readback the loop already does (test-pinned by a
    compile/dispatch count).

    Telemetry plane (docs/OBSERVABILITY.md): the batcher registers a
    ``serving_batcher`` READINESS check (``health=`` — the process
    default unless given; one batcher per process answers it, the
    latest registration wins) reporting admitting/saturated, and wraps
    its prefill/decode step fns in ``compile_watch`` — prompt-bucket
    explosion or a burst-size churn shows up as
    ``compile_watch_compiles_total{name="serving_prefill"|
    "serving_decode"}`` and storm-warns instead of silently paying an
    XLA compile per request.
    """

    def __init__(self, model, *, max_batch: int, num_pages: int,
                 page_size: int = 16, max_new_tokens: int = 32,
                 max_burst: int = 8, eos_id: int | None = None,
                 registry=None, summary=None, health=None,
                 watch=None, health_name: str = "serving_batcher",
                 on_complete=None, on_prefill=None, paged_kernel=None,
                 aot_cache=None, weight_version=None):
        meta = decode_meta(model)
        self.model = model
        # which published weight set this batcher serves (deploy plane;
        # None = unversioned). Exported KVSnapshots carry it and
        # adoption validates it — see _validate_snapshot/set_weights.
        self.weight_version = weight_version
        self.max_batch = max_batch
        self.max_new = max_new_tokens
        self.max_burst = max_burst
        self.eos_id = eos_id
        self.page_size = page_size
        # decode-kernel switch, forwarded to every prefill/decode call;
        # None keeps the callee's own "auto" resolution AND keeps the
        # kwarg off the wire (tests monkeypatch paged_prefill/
        # paged_decode with fakes that predate it)
        self.paged_kernel = paged_kernel
        self._kernel_kw = ({} if paged_kernel is None
                           else {"paged_kernel": paged_kernel})
        # AOT spin-up (ROADMAP 3): route prefill/decode through the
        # explicit lower->compile->cache pipeline and execute the
        # compiled executables directly. ``aot_cache`` accepts a
        # PagedStepCompilers (the pool shares ONE across replicas so
        # the Nth replica compiles nothing), an AOTCache, or a cache
        # directory path. None keeps the legacy jit dispatch path AND
        # keeps the kwarg off the wire for monkeypatched fakes.
        self.aot = None
        if aot_cache is not None and aot_cache is not False:
            self.aot = (aot_cache
                        if isinstance(aot_cache, PagedStepCompilers)
                        else PagedStepCompilers(aot_cache))
            self._kernel_kw = dict(self._kernel_kw, compilers=self.aot)
        kv = meta.get("num_kv_heads") or meta["num_heads"]
        head_dim = model.params["0"]["tok"].shape[1] // meta["num_heads"]
        self.cache = PagedKVCache(meta["num_layers"], num_pages,
                                  page_size, kv, head_dim)
        self._scratch = self.cache.alloc(page_size)[0]
        self._pool_pages = self.cache.pages_free   # after the scratch
        # the longest admissible prompt: bucket + budget must fit the
        # model's positions; per-row allocations include max_burst-1
        # slack because a fixed burst can overshoot max_new before the
        # retire check runs (overshoot tokens are discarded, but their
        # cache writes must land in the row's OWN pages)
        self.max_prompt = meta["max_len"] - max_new_tokens
        self._max_len = meta["max_len"]
        self.pages_per_slot = -(-(self.max_prompt + max_new_tokens
                                  + max_burst) // page_size)
        self.table = np.full((max_batch, self.pages_per_slot),
                             self._scratch, np.int32)
        self.lengths = np.zeros((max_batch,), np.int32)
        self.last = np.ones((max_batch,), np.int32)
        # slot -> (request_id, prompt tokens, [tokens so far]) or None
        self.slots: list = [None] * max_batch
        self._pages: list = [None] * max_batch
        self.queue: list = []
        self._done: list = []
        self.summary = summary
        self._step_count = 0
        # router hooks: on_complete(request_id, tokens) fires at retire;
        # on_prefill(request_id, prompt, snapshot_fn) fires right after
        # a real prefill, with a LAZY exporter the callee may invoke to
        # capture the clean prefix KV (assignable attributes — the
        # router wires them after construction)
        self.on_complete = on_complete
        self.on_prefill = on_prefill
        # request-timeline plumbing (observability/request_trace.py):
        # the router assigns ``tracker`` after construction (like the
        # hooks above); ``replica_name`` is stamped by
        # ``Replica.__init__`` the same way ``weight_version`` is by
        # the pool, so events carry fleet identity. ``_trace_rid`` is
        # the request whose prefill is on the device RIGHT NOW — the
        # compile-watch tap below uses it to pin a recompile to the
        # exact request that paid for it.
        self.tracker = None
        self.replica_name = None
        self._trace_rid = None
        reg = default_registry() if registry is None else registry
        self._m_queue = reg.gauge(
            "serving_queue_depth", "requests waiting for a slot")
        self._m_active = reg.gauge(
            "serving_active_slots", "slots decoding this step")
        self._m_util = reg.gauge(
            "serving_kv_page_utilization",
            "fraction of KV pool pages in use (incl. scratch)")
        self._m_admit = reg.counter(
            "serving_admissions_total", "requests admitted to a slot")
        self._m_retire = reg.counter(
            "serving_retirements_total",
            "requests finished (eos or budget)")
        self._m_tokens = reg.counter(
            "serving_generated_tokens_total",
            "decoded tokens kept for active rows")
        self._m_ttft = reg.histogram(
            "serving_ttft_seconds",
            "submit -> first token (queue wait + prefill)")
        self._m_tok_lat = reg.histogram(
            "serving_decode_token_seconds",
            "per-token decode latency: burst wall clock / burst")
        self._m_skips = reg.counter(
            "serving_prefill_skips_total",
            "admissions that adopted a KV snapshot instead of "
            "running prefill")
        self._m_suffix = reg.counter(
            "serving_suffix_prefills_total",
            "admissions that adopted a prefix snapshot and prefilled "
            "only the suffix (partial prefix-cache hits)")
        self._m_cancel = reg.counter(
            "serving_cancelled_total",
            "requests cancelled before completion (queued or in-flight)")
        self._m_export = reg.counter(
            "serving_exports_total",
            "KV snapshots exported for handoff/migration")
        # compile telemetry: signature-keyed compile counting on the
        # two step fns (module globals resolve at call time, so tests
        # that monkeypatch paged_prefill/paged_decode still intercept)
        self._watch = watch or _compile_watch.CompileWatch(registry=reg)
        self._prefill_fn = self._watch.watch(
            lambda *a, **k: paged_prefill(*a, **k),
            name="serving_prefill")
        self._suffix_fn = self._watch.watch(
            lambda *a, **k: paged_suffix_prefill(*a, **k),
            name="serving_suffix_prefill")
        self._decode_fn = self._watch.watch(
            lambda *a, **k: paged_decode(*a, **k),
            name="serving_decode")
        # a NEW signature during a request's prefill = that request
        # paid an XLA compile; land it on its timeline (no-op until
        # the router wires a tracker)
        self._watch.add_tap(self._compile_tap)
        # serving readiness: the load-balancer gate (/readyz)
        if health is None:
            from bigdl_tpu.observability.exporter import default_health
            health = default_health()
        self._health = health
        # ``health_name`` lets N replicas in one process each answer a
        # distinct /readyz check (the router names them per replica)
        self.health_name = str(health_name)
        self._health.register(self.health_name, self._ready,
                              kind="readiness")

    # -- request timelines (tracker lock is a leaf; no-ops when off) --
    def _tev(self, rid, event, **fields) -> None:
        tr = self.tracker
        if tr is not None:
            tr.event(rid, event, replica=self.replica_name,
                     weight_version=self.weight_version, **fields)

    def _compile_tap(self, name: str, n_signatures: int) -> None:
        rid = self._trace_rid
        if rid is not None:
            self._tev(rid, "compile", watch=name,
                      signatures=n_signatures)

    def _ready(self):
        """Readiness = admitting: a free slot exists, or nothing is
        waiting (back-pressure flips this off when every slot is busy
        AND requests queue behind them)."""
        free_slots = sum(s is None for s in self.slots)
        if free_slots > 0:
            return True, (f"admitting ({free_slots}/{self.max_batch} "
                          f"slots, {self.cache.pages_free} pages free)")
        return (not self.queue,
                f"saturated: 0/{self.max_batch} slots free, "
                f"{len(self.queue)} queued")

    @staticmethod
    def _bucket(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    def _need_pages(self, prompt_len: int) -> int:
        # the bucket clamps to max_prompt (not max_len): that keeps every
        # admissible request inside pages_per_slot AND the positional
        # range (round-5 review: a >pow2 prompt otherwise over-allocated
        # past the table width)
        bucket = min(self._bucket(prompt_len), self.max_prompt)
        return -(-(bucket + self.max_new + self.max_burst)
                 // self.page_size)

    def request_ids(self) -> set:
        """Ids currently queued or in flight (ids of FINISHED requests
        may be reused once collected)."""
        ids = {e[0] for e in self.queue}
        ids.update(s[0] for s in self.slots if s is not None)
        return ids

    def _validate_snapshot(self, snap: KVSnapshot) -> None:
        snap_version = getattr(snap, "weight_version", None)
        if (snap_version is not None and self.weight_version is not None
                and snap_version != self.weight_version):
            # a version-mismatched snapshot is never adopted silently:
            # the KV was computed under different params, so continuing
            # it here would mix weight versions inside one answer
            raise ValueError(
                f"snapshot weight_version {snap_version!r} != batcher "
                f"weight_version {self.weight_version!r} — finish the "
                "request on an old-version replica or resubmit its "
                "prompt fresh (docs/DEPLOYMENT.md, version skew)")
        if snap.page_size != self.page_size:
            raise ValueError(f"snapshot page_size {snap.page_size} != "
                             f"batcher page_size {self.page_size}")
        if len(snap.kv) != self.cache.num_layers:
            raise ValueError(f"snapshot has {len(snap.kv)} layers, "
                             f"cache has {self.cache.num_layers}")
        want = (self.page_size, self.cache.kv_heads, self.cache.head_dim)
        for li, (k, v) in enumerate(snap.kv):
            if tuple(k.shape[1:]) != want or tuple(v.shape[1:]) != want:
                raise ValueError(
                    f"snapshot layer {li} page shape {k.shape[1:]} != "
                    f"cache page shape {want}")
        if snap.n_pages > self._need_pages(len(snap.prompt)):
            raise ValueError(
                f"snapshot carries {snap.n_pages} pages but this "
                f"batcher allocates {self._need_pages(len(snap.prompt))}"
                f" for a {len(snap.prompt)}-token prompt — exporter "
                "geometry (max_new/max_burst/page_size) must match")
        if snap.n_cached > snap.n_pages * self.page_size:
            raise ValueError(
                f"snapshot n_cached {snap.n_cached} exceeds its "
                f"{snap.n_pages} pages x {self.page_size} slots")

    def set_weights(self, model, weight_version) -> None:
        """Swap the served weights in place (the deploy plane's reload
        step after a drain, ``bigdl_tpu/deploy/``). Only legal while
        idle: an in-flight sequence's KV was computed under the OLD
        params, and decoding it further under new ones would silently
        mix versions — the router drains first (finish-on-old or
        migrate), then swaps, then resumes. Geometry must match the
        construction model: the compiled prefill/decode executables key
        on abstract shapes with params as runtime arguments, so a
        same-geometry swap re-uses every executable and compiles
        nothing."""
        if not self.idle:
            raise RuntimeError(
                f"cannot swap weights with {len(self.queue)} queued and "
                f"{sum(s is not None for s in self.slots)} in-flight "
                "requests — drain the replica first")
        new, old = decode_meta(model), self.model.lm_meta
        keys = ("num_layers", "num_heads", "num_kv_heads", "max_len")
        if any(new.get(k) != old.get(k) for k in keys):
            raise ValueError(
                "set_weights requires identical model geometry: "
                + "; ".join(f"{k}: {old.get(k)} -> {new.get(k)}"
                            for k in keys if new.get(k) != old.get(k)))
        self.model = model
        self.weight_version = weight_version

    def submit(self, request_id, prompt=None, *,
               snapshot: KVSnapshot | None = None,
               prefill_from: int | None = None) -> None:
        """Queue one request (list of 1-based token ids) — or, with
        ``snapshot=``, a :class:`KVSnapshot` to ADOPT: admission then
        allocates pages and scatters the cached KV back in instead of
        running prefill (prefix-cache hits, disaggregated prefills and
        drain migration all enter here). With BOTH ``prompt`` and
        ``snapshot`` plus ``prefill_from=p``, the snapshot is a
        page-aligned PREFIX of the prompt (``KVSnapshot.truncate``):
        admission adopts its pages and prefills only tokens ``p..n`` at
        ``q_start=p`` — the partial prefix-cache hit. Raises on a
        ``request_id`` still queued or in flight — the router's
        timeout/retry story needs duplicate submission to be loud, not
        silently doubled."""
        if request_id in self.request_ids():
            raise ValueError(f"duplicate request_id {request_id!r}: "
                             "still queued or in flight")
        if prefill_from is not None:
            if snapshot is None or prompt is None:
                raise ValueError("prefill_from= needs BOTH the full "
                                 "prompt and the prefix snapshot")
            prompt = list(prompt)
            p = int(prefill_from)
            self._validate_snapshot(snapshot)
            if p != snapshot.n_cached:
                raise ValueError(
                    f"prefill_from {p} != snapshot n_cached "
                    f"{snapshot.n_cached} — truncate() the snapshot to "
                    "the adopted boundary first")
            if p <= 0 or p % self.page_size != 0:
                raise ValueError(f"prefill_from {p} must be a positive "
                                 f"multiple of page_size "
                                 f"{self.page_size}")
            if p >= len(prompt):
                raise ValueError(
                    f"prefill_from {p} leaves no suffix of the "
                    f"{len(prompt)}-token prompt to prefill (an exact "
                    "hit adopts the snapshot without prefill_from)")
            if list(snapshot.prompt) != prompt[:p]:
                raise ValueError(
                    "snapshot prefix tokens differ from prompt[:"
                    f"{p}] — adopting them would silently change the "
                    "output")
        elif snapshot is not None:
            if prompt is not None:
                raise ValueError("pass prompt OR snapshot, not both "
                                 "(both only with prefill_from=)")
            if snapshot.is_prefix_only:
                raise ValueError(
                    "prefix-only snapshot (no emitted token) needs "
                    "prefill_from= and the full prompt — direct "
                    "adoption has no first token to continue from")
            self._validate_snapshot(snapshot)
            prompt = snapshot.prompt
        elif prompt is None:
            raise ValueError("submit needs a prompt or a snapshot")
        if len(prompt) > self.max_prompt:
            raise ValueError(f"prompt of {len(prompt)} tokens exceeds "
                             f"max_prompt {self.max_prompt}")
        if self._need_pages(len(prompt)) > self._pool_pages:
            # head-of-line admission would otherwise livelock on a
            # request the pool can NEVER satisfy (round-5 review)
            raise ValueError(
                f"request needs {self._need_pages(len(prompt))} pages "
                f"but the pool holds {self._pool_pages} — enlarge "
                "num_pages or shorten the prompt/budget")
        if prefill_from is not None:
            payload = _SuffixJob(prompt, snapshot, prefill_from)
        elif snapshot is not None:
            payload = snapshot
        else:
            payload = list(prompt)
        self.queue.append((request_id, payload, time.monotonic()))
        self._m_queue.set(len(self.queue))

    def cancel(self, request_id) -> bool:
        """Cancel a request: queued -> removed from the queue; in
        flight -> the slot is released and its pages freed. Nothing is
        reported through ``finished()`` or ``on_complete``. Returns
        False for an unknown (or already finished) id — cancellation
        racing completion is a benign no-op, which is exactly what the
        router's timeout/retry path needs."""
        for i, entry in enumerate(self.queue):
            if entry[0] == request_id:
                self.queue.pop(i)
                self._m_queue.set(len(self.queue))
                self._m_cancel.inc()
                return True
        for slot, s in enumerate(self.slots):
            if s is not None and s[0] == request_id:
                self._release(slot)
                self._m_cancel.inc()
                return True
        return False

    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.queue:
                continue
            rid, payload, t_submit = self.queue[0]
            if isinstance(payload, KVSnapshot):
                if not self._admit_snapshot(slot, rid, payload,
                                            t_submit):
                    break                 # admit in arrival order only
                continue
            if isinstance(payload, _SuffixJob):
                if not self._admit_suffix(slot, rid, payload, t_submit):
                    break                 # admit in arrival order only
                continue
            prompt = payload
            bucket = min(self._bucket(len(prompt)), self.max_prompt)
            pages_needed = self._need_pages(len(prompt))
            if pages_needed > self.cache.pages_free:
                break                     # admit in arrival order only
            self.queue.pop(0)
            pages = self.cache.alloc(pages_needed * self.page_size)
            self._pages[slot] = pages
            row = np.full((self.pages_per_slot,), self._scratch,
                          np.int32)
            row[:len(pages)] = pages
            self.table[slot] = row
            # bucketed single-row prefill: the array pads to the bucket
            # width (bounds compilations to O(log max_len) shapes) while
            # the explicit length keeps positions/logits at the true
            # prompt end; padding columns never write pages
            padded = np.ones((1, bucket), np.int32)
            padded[0, :len(prompt)] = prompt
            self._tev(rid, "prefill_start", kind="full", bucket=bucket,
                      prompt_len=len(prompt))
            self._trace_rid = rid
            t_p0 = time.monotonic()
            with trace.span("prefill", cat="serving", bucket=bucket,
                            prompt_len=len(prompt),
                            host_sync="first-token readback"):
                # lengths as an ARRAY: it is a traced operand, so the
                # compile-watch signature must key on its shape, not
                # the per-request value
                first, _ = self._prefill_fn(
                    self.model, self.cache, row[None, :], padded,
                    lengths=np.asarray([len(prompt)], np.int32),
                    **self._kernel_kw)
                # deliberate sync: TTFT is DEFINED by this readback
                tok0 = int(np.asarray(first)[0])  # jaxlint: disable=JX1
            self._trace_rid = None
            t_p1 = time.monotonic()
            # TTFT = queue wait + prefill, closed by the readback above;
            # the exemplar links the bucket to /requests/<id>
            self._m_ttft.observe(t_p1 - t_submit, exemplar=str(rid))
            self._tev(rid, "first_token", via="prefill")
            self._tev(rid, "prefill_end",
                      dur_s=round(t_p1 - t_p0, 9),
                      queue_s=round(t_p0 - t_submit, 9))
            self._m_admit.inc()
            self.slots[slot] = (rid, list(prompt), [tok0])
            self.lengths[slot] = len(prompt)
            self.last[slot] = tok0
            if self.on_prefill is not None:
                # fired BEFORE any decode write lands in the partial
                # page, so a captured snapshot is prefix-clean
                try:
                    self.on_prefill(rid, list(prompt),
                                    functools.partial(self._export_slot,
                                                      slot))
                except Exception:
                    logging.getLogger(__name__).exception(
                        "on_prefill hook failed for %r", rid)
            if self.eos_id is not None and tok0 == self.eos_id:
                self._retire(slot)

    def _admit_snapshot(self, slot: int, rid, snap: KVSnapshot,
                        t_submit) -> bool:
        """Adopt a :class:`KVSnapshot` into ``slot`` — allocation and
        bookkeeping as a normal admit, but the KV pages are scattered
        back from the snapshot and NO prefill runs (the measured
        "prefill skip")."""
        pages_needed = self._need_pages(len(snap.prompt))
        if pages_needed > self.cache.pages_free:
            return False
        self.queue.pop(0)
        pages = self.cache.alloc(pages_needed * self.page_size)
        self._pages[slot] = pages
        row = np.full((self.pages_per_slot,), self._scratch, np.int32)
        row[:len(pages)] = pages
        self.table[slot] = row
        with trace.span("adopt", cat="serving",
                        prompt_len=len(snap.prompt),
                        n_cached=snap.n_cached, n_pages=snap.n_pages):
            self._adopt_kv(pages, snap)
        # TTFT for an adopted request is queue wait alone: its first
        # token arrived with the snapshot (prefill was paid elsewhere —
        # or skipped entirely on a prefix-cache hit)
        wait = time.monotonic() - t_submit
        self._m_ttft.observe(wait, exemplar=str(rid))
        self._tev(rid, "adopt", n_cached=snap.n_cached,
                  queue_s=round(wait, 9))
        self._tev(rid, "first_token", via="adopt")
        self._m_admit.inc()
        self._m_skips.inc()
        got = list(snap.emitted)
        self.slots[slot] = (rid, list(snap.prompt), got)
        self.lengths[slot] = snap.n_cached
        self.last[slot] = snap.last_token
        hit_eos = (self.eos_id is not None
                   and self.eos_id in got[:self.max_new])
        if hit_eos or len(got) >= self.max_new:
            self._retire(slot)        # migrated right at the finish line
        return True

    def _admit_suffix(self, slot: int, rid, job: "_SuffixJob",
                      t_submit) -> bool:
        """Adopt a page-aligned prefix snapshot into ``slot`` and
        prefill ONLY the suffix at ``q_start=job.start`` — the partial
        prefix-cache hit. Pages cover the FULL prompt (the suffix
        writes land past the adopted pages); the first token comes off
        the suffix prefill's logits exactly where full prefill would
        have read them."""
        prompt, snap, p = job.prompt, job.snapshot, job.start
        pages_needed = self._need_pages(len(prompt))
        if pages_needed > self.cache.pages_free:
            return False
        self.queue.pop(0)
        pages = self.cache.alloc(pages_needed * self.page_size)
        self._pages[slot] = pages
        row = np.full((self.pages_per_slot,), self._scratch, np.int32)
        row[:len(pages)] = pages
        self.table[slot] = row
        suffix = prompt[p:]
        bucket = min(self._bucket(len(suffix)), self.max_prompt)
        padded = np.ones((1, bucket), np.int32)
        padded[0, :len(suffix)] = suffix
        self._tev(rid, "prefill_start", kind="suffix", bucket=bucket,
                  prompt_len=len(prompt), prefill_from=p)
        self._trace_rid = rid
        t_p0 = time.monotonic()
        with trace.span("suffix prefill", cat="serving", bucket=bucket,
                        prompt_len=len(prompt), prefill_from=p,
                        host_sync="first-token readback"):
            self._adopt_kv(pages, snap)
            first, _ = self._suffix_fn(
                self.model, self.cache, row[None, :], padded,
                start=np.asarray([p], np.int32),
                lengths=np.asarray([len(prompt)], np.int32),
                **self._kernel_kw)
            # deliberate sync: TTFT is DEFINED by this readback
            tok0 = int(np.asarray(first)[0])  # jaxlint: disable=JX1
        self._trace_rid = None
        t_p1 = time.monotonic()
        self._m_ttft.observe(t_p1 - t_submit, exemplar=str(rid))
        self._tev(rid, "first_token", via="suffix")
        self._tev(rid, "prefill_end", dur_s=round(t_p1 - t_p0, 9),
                  queue_s=round(t_p0 - t_submit, 9))
        self._m_admit.inc()
        self._m_suffix.inc()
        self.slots[slot] = (rid, list(prompt), [tok0])
        self.lengths[slot] = len(prompt)
        self.last[slot] = tok0
        if self.on_prefill is not None:
            # the FULL prompt is now cached and prefix-clean: capture
            # extends the fleet index to the longer prefix
            try:
                self.on_prefill(rid, list(prompt),
                                functools.partial(self._export_slot,
                                                  slot))
            except Exception:
                logging.getLogger(__name__).exception(
                    "on_prefill hook failed for %r", rid)
        if self.eos_id is not None and tok0 == self.eos_id:
            self._retire(slot)
        return True

    def _adopt_kv(self, pages, snap: KVSnapshot) -> None:
        idx = jnp.asarray(np.asarray(pages[:snap.n_pages], np.int32))
        kp, vp = list(self.cache.kp), list(self.cache.vp)
        for li, (k, v) in enumerate(snap.kv):
            kp[li] = _scatter_pages(kp[li], idx, jnp.asarray(k))
            vp[li] = _scatter_pages(vp[li], idx, jnp.asarray(v))
        self.cache.kp, self.cache.vp = tuple(kp), tuple(vp)

    def _export_kv(self, pages, n_cached: int):
        """Gather the pages covering ``n_cached`` tokens to host in ONE
        packed readback. The exported page count is bucketed (next
        power of two of the token count, clamped to the allocation) so
        gather shapes stay O(log max_len) per pool geometry."""
        n_exp = min(-(-self._bucket(n_cached) // self.page_size),
                    len(pages))
        idx = jnp.asarray(np.asarray(pages[:n_exp], np.int32))
        kvs = [(self.cache.kp[li][idx], self.cache.vp[li][idx])
               for li in range(self.cache.num_layers)]
        # deliberate sync: the snapshot IS a host artifact; one packed
        # readback for all layers (jaxlint JX1's sanctioned shape)
        return jax.device_get(kvs)

    def _export_slot(self, slot: int) -> KVSnapshot:
        rid, prompt, got = self.slots[slot]
        n_cached = int(self.lengths[slot])
        with trace.span("export", cat="serving", prompt_len=len(prompt),
                        n_cached=n_cached,
                        host_sync="packed KV page readback"):
            kv = self._export_kv(self._pages[slot], n_cached)
        self._m_export.inc()
        return KVSnapshot(prompt, n_cached, kv, int(self.last[slot]),
                          got, self.page_size,
                          weight_version=self.weight_version)

    def export_request(self, request_id) -> KVSnapshot:
        """Export one IN-FLIGHT request for handoff: gathers its KV
        pages to host, frees the slot, and returns the snapshot —
        ``submit(rid, snapshot=...)`` on another identically configured
        batcher resumes it mid-decode, bitwise. Queued requests cannot
        be exported (there is nothing cached yet — ``pop_queued`` and
        resubmit instead); raises KeyError for unknown ids."""
        for slot, s in enumerate(self.slots):
            if s is not None and s[0] == request_id:
                t0 = time.monotonic()
                snap = self._export_slot(slot)
                self._release(slot)
                self._tev(request_id, "export", n_cached=snap.n_cached,
                          dur_s=round(time.monotonic() - t0, 9))
                return snap
        raise KeyError(f"request {request_id!r} is not in flight")

    def export_requests(self) -> list:
        """Export EVERY in-flight request (drain migration): returns
        ``[(request_id, KVSnapshot), ...]`` and leaves all slots
        free."""
        out = []
        for slot, s in enumerate(self.slots):
            if s is not None:
                t0 = time.monotonic()
                snap = self._export_slot(slot)
                self._release(slot)
                self._tev(s[0], "export", n_cached=snap.n_cached,
                          dur_s=round(time.monotonic() - t0, 9))
                out.append((s[0], snap))
        return out

    def pop_queued(self) -> list:
        """Remove and return every still-QUEUED entry as
        ``[(request_id, prompt_or_snapshot), ...]`` — on drain the
        router re-dispatches these to the surviving replicas. A queued
        suffix job unwraps to its FULL prompt: re-dispatch re-queries
        the fleet prefix index, which recovers the reuse (or better)
        on whichever replica admits it."""
        out = [(rid, payload.prompt if isinstance(payload, _SuffixJob)
                else payload) for rid, payload, _ in self.queue]
        self.queue = []
        self._m_queue.set(0)
        return out

    def prefill_only(self, request_id, prompt) -> KVSnapshot:
        """Run ONLY the prefill for ``prompt`` and hand the resulting
        KV back as a :class:`KVSnapshot`; the pages are freed again
        before returning, so this batcher keeps nothing. The
        disaggregation primitive: a long prompt prefills on a
        designated/low-load replica and the snapshot is adopted by a
        decode replica, whose decode bursts never stall behind it."""
        if len(prompt) > self.max_prompt:
            raise ValueError(f"prompt of {len(prompt)} tokens exceeds "
                             f"max_prompt {self.max_prompt}")
        bucket = min(self._bucket(len(prompt)), self.max_prompt)
        n_table = -(-bucket // self.page_size)
        n_real = min(n_table, -(-len(prompt) // self.page_size))
        pages = self.cache.alloc(n_real * self.page_size)
        try:
            row = np.full((n_table,), self._scratch, np.int32)
            row[:len(pages)] = pages
            padded = np.ones((1, bucket), np.int32)
            padded[0, :len(prompt)] = prompt
            with trace.span("prefill_only", cat="serving", bucket=bucket,
                            prompt_len=len(prompt),
                            host_sync="first-token readback"):
                first, _ = self._prefill_fn(
                    self.model, self.cache, row[None, :], padded,
                    lengths=np.asarray([len(prompt)], np.int32),
                    **self._kernel_kw)
                # deliberate sync: the first token rides the snapshot
                tok0 = int(np.asarray(first)[0])  # jaxlint: disable=JX1
            kv = self._export_kv(pages, len(prompt))
            self._m_export.inc()
        finally:
            self.cache.free(pages)
        return KVSnapshot(prompt, len(prompt), kv, tok0, [tok0],
                          self.page_size,
                          weight_version=self.weight_version)

    def _release(self, slot: int) -> None:
        """Free a slot's pages and reset its row — no result
        recorded (shared by retire / cancel / export)."""
        self.cache.free(self._pages[slot])
        self._pages[slot] = None
        self.slots[slot] = None
        self.table[slot] = self._scratch
        self.lengths[slot] = 0
        self.last[slot] = 1

    def _retire(self, slot: int) -> None:
        rid, _, toks = self.slots[slot]
        if self.eos_id is not None and self.eos_id in toks:
            toks = toks[:toks.index(self.eos_id) + 1]
        result = toks[:self.max_new]
        self._done.append((rid, result))
        self._release(slot)
        self._m_retire.inc()
        self._tev(rid, "retire", tokens=len(result))
        if self.on_complete is not None:
            # a crashing hook must not take the step loop down with it
            try:
                self.on_complete(rid, result)
            except Exception:
                logging.getLogger(__name__).exception(
                    "on_complete hook failed for %r", rid)

    def _resolve_burst(self, burst: int | None) -> int:
        """``None`` -> the largest default the construction allows
        (``min(8, max_burst)`` — a ``max_burst < 8`` batcher must work
        with no-arg calls, ADVICE.md)."""
        if burst is None:
            burst = min(8, self.max_burst)
        if burst > self.max_burst:
            raise ValueError(f"burst {burst} exceeds max_burst "
                             f"{self.max_burst} (page allocations carry "
                             "max_burst-1 overshoot slack)")
        return burst

    def warmup(self, *, bursts=(None,), prompt_buckets=()) -> dict:
        """Pre-build (compile or AOT-cache-load) the decode
        executable(s) — and, per entry in ``prompt_buckets``, the
        admission-shaped prefill executable — WITHOUT executing
        anything: lowering is shape-only, so a freshly added replica is
        ready before it takes traffic. With a warm cache the cost is
        deserialize time (~10 ms/step), not XLA compile time; with a
        cold one this pays the compile up front and stores it for every
        later replica. No-op without ``aot_cache``. Returns
        ``{"prepared": n, "hits": h, "misses": m}`` (cache counters are
        pool-lifetime totals)."""
        if self.aot is None:
            return {"prepared": 0, "hits": 0, "misses": 0}
        prepared = 0
        for b in bursts:
            burst = self._resolve_burst(b)
            paged_decode(self.model, self.cache, self.table,
                         self.lengths, self.last, burst,
                         warm_only=True, **self._kernel_kw)
            prepared += 1
        for n_tokens in prompt_buckets:
            bucket = min(self._bucket(int(n_tokens)), self.max_prompt)
            padded = np.ones((1, bucket), np.int32)
            row = np.full((1, self.pages_per_slot), self._scratch,
                          np.int32)
            paged_prefill(self.model, self.cache, row, padded,
                          lengths=np.asarray([bucket], np.int32),
                          warm_only=True, **self._kernel_kw)
            prepared += 1
        return {"prepared": prepared, "hits": self.aot.hits,
                "misses": self.aot.misses}

    def step(self, burst: int | None = None) -> int:
        """Admit + decode one fixed-shape burst; returns the number of
        ACTIVE rows that decoded. ``burst=None`` resolves to
        ``min(8, max_burst)``."""
        burst = self._resolve_burst(burst)
        self._admit()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        self._m_queue.set(len(self.queue))
        self._m_active.set(len(active))
        used = self.cache.num_pages - self.cache.pages_free
        self._m_util.set(used / self.cache.num_pages)
        if not active:
            return 0
        # free slots re-decode into the scratch page from length 0 every
        # burst so their positions never outgrow the capacity check
        for i in range(self.max_batch):
            if self.slots[i] is None:
                self.lengths[i] = 0
        # a decode burst is batch-wide: its compiles attribute to no
        # single request
        self._trace_rid = None
        t0 = time.monotonic()
        with trace.span("decode burst", cat="serving", burst=burst,
                        active=len(active),
                        host_sync="token readback"):
            toks, new_len = self._decode_fn(self.model, self.cache,
                                            self.table, self.lengths,
                                            self.last, n_new=burst,
                                            **self._kernel_kw)
            toks = np.asarray(toks)
        dt = time.monotonic() - t0
        self._m_tok_lat.observe(dt / burst)
        self._m_tokens.inc(len(active) * burst)
        # stall detection: a burst whose per-token latency blows past
        # the tracker's threshold (stall_factor x the SLO per-token
        # target) books the excess as stall seconds on every active
        # request — the attribution component that separates "decode
        # was busy" from "decode was stuck"
        stall = 0.0
        tr = self.tracker
        if tr is not None:
            th = tr.stall_threshold_s
            if th != float("inf") and dt / burst > th:
                stall = dt - th * burst
        self.lengths = np.asarray(new_len, np.int32).copy()
        for i in active:
            rid, prompt, got = self.slots[i]
            got.extend(int(t) for t in toks[i])
            self.last[i] = int(toks[i, -1])
            self.slots[i] = (rid, prompt, got)
            self._tev(rid, "decode", tokens=burst, dur_s=round(dt, 9),
                      stall_s=round(stall, 9))
            hit_eos = (self.eos_id is not None
                       and self.eos_id in got[:self.max_new])
            if hit_eos or len(got) >= self.max_new:
                self._retire(i)
        self._step_count += 1
        used = self.cache.num_pages - self.cache.pages_free
        self._m_util.set(used / self.cache.num_pages)
        self._m_active.set(sum(s is not None for s in self.slots))
        if self.summary is not None:
            s, n = self.summary, self._step_count
            s.add_scalar("ActiveSlots", len(active), n)
            s.add_scalar("QueueDepth", len(self.queue), n)
            s.add_scalar("KVPageUtilization",
                         used / self.cache.num_pages, n)
            s.add_scalar("DecodeTokensPerSec",
                         len(active) * burst / max(dt, 1e-9), n)
        return len(active)

    def finished(self):
        """Pop (request_id, tokens) results completed so far."""
        out, self._done = self._done, []
        return out

    @property
    def idle(self) -> bool:
        return not self.queue and all(s is None for s in self.slots)

    def run_to_completion(self, burst: int | None = None,
                          max_steps: int = 10000):
        """Drive step() until every submitted request finishes.
        ``burst=None`` resolves to ``min(8, max_burst)`` per step."""
        steps = 0
        while not self.idle:
            self.step(burst)
            steps += 1
            if steps > max_steps:
                raise RuntimeError("continuous batcher did not converge "
                                   f"in {max_steps} steps")
        return self.finished()

"""int8 quantized serving: weights + KV page pool at rest in one byte.

The autoscaler (``serving/autoscaler.py``) makes fleet size follow
load; this module shrinks what each replica costs, so one chip holds
more of them (ROADMAP item 3). Both halves ride the device codecs the
sharded-update wire already trusts
(``parameters/compression.py::int8_quantize`` — symmetric, last-axis
scale):

- **weights**: every float parameter leaf with ``ndim >= 2`` becomes
  ``{"q": int8, "s": f32 scale}`` (:func:`quantize_params`) — 4 bytes
  -> 1 + 4/k per element. LayerNorm gains/biases and other 1-D leaves
  stay f32 (they are tiny and precision-critical).
- **KV page pool**: :class:`QuantizedKVCache` holds the paged k/v
  pools as int8 with a per-(page, slot, kv_head) scale — the pool a
  replica parks between bursts drops ~4x.

Composition with the paged decode path is by DEQUANTIZE-THEN-COMPUTE
inside the compiled step: :func:`paged_decode_q8` /
:func:`paged_prefill_q8` take the quantized state as the executable's
*arguments* (that is what sits in HBM at rest and what the static
accounting counts), dequantize in-kernel, run the exact fp32 step —
including the Pallas paged-attention kernel; ``paged_kernel=`` is
honored unchanged — and re-quantize the updated pools before
returning. The dequantized copies are per-step temporaries the
compiler recycles; the at-rest footprint is the int8 state
(documented trade-off: this composes with any attention kernel at the
cost of transient dequantized pages in the step's working set).

Parity: quantization error is bounded by the codec (scale = amax/127
per row), and the dense and interpret-mode paged paths see IDENTICAL
quantized inputs — tests pin int8-dense == int8-interpret exactly,
and int8 vs fp32 within a documented tolerance
(tests/test_quantized_serving.py). Receipt: the ``int8`` section of
the ``serving_decode_hbm_bytes`` bench row — static byte accounting
of the decode step's resident weight+KV arguments, >= 3x smaller.

HOST-ONLY CONTRACT at import time (jaxlint JX5): jax only inside
functions.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["is_quantized_leaf", "quantize_params", "dequantize_params",
           "QuantizedKVCache", "paged_prefill_q8", "paged_decode_q8",
           "quantized_byte_report"]

_QKEYS = frozenset({"q", "s"})


def is_quantized_leaf(node) -> bool:
    """True for the ``{"q": int8, "s": scale}`` dicts this module puts
    in parameter/pool pytrees."""
    return isinstance(node, dict) and set(node) == _QKEYS


def quantize_params(params, *, min_ndim: int = 2):
    """f32 parameter leaves with ``ndim >= min_ndim`` ->
    ``{"q": int8, "s": scale}`` (codec: symmetric last-axis
    ``int8_quantize``). Smaller/integer leaves pass through untouched;
    :func:`dequantize_params` inverts the structure.

    Idempotence guard: a tree that ALREADY holds quantized leaves is
    rejected loudly. Re-quantizing would treat the int8 codes as
    floats and corrupt the weights silently — an easy foot-gun on the
    weight publisher's reload path, where a checkpoint may have been
    converted once already."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.parameters.compression import int8_quantize

    def one(leaf):
        if is_quantized_leaf(leaf):
            raise ValueError(
                "params are already int8-quantized (found a "
                "{'q', 's'} leaf) — quantizing twice would re-encode "
                "the int8 codes as floats and silently corrupt the "
                "weights; dequantize_params first if a re-quantize is "
                "really intended")
        x = jnp.asarray(leaf)
        if x.ndim < min_ndim or not jnp.issubdtype(x.dtype,
                                                   jnp.floating):
            return leaf
        q, s = int8_quantize(x.astype(jnp.float32))
        return {"q": q, "s": s}

    return jax.tree_util.tree_map(one, params,
                                  is_leaf=is_quantized_leaf)


def dequantize_params(qparams):
    """Invert :func:`quantize_params` (jit-traceable — this is the
    in-kernel half of dequantize-then-compute)."""
    import jax

    from bigdl_tpu.parameters.compression import int8_dequantize

    def one(node):
        if is_quantized_leaf(node):
            return int8_dequantize(node["q"], node["s"])
        return node

    return jax.tree_util.tree_map(one, qparams,
                                  is_leaf=is_quantized_leaf)


def _quantize_pools(pools):
    from bigdl_tpu.parameters.compression import int8_quantize
    out = []
    for p in pools:
        q, s = int8_quantize(p)
        out.append({"q": q, "s": s})
    return tuple(out)


def _dequantize_pools(qpools, dtype):
    from bigdl_tpu.parameters.compression import int8_dequantize
    return tuple(int8_dequantize(e["q"], e["s"]).astype(dtype)
                 for e in qpools)


class QuantizedKVCache:
    """int8-at-rest paged KV state over a
    :class:`~bigdl_tpu.models.transformer.serving.PagedKVCache`'s
    geometry.

    Built from an existing cache (adopting geometry, page allocator,
    and — quantizing — its current pool contents). ``qkp``/``qvp`` are
    per-layer ``{"q": (pages, S, KV, D) int8, "s": (pages, S, KV) f32}``
    dicts: one scale per page-slot per kv head, so a page's rows
    quantize independently and page migration stays local.
    ``alloc``/``free``/``pages_free`` delegate to the host-side
    allocator of the source cache (one allocator, whichever
    representation the pages live in)."""

    def __init__(self, cache):
        self._cache = cache
        self.num_pages, self.page_size = cache.num_pages, cache.page_size
        self.kv_heads, self.head_dim = cache.kv_heads, cache.head_dim
        self.num_layers = cache.num_layers
        self.dtype = cache.kp[0].dtype
        self.qkp = _quantize_pools(cache.kp)
        self.qvp = _quantize_pools(cache.vp)

    def alloc(self, n_tokens: int):
        return self._cache.alloc(n_tokens)

    def free(self, pages) -> None:
        self._cache.free(pages)

    @property
    def pages_free(self) -> int:
        return self._cache.pages_free

    def dequantize_into(self, cache=None):
        """Materialize float pools back into ``cache`` (default: the
        source cache) — the exit ramp to the fp32 serving path."""
        cache = cache if cache is not None else self._cache
        cache.kp = _dequantize_pools(self.qkp, self.dtype)
        cache.vp = _dequantize_pools(self.qvp, self.dtype)
        return cache

    @property
    def nbytes(self) -> int:
        return int(sum(e["q"].size * e["q"].dtype.itemsize
                       + e["s"].size * e["s"].dtype.itemsize
                       for e in (*self.qkp, *self.qvp)))


def _q8_impls():
    """The jitted q8 step impls, built lazily (module stays jax-free at
    import). Both take the QUANTIZED state as arguments — what HBM
    holds between steps — dequantize in-kernel, run the exact fp32
    paged step (``__wrapped__``: the un-jitted body, traced inline so
    no nested-jit donation), and re-quantize the updated pools."""
    import jax

    from bigdl_tpu.models.transformer.serving import (
        _paged_decode_impl, _paged_prefill_impl)

    @functools.partial(jax.jit, donate_argnums=(1, 2), static_argnames=(
        "num_layers", "num_heads", "page_size", "policy_key", "rope",
        "num_kv_heads", "paged_kernel", "pool_dtype"))
    def prefill_q8(qparams, qkp, qvp, table, prompt, lengths, *,
                   pool_dtype, **statics):
        params = dequantize_params(qparams)
        kp = _dequantize_pools(qkp, pool_dtype)
        vp = _dequantize_pools(qvp, pool_dtype)
        first, kp, vp = _paged_prefill_impl.__wrapped__(
            params, kp, vp, table, prompt, lengths, **statics)
        return first, _quantize_pools(kp), _quantize_pools(vp)

    @functools.partial(jax.jit, donate_argnums=(1, 2), static_argnames=(
        "num_layers", "num_heads", "n_new", "page_size", "temperature",
        "top_k", "policy_key", "rope", "num_kv_heads", "paged_kernel",
        "pool_dtype"))
    def decode_q8(qparams, qkp, qvp, table, lengths, tok0, rng, *,
                  pool_dtype, **statics):
        params = dequantize_params(qparams)
        kp = _dequantize_pools(qkp, pool_dtype)
        vp = _dequantize_pools(qvp, pool_dtype)
        toks, kp, vp, lengths = _paged_decode_impl.__wrapped__(
            params, kp, vp, table, lengths, tok0, rng, **statics)
        return (toks, _quantize_pools(kp), _quantize_pools(vp),
                lengths)

    return prefill_q8, decode_q8


@functools.lru_cache(maxsize=1)
def _impls_cached():
    return _q8_impls()


def _statics(model, qcache, *, paged_kernel):
    from bigdl_tpu.models.transformer.serving import (
        _pool_kernel_supported, _resolve_paged_kernel)
    from bigdl_tpu.models.transformer.model import decode_meta
    from bigdl_tpu.tensor import activation_dtype, compute_dtype
    meta = decode_meta(model)
    kernel = _resolve_paged_kernel(
        paged_kernel, lambda: _pool_kernel_supported(qcache))
    return dict(
        num_layers=meta["num_layers"], num_heads=meta["num_heads"],
        page_size=qcache.page_size,
        policy_key=(str(activation_dtype()), str(compute_dtype())),
        rope=meta.get("pos_encoding", "learned") == "rope",
        num_kv_heads=meta.get("num_kv_heads"), paged_kernel=kernel,
        pool_dtype=str(np.dtype(qcache.dtype)))


def paged_prefill_q8(model, qparams, qcache: QuantizedKVCache, table,
                     prompts, *, lengths=None, paged_kernel=None):
    """:func:`~bigdl_tpu.models.transformer.serving.paged_prefill` over
    int8 state: prompts prefill INTO the quantized pool (write-path
    quantization happens in-kernel after the fp32 step). Returns
    (greedy first tokens (B,), lengths (B,)); ``qcache`` pools are
    rebound."""
    import jax.numpy as jnp
    if lengths is None:
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        pmax = int(lengths.max())
        batch = np.ones((len(prompts), pmax), np.int32)
        for i, p in enumerate(prompts):
            batch[i, :len(p)] = np.asarray(p, np.int32)
    else:
        batch = np.asarray(prompts, np.int32)
        lengths = np.asarray(lengths, np.int32)
    prefill_q8, _ = _impls_cached()
    statics = _statics(model, qcache, paged_kernel=paged_kernel)
    first, qkp, qvp = prefill_q8(
        qparams, qcache.qkp, qcache.qvp, jnp.asarray(table, jnp.int32),
        jnp.asarray(batch), jnp.asarray(lengths), **statics)
    qcache.qkp, qcache.qvp = qkp, qvp
    return first, lengths


def paged_decode_q8(model, qparams, qcache: QuantizedKVCache, table,
                    lengths, last_tokens, n_new: int, *, config=None,
                    rng=None, paged_kernel=None):
    """:func:`~bigdl_tpu.models.transformer.serving.paged_decode` over
    int8 state. Returns (tokens (B, n_new), updated lengths);
    ``qcache`` pools are rebound (functional update, donated)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer.generate import GenerationConfig
    config = config or GenerationConfig(max_new_tokens=n_new)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    _, decode_q8 = _impls_cached()
    statics = _statics(model, qcache, paged_kernel=paged_kernel)
    statics.update(n_new=n_new, temperature=config.temperature,
                   top_k=config.top_k)
    toks, qkp, qvp, new_len = decode_q8(
        qparams, qcache.qkp, qcache.qvp, jnp.asarray(table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(last_tokens, jnp.int32), rng, **statics)
    qcache.qkp, qcache.qvp = qkp, qvp
    return toks, new_len


def _leaf_bytes(tree) -> int:
    import jax
    return int(sum(np.prod(x.shape) * np.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(tree)))


def quantized_byte_report(model, cache) -> dict:
    """Static accounting of the decode step's RESIDENT weight + KV
    arguments, fp32 vs int8 (the ``serving_decode_hbm_bytes`` int8
    receipt — no execution, pure shape arithmetic over the actual
    quantized pytrees)."""
    qparams = quantize_params(model.params)
    qcache = QuantizedKVCache(cache)
    w_fp32 = _leaf_bytes(model.params)
    w_int8 = _leaf_bytes(qparams)
    kv_fp32 = _leaf_bytes((cache.kp, cache.vp))
    kv_int8 = qcache.nbytes
    return {
        "weight_bytes_fp32": w_fp32, "weight_bytes_int8": w_int8,
        "kv_pool_bytes_fp32": kv_fp32, "kv_pool_bytes_int8": kv_int8,
        "weight_kv_bytes_fp32": w_fp32 + kv_fp32,
        "weight_kv_bytes_int8": w_int8 + kv_int8,
        "reduction": (w_fp32 + kv_fp32) / max(w_int8 + kv_int8, 1),
    }

"""Attention modules.

The reference predates transformers — its long-sequence story is scan
RNNs (SURVEY §5.7). On TPU, attention is the long-context workhorse, so
the module library carries a MultiHeadAttention whose core can run
locally, ring-parallel, or Ulysses-parallel over the mesh ``seq`` axis
(parallel/sequence.py) without changing the module's parameters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import init as init_mod
from bigdl_tpu.nn.module import Module
from bigdl_tpu.tensor import activation_dtype, compute_dtype, default_dtype

__all__ = ["MultiHeadAttention", "EvaAttention", "SparseSelectAttention",
           "LatentAttention", "apply_rope", "eva_chunk_summaries",
           "eva_attention_xla", "index_scores_xla", "select_topk_xla",
           "sparse_select_xla"]


def apply_rope(x, positions, theta: float = 10000.0):
    """Rotary position embedding over the head dim (GPT-NeoX split-half
    convention: pairs are (x[..., i], x[..., i + D/2])).

    ``x``: (..., S, H, D) with D even (any number of leading batch dims);
    ``positions``: (S,) absolute token
    positions (int). Rotation depends only on a token's own absolute
    position, so scores q_m . k_n depend only on m - n (pinned by
    tests/test_transformer.py) — the property that lets a KV cache store
    rotated keys and lets ring/Ulysses sharding rotate before the
    collective. Computed in f32, returned in x's dtype."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (S, hf)
    # angles in f32 (bf16 positions would alias beyond ~256), the
    # rotation itself in x's dtype — the f32 variant cost ~8 ms/step on
    # the d1024/12L flagship (24 widened elementwise passes)
    # broadcast shape built from x.ndim so any number of leading batch
    # dims aligns (S, hf) onto x's (S, ..., D/2) axes, not a hard-coded 4-D
    bshape = (1,) * (x.ndim - 3) + (ang.shape[0], 1, half)
    cos = jnp.cos(ang).reshape(bshape).astype(x.dtype)
    sin = jnp.sin(ang).reshape(bshape).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


class MultiHeadAttention(Module):
    """Self-attention over (batch, seq, embed).

    ``sequence_parallel`` selects the attention core: None (local),
    "ring" or "ulysses" (sequence-sharded over ``mesh_axis``; inputs must
    then be seq-sharded arrays under an active mesh, and seq/heads must
    divide the axis size — see parallel/sequence.py).

    ``rope=True`` rotates q/k by absolute position (``apply_rope``)
    before the attention core — pair with a model that skips additive
    positional embeddings (``TransformerLM(pos_encoding="rope")``).
    Composes with the sequence-parallel cores: rotation happens on the
    (GSPMD-sharded) global arrays before the collective, and positions
    are the global ``arange(S)``.

    ``num_kv_heads`` < ``num_heads`` selects grouped-query attention
    (GQA; num_kv_heads=1 is multi-query): k/v project to num_kv_heads
    heads and are repeated across each query group before the core. The
    parameter saving is in the k/v projections; the decode path's win is
    the num_heads/num_kv_heads-times smaller KV cache
    (models/transformer/generate.py).

    ``head_dim`` sets the heads' width where ``num_heads x head_dim`` is
    not ``embed_dim`` (Nemotron-H: 32 x 128 over 2688); q and the output
    projection are then ``num_heads x head_dim`` wide.

    ``window`` W (causal only) limits query i to keys i - W < j <= i
    (``dot_product_attention(window=)``; docs/window_attention.md), and
    ``rope_theta`` is the rotation's base: one module for a model whose
    layers differ in both (SmallThinker: windowed RoPE layers, global
    layers without position embedding).
    """

    def __init__(self, embed_dim: int, num_heads: int,
                 causal: bool = False, with_bias: bool = True,
                 sequence_parallel: str | None = None,
                 mesh_axis: str = "seq", rope: bool = False,
                 num_kv_heads: int | None = None,
                 head_dim: int | None = None,
                 window: int | None = None, rope_theta: float = 10000.0):
        super().__init__()
        assert head_dim is not None or embed_dim % num_heads == 0
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.head_dim = head_dim or embed_dim // num_heads
        if num_kv_heads is not None and num_kv_heads < 1:
            raise ValueError(f"num_kv_heads={num_kv_heads} must be >= 1 "
                             "(or None for full MHA)")
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads != 0:
            raise ValueError(f"num_heads={num_heads} must be a multiple "
                             f"of num_kv_heads={self.num_kv_heads}")
        self.causal = causal
        self.with_bias = with_bias
        if sequence_parallel not in (None, "ring", "ulysses"):
            raise ValueError(
                f"sequence_parallel={sequence_parallel!r} — expected "
                "None, 'ring' or 'ulysses'")
        self.sequence_parallel = sequence_parallel
        self.mesh_axis = mesh_axis
        self.rope = rope
        if rope:
            assert self.head_dim % 2 == 0, "rope needs an even head_dim"
        if window is not None and not causal:
            raise ValueError(f"window={window} needs causal=True")
        self.window, self.rope_theta = window, float(rope_theta)

    def init(self, rng):
        ks = jax.random.split(rng, 4)
        kv_dim = self.num_kv_heads * self.head_dim
        q_dim = self.num_heads * self.head_dim
        p = {}
        for name, k in zip(("q", "k", "v", "out"), ks):
            out_dim, in_dim = {"k": (kv_dim, self.embed_dim),
                               "v": (kv_dim, self.embed_dim),
                               "q": (q_dim, self.embed_dim),
                               "out": (self.embed_dim, q_dim)}[name]
            w = init_mod.init_weight(init_mod.Xavier, k, (out_dim, in_dim),
                                     fan_in=in_dim, fan_out=out_dim)
            p[f"{name}_weight"] = w
            if self.with_bias:
                p[f"{name}_bias"] = jnp.zeros((out_dim,), default_dtype())
        return p

    def _proj(self, params, name, x):
        y = jnp.matmul(x.astype(compute_dtype()),
                       params[f"{name}_weight"].astype(compute_dtype()).T)
        if self.with_bias:
            y = y + params[f"{name}_bias"].astype(compute_dtype())
        return y

    def apply(self, params, state, x, *, training=False, rng=None):
        from bigdl_tpu.parallel import sequence as seq
        b, s, e = x.shape
        heads = (self.num_heads, self.head_dim)
        q = self._proj(params, "q", x).reshape(b, s, *heads)
        k = self._proj(params, "k", x).reshape(
            b, s, self.num_kv_heads, self.head_dim)
        v = self._proj(params, "v", x).reshape(
            b, s, self.num_kv_heads, self.head_dim)
        if self.rope:
            pos = jnp.arange(s)
            q = apply_rope(q, pos, self.rope_theta)
            k = apply_rope(k, pos, self.rope_theta)
        group = self.num_heads // self.num_kv_heads
        if group > 1 and self.sequence_parallel is None:
            # GQA: each kv head serves `group` query heads. The ring and
            # Ulysses cores take the NARROW k/v and widen inside — ring
            # per hop, Ulysses after its all_to_all — so grouped blocks
            # travel the wire at kv width; only the local core (flash
            # kernel assumes matching H) needs full-width heads here
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        if self.sequence_parallel == "ring":
            o = seq.ring_attention(q, k, v, causal=self.causal,
                                   axis=self.mesh_axis, kv_groups=group,
                                   window=self.window)
        elif self.sequence_parallel == "ulysses":
            o = seq.ulysses_attention(q, k, v, causal=self.causal,
                                      axis=self.mesh_axis,
                                      kv_groups=group, window=self.window)
        else:
            o = seq.dot_product_attention(q, k, v, causal=self.causal,
                                          window=self.window)
        y = self._proj(params, "out",
                       o.reshape(b, s, self.num_heads * self.head_dim))
        return y.astype(activation_dtype()), state

    def __repr__(self):
        return (f"MultiHeadAttention({self.embed_dim}, "
                f"heads={self.num_heads}, causal={self.causal}, "
                f"sp={self.sequence_parallel}"
                + (f", window={self.window}" if self.window else "") + ")")


_NEG = -1e9  # finite mask value, as parallel/sequence.py


def eva_chunk_summaries(k, v, phi, mu, chunk: int):
    """One summary key and value per chunk of ``chunk`` tokens: with
    a_j = softmax over the chunk's j of (k_j . phi), k~ = sum_j a_j k_j +
    mu and v~ = sum_j a_j v_j. ``k, v``: (B, S, H, D); ``phi, mu``:
    (H, D); returns two (B, S / chunk, H, D) arrays of k's dtype. The
    pooling scores, the softmax and the sums are float32."""
    b, s, h, d = k.shape
    f32 = jnp.float32
    kc = k.astype(f32).reshape(b, s // chunk, chunk, h, d)
    vc = v.astype(f32).reshape(b, s // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, phi.astype(f32)),
                       axis=2)[..., None]
    ks = jnp.sum(a * kc, axis=2) + mu.astype(f32)
    vs = jnp.sum(a * vc, axis=2)
    return ks.astype(k.dtype), vs.astype(v.dtype)


def eva_attention_xla(q, k, v, ks, vs, *, window: int, chunk: int,
                      scale: float | None = None):
    """EVA attention in plain ``jax.numpy`` — the semantics the kernel
    (ops/pallas/eva_attention.py) is tested against, and the path off the
    TPU: query i attends its own window's keys j <= i exactly and every
    EARLIER window's chunk summaries, under one softmax. Materialises
    (W, W + S / chunk) scores a window: small sizes only."""
    b, s, h, d = q.shape
    nw, per = s // window, window // chunk
    f32 = jnp.float32
    scale = scale if scale is not None else d ** -0.5
    qw, kw, vw = (x.astype(f32).reshape(b, nw, window, h, d)
                  for x in (q, k, v))
    local = jnp.einsum("bnqhd,bnkhd->bnhqk", qw, kw) * scale
    pos = jnp.arange(window)
    local = jnp.where(pos[None, :] > pos[:, None], _NEG, local)
    remote = jnp.einsum("bnqhd,bchd->bnhqc", qw, ks.astype(f32)) * scale
    # summary c belongs to window c // per; a window sees those before it
    before = jnp.arange(nw * per)[None, :] // per < jnp.arange(nw)[:, None]
    remote = jnp.where(before[None, :, None, None, :], remote, _NEG)
    p = jax.nn.softmax(jnp.concatenate([local, remote], axis=-1), axis=-1)
    o = (jnp.einsum("bnhqk,bnkhd->bnqhd", p[..., :window], vw)
         + jnp.einsum("bnhqc,bchd->bnqhd", p[..., window:],
                      vs.astype(f32)))
    return o.reshape(b, s, h, d).astype(q.dtype)


class EvaAttention(Module):
    """EVA attention (Zheng et al., arXiv:2302.04542) as EvaByte uses it:
    causal self-attention over (batch, seq, embed) that is exact inside a
    window of ``window`` tokens and sees every earlier window through one
    learned summary per ``chunk`` tokens (``eva_chunk_summaries``, made
    once a call), both under one softmax; linear in seq. Bias-free
    projections named as ``MultiHeadAttention`` names them, RoPE on q and
    k (before the pooling, so summaries carry rotated keys), and per head
    the pooling query ``phi`` and the summary-key offset ``mu``, each
    (heads, head_dim). docs/eva_attention.md has the equations.

    On the TPU the core is the Pallas kernel and shapes it does not take
    are an error, never another path; elsewhere (the CPU tests) it is
    ``eva_attention_xla``. seq must be a multiple of ``window``."""

    def __init__(self, embed_dim: int, num_heads: int, window: int,
                 chunk: int, rope_theta: float = 10000.0):
        super().__init__()
        assert embed_dim % num_heads == 0
        if window % chunk:
            raise ValueError(f"EvaAttention: window {window} is not a "
                             f"multiple of chunk {chunk}")
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.head_dim = embed_dim // num_heads
        assert self.head_dim % 2 == 0, "rope needs an even head_dim"
        self.window, self.chunk = window, chunk
        self.rope_theta = rope_theta

    def init(self, rng):
        ks = jax.random.split(rng, 6)
        e, hd = self.embed_dim, (self.num_heads, self.head_dim)
        p = {f"{name}_weight": init_mod.init_weight(
                init_mod.Xavier, k, (e, e), fan_in=e, fan_out=e)
             for name, k in zip(("q", "k", "v", "out"), ks)}
        p.update({name: jax.random.normal(key, hd, default_dtype())
                  * self.head_dim ** -0.5
                  for name, key in zip(("phi", "mu"), ks[4:])})
        return p

    def apply(self, params, state, x, *, training=False, rng=None):
        b, s, e = x.shape
        if s % self.window:
            raise ValueError(
                f"EvaAttention: sequence length {s} is not a multiple of "
                f"window {self.window}")
        cd = compute_dtype()
        h = x.astype(cd)
        q, k, v = (jnp.matmul(h, params[f"{n}_weight"].astype(cd).T)
                   .reshape(b, s, self.num_heads, self.head_dim)
                   for n in "qkv")
        pos = jnp.arange(s)
        q = apply_rope(q, pos, self.rope_theta)
        k = apply_rope(k, pos, self.rope_theta)
        with jax.named_scope("eva_prep_kv"):
            ks, vs = eva_chunk_summaries(k, v, params["phi"],
                                         params["mu"], self.chunk)
        with jax.named_scope("eva_attention"):
            if jax.default_backend() == "tpu":
                from bigdl_tpu.ops.pallas.eva_attention import eva_attention
                core = eva_attention
            else:
                core = eva_attention_xla
            o = core(q, k, v, ks, vs, window=self.window, chunk=self.chunk)
        y = jnp.matmul(o.reshape(b, s, e),
                       params["out_weight"].astype(cd).T)
        return y.astype(activation_dtype()), state

    def __repr__(self):
        return (f"EvaAttention({self.embed_dim}, heads={self.num_heads}, "
                f"window={self.window}, chunk={self.chunk})")


def index_scores_xla(qi, ki, wi):
    """The indexer's scores in plain ``jax.numpy``: I[b, t, s] = sum_j
    wi[b, t, j] ReLU(qi[b, t, j] . ki[b, s]); ``qi`` (B, S, J, DI), ``ki``
    (B, S, DI), ``wi`` (B, S, J) -> (B, S, S) float32. Materialises
    (B, S, J, S): small sizes only."""
    f32 = jnp.float32
    r = jnp.einsum("btjd,bsd->btjs", qi.astype(f32), ki.astype(f32),
                   precision="highest")
    return jnp.einsum("btj,btjs->bts", wi.astype(f32), jax.nn.relu(r),
                      precision="highest")


def select_topk_xla(scores, topk: int):
    """The selection in plain ``jax.numpy``: ``scores`` (B, S, S) ->
    (masked scores, row logsumexp): the scores with -inf wherever key s
    is NOT among query t's ``topk`` largest over s <= t (every s <= t
    while t < topk; among equal scores the lower s wins), and the
    logsumexp of what is left of each row (B, S)."""
    s = scores.shape[-1]
    pos = jnp.arange(s)
    valid = pos[None, :] <= pos[:, None]
    masked = jnp.where(valid, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, min(topk, s))[0][..., -1:]
    above = masked > kth
    equal = (masked == kth) & valid
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    keep = (above | (equal & (jnp.cumsum(equal, axis=-1) <= room))) & valid
    kept = jnp.where(keep, scores, -jnp.inf)
    return kept, jax.scipy.special.logsumexp(kept, axis=-1)


@jax.custom_vjp
def _with_gradient_of(y, aux):
    """``y``, with ``aux``'s gradient at weight one riding ``y``'s
    backward pass whatever cotangent ``y`` gets (the public precedent is
    Megatron-LM's MoEAuxLossAutoScaler)."""
    return y


_with_gradient_of.defvjp(
    lambda y, aux: (y, aux),
    lambda aux, g: (g, jnp.ones_like(aux)))


def sparse_select_xla(q, k, v, qi, ki, wi, *, topk: int,
                      scale: float | None = None):
    """Learned sparse attention in plain ``jax.numpy`` — the semantics
    the kernels (ops/pallas/sparse_attention.py) are tested against, and
    the path off the TPU. ``q`` (B, S, H, D), ``k, v`` (B, S, G, D) with
    query head j reading key/value head j // (H / G); ``qi, ki, wi`` the
    indexer's queries, key and head weights (``index_scores_xla``).
    Returns (o, L_I, masked index scores): o (B, S, H, D) attends the
    selected keys alone; L_I is the mean over (b, t) of KL(p_t ||
    softmax over S_t of I[t]), p_t the probabilities summed over heads,
    L1-normalised and detached, and its gradient (weight one, to qi, ki
    and wi only) rides o's backward pass. Materialises (B, H, S, S):
    small sizes only."""
    b, s, h, d = q.shape
    g = k.shape[2]
    f32 = jnp.float32
    scale = scale if scale is not None else d ** -0.5
    scores = index_scores_xla(qi, ki, wi)
    keep = select_topk_xla(jax.lax.stop_gradient(scores), topk)[0] > -jnp.inf
    kept = jnp.where(keep, scores, -jnp.inf)
    lse_i = jax.scipy.special.logsumexp(kept, axis=-1)
    qg = q.astype(f32).reshape(b, s, g, h // g, d)
    sc = jnp.einsum("btgpd,bsgd->bgpts", qg, k.astype(f32)) * scale
    p = jax.nn.softmax(jnp.where(keep[:, None, None], sc, -jnp.inf), -1)
    o = jnp.einsum("bgpts,bsgd->btgpd", p, v.astype(f32))
    target = jax.lax.stop_gradient(jnp.sum(p, axis=(1, 2)) / h)
    logq = jnp.where(keep, scores - lse_i[..., None], 0.0)
    logp = jnp.log(jnp.where(target > 0, target, 1.0))
    l_i = jnp.mean(jnp.sum(target * (logp - logq), axis=-1))
    o = _with_gradient_of(o.reshape(b, s, h, d).astype(q.dtype), l_i)
    return o, l_i, kept


class SparseSelectAttention(Module):
    """Learned sparse attention (DeepSeek-V3.2-Exp's DSA; the block of
    Keye-VL-2.0-30B-A3B): grouped-query causal self-attention over
    (batch, seq, embed) in which query t attends only the ``topk`` keys
    s <= t that a small INDEXER scores highest (every s <= t while
    t < topk), and the indexer is trained to imitate the attention it
    steers. docs/sparse_attention.md has the equations.

    Main path: bias-free q (``num_heads`` x ``head_dim``), k, v
    (``num_kv_heads`` x ``head_dim``: K/V are never repeated across a
    group) and out projections, an RMS norm per head on q and k
    (``q_norm`` / ``k_norm``, a weight of ``head_dim`` each), RoPE.
    Indexer, on the input DETACHED: ``index_heads`` queries of
    ``index_dim``, ONE key of ``index_dim`` under a LayerNorm, RoPE on
    both, a weight a head scaled by index_heads^-1/2 index_dim^-1/2;
    I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]), float32; the
    selection is exact (lower s wins among equals) and passes no
    gradient. The indexer's loss L_I = mean_t KL(p_t || softmax over the
    selection of I[t]) (p_t: the heads' probabilities summed,
    normalised, detached) is NOT added to anything the caller sees: its
    gradient, at weight one, is injected into this layer's backward
    pass and reaches the indexer's five leaves alone, while the
    caller's loss reaches every other leaf — so ``jax.grad`` of
    ``criterion(model.apply(...))`` IS the training gradient (DSA's
    sparse stage), with nothing for an optimizer to opt into. Averaged
    over micro-batches like any gradient.

    On the TPU the core is the Pallas kernels and shapes they do not
    take are an error, never another path; elsewhere (the CPU tests) it
    is ``sparse_select_xla``."""

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, index_heads: int, index_dim: int, topk: int,
                 rope_theta: float = 10000.0, eps: float = 1e-6):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads={num_heads} must be a multiple "
                             f"of num_kv_heads={num_kv_heads}")
        assert head_dim % 2 == 0 and index_dim % 2 == 0, "rope: even dims"
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.index_heads, self.index_dim = index_heads, index_dim
        self.topk, self.rope_theta, self.eps = topk, rope_theta, eps

    def init(self, rng):
        e, d = self.embed_dim, self.head_dim
        shapes = {"q_weight": (self.num_heads * d, e),
                  "k_weight": (self.num_kv_heads * d, e),
                  "v_weight": (self.num_kv_heads * d, e),
                  "out_weight": (e, self.num_heads * d),
                  "iq_weight": (self.index_heads * self.index_dim, e),
                  "ik_weight": (self.index_dim, e),
                  "iw_weight": (self.index_heads, e)}
        p = {name: init_mod.init_weight(init_mod.Xavier, key, shape,
                                        fan_in=shape[1], fan_out=shape[0])
             for (name, shape), key in zip(
                 shapes.items(), jax.random.split(rng, len(shapes)))}
        p["q_norm"] = jnp.ones((d,), default_dtype())
        p["k_norm"] = jnp.ones((d,), default_dtype())
        p["ik_norm_weight"] = jnp.ones((self.index_dim,), default_dtype())
        p["ik_norm_bias"] = jnp.zeros((self.index_dim,), default_dtype())
        return p

    def _head_norm(self, x, w, scale=1.0):
        """RMS norm over the head dim in float32, rounded once; ``scale``
        rides the weight (q takes the softmax's head_dim^-1/2 here, so
        no score tile is ever multiplied by it)."""
        xs = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(xs), -1, keepdims=True)
                            + self.eps)
        return (xs * inv * (w.astype(jnp.float32) * scale)).astype(x.dtype)

    def indexer(self, params, h):
        """(qI (B, S, J, DI), kI (B, S, DI), w (B, S, J)), float32, from
        the layer's normed input ``h`` in the compute dtype; no gradient
        reaches ``h``."""
        f32 = jnp.float32
        b, s, _ = h.shape
        h = jax.lax.stop_gradient(h)

        def proj(name):
            return jnp.matmul(h, params[name].astype(h.dtype).T,
                              preferred_element_type=f32)

        pos = jnp.arange(s)
        qi = apply_rope(proj("iq_weight").reshape(
            b, s, self.index_heads, self.index_dim), pos, self.rope_theta)
        ki = proj("ik_weight")
        mu = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mu), axis=-1, keepdims=True)
        ki = ((ki - mu) * jax.lax.rsqrt(var + self.eps)
              * params["ik_norm_weight"].astype(f32)
              + params["ik_norm_bias"].astype(f32))
        ki = apply_rope(ki[:, :, None, :], pos, self.rope_theta)[:, :, 0]
        wi = proj("iw_weight") * (self.index_heads ** -0.5
                                  * self.index_dim ** -0.5)
        return qi, ki, wi

    def core_inputs(self, params, x):
        """(q, k, v, qI, kI, w) of ``sparse_select_xla`` from the layer's
        normed input: q carries the softmax's head_dim^-1/2 (so the core
        runs at scale 1)."""
        b, s, _ = x.shape
        cd = compute_dtype()
        h = x.astype(cd)

        def proj(name, heads):
            return jnp.matmul(h, params[f"{name}_weight"].astype(cd).T) \
                .reshape(b, s, heads, self.head_dim)

        pos = jnp.arange(s)
        q = apply_rope(self._head_norm(proj("q", self.num_heads),
                                       params["q_norm"],
                                       self.head_dim ** -0.5),
                       pos, self.rope_theta)
        k = apply_rope(self._head_norm(proj("k", self.num_kv_heads),
                                       params["k_norm"]),
                       pos, self.rope_theta)
        with jax.named_scope("indexer"):
            qi, ki, wi = self.indexer(params, h)
        return q, k, proj("v", self.num_kv_heads), qi, ki, wi

    def indexer_loss(self, params, x):
        """L_I of this layer on its normed input ``x``, by the jnp path
        (small sizes): the training path forms only its gradient."""
        return sparse_select_xla(*self.core_inputs(params, x),
                                 topk=self.topk, scale=1.0)[1]

    def apply(self, params, state, x, *, training=False, rng=None):
        from bigdl_tpu.observability import trace
        b, s, _ = x.shape
        inputs = self.core_inputs(params, x)
        kept = min(self.topk, s)
        selected = kept * (kept + 1) // 2 + (s - kept) * kept
        # python runs this when the layer is traced for a compile, never
        # in a step
        trace.instant("sparse_select", cat="nn", seq=s, topk=self.topk,
                      selected_pairs=b * selected,
                      causal_pairs=b * s * (s + 1) // 2,
                      materialised_bytes=b * s * s * 4)
        if jax.default_backend() == "tpu":
            from bigdl_tpu.ops.pallas.sparse_attention import (
                sparse_select_attention)
            o = sparse_select_attention(*inputs, topk=self.topk, scale=1.0)
        else:
            o = sparse_select_xla(*inputs, topk=self.topk, scale=1.0)[0]
        y = jnp.matmul(o.reshape(b, s, self.num_heads * self.head_dim),
                       params["out_weight"].astype(compute_dtype()).T)
        return y.astype(activation_dtype()), state

    def __repr__(self):
        return (f"SparseSelectAttention({self.embed_dim}, "
                f"heads={self.num_heads}/{self.num_kv_heads}x"
                f"{self.head_dim}, indexer={self.index_heads}x"
                f"{self.index_dim}, topk={self.topk})")


class LatentAttention(Module):
    """Multi-head latent attention (DeepSeek-V2's MLA, arXiv:2405.04434,
    as the language model of Kimi-VL-A3B trains it): causal
    self-attention over (batch, seq, embed) whose keys and values are
    expanded from ONE compressed latent a position, and whose score has
    two parts — a per-head content part and a rotary part whose key is
    one vector shared by all heads. docs/latent_attention.md has the
    equations.

    No bias anywhere. ``q_weight`` projects the input directly to
    ``num_heads`` x (``qk_nope`` + ``qk_rope``) (no query latent);
    ``kva_weight`` to ``kv_rank`` + ``qk_rope``: the latent c, RMS-normed
    (``kv_norm``, a weight of ``kv_rank``), and the rotary key r;
    ``kvb_weight`` expands the normed latent to ``num_heads`` x
    (``qk_nope`` + ``v_dim``): a head's content key and its value;
    ``out_weight`` takes the heads' ``v_dim``-wide sums back to embed.
    RoPE (``apply_rope``'s half-split pairs) on q's rotary part and on
    r. The softmax scale (qk_nope + qk_rope)^-1/2 rides W_q — multiplied
    into the float32 weight before it is rounded to the compute dtype —
    so the core runs at scale 1 and no score tile is multiplied by it.
    The latent and r keep their float32 accumulators through the norm
    and the rotation and are rounded once.

    On the TPU the core is the Pallas kernels
    (``ops/pallas/latent_attention.py``: the shared rotary key is never
    copied over the heads, its gradient is summed over them in the
    kernel) and shapes they do not take are an error, never another
    path; elsewhere (the CPU tests) it is ``latent_attention_xla``."""

    def __init__(self, embed_dim: int, num_heads: int, qk_nope: int,
                 qk_rope: int, v_dim: int, kv_rank: int,
                 rope_theta: float = 10000.0, eps: float = 1e-6):
        super().__init__()
        assert qk_rope % 2 == 0, "rope needs an even qk_rope"
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.qk_nope, self.qk_rope, self.v_dim = qk_nope, qk_rope, v_dim
        self.kv_rank, self.rope_theta, self.eps = kv_rank, rope_theta, eps

    def init(self, rng):
        e, h = self.embed_dim, self.num_heads
        shapes = {"q_weight": (h * (self.qk_nope + self.qk_rope), e),
                  "kva_weight": (self.kv_rank + self.qk_rope, e),
                  "kvb_weight": (h * (self.qk_nope + self.v_dim),
                                 self.kv_rank),
                  "out_weight": (e, h * self.v_dim)}
        p = {name: init_mod.init_weight(init_mod.Xavier, key, shape,
                                        fan_in=shape[1], fan_out=shape[0])
             for (name, shape), key in zip(
                 shapes.items(), jax.random.split(rng, len(shapes)))}
        p["kv_norm"] = jnp.ones((self.kv_rank,), default_dtype())
        return p

    def _latent_norm(self, c, w):
        """RMS norm of the float32 latent over its ``kv_rank``."""
        return c * jax.lax.rsqrt(jnp.mean(jnp.square(c), -1, keepdims=True)
                                 + self.eps) * w.astype(jnp.float32)

    def core_inputs(self, params, x):
        """(qN, qR, kN, kR, v) of ``latent_attention_xla`` from the
        layer's normed input; q carries the softmax scale."""
        b, s, _ = x.shape
        cd, f32 = compute_dtype(), jnp.float32
        h, heads = x.astype(cd), self.num_heads
        pos = jnp.arange(s)
        scale = (self.qk_nope + self.qk_rope) ** -0.5
        q = jnp.matmul(h, (params["q_weight"].astype(f32) * scale)
                       .astype(cd).T).reshape(b, s, heads, -1)
        qn = q[..., :self.qk_nope]
        qr = apply_rope(q[..., self.qk_nope:], pos, self.rope_theta)
        cr = jnp.matmul(h, params["kva_weight"].astype(cd).T,
                        preferred_element_type=f32)
        c, r = cr[..., :self.kv_rank], cr[..., self.kv_rank:]
        kv = jnp.matmul(self._latent_norm(c, params["kv_norm"]).astype(cd),
                        params["kvb_weight"].astype(cd).T) \
            .reshape(b, s, heads, -1)
        kr = apply_rope(r[:, :, None, :], pos, self.rope_theta)[:, :, 0]
        return (qn, qr, kv[..., :self.qk_nope], kr.astype(cd),
                kv[..., self.qk_nope:])

    def apply(self, params, state, x, *, training=False, rng=None):
        from bigdl_tpu.observability import trace
        from bigdl_tpu.ops.pallas import latent_attention as kernels
        b, s, _ = x.shape
        on_kernels = jax.default_backend() == "tpu"
        # python runs this when the layer is traced for a compile, never
        # in a step. The kernels write no score array and no broadcast
        # key; the jnp path writes float32 scores for every pair
        trace.instant("latent_attention", cat="nn", seq=s,
                      heads=self.num_heads, qk_nope=self.qk_nope,
                      qk_rope=self.qk_rope, v_dim=self.v_dim,
                      kv_rank=self.kv_rank,
                      causal_pairs=b * s * (s + 1) // 2,
                      materialised_bytes=0 if on_kernels
                      else b * self.num_heads * s * s * 4)
        with jax.named_scope("mla_project"):
            inputs = self.core_inputs(params, x)
        with jax.named_scope("mla_attention"):
            core = kernels.latent_attention if on_kernels \
                else kernels.latent_attention_xla
            o = core(*inputs)
        y = jnp.matmul(o.reshape(b, s, self.num_heads * self.v_dim),
                       params["out_weight"].astype(compute_dtype()).T)
        return y.astype(activation_dtype()), state

    def __repr__(self):
        return (f"LatentAttention({self.embed_dim}, heads={self.num_heads}"
                f"x({self.qk_nope}+{self.qk_rope}|{self.v_dim}), "
                f"latent={self.kv_rank})")

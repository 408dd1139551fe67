"""Autoregressive decoding for ``TransformerLM`` with a static KV cache.

TPU-native inference loop: the cache is a pre-allocated (L, B, max_len,
H, Dh) pair of arrays, the decode loop is a ``lax.scan`` over token
positions (one compiled program regardless of length), and every shape
is static — nothing retraces as the sequence grows. The reference has no
generation story (its RNN era predates it, SURVEY §5.7); this completes
the transformer family's API the way Test CLIs complete the conv
families'.

Implementation note: modules are pure init/apply, so the decode path
reuses the model's *param tree* directly (embed / blocks / final norm /
lm head, keyed by their Sequential positions) rather than threading a
cache through module classes — the module graph stays inference-free and
the cache layout stays an implementation detail of this file. The tree
layout is pinned by tests/test_generate.py's greedy-parity test: any
change to TransformerLM's structure that breaks these paths fails
loudly there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.models.transformer.model import decode_meta
from bigdl_tpu.tensor import activation_dtype, compute_dtype

__all__ = ["generate", "beam_search", "GenerationConfig"]


class GenerationConfig:
    """Decode knobs: temperature 0 = greedy; top_k limits the softmax
    support; max_new_tokens is a static bound (one compile per value)."""

    def __init__(self, max_new_tokens: int = 32, temperature: float = 0.0,
                 top_k: int | None = None):
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k


def _split_heads(x, num_heads):
    b, s, e = x.shape
    return x.reshape(b, s, num_heads, e // num_heads)


def _ln(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["weight"] + p["bias"]).astype(x.dtype)


def _proj(p, name, x):
    # mirrors MultiHeadAttention._proj: compute-dtype operands/output
    y = jnp.matmul(x.astype(compute_dtype()),
                   p[f"{name}_weight"].astype(compute_dtype()).T)
    if f"{name}_bias" in p:
        y = y + p[f"{name}_bias"].astype(compute_dtype())
    return y


def _linear(p, x):
    # mirrors nn.Linear.apply's dtype path
    y = jnp.matmul(x.astype(compute_dtype()),
                   p["weight"].astype(compute_dtype()).T)
    y = y + p["bias"].astype(compute_dtype())
    return y.astype(activation_dtype())


def _ffn(p, x):
    return _linear(p["2"], jax.nn.relu(_linear(p["0"], x)))


def _block_step(bp, x, ck, cv, pos, num_heads, max_len, rope=False,
                num_kv_heads=None):
    """One TransformerBlock on a (B, T) slice ending at absolute position
    ``pos`` (T==1 decode or T==P prefill with pos==P-1). Returns output
    and the updated (ck, cv) cache for this layer.

    Param paths (TransformerBlock): bp["0"] = _Residual(LN, MHA),
    bp["1"] = _Residual(LN, FFN-Sequential).

    ``rope=True`` rotates q/k at their absolute positions before caching
    — a key's rotation is fixed at its own position, so the cache holds
    rotated keys and decode steps never re-rotate history.
    """
    mha_p = bp["0"]["1"]
    kv = num_kv_heads or num_heads
    h = _ln(bp["0"]["0"], x)
    d = h.shape[-1]
    scale = (d // num_heads) ** -0.5
    q = _split_heads(_proj(mha_p, "q", h), num_heads)
    k = _split_heads(_proj(mha_p, "k", h), kv)
    v = _split_heads(_proj(mha_p, "v", h), kv)
    t = x.shape[1]
    start = pos - (t - 1)
    if rope:
        from bigdl_tpu.nn.attention import apply_rope
        positions = start + jnp.arange(t)
        q = apply_rope(q, positions)
        k = apply_rope(k, positions)
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                      (0, start, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                      (0, start, 0, 0))
    # each query row i (absolute position start+i) sees cache <= start+i.
    # Operands stay in the cache dtype with f32 ACCUMULATION — an
    # .astype(f32) on the cache materialized a full f32 copy of the
    # static (B, max_len, H, Dh) buffers per layer per step, which is
    # what made batch-128 decode REGRESS below batch 64 (2 GB of
    # converts/step at B=128; round 3, docs/PERF.md)
    upto = start + jnp.arange(t)
    # one grouped path (g == 1 IS plain MHA: the (kv, g) reshape is
    # free): the cache stays at kv heads — the GQA memory/bandwidth win
    # — and queries group as (B, T, kv, G, D) so no repeated kv ever
    # materializes. Operands stay in the cache dtype with f32
    # ACCUMULATION (see the note above).
    g = num_heads // kv
    b_, hd = x.shape[0], q.shape[-1]
    qg = q.reshape(b_, t, kv, g, hd)
    s = jnp.einsum("btkgd,bmkd->bkgtm", qg.astype(ck.dtype), ck,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(max_len)[None, None, None, None, :]
    s = jnp.where(kpos > upto[None, None, None, :, None], -1e9, s)
    o = jnp.einsum("bkgtm,bmkd->btkgd",
                   jax.nn.softmax(s, axis=-1).astype(cv.dtype), cv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b_, t, num_heads, hd).astype(x.dtype)
    o = _proj(mha_p, "out",
              o.reshape(x.shape)).astype(activation_dtype())
    x = x + o
    x = x + _ffn(bp["1"]["1"], _ln(bp["1"]["0"], x))
    return x, ck, cv


def _model_parts(params, num_layers):
    """Sequential positions: 0 embed, 1..L blocks, L+1 final LN,
    L+2 lm head (L+3 LogSoftMax is parameterless)."""
    embed = params["0"]
    blocks = [params[str(1 + i)] for i in range(num_layers)]
    norm = params[str(num_layers + 1)]
    head = params[str(num_layers + 2)]
    return embed, blocks, norm, head


def _embed(ep, tokens, start):
    idx = tokens.astype(jnp.int32) - 1        # 1-based ids
    vocab = ep["tok"].shape[0]
    y = jnp.take(ep["tok"], jnp.clip(idx, 0, vocab - 1), axis=0)
    if "pos" in ep:        # learned positions; absent under RoPE
        y = y + jax.lax.dynamic_slice_in_dim(
            ep["pos"], start, tokens.shape[1], axis=0)
    return y


def _logits(params, num_layers, x):
    _, _, norm, head = _model_parts(params, num_layers)
    return _linear(head, _ln(norm, x[:, -1]))


def _prefill(params, prompt, num_layers, num_heads, max_len,
             rope=False, num_kv_heads=None):
    """Cache allocation + prompt prefill. Returns (ck, cv, x, pos0)."""
    embed, blocks, _, _ = _model_parts(params, num_layers)
    head_dim = embed["tok"].shape[1] // num_heads
    dtype = activation_dtype()
    b = prompt.shape[0]
    # per-layer cache TUPLES, not one stacked (L, ...) array: each layer's
    # cache is then its own scan-carry leaf, which XLA updates in place —
    # the stacked form's .at[li].set forced whole-cache copies per step
    # (measured: batch-64 decode 212 -> 4.06 ms/step)
    kv = num_kv_heads or num_heads
    zero = lambda: jnp.zeros((b, max_len, kv, head_dim), dtype)
    ck, cv = [], []
    x = _embed(embed, prompt, 0).astype(dtype)
    pos0 = prompt.shape[1] - 1
    for li in range(num_layers):
        x, k_l, v_l = _block_step(blocks[li], x, zero(), zero(),
                                  jnp.asarray(pos0), num_heads, max_len,
                                  rope, num_kv_heads)
        ck.append(k_l)
        cv.append(v_l)
    return tuple(ck), tuple(cv), x, pos0


def _decode_setup(model, prompt, n_new, params):
    """Shared eager preamble for generate/beam_search: meta + length
    validation and the dtype-policy jit-cache key (the compiled program
    bakes in the policy at trace time — keying on it makes set_policy()
    between calls retrace instead of silently reusing stale-dtype
    executables)."""
    params = model.params if params is None else params
    meta = decode_meta(model)
    prompt = jnp.asarray(prompt)
    if prompt.shape[1] + n_new > meta["max_len"]:
        raise ValueError(f"prompt {prompt.shape[1]} + new {n_new} exceeds "
                         f"the model's max_len {meta['max_len']}")
    policy_key = (str(activation_dtype()), str(compute_dtype()))
    return params, prompt, meta, policy_key


def _sample(logits, key, temperature, top_k):
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1) + 1          # back to 1-based
    logits = logits / temperature
    if top_k is not None:
        k_eff = min(top_k, logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[:, -k_eff][:, None]
        logits = jnp.where(logits < kth, -1e9, logits)
    return jax.random.categorical(key, logits, axis=-1) + 1


@functools.partial(jax.jit, static_argnames=(
    "num_layers", "num_heads", "max_len", "n_new", "temperature",
    "top_k", "policy_key", "rope", "num_kv_heads"))
def _generate_impl(params, prompt, rng, *, num_layers, num_heads,
                   max_len, n_new, temperature, top_k, policy_key,
                   rope=False, num_kv_heads=None):
    """The whole prefill+decode program as ONE module-level jitted
    function: repeated ``generate`` calls with the same shapes/config hit
    the jit cache instead of re-tracing a per-call closure (which
    recompiled the scan on every call — the dominant cost of the round-2
    decode numbers when used as an API rather than a one-shot)."""
    embed, blocks, _, _ = _model_parts(params, num_layers)
    dtype = activation_dtype()
    ck, cv, x, pos = _prefill(params, prompt, num_layers, num_heads,
                              max_len, rope, num_kv_heads)
    logits = _logits(params, num_layers, x)
    rng, key0 = jax.random.split(rng)
    first = _sample(logits, key0, temperature, top_k)

    # ---- decode: lax.scan over the remaining n_new - 1 positions ------
    def step(carry, key):
        tok, ck, cv, pos = carry
        x = _embed(embed, tok[:, None], pos + 1).astype(dtype)
        new_ck, new_cv = list(ck), list(cv)
        for li in range(num_layers):
            x, new_ck[li], new_cv[li] = _block_step(
                blocks[li], x, ck[li], cv[li], pos + 1, num_heads,
                max_len, rope, num_kv_heads)
        logits = _logits(params, num_layers, x)
        nxt = _sample(logits, key, temperature, top_k)
        return (nxt, tuple(new_ck), tuple(new_cv), pos + 1), nxt

    keys = jax.random.split(rng, max(n_new - 1, 1))
    (_, _, _, _), rest = jax.lax.scan(
        step, (first, ck, cv, jnp.asarray(pos)), keys[:n_new - 1])
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def generate(model, prompt, config: GenerationConfig | None = None, *,
             rng=None, params=None):
    """Decode ``config.max_new_tokens`` tokens after ``prompt`` (B, P)
    1-based token ids. Returns (B, max_new_tokens) generated ids.

    ``model`` is a materialized ``TransformerLM`` (its ``num_layers``/
    ``num_heads``/``max_len`` attributes come from the builder); pass
    ``params`` to decode with externally-updated parameters. Activations
    and the KV cache follow the session dtype policy at first trace;
    repeated calls with the same prompt shape and config reuse the
    compiled program.
    """
    config = config or GenerationConfig()
    n_new = config.max_new_tokens
    params, prompt, meta, policy_key = _decode_setup(model, prompt,
                                                     n_new, params)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _generate_impl(
        params, prompt, rng, num_layers=meta["num_layers"],
        num_heads=meta["num_heads"], max_len=meta["max_len"],
        n_new=n_new, temperature=config.temperature, top_k=config.top_k,
        policy_key=policy_key,
        rope=meta.get("pos_encoding", "learned") == "rope",
        num_kv_heads=meta.get("num_kv_heads"))


def beam_search(model, prompt, *, num_beams: int = 4,
                max_new_tokens: int = 32, length_penalty: float = 1.0,
                eos_id: int | None = None, params=None):
    """Length-normalized beam search with the same static KV cache.

    Returns ``(tokens, scores)``: (B, num_beams, max_new_tokens) 1-based
    ids and (B, num_beams) total log-probabilities divided by
    ``n_tokens ** length_penalty``, beams sorted best-first. Beams that
    emit ``eos_id`` freeze (their score stops accumulating; the eos
    position is part of the output).

    Beams fold into the batch dim (B*K rows) so every step is the same
    single-token cache step as ``generate``; each step's top-k reorders
    beam histories AND cache rows with one gather. Like ``generate``,
    the whole program is one module-level jitted function — repeated
    calls with the same shapes and knobs reuse the compiled executable.
    """
    params, prompt, meta, policy_key = _decode_setup(
        model, prompt, max_new_tokens, params)
    return _beam_search_impl(
        params, prompt, num_layers=meta["num_layers"],
        num_heads=meta["num_heads"], max_len=meta["max_len"],
        n_new=max_new_tokens, k=num_beams,
        length_penalty=length_penalty, eos_id=eos_id,
        policy_key=policy_key,
        rope=meta.get("pos_encoding", "learned") == "rope",
        num_kv_heads=meta.get("num_kv_heads"))


@functools.partial(jax.jit, static_argnames=(
    "num_layers", "num_heads", "max_len", "n_new", "k",
    "length_penalty", "eos_id", "policy_key", "rope", "num_kv_heads"))
def _beam_search_impl(params, prompt, *, num_layers, num_heads, max_len,
                      n_new, k, length_penalty, eos_id, policy_key,
                      rope=False, num_kv_heads=None):
    embed, blocks, _, _ = _model_parts(params, num_layers)
    dtype = activation_dtype()
    ck, cv, x, pos0 = _prefill(params, prompt, num_layers, num_heads,
                               max_len, rope, num_kv_heads)
    b = prompt.shape[0]
    logp0 = jax.nn.log_softmax(
        _logits(params, num_layers, x).astype(jnp.float32), axis=-1)

    # first expansion: top-k of the single distribution seeds the beams
    # (k > vocab: seed the extra beams at -inf; the next step's top-k
    # over k*vocab candidates never selects them)
    k0 = min(k, vocab := embed["tok"].shape[0])
    scores, tok0 = jax.lax.top_k(logp0, k0)           # (B, k0) each
    if k0 < k:
        scores = jnp.pad(scores, ((0, 0), (0, k - k0)),
                         constant_values=-jnp.inf)
        tok0 = jnp.pad(tok0, ((0, 0), (0, k - k0)))
    tok0 = tok0 + 1                                   # back to 1-based
    finished = (tok0 == eos_id) if eos_id is not None \
        else jnp.zeros((b, k), bool)
    lengths = jnp.ones((b, k), jnp.float32)   # real tokens incl. eos
    history = jnp.zeros((b, k, n_new), jnp.int32)
    history = history.at[:, :, 0].set(tok0)

    # beams share the prompt cache: tile rows to (B*K, M, H, Dh)
    ck = tuple(jnp.repeat(c, k, axis=0) for c in ck)
    cv = tuple(jnp.repeat(c, k, axis=0) for c in cv)
    batch_offset = (jnp.arange(b) * k)[:, None]       # (B, 1)

    def step(carry, i):
        tok, ck, cv, scores, finished, lengths, history = carry
        # the token fed was produced at step i-1: absolute position
        # p_len + i - 1 = pos0 + i
        pos = pos0 + i
        x = _embed(embed, tok.reshape(b * k, 1), pos).astype(dtype)
        new_ck, new_cv = list(ck), list(cv)
        for li in range(num_layers):
            x, new_ck[li], new_cv[li] = _block_step(
                blocks[li], x, ck[li], cv[li], pos, num_heads, max_len,
                rope, num_kv_heads)
        logp = jax.nn.log_softmax(
            _logits(params, num_layers, x).astype(jnp.float32), axis=-1)
        logp = logp.reshape(b, k, vocab)
        # frozen beams contribute exactly one continuation (token 1,
        # score unchanged) so they occupy one top-k slot, not V
        frozen = jnp.full((vocab,), -jnp.inf).at[0].set(0.0)
        logp = jnp.where(finished[..., None], frozen[None, None], logp)
        cand = (scores[..., None] + logp).reshape(b, k * vocab)
        # prune in NORMALIZED space (GNMT-style): a finished hypothesis
        # competes at its own length, so length_penalty can keep a short
        # eos'd beam alive against longer raw-score continuations
        cand_len = jnp.where(finished, lengths, lengths + 1.0)
        norm_cand = (cand.reshape(b, k, vocab)
                     / (cand_len ** length_penalty)[..., None]
                     ).reshape(b, k * vocab)
        _, flat = jax.lax.top_k(norm_cand, k)         # (B, K)
        scores = jnp.take_along_axis(cand, flat, axis=1)
        beam_idx = flat // vocab                      # (B, K) source beam
        tok_new = flat % vocab + 1                    # 1-based
        # reorder histories and caches to the chosen source beams
        history = jnp.take_along_axis(history, beam_idx[..., None],
                                      axis=1)
        finished = jnp.take_along_axis(finished, beam_idx, axis=1)
        lengths = jnp.take_along_axis(lengths, beam_idx, axis=1)
        # frozen beams emit padding id 0, not a real token
        history = history.at[:, :, i].set(
            jnp.where(finished, 0, tok_new))
        lengths = lengths + jnp.where(finished, 0.0, 1.0)
        if eos_id is not None:
            finished = finished | (tok_new == eos_id)
        rows = (batch_offset + beam_idx).reshape(-1)  # (B*K,)
        new_ck = tuple(c[rows] for c in new_ck)
        new_cv = tuple(c[rows] for c in new_cv)
        return (tok_new, new_ck, new_cv, scores, finished, lengths,
                history), None

    if n_new > 1:
        (tok, ck, cv, scores, finished, lengths, history), _ = \
            jax.lax.scan(step, (tok0, ck, cv, scores, finished, lengths,
                                history), jnp.arange(1, n_new))
    # normalize by each beam's ACTUAL emitted length (eos-frozen beams
    # stop growing), so length_penalty genuinely reorders beams
    norm = scores / (lengths ** length_penalty)
    order = jnp.argsort(-norm, axis=1)
    return (jnp.take_along_axis(history, order[..., None], axis=1),
            jnp.take_along_axis(norm, order, axis=1))

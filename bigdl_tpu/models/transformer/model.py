"""Transformer language model — the long-context flagship.

Beyond the reference's scope (its era ends at scan RNNs, SURVEY §5.7),
but the capability target this framework treats as first-class: a causal
decoder whose attention core can run locally, ring-parallel, or
Ulysses-parallel over the mesh ``seq`` axis (nn/attention.py +
parallel/sequence.py) without touching the parameters. Pre-LN blocks,
learned positional embeddings, weight-tied-free output head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import nn
from bigdl_tpu.nn import init as init_mod
from bigdl_tpu.nn.module import Container, Module
from bigdl_tpu.tensor import activation_dtype, default_dtype

__all__ = ["TransformerLM", "TransformerBlock", "PreNormBlock", "EvaByteLM",
           "KeyeLM", "KimiLM", "decode_meta"]


class _Residual(Container):
    """y = x + inner(norm(x)) — pre-norm residual wrapper. With a
    ``residual_dtype`` the sum, and so the stream the blocks hand on, is
    kept in that dtype (float32 under bf16 activations: the branch's
    output is widened, the stream never rounded)."""

    def __init__(self, norm: Module, inner: Module, residual_dtype=None):
        super().__init__(norm, inner)
        self.residual_dtype = residual_dtype

    def apply(self, params, state, x, *, training=False, rng=None):
        h, s0 = self.modules[0].apply(params["0"], state["0"], x,
                                      training=training)
        h, s1 = self.modules[1].apply(params["1"], state["1"], h,
                                      training=training, rng=rng)
        if self.residual_dtype is not None:
            x, h = x.astype(self.residual_dtype), h.astype(self.residual_dtype)
        return x + h, {"0": s0, "1": s1}


def PreNormBlock(norm, attention: Module, ffn: Module,
                 residual_dtype=None) -> nn.Sequential:
    """x + attention(norm(x)); x + ffn(norm(x)). ``norm`` is called once
    for each of the two norms; ``attention`` and ``ffn`` are the modules
    themselves — a new kind of block is new arguments, not a new case
    here. The parameter tree is {"0": {"0": norm, "1": attention},
    "1": {"0": norm, "1": ffn}} whatever they are."""
    return (nn.Sequential()
            .add(_Residual(norm(), attention, residual_dtype))
            .add(_Residual(norm(), ffn, residual_dtype)))


def TransformerBlock(d_model: int, num_heads: int, ffn_mult: int = 4,
                     dropout: float = 0.0,
                     sequence_parallel: str | None = None,
                     rope: bool = False,
                     num_kv_heads: int | None = None):
    """Pre-LN block: x + MHA(LN(x)); x + FFN(LN(x))."""
    mha = nn.MultiHeadAttention(d_model, num_heads, causal=True,
                                sequence_parallel=sequence_parallel,
                                rope=rope, num_kv_heads=num_kv_heads)
    ffn = (nn.Sequential()
           .add(nn.Linear(d_model, ffn_mult * d_model))
           .add(nn.ReLU())
           .add(nn.Linear(ffn_mult * d_model, d_model)))
    if dropout > 0:
        ffn.add(nn.Dropout(dropout))
    return PreNormBlock(lambda: nn.LayerNorm(d_model), mha, ffn)


class _TokenAndPosition(Module):
    """LookupTable embedding + learned positional embedding (or token
    embedding alone under ``with_pos=False`` — the RoPE recipe, where
    position enters through the attention rotation instead)."""

    def __init__(self, vocab: int, d_model: int, max_len: int,
                 with_pos: bool = True, out_dtype=None,
                 init_std: float | None = None):
        super().__init__()
        self.vocab, self.d_model, self.max_len = vocab, d_model, max_len
        self.with_pos = with_pos
        self.out_dtype = out_dtype      # None: the policy's activations
        self.init_std = init_std        # None: d_model^-1/2

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        scale = 1.0 / np.sqrt(self.d_model) if self.init_std is None \
            else self.init_std
        p = {"tok": jax.random.normal(
            k1, (self.vocab, self.d_model), default_dtype()) * scale}
        if self.with_pos:
            p["pos"] = jax.random.normal(
                k2, (self.max_len, self.d_model), default_dtype()) * scale
        return p

    def apply(self, params, state, x, *, training=False, rng=None):
        # x: (batch, seq) 1-based token ids (LookupTable convention); an
        # id outside 1..vocab reads the nearest row, it does not raise
        # (tests/test_evabyte.py pins it)
        idx = x.astype(jnp.int32) - 1
        s = x.shape[1]
        y = jnp.take(params["tok"], jnp.clip(idx, 0, self.vocab - 1),
                     axis=0)
        if self.with_pos:
            y = y + params["pos"][:s]
        return y.astype(self.out_dtype or activation_dtype()), state


def TransformerLM(vocab_size: int, d_model: int = 128, num_heads: int = 4,
                  num_layers: int = 2, max_len: int = 512,
                  ffn_mult: int = 4, dropout: float = 0.0,
                  sequence_parallel: str | None = None,
                  with_log_softmax: bool = True,
                  pos_encoding: str = "learned",
                  num_kv_heads: int | None = None) -> nn.Sequential:
    """Causal LM: tokens (B, S) -> log-probs (B, S, vocab).

    ``with_log_softmax=False`` ends at raw logits — pair it with
    ``CrossEntropyCriterion`` to skip materializing the f32 log-prob
    tensor (the memory-lean LM training recipe, docs/PERF.md).

    ``pos_encoding``: "learned" (additive table, capped at ``max_len``)
    or "rope" (rotary q/k rotation inside attention — no additive table,
    no hard length cap beyond the decode cache's allocation).

    ``num_kv_heads`` < ``num_heads`` selects grouped-query attention:
    the decode KV cache shrinks by num_heads/num_kv_heads (the
    batch-scaling lever for serving; generate.py keeps the cache at kv
    heads and groups queries instead of repeating keys).
    """
    if pos_encoding not in ("learned", "rope"):
        raise ValueError(f"pos_encoding={pos_encoding!r}")
    rope = pos_encoding == "rope"
    model = (nn.Sequential()
             .add(_TokenAndPosition(vocab_size, d_model, max_len,
                                    with_pos=not rope)
                  .set_name("embed")))
    for i in range(num_layers):
        model.add(TransformerBlock(
            d_model, num_heads, ffn_mult, dropout,
            sequence_parallel, rope=rope,
            num_kv_heads=num_kv_heads).set_name(f"block_{i}"))
    model.add(nn.LayerNorm(d_model).set_name("final_norm"))
    model.add(nn.Linear(d_model, vocab_size,
                        init_method=init_mod.Xavier).set_name("lm_head"))
    if with_log_softmax:
        model.add(nn.LogSoftMax())
    # decode-path metadata (models/transformer/generate.py)
    model.lm_meta = {"num_layers": num_layers, "num_heads": num_heads,
                     "max_len": max_len, "d_model": d_model,
                     "vocab": vocab_size, "pos_encoding": pos_encoding,
                     "num_kv_heads": num_kv_heads}
    return model


def EvaByteLM(vocab_size: int = 320, d_model: int = 4096,
              num_heads: int = 32, num_layers: int = 32,
              ffn_dim: int = 11008, window: int = 2048, chunk: int = 16,
              num_pred_heads: int = 8, rope_theta: float = 100000.0,
              rms_eps: float = 1e-5,
              remat: str | None = "per_block") -> nn.Sequential:
    """EvaByte (huggingface.co/EvaByte/EvaByte): a byte-level decoder of
    pre-norm blocks x + EVA(RMSNorm(x)); x + SwiGLU(RMSNorm(x)) —
    ``nn.EvaAttention``, RMS norms with a unit offset, no bias anywhere,
    RoPE and no position table — on a float32 residual stream, ending in
    ``num_pred_heads`` x ``vocab_size`` float32 logits a position (pair
    with ``nn.MultiBytePredictionCriterion``). bytes (B, S) 1-based, S a
    multiple of ``window``. docs/eva_attention.md has the equations.

    ``remat`` is the training recipe of the model AS BUILT: each child
    is recomputed in the backward pass (``Sequential.set_remat``), under
    ``Optimizer`` and under a bare ``jax.grad`` of ``apply`` alike.

    The same ``embed`` / ``block_i`` / ``final_norm`` / ``lm_head``
    children as ``TransformerLM``, but no ``lm_meta``: there is no
    decode path for EvaAttention yet (``decode_meta``)."""
    def norm():
        return nn.RMSNorm(d_model, eps=rms_eps, unit_offset=True)

    model = (nn.Sequential()
             .add(_TokenAndPosition(vocab_size, d_model, 0, with_pos=False,
                                    out_dtype=jnp.float32)
                  .set_name("embed")))
    for i in range(num_layers):
        model.add(PreNormBlock(
            norm,
            nn.EvaAttention(d_model, num_heads, window, chunk, rope_theta),
            nn.GatedFFN(d_model, ffn_dim, act="silu"),
            residual_dtype=jnp.float32).set_name(f"block_{i}"))
    model.add(norm().set_name("final_norm"))
    # on these few columns Xavier gives logits of deviation 1.1
    model.add(nn.Linear(d_model, num_pred_heads * vocab_size,
                        with_bias=False, init_method=_small_head(d_model),
                        output_dtype=jnp.float32).set_name("lm_head"))
    model.no_decode_path = "EvaAttention: ROADMAP B7"
    return model.set_remat(remat)


def _small_head(d_model: int):
    """A head initialiser: logits of standard deviation 0.28 on a
    unit-RMS input — what Xavier gives TransformerLM's 50272-row head at
    OPT-1.3B's width. On fewer columns Xavier gives more, and every
    gradient of a random-init model, and so what rounding the weights to
    bf16 moves the loss by, grows with it (PERF.md section 6, PR 27)."""
    def small_normal(rng, shape, dtype):
        return jax.random.normal(rng, shape, dtype) * 0.28 * d_model ** -0.5
    return small_normal


def KeyeLM(vocab_size: int = 151936, d_model: int = 2048,
           num_heads: int = 32, num_kv_heads: int = 4, head_dim: int = 128,
           num_layers: int = 48, expert_dim: int = 768,
           experts_total: int = 128, experts_per_token: int = 8,
           experts_held: int | None = None, experts_offset: int = 0,
           index_heads: int = 16, index_dim: int = 64, topk: int = 2048,
           rope_theta: float = 1e7, rms_eps: float = 1e-6,
           remat: str | None = "per_block") -> nn.Sequential:
    """The language model of Keye-VL-2.0-30B-A3B
    (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B): a decoder of
    pre-norm blocks x + SparseAttn(RMSNorm(x)); x + MoE(RMSNorm(x)) —
    ``nn.SparseSelectAttention`` (GQA with per-head q/k RMS norms and
    RoPE, a learned top-``topk`` selection with its indexer and the
    indexer's loss) and ``parallel.expert.ExpertShare`` (a float32
    softmax router over ``experts_total``, ``experts_per_token`` a token
    renormalised, SwiGLU experts of which ``experts_held`` from
    ``experts_offset`` live here, dropless) — on a float32 residual
    stream, no bias anywhere, no position table, an untied head of
    float32 logits over ``vocab_size`` rows (a chip's slice of the
    vocabulary is a smaller ``vocab_size``). tokens (B, S) 1-based; text
    positions only (M-RoPE's rows equal, so plain RoPE); no vision
    tower. The norms hand float32 to the blocks: the router and the
    indexer read it, the large matmuls round it to the compute dtype.
    docs/sparse_attention.md and docs/expert_share.md have the
    equations.

    ``remat`` as ``EvaByteLM``'s; the same ``embed`` / ``block_i`` /
    ``final_norm`` / ``lm_head`` children and no ``lm_meta``: there is
    no decode path for the selection yet (``decode_meta``)."""
    from bigdl_tpu.parallel.expert import ExpertShare

    def norm():
        return nn.RMSNorm(d_model, eps=rms_eps, fp32=True)

    model = (nn.Sequential()
             .add(_TokenAndPosition(vocab_size, d_model, 0, with_pos=False,
                                    out_dtype=jnp.float32)
                  .set_name("embed")))
    for i in range(num_layers):
        model.add(PreNormBlock(
            norm,
            nn.SparseSelectAttention(d_model, num_heads, num_kv_heads,
                                     head_dim, index_heads, index_dim, topk,
                                     rope_theta, rms_eps),
            ExpertShare(d_model, expert_dim, experts_total,
                        experts_per_token, experts_held=experts_held,
                        experts_offset=experts_offset),
            residual_dtype=jnp.float32).set_name(f"block_{i}"))
    model.add(nn.RMSNorm(d_model, eps=rms_eps).set_name("final_norm"))
    model.add(nn.Linear(d_model, vocab_size, with_bias=False,
                        init_method=_small_head(d_model),
                        output_dtype=jnp.float32).set_name("lm_head"))
    model.no_decode_path = "SparseSelectAttention: ROADMAP B11"
    return model.set_remat(remat)


KIMI_EMBEDDING_STD = 1.0


def KimiLM(vocab_size: int = 163840, d_model: int = 2048,
           num_heads: int = 16, qk_nope: int = 128, qk_rope: int = 64,
           v_dim: int = 128, kv_rank: int = 512, num_layers: int = 27,
           dense_layers: int = 1, ffn_dim: int = 11264,
           expert_dim: int = 1408, experts_total: int = 64,
           experts_per_token: int = 6, shared_experts: int = 2,
           route_scale: float = 2.446, bias_update_rate: float = 0.001,
           experts_held: int | None = None, experts_offset: int = 0,
           rope_theta: float = 8e5, rms_eps: float = 1e-5,
           remat: str | None = "per_block") -> nn.Sequential:
    """The language model of Kimi-VL-A3B-Instruct
    (huggingface.co/moonshotai/Kimi-VL-A3B-Instruct; the DeepSeek-V3
    style decoder Moonlight-16B-A3B): pre-norm blocks
    x + MLA(RMSNorm(x)); x + F(RMSNorm(x)) — ``nn.LatentAttention``
    (keys and values from one RMS-normed latent of ``kv_rank``, a score
    of a ``qk_nope``-wide content part and a ``qk_rope``-wide rotary
    part whose key all heads share, values ``v_dim`` wide) — where F is
    a dense SwiGLU of ``ffn_dim`` (``nn.GatedFFN``) in the first
    ``dense_layers`` blocks and ``parallel.expert.ExpertShare`` after
    them: a float32 SIGMOID router over ``experts_total``, the
    ``experts_per_token`` largest of score + bias chosen, the scores
    alone normalised over the chosen and scaled by ``route_scale``,
    SwiGLU experts of ``expert_dim`` of which ``experts_held`` from
    ``experts_offset`` live here, dropless, plus ONE shared SwiGLU of
    ``shared_experts`` x ``expert_dim`` every token passes. The bias is
    module state the step updates from its own expert counts at
    ``bias_update_rate`` (aux-loss-free balancing); there is no balance
    loss. A float32 residual stream, no bias term anywhere, no position
    table, an untied head of float32 logits over ``vocab_size`` rows (a
    chip's slice of the vocabulary is a smaller ``vocab_size``). tokens
    (B, S) 1-based; text positions only, no vision tower.
    docs/latent_attention.md and docs/expert_share.md have the
    equations.

    The token embedding is drawn at UNIT deviation an element
    (``KIMI_EMBEDDING_STD``: the scale a model that multiplies its
    embedding by sqrt(d) starts from), not at ``TransformerLM``'s
    d^-1/2. At d^-1/2 the blocks' outputs outweigh the embedding from
    the first layer on, averaging attention makes the residual stream
    one vector common to every token, and an untrained router sends
    every token to the same experts (PERF.md section 6, PRs 31 and 33);
    at one the stream stays the token's own and the router spreads the
    tokens over the experts as a trained one does. The price: the
    stream's scale divides every gradient that passes the final norm,
    so the blocks' gradients are smaller beside the head's than at
    d^-1/2 (the attention's 14-34 times, the embedding's 57; PERF.md
    section 6, PR 33).

    ``remat`` as ``EvaByteLM``'s; the same ``embed`` / ``block_i`` /
    ``final_norm`` / ``lm_head`` children and no ``lm_meta``: there is
    no decode path over a latent cache yet (``decode_meta``)."""
    from bigdl_tpu.parallel.expert import ExpertShare

    def norm():
        return nn.RMSNorm(d_model, eps=rms_eps, fp32=True)

    model = (nn.Sequential()
             .add(_TokenAndPosition(vocab_size, d_model, 0, with_pos=False,
                                    out_dtype=jnp.float32,
                                    init_std=KIMI_EMBEDDING_STD)
                  .set_name("embed")))
    for i in range(num_layers):
        ffn = nn.GatedFFN(d_model, ffn_dim) if i < dense_layers else \
            ExpertShare(d_model, expert_dim, experts_total,
                        experts_per_token, experts_held=experts_held,
                        experts_offset=experts_offset, scoring="sigmoid",
                        route_scale=route_scale,
                        bias_update_rate=bias_update_rate,
                        shared_width=shared_experts * expert_dim)
        model.add(PreNormBlock(
            norm,
            nn.LatentAttention(d_model, num_heads, qk_nope, qk_rope, v_dim,
                               kv_rank, rope_theta, rms_eps),
            ffn, residual_dtype=jnp.float32).set_name(f"block_{i}"))
    model.add(nn.RMSNorm(d_model, eps=rms_eps).set_name("final_norm"))
    model.add(nn.Linear(d_model, vocab_size, with_bias=False,
                        init_method=_small_head(d_model),
                        output_dtype=jnp.float32).set_name("lm_head"))
    model.no_decode_path = "LatentAttention: ROADMAP B5"
    return model.set_remat(remat)


def decode_meta(model) -> dict:
    """The decode-path metadata ``TransformerLM`` attaches (generate.py,
    serving.py). A model without it is refused by name, never guessed
    at."""
    meta = getattr(model, "lm_meta", None)
    if meta is None:
        why = getattr(model, "no_decode_path", None)
        raise ValueError(
            f"no decode path for {why}" if why else
            "model has no lm_meta — build it with TransformerLM(...) to "
            "generate")
    return meta

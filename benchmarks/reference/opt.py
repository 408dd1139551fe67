"""OPT (arXiv:2205.01068) forward pass, loss and gradients in plain
``jax.numpy`` float32. No kernel, no cache, no batching tricks, nothing
imported from bigdl_tpu: the same mathematics written down once more.

    x   = tok[ids] + pos[0..S-1]
    per layer:  h = LN(x);  x = x + (softmax(causal(q k^T / sqrt(D))) v) Wo + bo
                h = LN(x);  x = x + relu(h W1^T + b1) W2^T + b2
    logits = LN_f(x) Wh^T + bh
    loss   = mean over positions of logsumexp(logits) - logits[target]

Departures from the published model, shared with the system under test
and listed in every configuration's ``assumed``: the output head is not
tied to the embedding, and the position table has no offset-2 rows.

Weights arrive as a dict of named arrays in whatever dtype they are held
in and are upcast to float32 one layer at a time INSIDE each layer's
call, so the reference fits beside a served model. On a TPU a float32
matmul runs in lower precision unless asked otherwise:
everything here runs under ``default_matmul_precision("highest")``.

Token ids here are 0-based.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
F32 = jnp.float32


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return wrapped


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * g.astype(F32) \
        + b.astype(F32)


def _linear(x, w, b):
    return x @ w.astype(F32).T + b.astype(F32)


def embed(w, ids):
    """(B, S) 0-based ids -> (B, S, d) float32."""
    s = ids.shape[1]
    return w["tok"].astype(F32)[ids] + w["pos"].astype(F32)[:s]


def layer(lw, x, num_heads: int):
    """One pre-LN decoder layer on (B, S, d) float32."""
    b, s, d = x.shape
    hd = d // num_heads
    h = _ln(x, lw["ln1_g"], lw["ln1_b"])

    def heads(t):
        return t.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)

    q = heads(_linear(h, lw["q_w"], lw["q_b"]))
    k = heads(_linear(h, lw["k_w"], lw["k_b"]))
    v = heads(_linear(h, lw["v_w"], lw["v_b"]))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + _linear(o, lw["o_w"], lw["o_b"])
    h = _ln(x, lw["ln2_g"], lw["ln2_b"])
    h = jax.nn.relu(_linear(h, lw["fc1_w"], lw["fc1_b"]))
    return x + _linear(h, lw["fc2_w"], lw["fc2_b"])


def head(w, x):
    """(B, S, d) -> (B, S, V) logits."""
    return _linear(_ln(x, w["lnf_g"], w["lnf_b"]), w["head_w"],
                   w["head_b"])


def _forward(w, ids, num_heads):
    x = embed(w, ids)
    for lw in w["layers"]:
        x = layer(lw, x, num_heads)
    return head(w, x)


def _nll(logits, targets):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


_embed_jit = jax.jit(_highest(embed))
_layer_jit = jax.jit(_highest(layer), static_argnums=2)
_head_jit = jax.jit(_highest(head))


def hidden(w, ids, num_heads: int):
    """Final hidden states (before the last norm), one jitted call per
    layer so only one layer's float32 copy is alive at a time."""
    x = _embed_jit(w, ids)
    for lw in w["layers"]:
        x = _layer_jit(lw, x, num_heads)
    return x


def logits(w, ids, num_heads: int):
    """(B, S) 0-based ids -> (B, S, V) float32 logits."""
    return _head_jit(w, hidden(w, ids, num_heads))


@jax.jit
@_highest
def _head_at(w, x, positions):
    picked = jnp.take_along_axis(x, positions[..., None], axis=1)
    return head(w, picked)


def logits_at(w, ids, positions, num_heads: int):
    """Logits at ``positions`` (B, K) of each row of ``ids`` (B, S) only:
    (B, K, V) float32 — the teacher-forced pass of the serving check."""
    return _head_at(w, hidden(w, ids, num_heads), positions)


@jax.jit
@_highest
def _loss_of_hidden(w, x, targets):
    return _nll(head(w, x), targets)


def loss(w, ids, targets, num_heads: int) -> float:
    """Mean next-token cross-entropy over (B, S), sequence by sequence so
    the (S, V) float32 logits of one sequence are all that is alive."""
    total = 0.0
    for i in range(ids.shape[0]):
        x = hidden(w, ids[i:i + 1], num_heads)
        total += float(_loss_of_hidden(w, x, targets[i:i + 1]))
    return total / ids.shape[0]


@functools.partial(jax.jit, static_argnums=3)
@_highest
def loss_and_grads(w, ids, targets, num_heads: int):
    """(loss, d loss / d w) of the whole model on (B, S): one program,
    for the gradient comparison on a one-sequence sample."""
    return jax.value_and_grad(
        lambda ww: _nll(_forward(ww, ids, num_heads), targets))(w)

"""SmallThinker-21BA3B-Instruct's language model
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct) forward pass,
loss and gradients in plain ``jax.numpy`` float32. No kernel, no cache,
nothing imported from bigdl_tpu: the mathematics written down once more,
one sequence at a time, for ONE chip's share of each layer's experts
(``Spec.experts_offset`` .. + the experts held, and the rows of the
vocabulary that ``tok`` / ``head_w`` hold).

Layer l on x in R^{S x d}, RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g:

    r  = RMSNorm_in(x)
    q = r Wq^T (Hq x D), k = r Wk^T, v = r Wv^T (Hkv x D), no bias;
        query head i reads key head floor(i / (Hq / Hkv))
    window layer (sliding_window_layout 1): query i sees keys j with
        j <= i AND i - j < W; global layer (0): every j <= i
    rope_layout 1: q, k rotated by position (theta, pairs (x[i],
        x[i + D/2])); 0: no position embedding (NoPE)
    x' = x + concat_heads(softmax(q.k D^-1/2 over the keys seen) v) Wo^T
    h  = RMSNorm_post(x')
    p  = softmax(Wrouter r) over all E     -- the router reads r, the
                                              ATTENTION's input
    S  = the K largest of p (lower index first among equals);
         w_e = p_e / sum_{S} p
    y  = x' + sum_{e in S AND held here} w_e Wdown_e( relu(Wgate_e h) *
         (Wup_e h) )                        -- ReGLU experts, no shared one

What experts elsewhere would add is left out. After the last layer:
logits = RMSNorm_f(y) Whead^T over the rows held here; loss = mean_t
CE(logits[t], id[t + 1]). Embedding V x d, not tied.

Departures from the released model, shared with the system under test
and listed in the configuration's ``assumed``: the router's input is
read as RMSNorm_in(x) ("before attention"); no secondary experts; no
balance loss; no dropout; text only. The reference keeps every
activation in float32 where the program computes in bfloat16 (the
program's rounding, which the comparison's tolerances measure).

It is computed so that it fits beside a model in training: the
attention a block of ``QUERY_BLOCK`` queries at a time against every key
(the masks as written above, the whole score row), the experts and the
head a block of tokens at a time, one jitted program a layer, forward
and backward, under ``default_matmul_precision("highest")``.

Token ids here are 0-based. Weights arrive as ``Weights`` (kimi's: a
pytree of named arrays with a static ``Spec``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.kimi import (Weights, _highest, _in_blocks, _rms,
                                       _rope)

__all__ = ["Spec", "Weights", "kind_of", "layer", "loss",
           "loss_and_grads", "logits", "route"]

F32 = jnp.float32
QUERY_BLOCK = 512         # queries attended at a time
TOKEN_BLOCK = 2048        # tokens a block of the experts and the head


class Spec(NamedTuple):
    """What the arrays' shapes do not say."""
    layout: tuple             # (windowed, rotary) a layer
    num_kv_heads: int
    window: int
    rope_theta: float
    experts_total: int
    experts_offset: int       # number of the first expert held here
    experts_per_token: int
    eps: float

    @property
    def kinds(self) -> tuple:
        return tuple(kind_of(*at) for at in self.layout)


def kind_of(windowed: bool, rotary: bool) -> str:
    """A layer's attention as one static name: ``window`` / ``global``
    and ``rope`` / ``nope``."""
    return f"{'window' if windowed else 'global'}_" \
        f"{'rope' if rotary else 'nope'}"


def attention(lw, u, num_heads: int, kind: str, spec: Spec):
    """The attention sublayer on u = r (S, d)."""
    s = u.shape[0]
    d_head = lw["q_w"].shape[0] // num_heads
    rep = num_heads // spec.num_kv_heads
    q = (u @ lw["q_w"].astype(F32).T).reshape(s, num_heads, d_head)
    k = (u @ lw["k_w"].astype(F32).T).reshape(s, -1, d_head)
    v = (u @ lw["v_w"].astype(F32).T).reshape(s, -1, d_head)
    if kind.endswith("_rope"):
        q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    keys = jnp.arange(s)[None, :]

    def block(xs):
        rows, qb = xs
        sc = jnp.einsum("thd,jhd->htj", qb, k) * d_head ** -0.5
        seen = keys <= rows[:, None]
        if kind.startswith("window"):
            seen = seen & (rows[:, None] - keys < spec.window)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("htj,jhd->thd", p, v).reshape(rows.shape[0], -1)

    return _in_blocks(block, (jnp.arange(s), q), QUERY_BLOCK) \
        @ lw["o_w"].astype(F32).T


def route(lw, r, spec: Spec):
    """The router on r (T, d): (numbers of the K experts chosen (T, K),
    their weights renormalised over the K)."""
    p = jax.nn.softmax(r @ lw["router_w"].astype(F32).T, axis=-1)
    top, idx = jax.lax.top_k(p, spec.experts_per_token)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def experts(lw, h, r, spec: Spec):
    """The expert sublayer on a block of tokens: every held expert
    computed on every token's h and weighted by the choice made from
    its r (zero where the token did not choose it)."""
    idx, w = route(lw, r, spec)
    out = jnp.zeros_like(h)
    for e in range(lw["up_w"].shape[0]):
        weight = jnp.sum(jnp.where(idx == spec.experts_offset + e, w, 0.0),
                         axis=-1, keepdims=True)
        out = out + weight * ((jax.nn.relu(h @ lw["gate_w"][e].astype(F32).T)
                               * (h @ lw["up_w"][e].astype(F32).T))
                              @ lw["down_w"][e].astype(F32).T)
    return out


def layer(lw, x, kind: str, num_heads: int, spec: Spec):
    """One layer of ``kind`` (``kind_of``) on (S, d) float32."""
    r = _rms(x, lw["ln1_g"], spec.eps)
    x = x + attention(lw, r, num_heads, kind, spec)
    h = _rms(x, lw["ln2_g"], spec.eps)
    return x + _in_blocks(lambda hr: experts(lw, *hr, spec), (h, r),
                          TOKEN_BLOCK)


def _head_loss(head, x, targets, spec):
    def block(args):
        xb, tb = args
        lg = _rms(xb, head["lnf_g"], spec.eps) @ head["head_w"].astype(F32).T
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return lse - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]

    return jnp.mean(_in_blocks(block, (x, targets), TOKEN_BLOCK))


_embed_jit = jax.jit(lambda tok, ids: tok.astype(F32)[ids])
_layer_jit = jax.jit(_highest(layer), static_argnums=(2, 3, 4))
_head_loss_jit = jax.jit(_highest(_head_loss), static_argnums=3)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _layer_vjp(lw, x, g, kind, num_heads, spec):
    return jax.vjp(lambda a, b: layer(a, b, kind, num_heads, spec),
                   lw, x)[1](g)


@functools.partial(jax.jit, static_argnums=3)
@_highest
def _head_vjp(head, x, targets, spec):
    return jax.value_and_grad(_head_loss, argnums=(0, 1))(
        head, x, targets, spec)


@jax.jit
def _embed_vjp(tok, ids, g):
    return jnp.zeros(tok.shape, F32).at[ids].add(g)


def _head_of(w):
    return {"lnf_g": w["lnf_g"], "head_w": w["head_w"]}


def hidden(w, ids, num_heads: int, keep: bool = False):
    """The last layer's output (S, d) for one sequence of 0-based ids;
    with ``keep`` every layer's input too."""
    x = _embed_jit(w["tok"], ids)
    inputs = []
    for lw, kind in zip(w["layers"], w.spec.kinds):
        inputs.append(x if keep else None)
        x = _layer_jit(lw, x, kind, num_heads, w.spec)
    return (x, inputs) if keep else x


def logits(w, ids, num_heads: int):
    """(S,) 0-based ids -> (S, V_held) float32 logits."""
    x = hidden(w, ids, num_heads)
    with jax.default_matmul_precision("highest"):
        return _rms(x, w["lnf_g"], w.spec.eps) @ w["head_w"].astype(F32).T


def loss(w, ids, targets, num_heads: int) -> float:
    """Mean over the (B, S) batch's sequences of the loss; ``targets``
    are the 0-based next ids."""
    return sum(float(_head_loss_jit(
        _head_of(w), hidden(w, ids[i], num_heads), targets[i], w.spec))
        for i in range(ids.shape[0])) / ids.shape[0]


def loss_and_grads(w, ids, targets, num_heads: int):
    """(the loss, its gradient for every leaf) of the whole model on
    (B, S): the chain rule by hand between layers, each layer's gradient
    ``jax.vjp`` of ``layer`` and fetched to the host as it is made. The
    gradient tree's leaves are numpy arrays."""
    import numpy as np
    total, grads = 0.0, None
    for i in range(ids.shape[0]):
        x, inputs = hidden(w, ids[i], num_heads, keep=True)
        value, (g_head, g) = _head_vjp(_head_of(w), x, targets[i], w.spec)
        del x
        total += float(value)
        g_layers = []
        for lw, kind in zip(reversed(w["layers"]),
                            reversed(w.spec.kinds)):
            g_lw, g = _layer_vjp(lw, inputs.pop(), g, kind, num_heads,
                                 w.spec)
            g_layers.append(jax.device_get(g_lw))
        one = dict(jax.device_get(g_head), layers=g_layers[::-1],
                   tok=jax.device_get(_embed_vjp(w["tok"], ids[i], g)))
        grads = one if grads is None else jax.tree.map(np.add, grads, one)
    n = ids.shape[0]
    return total / n, Weights(jax.tree.map(lambda a: a / n, grads), w.spec)

"""EvaByte (huggingface.co/EvaByte/EvaByte; EVA attention: Zheng et al.,
"Efficient Attention via Control Variates", arXiv:2302.04542) forward
pass, loss and gradients in plain ``jax.numpy`` float32. No kernel, no
cache, nothing imported from bigdl_tpu: the mathematics written down
once more, one sequence at a time.

One layer, on x in R^{S x d} (float32 throughout; ``fp32_skip_add``):

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)   (norm_add_unit_offset)

1.  h = RMSNorm_1(x); q, k, v = h Wq^T, h Wk^T, h Wv^T (no bias), split
    into H heads of D; RoPE (theta from the config, positions 0..S-1,
    pairs (x[i], x[i + D/2])) on q and k.
2.  Chunk summaries. The sequence is cut into chunks of C and windows of
    W tokens. Each head has two learned vectors phi, mu in R^D. For
    chunk c:  a_j = softmax over j in c of (k_j . phi);
    k~_c = sum_j a_j k_j + mu;  v~_c = sum_j a_j v_j   (k already rotated).
3.  One softmax over exact and summarised keys. For query i in window
    w(i), s = D^-1/2:  local(i) = { j in w(i), j <= i },
    remote(i) = { c : chunk c lies in a window before w(i) },
    o_i = [ sum_local exp(s q_i.k_j) v_j + sum_remote exp(s q_i.k~_c) v~_c ]
          / [ sum_local exp(s q_i.k_j) + sum_remote exp(s q_i.k~_c) ].
    The first window has no remote set; a window's own chunks are never
    in its remote set (they are seen exactly).
4.  x <- x + concat_heads(o) Wo^T;  x <- x + Wdown( silu(Wgate h') *
    (Wup h') ), h' = RMSNorm_2(x), no bias.
5.  After the last layer: logits = RMSNorm_f(x) Whead^T in R^{S x (P x
    V)}, read as P heads of V. Head i = 1..P at position t is scored
    against byte t + i; loss = mean over heads of the mean over the
    positions that have such a byte of CE(logits[t, i], byte[t + i]).
    Embedding V x d, not tied, no position table.

Departures and assumptions, shared with the system under test and listed
in the configuration's ``assumed`` (the catalog gives config.json, not
the released modelling code): the pooling score k_j . phi is NOT scaled
by s; mu is added after pooling; RoPE before pooling; the P heads weigh
equally; ``fp32_ln`` false is read as "mean and division in float32,
rounded once, scaled in the activation dtype" by the system and is plain
float32 here; ``mixedp_attn`` is the system's bf16 matmuls with float32
softmax statistics and plain float32 here; no dropout.

It is computed in blocks so that it fits beside a model in training:
attention a group of heads at a time from projection to projection and
inside that a window at a time, the FFN and the head a block of tokens
at a time, one jitted program a layer, forward and backward.
On a TPU a float32 matmul runs in lower precision unless asked
otherwise: everything runs under ``default_matmul_precision("highest")``.

Byte ids here are 0-based. Weights arrive as ``Weights``: a pytree of
named arrays whose static part carries the sizes no array shape shows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_GROUP = 8            # heads attended at a time
TOKEN_BLOCK = 2048        # tokens a block of the FFN and of the head


class Spec(NamedTuple):
    """What the arrays' shapes do not say."""
    window: int
    chunk: int
    pred_heads: int
    rope_theta: float
    eps: float


@jax.tree_util.register_pytree_with_keys_class
class Weights:
    """``arrays``: {"tok", "layers": [{"ln1_g", "q_w", "k_w", "v_w",
    "o_w", "phi", "mu", "ln2_g", "gate_w", "up_w", "down_w"}], "lnf_g",
    "head_w"}; matrices are (out, in). ``spec``: a ``Spec`` (static)."""

    def __init__(self, arrays: dict, spec: Spec):
        self.arrays, self.spec = arrays, spec

    def __getitem__(self, name):
        return self.arrays[name]

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("arrays"), self.arrays),), \
            self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(children[0], spec)


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return wrapped


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def _rope(x, theta):
    """(S, H, D), positions 0..S-1, pairs (x[i], x[i + D/2])."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _summaries(k, v, phi, mu, chunk):
    """(S, H, D) -> two (S / chunk, H, D): step 2."""
    s, h, d = k.shape
    kc, vc = (t.reshape(s // chunk, chunk, h, d) for t in (k, v))
    a = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi), axis=1)
    return (jnp.einsum("nch,nchd->nhd", a, kc) + mu,
            jnp.einsum("nch,nchd->nhd", a, vc))


def _attend(qw, kw, vw, ks, vs, seen):
    """Step 3 for ONE window and a group of heads: qw, kw, vw (W, G, D);
    ks, vs (S / C, G, D); ``seen`` (S / C,) bool: the summaries of the
    windows before this one."""
    w, _, d = qw.shape
    scale = 1.0 / jnp.sqrt(F32(d))
    local = jnp.einsum("qgd,kgd->gqk", qw, kw) * scale
    local = jnp.where(jnp.tril(jnp.ones((w, w), bool)), local, -jnp.inf)
    remote = jnp.einsum("qgd,cgd->gqc", qw, ks) * scale
    remote = jnp.where(seen, remote, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([local, remote], axis=-1), axis=-1)
    return (jnp.einsum("gqk,kgd->qgd", p[..., :w], vw)
            + jnp.einsum("gqc,cgd->qgd", p[..., w:], vs))


def _in_blocks(fn, xs, block):
    """``fn`` over row blocks of the arrays ``xs`` (each (S, ...)), one
    block alive at a time and recomputed in a gradient."""
    s = jax.tree.leaves(xs)[0].shape[0]
    block = min(block, s)
    if s % block:
        return fn(xs)
    y = jax.lax.map(jax.checkpoint(fn), jax.tree.map(
        lambda x: x.reshape(s // block, block, *x.shape[1:]), xs))
    return y.reshape(s, *y.shape[2:])


def _attention(lw, h, num_heads, spec):
    """Steps 1-3 and the output projection, a group of heads at a time
    from projection to projection (each group's part of o Wo^T is added
    up), so that no (S, d)-sized q, k, v or o exists."""
    s, dm = h.shape
    hd = dm // num_heads
    if s % spec.window:
        raise ValueError(f"sequence length {s} is not a multiple of the "
                         f"window {spec.window}")
    nw, per = s // spec.window, spec.window // spec.chunk
    group = HEAD_GROUP if num_heads % HEAD_GROUP == 0 else num_heads
    ng = num_heads // group

    def rows(w):                            # (H D, d) -> (ng, G D, d)
        return w.astype(F32).reshape(ng, group * hd, dm)

    def vectors(p):                         # (H, D) -> (ng, G, D)
        return p.astype(F32).reshape(ng, group, hd)

    def one_group(ws):
        wq, wk, wv, wo, phi, mu = ws

        def heads(w):
            return (h @ w.T).reshape(s, group, hd)

        q = _rope(heads(wq), spec.rope_theta)
        k = _rope(heads(wk), spec.rope_theta)
        v = heads(wv)
        ks, vs = _summaries(k, v, phi, mu, spec.chunk)

        def one_window(xs):
            n, qw, kw, vw = xs
            seen = jnp.arange(nw * per) // per < n
            return _attend(qw, kw, vw, ks, vs, seen)

        def windows(t):
            return t.reshape(nw, spec.window, group, hd)

        o = jax.lax.map(jax.checkpoint(one_window),
                        (jnp.arange(nw), windows(q), windows(k),
                         windows(v)))
        return o.reshape(s, group * hd) @ wo.T

    groups = (rows(lw["q_w"]), rows(lw["k_w"]), rows(lw["v_w"]),
              lw["o_w"].astype(F32).reshape(dm, ng, group * hd)
              .transpose(1, 0, 2),
              vectors(lw["phi"]), vectors(lw["mu"]))
    part = jax.checkpoint(one_group)
    out, _ = jax.lax.scan(lambda acc, ws: (acc + part(ws), None),
                          jnp.zeros((s, dm), F32), groups)
    return out


def _ffn(lw, h):
    gate = h @ lw["gate_w"].astype(F32).T
    up = h @ lw["up_w"].astype(F32).T
    return (jax.nn.silu(gate) * up) @ lw["down_w"].astype(F32).T


def layer(lw, x, num_heads: int, spec: Spec):
    """One decoder layer on (S, d) float32: steps 1-4."""
    x = x + _attention(lw, _rms(x, lw["ln1_g"], spec.eps), num_heads, spec)
    return x + _in_blocks(
        lambda xb: _ffn(lw, _rms(xb, lw["ln2_g"], spec.eps)), x,
        TOKEN_BLOCK)


def _nll_of_hidden(w, x, targets):
    """Step 5 on the last layer's output (S, d): ``targets`` (S,) are the
    0-based NEXT bytes (targets[t] = byte t + 1), so head i = 1..P at
    position t reads targets[t + i - 1]."""
    spec = w.spec
    s = x.shape[0]
    ahead = jnp.arange(s)[:, None] + jnp.arange(spec.pred_heads)   # (S, P)
    valid = ahead < s
    tgt = targets[jnp.minimum(ahead, s - 1)]

    def block(args):
        xb, tb = args
        logits = (_rms(xb, w["lnf_g"], spec.eps)
                  @ w["head_w"].astype(F32).T)
        logits = logits.reshape(xb.shape[0], spec.pred_heads, -1)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, tb[..., None], -1)[..., 0]

    ce = _in_blocks(block, (x, tgt), TOKEN_BLOCK)
    per_head = jnp.sum(jnp.where(valid, ce, 0.0), axis=0) \
        / jnp.sum(valid, axis=0)
    return jnp.mean(per_head)


def _head_loss(head, x, targets, spec):
    return _nll_of_hidden(Weights(head, spec), x, targets)


# one jitted program a layer, a head and an embedding: one layer's float32
# working set is all that is alive beside the weights
_embed_jit = jax.jit(lambda tok, ids: tok.astype(F32)[ids])
_layer_jit = jax.jit(_highest(layer), static_argnums=(2, 3))
_head_loss_jit = jax.jit(_highest(_head_loss), static_argnums=3)


@functools.partial(jax.jit, static_argnums=(3, 4))
@_highest
def _layer_vjp(lw, x, g, num_heads, spec):
    """(d loss / d lw, d loss / d x) of one layer from d loss / d output."""
    return jax.vjp(lambda a, b: layer(a, b, num_heads, spec), lw, x)[1](g)


@functools.partial(jax.jit, static_argnums=3)
@_highest
def _head_vjp(head, x, targets, spec):
    """loss, (d loss / d head weights, d loss / d x)."""
    return jax.value_and_grad(_head_loss, argnums=(0, 1))(
        head, x, targets, spec)


@jax.jit
def _embed_vjp(tok, ids, g):
    return jnp.zeros(tok.shape, F32).at[ids].add(g)


def _head_of(w):
    return {"lnf_g": w["lnf_g"], "head_w": w["head_w"]}


def hidden(w, ids, num_heads: int, keep: bool = False):
    """The last layer's output (S, d) for one sequence of 0-based ids,
    one jitted call a layer; with ``keep`` every layer's input too."""
    x = _embed_jit(w["tok"], ids)
    inputs = []
    for lw in w["layers"]:
        inputs.append(x if keep else None)
        x = _layer_jit(lw, x, num_heads, w.spec)
    return (x, inputs) if keep else x


def logits(w, ids, num_heads: int):
    """(S,) 0-based ids -> (S, P, V) float32 logits of one sequence
    (small sizes: the head is not blocked)."""
    x = hidden(w, ids, num_heads)
    with jax.default_matmul_precision("highest"):
        out = _rms(x, w["lnf_g"], w.spec.eps) @ w["head_w"].astype(F32).T
    return out.reshape(ids.shape[0], w.spec.pred_heads, -1)


def loss(w, ids, targets, num_heads: int) -> float:
    """Mean over the (B, S) batch's sequences of step 5's loss;
    ``targets`` are the 0-based next bytes."""
    return sum(float(_head_loss_jit(_head_of(w), hidden(w, ids[i], num_heads),
                                    targets[i], w.spec))
               for i in range(ids.shape[0])) / ids.shape[0]


def loss_and_grads(w, ids, targets, num_heads: int):
    """(loss, d loss / d w) of the whole model on (B, S). The chain rule
    by hand BETWEEN layers (each layer's gradient is ``jax.vjp`` of
    ``layer``, from its kept input and the gradient of its output) and
    each layer's gradients fetched to the host as they are made: at
    published widths the device holds the weights, the system's
    gradients and one layer's working set, not a third parameter-sized
    tree. The gradient tree's leaves are numpy arrays."""
    import numpy as np
    n = ids.shape[0]
    total, grads = 0.0, None
    for i in range(n):
        x, inputs = hidden(w, ids[i], num_heads, keep=True)
        value, (g_head, g) = _head_vjp(_head_of(w), x, targets[i], w.spec)
        del x
        total += float(value)
        g_layers = []
        for lw in reversed(w["layers"]):
            g_lw, g = _layer_vjp(lw, inputs.pop(), g, num_heads, w.spec)
            g_layers.append(jax.device_get(g_lw))
        one = dict(jax.device_get(g_head), layers=g_layers[::-1],
                   tok=jax.device_get(_embed_vjp(w["tok"], ids[i], g)))
        grads = one if grads is None else jax.tree.map(np.add, grads, one)
    return total / n, Weights(jax.tree.map(lambda a: a / n, grads), w.spec)

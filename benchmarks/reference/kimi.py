"""Kimi-VL-A3B-Instruct's language model
(huggingface.co/moonshotai/Kimi-VL-A3B-Instruct: the DeepSeek-V3 style
decoder Moonlight-16B-A3B) forward pass, loss and gradients in plain
``jax.numpy`` float32. No kernel, no cache, nothing imported from
bigdl_tpu: the mathematics written down once more, one sequence at a
time, for ONE chip's share of a layer (``Spec.experts_offset`` .. + the
experts held, and the rows of the vocabulary that ``tok`` / ``head_w``
hold).

One layer, on x in R^{S x d} (float32 throughout), H heads, a content
width DN, a rotary width DR, a value width DV, a latent of C, E routed
experts of which the ones numbered ``experts_offset .. experts_offset +
E_held - 1`` live here, K experts a token:

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g

1.  u = RMSNorm_1(x).  q = u Wq^T (H x (DN + DR)) = [qN | qR], no query
    latent (``q_lora_rank`` null), no bias.  u Wkva^T in R^{C + DR} =
    [c | r];  c^ = RMSNorm_C(c) (a weight of C);  c^ Wkvb^T
    (H x (DN + DV)) = [kN | v] per head;  kR = RoPE(r) — ONE rotary key
    a position for all heads — and qR = RoPE(qR) per head (theta from
    the config, positions 0..S-1, pairs (x[i], x[i + DR/2])).
2.  s[t, j, h] = (qN[t, h] . kN[j, h] + qR[t, h] . kR[j]) (DN + DR)^-1/2
    for j <= t;  o[t, h] = sum_j softmax_j(s[t, ., h]) v[j, h];
    att = concat_heads(o) Wo^T (d x H DV).
3.  h = x + att;  u2 = RMSNorm_2(h).  In the leading dense layer
    F(u2) = Wdown( silu(Wgate u2) * (Wup u2) ) of the dense width.  In
    an expert layer:  z = sigmoid(u2 Wr^T) over E;  the CHOICE is the K
    largest of z + b (lower index first among equals; b in R^E is no
    parameter: it enters the choice only and no gradient reaches it);
    g_e = route_scale * z_e / sum_{e' chosen} z_e' for e chosen;
        F(u2) = sum_{e chosen AND held here} g_e E_e(u2) + S(u2),
    E_e and the ONE shared expert S SwiGLUs as above (S of
    n_shared_experts x the expert width).  y = h + F(u2).  No capacity,
    no token dropped; what the experts that live elsewhere would add is
    left out; the weights are normalised over all K chosen, held or
    not; the shared expert is whole on every chip.
4.  After a training step b_e += rate * sign(mean_e'(count_e') -
    count_e), count_e the step's tokens whose choice holds e, over ALL
    E (``bias_update``).  No balance loss.
5.  After the last layer: logits = RMSNorm_f(y) Whead^T over the rows of
    the vocabulary held here; loss = mean_t CE(logits[t], id[t + 1]).
    Embedding V x d, not tied, no position table.

Departures and assumptions, shared with the system under test and listed
in the configuration's ``assumed`` (the catalog gives config.json, not
the released modelling code): text positions only, no vision tower; the
released code stores the rotary columns interleaved and permutes them
to the half-split pairing at run time — with weights from a seed the two
differ by a fixed permutation of Wq's and Wkva's rotary columns; the
update rate of b (0.001, DeepSeek-V3's) is not in the config; the
sequence-wise balance loss ``seq_aux`` names has no coefficient there
and is left out; no dropout.

It is computed in blocks so that it fits beside a model in training: the
attention a block of queries at a time, the feed-forward part and the
head a block of tokens at a time, one jitted program a layer, forward
and backward. On a TPU a float32 matmul runs in lower precision unless
asked otherwise: everything runs under
``default_matmul_precision("highest")``.

Token ids here are 0-based. Weights arrive as ``Weights``: a pytree of
named arrays whose static part carries the sizes no array shape shows.
``biases``, where a function takes it, is one (E,) array an expert layer
in layer order (None: zeros).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512         # queries attended at a time
TOKEN_BLOCK = 2048        # tokens a block of the feed-forward and the head


class Spec(NamedTuple):
    """What the arrays' shapes do not say."""
    qk_nope: int
    qk_rope: int
    experts_total: int
    experts_offset: int       # number of the first expert held here
    experts_per_token: int
    route_scale: float
    rope_theta: float
    eps: float


@jax.tree_util.register_pytree_with_keys_class
class Weights:
    """``arrays``: {"tok", "layers": [...], "lnf_g", "head_w"}; every
    layer has {"ln1_g", "q_w", "kva_w", "kvn_g", "kvb_w", "o_w",
    "ln2_g", "gate_w", "up_w", "down_w"}, an expert layer (its
    ``gate_w`` stacked (E_held, out, in)) also {"router_w", "sh_gate_w",
    "sh_up_w", "sh_down_w"}; matrices are (out, in). ``spec``: a
    ``Spec`` (static)."""

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
        * g.astype(F32)


def _rope(x, theta):
    """(S, H, D), positions 0..S-1, pairs (x[i], x[i + D/2])."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _in_blocks(fn, xs, block):
    """``fn`` over row blocks of the arrays ``xs`` (each (S, ...)), one
    block alive at a time and recomputed in a gradient."""
    s = jax.tree.leaves(xs)[0].shape[0]
    block = min(block, s)
    if s % block:
        return fn(xs)
    ys = jax.lax.map(jax.checkpoint(fn), jax.tree.map(
        lambda x: x.reshape(s // block, block, *x.shape[1:]), xs))
    return jax.tree.map(lambda y: y.reshape(s, *y.shape[2:]), ys)


def _swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w.astype(F32).T) * (x @ up_w.astype(F32).T)
            ) @ down_w.astype(F32).T


def latent_inputs(lw, u, num_heads: int, spec: Spec):
    """Step 1 on u (S, d): (qN (S, H, DN), qR (S, H, DR) rotated, kN
    (S, H, DN), kR (S, DR) rotated, v (S, H, DV))."""
    s = u.shape[0]
    dn, dr = spec.qk_nope, spec.qk_rope
    c_dim = lw["kvn_g"].shape[0]
    q = (u @ lw["q_w"].astype(F32).T).reshape(s, num_heads, dn + dr)
    cr = u @ lw["kva_w"].astype(F32).T
    kv = (_rms(cr[:, :c_dim], lw["kvn_g"], spec.eps)
          @ lw["kvb_w"].astype(F32).T).reshape(s, num_heads, -1)
    kr = _rope(cr[:, None, c_dim:], spec.rope_theta)[:, 0]
    return (q[..., :dn], _rope(q[..., dn:], spec.rope_theta), kv[..., :dn],
            kr, kv[..., dn:])


def _attention(lw, u, num_heads, spec):
    """Steps 1-2 on u (S, d): concat_heads(o) Wo^T."""
    s = u.shape[0]
    qn, qr, kn, kr, v = latent_inputs(lw, u, num_heads, spec)
    scale = (spec.qk_nope + spec.qk_rope) ** -0.5

    def block(xs):
        rows, qnb, qrb = xs
        sc = (jnp.einsum("thd,jhd->htj", qnb, kn)
              + jnp.einsum("thd,jd->htj", qrb, kr)) * scale
        seen = jnp.arange(s)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("htj,jhd->thd", p, v).reshape(rows.shape[0], -1)

    o = _in_blocks(block, (jnp.arange(s), qn, qr), QUERY_BLOCK)
    return o @ lw["o_w"].astype(F32).T


def route(lw, u, spec: Spec, bias=None):
    """Step 3's router on u (T, d): (numbers of the K experts chosen
    (T, K), their weights: the scores alone, normalised over the K and
    scaled). ``bias`` (E,) enters the choice only."""
    z = jax.nn.sigmoid(u @ lw["router_w"].astype(F32).T)
    by = z if bias is None else z + jax.lax.stop_gradient(bias.astype(F32))
    idx = jax.lax.top_k(by, spec.experts_per_token)[1]
    top = jnp.take_along_axis(z, idx, axis=-1)
    return idx, spec.route_scale * top / jnp.sum(top, axis=-1, keepdims=True)


def shared_expert(lw, u):
    """Step 3's S(u): the one shared SwiGLU, whole on every chip."""
    return _swiglu(u, lw["sh_gate_w"], lw["sh_up_w"], lw["sh_down_w"])


def routed_experts(lw, u, spec: Spec, bias=None):
    """Step 3's sum over the experts chosen AND held here, on a block of
    tokens: every held expert computed on every token and weighted (zero
    where the token did not choose it)."""
    idx, g = route(lw, u, spec, bias)
    out = jnp.zeros_like(u)
    for e in range(lw["gate_w"].shape[0]):
        weight = jnp.sum(jnp.where(idx == spec.experts_offset + e, g, 0.0),
                         axis=-1, keepdims=True)
        out = out + weight * _swiglu(u, lw["gate_w"][e], lw["up_w"][e],
                                     lw["down_w"][e])
    return out


def feed_forward(lw, u, spec: Spec, bias=None):
    """Step 3's F on a block of tokens: the dense SwiGLU, or the routed
    part plus the shared expert."""
    if "router_w" not in lw:
        return _swiglu(u, lw["gate_w"], lw["up_w"], lw["down_w"])
    return routed_experts(lw, u, spec, bias) + shared_expert(lw, u)


def bias_update(bias, chosen, rate: float):
    """Step 4: the bias after a step whose tokens chose ``chosen``
    (T, K) expert numbers, over all E = len(bias) experts."""
    counts = jnp.sum(chosen.reshape(-1)[:, None] == jnp.arange(
        bias.shape[0]), axis=0).astype(F32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def layer(lw, x, num_heads: int, spec: Spec, bias=None):
    """One decoder layer on (S, d) float32."""
    h = x + _attention(lw, _rms(x, lw["ln1_g"], spec.eps), num_heads, spec)
    return h + _in_blocks(
        lambda hb: feed_forward(lw, _rms(hb, lw["ln2_g"], spec.eps), spec,
                                bias), h, TOKEN_BLOCK)


def _head_loss(head, x, targets, spec):
    """Step 5 on the last layer's output (S, d); ``targets`` (S,) are the
    0-based next ids."""
    def block(args):
        xb, tb = args
        logits = _rms(xb, head["lnf_g"], spec.eps) \
            @ head["head_w"].astype(F32).T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]

    return jnp.mean(_in_blocks(block, (x, targets), TOKEN_BLOCK))


# one jitted program a layer, a head and an embedding: one layer's float32
# working set is all that is alive beside the weights
_embed_jit = jax.jit(lambda tok, ids: tok.astype(F32)[ids])
_layer_jit = jax.jit(_highest(layer), static_argnums=(2, 3))
_head_loss_jit = jax.jit(_highest(_head_loss), static_argnums=3)


@functools.partial(jax.jit, static_argnums=(3, 4))
@_highest
def _layer_vjp(lw, x, g, num_heads, spec, bias=None):
    """(d / d lw, d / d x) of one layer from d loss / d output."""
    return jax.vjp(lambda a, b: layer(a, b, num_heads, spec, bias), lw,
                   x)[1](g)


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


def _layer_biases(w, biases):
    """One bias (or None) a layer, in layer order, from one an EXPERT
    layer."""
    left = iter(biases or ())
    return [next(left, None) if "router_w" in lw else None
            for lw in w["layers"]]


def hidden(w, ids, num_heads: int, keep: bool = False, biases=None):
    """The last layer's output (S, d) for one sequence of 0-based ids,
    one jitted call a layer; with ``keep`` every layer's input too."""
    x = _embed_jit(w["tok"], ids)
    inputs = []
    for lw, bias in zip(w["layers"], _layer_biases(w, biases)):
        inputs.append(x if keep else None)
        x = _layer_jit(lw, x, num_heads, w.spec, bias)
    return (x, inputs) if keep else x


def logits(w, ids, num_heads: int, biases=None):
    """(S,) 0-based ids -> (S, V_held) float32 logits of one sequence
    (small sizes: the head is not blocked)."""
    x = hidden(w, ids, num_heads, biases=biases)
    with jax.default_matmul_precision("highest"):
        return _rms(x, w["lnf_g"], w.spec.eps) @ w["head_w"].astype(F32).T


def chosen(w, ids, num_heads: int, biases=None):
    """Each expert layer's choice (B S, K) on a (B, S) batch of 0-based
    ids: what step 4 counts."""
    per_layer = []
    for i in range(ids.shape[0]):
        _, inputs = hidden(w, ids[i], num_heads, keep=True, biases=biases)
        inputs.append(None)
        found = []
        for n, (lw, bias) in enumerate(zip(w["layers"],
                                           _layer_biases(w, biases))):
            if "router_w" not in lw:
                continue
            with jax.default_matmul_precision("highest"):
                x = inputs[n]
                h = x + _attention(lw, _rms(x, lw["ln1_g"], w.spec.eps),
                                   num_heads, w.spec)
                found.append(route(lw, _rms(h, lw["ln2_g"], w.spec.eps),
                                   w.spec, bias)[0])
        per_layer.append(found)
    return [jnp.concatenate(seqs, axis=0) for seqs in zip(*per_layer)]


def loss(w, ids, targets, num_heads: int, biases=None) -> float:
    """Mean over the (B, S) batch's sequences of step 5's loss;
    ``targets`` are the 0-based next ids."""
    return sum(float(_head_loss_jit(
        _head_of(w), hidden(w, ids[i], num_heads, biases=biases),
        targets[i], w.spec)) for i in range(ids.shape[0])) / ids.shape[0]


def loss_and_grads(w, ids, targets, num_heads: int, biases=None):
    """(step 5's loss, its gradient for every leaf) of the whole model
    on (B, S). The chain rule by hand BETWEEN layers (each layer's
    gradient is ``jax.vjp`` of ``layer``, from its kept input and the
    gradient of its output) and each layer's gradients fetched to the
    host as they are made: at published widths the device holds the
    weights, the system's gradients and one layer's working set, not a
    third parameter-sized tree. The gradient tree's leaves are numpy
    arrays."""
    import numpy as np
    n = ids.shape[0]
    total, grads = 0.0, None
    by_layer = _layer_biases(w, biases)
    for i in range(n):
        x, inputs = hidden(w, ids[i], num_heads, keep=True, biases=biases)
        value, (g_head, g) = _head_vjp(_head_of(w), x, targets[i], w.spec)
        del x
        total += float(value)
        g_layers = []
        for lw, bias in zip(reversed(w["layers"]), reversed(by_layer)):
            g_lw, g = _layer_vjp(lw, inputs.pop(), g, num_heads, w.spec,
                                 bias)
            g_layers.append(jax.device_get(g_lw))
        one = dict(jax.device_get(g_head), layers=g_layers[::-1],
                   tok=jax.device_get(_embed_vjp(w["tok"], ids[i], g)))
        grads = one if grads is None else jax.tree.map(np.add, grads, one)
    return total / n, Weights(jax.tree.map(lambda a: a / n, grads), w.spec)

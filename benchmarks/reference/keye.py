"""Keye-VL-2.0-30B-A3B's language model
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B; the block is Qwen3-MoE's,
the sparse attention DeepSeek-V3.2-Exp's DSA with its lightning indexer)
forward pass, losses and gradients in plain ``jax.numpy`` float32. No
kernel, no cache, nothing imported from bigdl_tpu: the mathematics
written down once more, one sequence at a time, for ONE chip's share of
a layer (``Spec.experts_offset`` .. + the experts held, and the rows of
the vocabulary that ``tok`` / ``head_w`` hold).

One layer, on x in R^{S x d} (float32 throughout), H query heads and G
key/value heads of D, J indexer heads of DI, E routed experts of which
the ones numbered ``experts_offset .. experts_offset + E_held - 1`` live
here, K experts a token, top-k keys a query:

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g

1.  u = RMSNorm_1(x); q = u Wq^T (H x D), k = u Wk^T, v = u Wv^T (G x D),
    no bias; q and k RMS-normed per head over D (weights qn, kn), then
    RoPE (theta from the config, positions 0..S-1, pairs (x[i],
    x[i + D/2])). Query head j reads key/value head j // (H / G).
2.  Indexer, on u' = stop_gradient(u):  qI = u' WqI^T (J x DI);
    kI = LayerNorm_DI(u' WkI^T) (ONE head; weight and bias); RoPE on
    both over all DI dimensions; w = u' Ww^T (J) * J^-1/2 * DI^-1/2;
        I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]).
3.  Selection. S_t = the top-k largest I[t, s] over s <= t (every s <= t
    while t < top-k), EXACT; among equal scores the lower s wins. No
    gradient passes through it.
4.  o[t, j] = sum_{s in S_t} softmax_{s in S_t}(q[t, j] . k[s] D^-1/2)
    v[s];  att = concat_heads(o) Wo^T.
5.  The indexer's loss (DSA's sparse training stage):
        L_I = mean_t KL( p_t || softmax_{s in S_t} I[t, s] ),
    p_t = the main attention's probabilities over S_t summed over the H
    heads and divided by H (so it sums to one), detached. L_I is summed
    over layers with weight 1 and trains ONLY WqI, WkI, the LayerNorm
    and Ww (u is detached in step 2, p_t here); the language-model loss
    trains everything else and sees S_t as a constant.
6.  h = x + att;  u2 = RMSNorm_2(h);  r = softmax(u2 Wr^T) over E;
    the K largest r (lower index first among equals), divided by their
    sum, are the weights c_e;
        moe = sum_{e in top-K AND held here} c_e Wdown_e( silu(Wgate_e
        u2) * (Wup_e u2) );   y = h + moe.
    No shared expert, no capacity, no token dropped; what the experts
    that live elsewhere would add is left out (a token none of whose K
    experts is held here gets zero from this layer). The weights are
    normalised over all K chosen, held or not.
7.  After the last layer: logits = RMSNorm_f(y) Whead^T over the rows of
    the vocabulary held here; loss = mean_t CE(logits[t], id[t + 1]).
    Embedding V x d, not tied, no position table.

``loss`` is step 7's. ``loss_and_grads`` returns that loss and, for every
leaf but the indexer's, its gradient; for the indexer's five leaves the
gradient of sum-over-layers L_I — what the system's one backward pass
gives, which injects L_I's gradient at the attention's output and adds
no term to the scalar it reports (``nn.SparseSelectAttention``).
``indexer_loss`` returns the L_I of each layer.

Departures and assumptions, shared with the system under test and listed
in the configuration's ``assumed`` (the catalog gives config.json, not
the released modelling code): text positions only, so M-RoPE's three
rows are equal and ``mrope_section`` reduces to plain RoPE; no vision
tower; q/k per-head RMS norm before RoPE and the float32 router softmax
with ``norm_topk_prob`` from the Qwen3-MoE block these sizes are; the
indexer's inputs, its key LayerNorm (eps as ``rms_norm_eps``), RoPE over
all DI dimensions and the weight scale from DeepSeek's released indexer,
without its Hadamard rotation and fp8 rounding (an orthogonal map of
both sides changes no score); ``q_chunk_size`` / ``kv_chunk_size`` are
tilings with no effect on the result; no load-balancing loss; no
dropout.

It is computed in blocks so that it fits beside a model in training: the
index scores, the selection, the attention and p_t a block of queries at
a time (and inside that a key/value head at a time), the experts and the
head a block of tokens at a time, one jitted program a layer, forward
and backward. On a TPU a float32 matmul runs in lower precision unless
asked otherwise: everything runs under
``default_matmul_precision("highest")``.

Token ids here are 0-based. Weights arrive as ``Weights``: a pytree of
named arrays whose static part carries the sizes no array shape shows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512         # queries scored, selected and attended at a time
TOKEN_BLOCK = 2048        # tokens a block of the experts and of the head

INDEXER_LEAVES = ("iq_w", "ik_w", "ik_ln_g", "ik_ln_b", "iw_w")


class Spec(NamedTuple):
    """What the arrays' shapes do not say."""
    kv_heads: int
    index_heads: int
    topk: int                 # keys a query keeps
    experts_total: int
    experts_offset: int       # number of the first expert held here
    experts_per_token: int
    rope_theta: float
    eps: float


@jax.tree_util.register_pytree_with_keys_class
class Weights:
    """``arrays``: {"tok", "layers": [{"ln1_g", "q_w", "k_w", "v_w",
    "o_w", "qn_g", "kn_g", "iq_w", "ik_w", "ik_ln_g", "ik_ln_b", "iw_w",
    "ln2_g", "router_w", "gate_w", "up_w", "down_w"}], "lnf_g",
    "head_w"}; matrices are (out, in), the experts' stacked (E_held, out,
    in). ``spec``: a ``Spec`` (static)."""

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


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.astype(F32) + b.astype(F32)


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
    block alive at a time and recomputed in a gradient. ``fn`` returns
    one array or a tuple of arrays, each with the block's rows first."""
    s = jax.tree.leaves(xs)[0].shape[0]
    block = min(block, s)
    if s % block:
        return fn(xs)
    ys = jax.lax.map(jax.checkpoint(fn), jax.tree.map(
        lambda x: x.reshape(s // block, block, *x.shape[1:]), xs))
    return jax.tree.map(lambda y: y.reshape(s, *y.shape[2:]), ys)


def indexer(lw, u, spec: Spec):
    """Step 2's (qI (S, J, DI), kI (S, DI), w (S, J) already scaled)."""
    s, j = u.shape[0], spec.index_heads
    di = lw["iq_w"].shape[0] // j
    qi = _rope((u @ lw["iq_w"].astype(F32).T).reshape(s, j, di),
               spec.rope_theta)
    ki = _rope(_layer_norm(u @ lw["ik_w"].astype(F32).T, lw["ik_ln_g"],
                           lw["ik_ln_b"], spec.eps)[:, None, :],
               spec.rope_theta)[:, 0]
    return qi, ki, (u @ lw["iw_w"].astype(F32).T) * (j ** -0.5 * di ** -0.5)


def index_scores(qi, ki, wi):
    """Step 2's I for a block of queries: qi (T, J, DI), ki (S, DI), wi
    (T, J) already scaled -> (T, S)."""
    return jnp.einsum("tj,tjs->ts", wi,
                      jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki)))


def select(scores, rows, topk):
    """Step 3 for a block: ``scores`` (T, S) of the queries at positions
    ``rows`` (T,) -> (T, S) bool, True on S_t. Exact: the top-k-th
    largest causal score by a full sort; of the scores equal to it, the
    first (lowest s) that still fit."""
    s = scores.shape[1]
    valid = jnp.arange(s)[None, :] <= rows[:, None]
    masked = jnp.where(valid, scores, -jnp.inf)
    kth = jnp.sort(masked, axis=-1)[:, s - min(topk, s)][:, None]
    above = masked > kth
    equal = (masked == kth) & valid
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, axis=-1) <= room))) & valid


def _attention(lw, u, num_heads, spec):
    """Steps 1-5 on u (S, d): (concat_heads(o) Wo^T, L_I, selection
    (S, S) bool when asked)."""
    s, dm = u.shape
    g = spec.kv_heads
    hd = lw["q_w"].shape[0] // num_heads
    per = num_heads // g

    def proj(x, name):
        return x @ lw[name].astype(F32).T

    q = _rope(_rms(proj(u, "q_w").reshape(s, num_heads, hd), lw["qn_g"],
                   spec.eps), spec.rope_theta)
    k = _rope(_rms(proj(u, "k_w").reshape(s, g, hd), lw["kn_g"], spec.eps),
              spec.rope_theta)
    v = proj(u, "v_w").reshape(s, g, hd)
    qi, ki, wi = indexer(lw, jax.lax.stop_gradient(u), spec)
    scale = hd ** -0.5

    def block(xs):
        rows, qb, qib, wib = xs
        scores = index_scores(qib, ki, wib)
        keep = select(jax.lax.stop_gradient(scores), rows, spec.topk)
        o, psum = [], 0.0
        for kv in range(g):
            sc = jnp.einsum("thd,sd->hts", qb[:, kv * per:(kv + 1) * per],
                            k[:, kv]) * scale
            p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
            o.append(jnp.einsum("hts,sd->thd", p, v[:, kv]))
            psum = psum + jnp.sum(p, axis=0)
        target = jax.lax.stop_gradient(psum / num_heads)
        logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(target > 0, target * (
            jnp.log(jnp.where(target > 0, target, 1.0))
            - jnp.where(keep, logq, 0.0)), 0.0), axis=-1)
        return jnp.concatenate(o, axis=1).reshape(rows.shape[0], -1), kl

    o, kl = _in_blocks(block, (jnp.arange(s), q, qi, wi), QUERY_BLOCK)
    return o @ lw["o_w"].astype(F32).T, jnp.mean(kl)


def selection(lw, u, spec: Spec, block: int = QUERY_BLOCK):
    """The (S, S) bool selection of one layer from its normed input u, a
    block of queries at a time (the tests and dev/keye_flips.py compare
    it with the system's)."""
    qi, ki, wi = indexer(lw, u, spec)
    return _in_blocks(
        lambda xs: select(index_scores(xs[1], ki, xs[2]), xs[0], spec.topk),
        (jnp.arange(u.shape[0]), qi, wi), block)


def route(lw, u, spec: Spec):
    """Step 6's router on u (T, d): (numbers of the K experts chosen
    (T, K), their weights, normalised over the K)."""
    r = jax.nn.softmax(u @ lw["router_w"].astype(F32).T, axis=-1)
    top, idx = jax.lax.top_k(r, spec.experts_per_token)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def _moe(lw, u, spec):
    """Step 6 on a block of tokens: the held experts' part, every held
    expert computed on every token and weighted (zero where the token
    did not choose it)."""
    idx, c = route(lw, u, spec)
    out = jnp.zeros_like(u)
    for e in range(lw["gate_w"].shape[0]):
        weight = jnp.sum(jnp.where(idx == spec.experts_offset + e, c, 0.0),
                         axis=-1, keepdims=True)
        gate = u @ lw["gate_w"][e].astype(F32).T
        up = u @ lw["up_w"][e].astype(F32).T
        out = out + weight * ((jax.nn.silu(gate) * up)
                              @ lw["down_w"][e].astype(F32).T)
    return out


def layer(lw, x, num_heads: int, spec: Spec):
    """One decoder layer on (S, d) float32: (y, L_I)."""
    att, l_i = _attention(lw, _rms(x, lw["ln1_g"], spec.eps), num_heads,
                          spec)
    h = x + att
    return h + _in_blocks(
        lambda hb: _moe(lw, _rms(hb, lw["ln2_g"], spec.eps), spec), h,
        TOKEN_BLOCK), l_i


def _head_loss(head, x, targets, spec):
    """Step 7 on the last layer's output (S, d); ``targets`` (S,) are the
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
def _layer_vjp(lw, x, g, num_heads, spec):
    """(d / d lw, d / d x) of one layer from d loss / d output, with L_I
    entering at weight 1: its gradient reaches the indexer's leaves
    alone, the language-model loss's every other."""
    return jax.vjp(lambda a, b: layer(a, b, num_heads, spec), lw, x)[1](
        (g, jnp.ones((), F32)))


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
    one jitted call a layer, and every layer's L_I; with ``keep`` every
    layer's input too."""
    x = _embed_jit(w["tok"], ids)
    inputs, l_i = [], []
    for lw in w["layers"]:
        inputs.append(x if keep else None)
        x, one = _layer_jit(lw, x, num_heads, w.spec)
        l_i.append(one)
    return (x, l_i, inputs) if keep else (x, l_i)


def logits(w, ids, num_heads: int):
    """(S,) 0-based ids -> (S, V_held) float32 logits of one sequence
    (small sizes: the head is not blocked)."""
    x, _ = hidden(w, ids, num_heads)
    with jax.default_matmul_precision("highest"):
        return _rms(x, w["lnf_g"], w.spec.eps) @ w["head_w"].astype(F32).T


def indexer_loss(w, ids, num_heads: int):
    """Each layer's L_I, the mean over the (B, S) batch's sequences."""
    per_seq = [hidden(w, ids[i], num_heads)[1] for i in range(ids.shape[0])]
    return [sum(float(seq[n]) for seq in per_seq) / len(per_seq)
            for n in range(len(w["layers"]))]


def loss(w, ids, targets, num_heads: int) -> float:
    """Mean over the (B, S) batch's sequences of step 7's loss;
    ``targets`` are the 0-based next ids."""
    return sum(float(_head_loss_jit(
        _head_of(w), hidden(w, ids[i], num_heads)[0], targets[i], w.spec))
        for i in range(ids.shape[0])) / ids.shape[0]


def loss_and_grads(w, ids, targets, num_heads: int):
    """(step 7's loss, gradients) of the whole model on (B, S): for the
    indexer's leaves the gradient of sum-over-layers L_I, for every
    other leaf the gradient of the loss (see the module docstring). The
    chain rule by hand BETWEEN layers (each layer's gradient is
    ``jax.vjp`` of ``layer``, from its kept input and the gradient of
    its output) and each layer's gradients fetched to the host as they
    are made: at published widths the device holds the weights, the
    system's gradients and one layer's working set, not a third
    parameter-sized tree. The gradient tree's leaves are numpy arrays."""
    import numpy as np
    n = ids.shape[0]
    total, grads = 0.0, None
    for i in range(n):
        x, _, inputs = hidden(w, ids[i], num_heads, keep=True)
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

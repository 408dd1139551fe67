"""Expert parallelism: mixture-of-experts with all_to_all dispatch.

The reference's closest ancestor is ``MixtureTable`` (nn/MixtureTable.scala
— dense gating over experts that all live everywhere). Expert parallelism
is the TPU-scale version: each mesh shard OWNS one expert's parameters,
tokens are routed top-k by a learned gate (k=1 Switch-style default,
k=2 GShard-style), hop to their experts' devices with one
``all_to_all``, run the expert, and hop back. Capacity-based dispatch
(fixed C slots per expert) keeps every shape static for XLA; overflow
ranks drop, fully-dropped tokens pass through unchanged (standard MoE
practice).

Functional and differentiable end-to-end: the gate receives gradients
through the combine weights, experts through their tokens.

Production wiring (ISSUE 11): :class:`MoE` is the layer a ``Sequential``
model drops in (built-in two-layer FFN experts, learned gate, the
load-balancing aux loss and the dispatch telemetry carried in module
STATE so they ride the train step without extra host syncs), and
``DistriOptimizer.set_expert_parallel()`` threads the aux loss into the
training objective and publishes the drop/overflow/imbalance counters to
the metric registry at epoch boundaries (one batched ``jax.device_get``
per epoch — never a per-step sync; see docs/PERFORMANCE.md).

One chip's SHARE of a layer with more experts than a chip holds
(PR 31): :class:`ExpertShare` is told which experts it holds, routes
over all of them, renormalises over the k chosen and computes its own
experts' part by dropless grouped matrix products — no capacity, no
all_to_all (on one chip it runs without its exchange). It shares the
router (``route_top_k``) with :class:`MoE`; docs/expert_share.md.

Combine-weight semantics after capacity drops: the k gate probabilities
renormalize over the KEPT ranks only. A dropped second choice used to
leave the first choice's weight at p1/(p1+p2) — every affected token's
output was silently scaled down by the dropped rank's share, biasing the
combine toward underweighted outputs (ISSUE 11 satellite; pinned in
tests/test_expert_parallel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.parallel.collective import shard_map
from bigdl_tpu.parallel.engine import get_mesh

__all__ = ["moe_apply", "MoE", "ExpertShare", "route_top_k",
           "grouped_matmul", "moe_aux_total", "moe_state_stats",
           "publish_moe_metrics"]

#: module-state keys the MoE layer maintains (floats — they survive the
#: gradient-accumulation scan's inexact-leaf averaging)
MOE_STATE_KEYS = ("moe_aux", "moe_dropped_rank_frac",
                  "moe_dropped_token_frac", "moe_overflow_tokens",
                  "moe_load_imbalance")


#: module-state keys ``ExpertShare`` maintains (floats, as above)
SHARE_STATE_KEYS = ("moe_held_load_max", "moe_held_load_mean",
                    "moe_local_assignment_share", "moe_tokens_without_local",
                    "moe_product_row_share", "moe_chunks_run")

#: and, where it balances its router by a selection bias: the bias
#: itself ((experts_total,), state the STEP updates, no gradient) and
#: two floats over ALL experts — the largest |bias|, and the busiest
#: expert's assignments over the mean one's (DeepSeek's MaxVio + 1)
BIAS_STATE_KEY = "moe_bias"
BALANCE_STATE_KEYS = ("moe_bias_abs_max", "moe_load_max_over_mean")


def route_top_k(x, gate_w, k: int, precision=None, *,
                scoring: str = "softmax", bias=None):
    """The router both layers share: float32 logits ``x @ gate_w``
    (``gate_w``: (d, E)), scored over all E experts by a float32
    ``"softmax"`` or an elementwise ``"sigmoid"``, the k largest scores
    of each token (lower expert number first among equals). With a
    ``bias`` (E,) the CHOICE is the k largest of score + bias and the
    weights are the scores alone (aux-loss-free balancing,
    arXiv:2408.15664): the bias steers the choice, no gradient reaches
    it. Returns (scores (T, E), top_p (T, k), top (T, k))."""
    f32 = jnp.float32
    logits = jnp.matmul(x.astype(f32), gate_w.astype(f32),
                        precision=precision)
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"route_top_k: scoring={scoring!r}")
    probs = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    if bias is None:
        top_p, top = jax.lax.top_k(probs, k)
    else:
        _, top = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(f32)), k)
        top_p = jnp.take_along_axis(probs, top, axis=-1)
    return probs, top_p, top


def moe_apply(expert_apply, stacked_expert_params, x, gate_w, *,
              capacity_factor: float = 1.25, axis: str = "model",
              mesh: Mesh | None = None, k: int = 1,
              renormalize: bool = True, with_stats: bool = False):
    """Top-k mixture of experts over mesh ``axis`` (one expert per shard).

    - ``expert_apply(expert_params, tokens) -> tokens``: one expert's pure
      function over (n, d) tokens.
    - ``stacked_expert_params``: leaves with leading dim E == axis size
      (expert e's params live on shard e).
    - ``x``: (tokens, d), sharded over ``axis`` (each shard's local
      tokens); ``gate_w``: (d, E) replicated.
    - ``k``: experts per token — 1 (Switch-style, the default) or 2+
      (GShard-style). Ranks claim capacity slots in order (every token's
      first choice before any second choice); a rank whose expert queue
      is full is dropped for that rank only. ``renormalize`` divides the
      gate probs of the ranks that were actually KEPT by their sum
      (post-drop renormalization — a dropped rank's share is
      redistributed to the surviving ranks instead of silently shrinking
      the output; ignored at k=1).

    Returns ``(y, aux_loss)`` — y shaped like x (tokens with EVERY rank
    dropped pass through unchanged); aux_loss is the standard
    load-balancing loss over first-choice assignments
    (E * sum_e fraction_e * prob_e). ``with_stats=True`` returns
    ``(y, aux_loss, stats)`` where ``stats`` holds the dispatch
    telemetry, reduced across shards: ``dropped_rank_frac`` (rank
    assignments lost to capacity), ``dropped_token_frac`` (tokens that
    lost EVERY rank and passed through), ``overflow_tokens`` (total
    demand beyond capacity), and ``load_imbalance`` (max over experts of
    first-choice fraction x E; 1.0 = perfectly balanced).
    """
    mesh = mesh or get_mesh()
    e = mesh.shape[axis]
    n_exp = jax.tree.leaves(stacked_expert_params)[0].shape[0]
    if n_exp != e:
        raise ValueError(f"{n_exp} experts != mesh axis '{axis}' size {e}")
    if x.shape[0] % e:
        raise ValueError(f"tokens {x.shape[0]} not divisible by {e} shards")
    if gate_w.shape[-1] != e:
        raise ValueError(f"gate has {gate_w.shape[-1]} outputs for {e} "
                         "experts")
    if not 1 <= k <= e:
        raise ValueError(f"k={k} must be in [1, {e}]")
    import math
    t_local = x.shape[0] // e
    # true ceil: fractional headroom must survive small tokens-per-expert
    cap = max(1, math.ceil(k * t_local * capacity_factor / e))

    def body(expert_params, xb, gw):
        # xb: (t_local, d) — this shard's tokens
        f32 = jnp.float32
        probs, top_p, top = route_top_k(xb, gw, k)            # (T, k)

        # rank-ordered capacity assignment: rank r's queue positions
        # start where ranks < r left each expert's occupancy
        occupied = jnp.zeros((e,), f32)
        ranks = []
        for r in range(k):
            onehot = jax.nn.one_hot(top[:, r], e, dtype=f32)  # (T, E)
            pos = ((jnp.cumsum(onehot, axis=0) - 1.0)
                   + occupied[None, :]) * onehot              # (T, E)
            in_cap = (pos < cap) & (onehot > 0)               # (T, E)
            kept = jnp.any(in_cap, axis=-1)                   # (T,)
            slot = jnp.where(in_cap, pos, 0.0) \
                .sum(axis=-1).astype(jnp.int32)
            occupied = occupied + jnp.sum(
                jnp.where(in_cap, 1.0, 0.0), axis=0)
            ranks.append((onehot, kept, slot))

        if renormalize and k > 1:
            # post-drop renormalization: only the ranks that actually
            # made it into capacity share the combine weight (ISSUE 11
            # satellite — dividing by the pre-drop sum left a dropped
            # second choice's share subtracted from the output)
            kept_w = jnp.stack([kept for _, kept, _ in ranks],
                               axis=1).astype(f32)            # (T, k)
            denom = jnp.sum(top_p * kept_w, axis=-1, keepdims=True)
            top_p = top_p / jnp.maximum(denom, 1e-9)

        # dispatch tensor (E, C, d): rank r of token t -> slot
        # (top[t, r], slot_r[t]); ranks target distinct slots so the
        # scatter-adds never collide
        disp = jnp.zeros((e, cap, xb.shape[1]), xb.dtype)
        for r, (_, kept, slot) in enumerate(ranks):
            disp = disp.at[top[:, r], slot].add(
                jnp.where(kept[:, None], xb, 0).astype(xb.dtype))

        # to experts: all_to_all over the expert dim — shard i receives
        # (E, C, d) where dim 0 is the SOURCE shard, all for expert i
        recv = jax.lax.all_to_all(disp, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        yexp = expert_apply(
            jax.tree.map(lambda l: l[0], expert_params),
            recv.reshape(e * cap, xb.shape[1]))
        # back to sources (inverse all_to_all)
        back = jax.lax.all_to_all(yexp.reshape(e, cap, xb.shape[1]),
                                  axis, split_axis=0, concat_axis=0,
                                  tiled=True)

        # combine: sum each kept rank's expert output weighted by its
        # gate prob; tokens with every rank dropped pass through
        y = jnp.zeros(xb.shape, f32)
        kept_any = jnp.zeros((xb.shape[0],), bool)
        for r, (_, kept, slot) in enumerate(ranks):
            gathered = back[top[:, r], slot]                  # (T, d)
            y = y + jnp.where(kept[:, None],
                              gathered.astype(f32)
                              * top_p[:, r][:, None], 0.0)
            kept_any = kept_any | kept
        y = jnp.where(kept_any[:, None], y, xb.astype(f32)) \
            .astype(xb.dtype)

        # load-balancing loss (Shazeer-style, over first choices):
        # E * sum_e f_e * p_e
        frac = jnp.mean(ranks[0][0], axis=0)
        mean_p = jnp.mean(probs, axis=0)
        aux = jnp.sum(frac * mean_p) * e
        aux = jax.lax.pmean(aux, axis)

        # dispatch telemetry, reduced across shards (stop_gradient —
        # observational, never part of the objective)
        kept_total = sum(jnp.sum(kept.astype(f32))
                         for _, kept, _ in ranks)
        demand = sum(jnp.sum(oh, axis=0) for oh, _, _ in ranks)  # (E,)
        demand = jax.lax.psum(demand, axis)
        n_tok = jax.lax.psum(jnp.asarray(float(t_local), f32), axis)
        stats = {
            "dropped_rank_frac":
                1.0 - jax.lax.psum(kept_total, axis) / (n_tok * k),
            "dropped_token_frac":
                jax.lax.psum(jnp.sum(1.0 - kept_any.astype(f32)),
                             axis) / n_tok,
            "overflow_tokens":
                jnp.sum(jnp.maximum(demand - cap * e, 0.0)),
            "load_imbalance":
                jnp.max(jax.lax.pmean(frac, axis)) * e,
        }
        stats = jax.tree.map(jax.lax.stop_gradient, stats)
        return y, aux, stats

    pspec = jax.tree.map(lambda _: P(axis), stacked_expert_params)
    y, aux, stats = shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P(axis), P()),
        out_specs=(P(axis), P(), {k_: P() for k_ in
                                  ("dropped_rank_frac",
                                   "dropped_token_frac",
                                   "overflow_tokens",
                                   "load_imbalance")}),
        check_rep=False)(stacked_expert_params, x, gate_w)
    if with_stats:
        return y, aux, stats
    return y, aux


from bigdl_tpu.nn.module import Module as _Module  # noqa: E402


class MoE(_Module):
    """Mixture-of-experts layer for ``Sequential`` models: built-in
    two-layer tanh FFN experts (``d -> hidden -> d``), a learned gate,
    top-k expert-parallel dispatch over the given mesh axis.

    The load-balancing aux loss and the dispatch telemetry ride the
    module STATE (``moe_aux`` etc.) — ``set_expert_parallel()`` on the
    optimizer adds the aux term to the training objective and publishes
    the telemetry to the metric registry at epoch boundaries. The state
    leaves are floats, so the gradient-accumulation scan's
    inexact-leaf averaging applies to them like any batch statistic.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int, *,
                 k: int = 1, capacity_factor: float = 1.25,
                 axis: str = "expert", renormalize: bool = True,
                 mesh: Mesh | None = None):
        super().__init__()
        self.d_model = int(d_model)
        self.d_hidden = int(d_hidden)
        self.num_experts = int(num_experts)
        self.k = int(k)
        self.capacity_factor = float(capacity_factor)
        self.axis = axis
        self.renormalize = bool(renormalize)
        self._mesh = mesh

    def init(self, rng):
        import numpy as np
        kg, k1, k2 = jax.random.split(rng, 3)
        e, d, h = self.num_experts, self.d_model, self.d_hidden
        return {
            "gate": (jax.random.normal(kg, (d, e), jnp.float32)
                     / np.sqrt(d)),
            "experts": {
                "w1": (jax.random.normal(k1, (e, d, h), jnp.float32)
                       / np.sqrt(d)),
                "b1": jnp.zeros((e, h), jnp.float32),
                "w2": (jax.random.normal(k2, (e, h, d), jnp.float32)
                       / np.sqrt(h)),
                "b2": jnp.zeros((e, d), jnp.float32),
            },
        }

    def init_state(self):
        return {key: jnp.zeros((), jnp.float32)
                for key in MOE_STATE_KEYS}

    @staticmethod
    def _expert_apply(p, tokens):
        h = jnp.tanh(tokens @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    def apply(self, params, state, x, *, training=False, rng=None):
        d = x.shape[-1]
        if d != self.d_model:
            raise ValueError(f"MoE built for d_model={self.d_model}, "
                             f"got feature dim {d}")
        tokens = x.reshape(-1, d)
        y, aux, stats = moe_apply(
            self._expert_apply, params["experts"], tokens,
            params["gate"], k=self.k,
            capacity_factor=self.capacity_factor, axis=self.axis,
            mesh=self._mesh or get_mesh(),
            renormalize=self.renormalize, with_stats=True)
        new_state = {"moe_aux": aux}
        for key in MOE_STATE_KEYS:
            short = key[len("moe_"):]
            if short in stats:
                new_state[key] = stats[short].astype(jnp.float32)
        return y.reshape(x.shape), new_state

    def __repr__(self):
        return (f"MoE(d{self.d_model}x{self.d_hidden}, "
                f"E={self.num_experts}, k={self.k}, "
                f"cf={self.capacity_factor}, axis={self.axis!r})")


def grouped_matmul(x, w, group_sizes, *, interpret: bool = False):
    """Rows of ``x`` (M, k), sorted into consecutive groups of
    ``group_sizes`` (E + 1,) rows, each group times its own matrix:
    group e < E by ``w[e]`` ((E, n, k): out by in, as every matrix
    here), and the LAST group — rows no matrix here is for — gives
    zeros. (M, n) in x's dtype, float32 accumulation; differentiable in
    x and w. Work follows the rows that have a matrix, not M: on the TPU
    ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (its grid is the
    row tiles in use, 512 rows each: smaller tiles were timed and lose,
    PERF.md section 6, PR 31, where ``lax.ragged_dot`` is timed beside
    it), elsewhere ``lax.ragged_dot``."""
    m, k = x.shape
    n = w.shape[1]
    if jax.default_backend() == "tpu" or interpret:
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        tm = next((t for t in (512, 256, 128, 64, 32, 16, 8)
                   if m % t == 0), m)

        def tile(size, most):
            return next((t for t in range(most, 127, -128)
                         if size % t == 0), size)

        return gmm(x, w, group_sizes, x.dtype,
                   (tm, tile(k, 1024), tile(n, 1024)), None, None, True,
                   interpret)
    return jax.lax.ragged_dot(x, w.swapaxes(1, 2), group_sizes[:-1],
                              preferred_element_type=x.dtype)


def _sum_by_token(rows, at, weights=None):
    """(T, d) float32: token t's sum over its k assignments of
    ``rows[at[t, j]]`` (times ``weights[t, j]``), where ``at`` (T, k) is
    ``len(rows)`` for an assignment that has no row there: it adds zero.
    One gather of T rows an assignment, so nothing of T k rows is ever
    made."""
    total = 0.0
    for j in range(at.shape[1]):
        part = jnp.take(rows, at[:, j], axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
        total = total + (part if weights is None
                         else weights[:, j, None] * part)
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_by_expert(x, idx, at, k):
    """Token rows (T, d) -> the assignment rows (R, d) that ``idx`` (R,)
    names (assignment a is token ``a // k``). ``at`` (T, k) says where
    among the R each assignment went, R for "not among them": the
    backward is gathers by it summed over a token's k, never a
    scatter."""
    return jnp.take(x, idx // k, axis=0)


_rows_by_expert.defvjp(
    lambda x, idx, at, k: (_rows_by_expert(x, idx, at, k), at),
    lambda k, at, g: (_sum_by_token(g, at).astype(g.dtype), None, None))


@jax.custom_vjp
def _combine(out, cw, idx, at):
    """y[t] = sum over t's k assignments of ``cw[t, j] out[at[t, j]]``
    ((T, d) float32; an assignment that is not among ``out``'s R rows
    adds zero). The backward works on the R rows alone:
    ``d out[r] = cw[idx[r]] dy[idx[r] // k]`` and ``d cw`` from the R
    dot products ``dy[idx[r] // k] . out[r]``."""
    return _sum_by_token(out, at, cw)


def _combine_bwd(res, dy):
    out, cw, idx, at = res
    dy_r = jnp.take(dy, idx // cw.shape[1], axis=0)
    cw_r = jnp.take(cw.reshape(-1), idx)
    d_cw_r = jnp.sum(dy_r * out, axis=-1)
    return ((cw_r[:, None] * dy_r).astype(out.dtype),
            jnp.take(d_cw_r, at, mode="fill", fill_value=0).astype(cw.dtype),
            None, None)


_combine.defvjp(lambda out, cw, idx, at: (_combine(out, cw, idx, at),
                                          (out, cw, idx, at)), _combine_bwd)


def _chunk_rows(assignments: int, held: int, total: int,
                self_balancing: bool) -> int:
    """The FIRST span of the sorted assignment rows, the one every step
    works on: TWICE what lands here when the router is balanced
    (``assignments held / total``) where the router holds itself
    balanced (``self_balancing``: a selection bias that every step
    updates), FOUR times where nothing does, as the nearest whole
    division of ``assignments``. The rows past it are ONE second span,
    worked on only when the live rows pass the first (``_in_chunks``): at
    most two spans, so two bodies in the compiled step (a ``lax.cond``,
    eleven grouped-product kernels a body, no loop) whatever the first
    one's size — equal chunks of twice the share made four bodies, a
    quarter more executable and a warm start 15% longer (PERF.md section
    6, PR 34). With every expert held, or where the multiple of the share
    covers all rows, the first span is all of them.

    Twice, because the grouped products' work follows the rows the held
    experts were sent, but the gather of a span's rows, the pass between
    the products and the combine's backward are the span's size whatever
    it holds: at a balanced router the live rows are 1.0 x the share
    (PERF.md section 6, PRs 34 and 36: the layer runs a third faster
    than at four times). Not less, because a spill costs the WHOLE second
    span and what every span pays whatever its size (k gathers of T rows
    out and back): more than the first span itself.

    Four times where nothing holds the router balanced, because then the
    step's TIME would follow the router's state. An untrained router
    sends every token to the same k experts (PERF.md section 6, PR 31):
    the share that lands here is j / k with j of them held, and twice
    the share is passed at j > 2 of 8, 6.1% of the draws at 16 of 128
    when the k are drawn without favour. The keye cell's router passed
    it in 4-16% of layer-steps by seed, a step with 0 | 1 | 2 spills took
    985 | 1017 | 1050 ms and the cell's runs spread 4% where they had
    spread 0.9% (PERF.md section 6, PR 36); four times the share is
    passed at j > 4, 7 draws in 10000, and never was in that cell. (A
    sigmoid router orders the experts as a softmax does and collapses
    alike, at 8 of 64 and k = 6 past twice the share at j > 1, 15.9%:
    what keeps it from that is the bias, which is why the bias and not
    the scoring decides.)"""
    most = max(1, total // ((2 if self_balancing else 4) * held))
    return assignments // max(n for n in range(1, most + 1)
                              if assignments % n == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _in_chunks(chunk, first, weights, tokens, cw, where, live):
    """``chunk(weights, tokens, cw, where, lo=0, rows=first)`` always,
    plus the ONE span of all the rows past ``first`` when ``live >
    first`` (a ``lax.cond``). It keeps its arguments and nothing else:
    the backward pass recomputes each span that ran and adds its
    gradients into ONE set of accumulators handed through the ``cond``,
    where autodiff of the ``cond`` would return a parameter-sized set of
    zeros from a span that did not run."""
    rest = where[0].shape[0] - first
    y = chunk(weights, tokens, cw, where, lo=0, rows=first)
    if rest:
        y = jax.lax.cond(
            live > first,
            lambda y: y + chunk(weights, tokens, cw, where, lo=first,
                                rows=rest),
            lambda y: y, y)
    return y


def _in_chunks_bwd(chunk, first, res, dy):
    weights, tokens, cw, where, live = res
    rest = where[0].shape[0] - first

    def grads(lo, rows):
        # the span's forward made again, and its backward: the scopes
        # are how a trace tells recomputation done by hand from the
        # backward products. (A custom_vjp's backward rule is named after
        # where its FORWARD was called, so it carries both.)
        with jax.named_scope("recompute"):
            _, pull = jax.vjp(lambda *a: chunk(*a, where, lo=lo, rows=rows),
                              weights, tokens, cw)
        with jax.named_scope("pullback"):
            return pull(dy)

    acc = grads(0, first)
    if rest:
        acc = jax.lax.cond(
            live > first,
            lambda acc: jax.tree.map(jnp.add, acc, grads(first, rest)),
            lambda acc: acc, acc)
    return (*acc, None, None)


_in_chunks.defvjp(
    lambda chunk, first, *args: (_in_chunks(chunk, first, *args), args),
    _in_chunks_bwd)


class ExpertShare(_Module):
    """One chip's share of a routed mixture-of-experts layer: it is TOLD
    which experts it holds — ``experts_held`` of ``experts_total``, from
    number ``experts_offset`` — routes every token over all
    ``experts_total`` (float32 logits and softmax, the ``top_k`` largest,
    divided by their sum over all ``top_k``, held here or not), and
    computes the part of the result its own experts give:

        y[t] = sum over e in top_k(t) AND held here of
               c[t, e] W_down,e( silu(W_gate,e x[t]) * (W_up,e x[t]) ).

    A token none of whose experts live here gets zero. DROPLESS: there
    is no capacity. The T k assignments are sorted by expert, those for
    experts elsewhere last, and the sorted rows are worked on as at most
    TWO spans (``_chunk_rows``: the first is twice the balanced share
    where a ``bias_update_rate`` holds the router balanced and four
    times where nothing does, the second all the rest), each through
    grouped matrix products over ragged groups (``grouped_matmul``),
    forward and backward. The first
    span always runs; the second runs only when the assignments that
    land here pass the first (``_in_chunks``: one ``lax.cond``, each
    span that ran recomputed in the backward pass), so one expert may
    take every token and the step is sized for the routing it meets, not
    for the worst. A span's rows for experts elsewhere are the products'
    LAST group, the one no matrix here is for: they cost no product work
    and come out zeros (their combine weight is zero too), so the
    products' work is the rows the held experts were sent, forward and
    backward; the gather of a span's rows, the pass between the products
    and the combine are still a span's size. With ``experts_held ==
    experts_total`` it is the whole layer in one span. On one chip it
    runs without an exchange and nothing here stands in for the absent
    chips: summed over the shares of a layer, the results are the uncut
    layer's (tests/test_keye.py).

    It shares ``route_top_k`` with ``MoE``. The combine is its own:
    ``MoE`` reads each rank's output back from a capacity slot and
    renormalises over the ranks that were KEPT; here nothing is dropped,
    the weights are normalised before anything is placed, and a token's
    k rows come back by one gather. No load-balancing term: ``moe_aux``
    is not in its state, and ``moe_aux_total`` skips it.

    Run-time routing telemetry rides the module STATE as ``MoE``'s does
    (read out with the losses, never a sync of its own):
    ``moe_held_load_max`` / ``moe_held_load_mean`` (assignments of the
    busiest held expert, and of the mean one),
    ``moe_local_assignment_share`` (assignments landing here over all
    T k), ``moe_tokens_without_local`` (share of tokens that get
    zero), ``moe_chunks_run`` (spans of sorted rows the step worked
    on: 1, or 2 when the second ran) and ``moe_product_row_share`` (rows
    in the held experts' groups over the rows of those spans: the part
    of them the grouped products multiply).

    The router's variants (``route_top_k``): ``scoring`` ``"softmax"``
    or ``"sigmoid"``; ``route_scale`` multiplies the normalised weights
    (DeepSeek-V3's ``routed_scaling_factor``); with a
    ``bias_update_rate`` the choice is made by score PLUS a bias that is
    module STATE (``moe_bias``, zeros at first): it enters the choice
    only, no gradient reaches it, and a training step hands on
    ``bias + rate * sign(mean(count) - count)``, ``count`` the step's
    assignments to each of ALL ``experts_total`` experts (the whole
    router runs here, so every chip of a layer computes the same update.
    Under the jit / GSPMD step a ``data`` mesh axis splits the tokens
    and the count is over all of them, so replicas hold one bias; a
    step mapped per shard would count its shard alone, and nothing here
    reconciles that yet: ROADMAP B4). ``moe_bias_abs_max`` and
    ``moe_load_max_over_mean`` ride the state beside it.
    ``shared_width`` > 0 adds a SHARED expert: one
    ``GatedFFN`` of that width (scope ``moe_shared``) that every token
    passes and every chip of a layer computes whole — summed over a
    layer's shares it must be counted once."""

    def __init__(self, d_model: int, d_ff: int, experts_total: int,
                 top_k: int, *, experts_held: int | None = None,
                 experts_offset: int = 0, scoring: str = "softmax",
                 route_scale: float = 1.0,
                 bias_update_rate: float | None = None,
                 shared_width: int = 0):
        super().__init__()
        held = experts_total if experts_held is None else experts_held
        if not 0 <= experts_offset <= experts_total - held:
            raise ValueError(
                f"experts {experts_offset}..{experts_offset + held - 1} "
                f"are not among {experts_total}")
        if not 1 <= top_k <= experts_total:
            raise ValueError(f"top_k={top_k} of {experts_total} experts")
        self.d_model, self.d_ff = int(d_model), int(d_ff)
        self.experts_total, self.experts_held = int(experts_total), held
        self.experts_offset, self.top_k = int(experts_offset), int(top_k)
        self.scoring, self.route_scale = scoring, float(route_scale)
        self.bias_update_rate = bias_update_rate
        self.shared = None
        if shared_width:
            from bigdl_tpu.nn.linear import GatedFFN
            self.shared = GatedFFN(self.d_model, int(shared_width))

    def init(self, rng):
        from bigdl_tpu.nn import init as init_mod
        e, d, f = self.experts_held, self.d_model, self.d_ff
        kr, *ks = jax.random.split(rng, 4)
        shapes = {"gate_weight": (e, f, d), "up_weight": (e, f, d),
                  "down_weight": (e, d, f)}
        p = {name: init_mod.init_weight(init_mod.Default, key, shape,
                                        fan_in=shape[2], fan_out=shape[1])
             for (name, shape), key in zip(shapes.items(), ks)}
        p["router_weight"] = init_mod.init_weight(
            init_mod.Xavier, kr, (self.experts_total, d), fan_in=d,
            fan_out=self.experts_total)
        if self.shared is not None:
            p["shared"] = self.shared.init(jax.random.fold_in(rng, 1))
        return p

    def init_state(self):
        state = {key: jnp.zeros((), jnp.float32) for key in SHARE_STATE_KEYS}
        if self.bias_update_rate is not None:
            state.update({key: jnp.zeros((), jnp.float32)
                          for key in BALANCE_STATE_KEYS})
            state[BIAS_STATE_KEY] = jnp.zeros((self.experts_total,),
                                              jnp.float32)
        return state

    def route(self, params, tokens, bias=None):
        """(numbers of each token's ``top_k`` experts (T, k), their
        weights normalised over the k and scaled)."""
        _, top_p, top = route_top_k(tokens, params["router_weight"].T,
                                    self.top_k, precision="highest",
                                    scoring=self.scoring, bias=bias)
        c = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        return top, c if self.route_scale == 1.0 else c * self.route_scale

    def _balance(self, bias, top, training):
        """The state a step hands on where the router has a bias: the
        bias after this step's update (unchanged outside training) and
        the two balance figures, from the assignments to ALL experts."""
        counts = jnp.sum(
            top.reshape(-1)[:, None] == jnp.arange(self.experts_total),
            axis=0, dtype=jnp.float32)
        mean = jnp.mean(counts)
        if training:
            bias = bias + self.bias_update_rate * jnp.sign(mean - counts)
        return {BIAS_STATE_KEY: bias,
                "moe_bias_abs_max": jnp.max(jnp.abs(bias)),
                "moe_load_max_over_mean": jnp.max(counts) / mean}

    def _chunk(self, weights, tokens, cw, where, *, lo, rows):
        """What sorted assignment rows ``lo .. lo + rows - 1`` add to the
        result: (T, d) float32. ``weights``: the experts' three stacks in
        the compute dtype; ``cw`` (T, k): the combine weights, zero for
        an expert elsewhere; ``where``: the sorted order of the T k
        assignments, its inverse, and (held + 1,) the sorted row at
        which each held expert's group starts and the last one ends."""
        order, inverse, starts = where
        idx = jax.lax.slice_in_dim(order, lo, lo + rows)
        at = jnp.where((inverse >= lo) & (inverse < lo + rows),
                       inverse - lo, rows).reshape(cw.shape)
        # ``held`` groups for the held experts' rows in this chunk and the
        # last for the rest of it, rows for experts elsewhere: they cost
        # the products no work and come out zeros
        sizes = jnp.diff(jnp.clip(starts, lo, lo + rows), append=lo + rows)
        x = _rows_by_expert(tokens, idx, at, self.top_k)
        gate, up, down = (functools.partial(grouped_matmul, w=w,
                                            group_sizes=sizes)
                          for w in weights)
        return _combine(down(jax.nn.silu(gate(x)) * up(x)), cw, idx, at)

    def apply(self, params, state, x, *, training=False, rng=None):
        from bigdl_tpu.observability import trace
        from bigdl_tpu.tensor import activation_dtype, compute_dtype
        d, k, held = x.shape[-1], self.top_k, self.experts_held
        if d != self.d_model:
            raise ValueError(f"ExpertShare built for d_model="
                             f"{self.d_model}, got feature dim {d}")
        tokens = x.reshape(-1, d)
        t = tokens.shape[0]
        first = _chunk_rows(t * k, held, self.experts_total,
                            self.bias_update_rate is not None)
        # python runs this when the layer is traced for a compile, never
        # in a step
        trace.instant("moe_share", cat="nn", experts_total=self.experts_total,
                      experts_held=held, top_k=k, tokens=t,
                      expected_local_assignments=t * k * held
                      / self.experts_total, chunk_rows=first,
                      chunks=1 + (first < t * k), rest_rows=t * k - first,
                      scoring=self.scoring,
                      shared_width=self.shared.d_ff if self.shared else 0,
                      bias_update_rate=self.bias_update_rate or 0.0)
        bias = state.get(BIAS_STATE_KEY)
        with jax.named_scope("moe_router"):
            top, c = self.route(params, tokens, bias)
            local = top - self.experts_offset
            here = (local >= 0) & (local < held)
            # assignments by expert held, those for elsewhere last
            group = jnp.where(here, local, held).reshape(-1)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            counts = jnp.sum(group[:, None] == jnp.arange(held), axis=0,
                             dtype=jnp.int32)
            starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                      jnp.cumsum(counts)])
        with jax.named_scope("moe_experts"):
            cd = compute_dtype()
            # the experts' matrices in the compute dtype, made ONCE
            y = _in_chunks(
                self._chunk, first,
                tuple(params[name].astype(cd) for name in
                      ("gate_weight", "up_weight", "down_weight")),
                tokens.astype(cd), jnp.where(here, c, 0.0),
                (order, inverse, starts), starts[held])
        if self.shared is not None:
            with jax.named_scope("moe_shared"):
                y = y + self.shared.apply(params["shared"], {}, tokens)[0] \
                    .astype(jnp.float32)
        f32 = jnp.float32
        loads = counts.astype(f32)
        # the first span always runs, the rest when live > first
        live = starts[held]
        spilled = live > first
        stats = {
            "moe_held_load_max": jnp.max(loads),
            "moe_held_load_mean": jnp.mean(loads),
            "moe_local_assignment_share": jnp.sum(loads) / (t * k),
            "moe_tokens_without_local":
                1.0 - jnp.mean(jnp.any(here, axis=-1), dtype=f32),
            "moe_product_row_share":
                live / jnp.where(spilled, t * k, first),
            "moe_chunks_run": 1.0 + spilled.astype(f32),
        }
        if bias is not None:
            with jax.named_scope("moe_router"):
                stats.update(self._balance(bias, top, training))
        return (y.reshape(x.shape).astype(activation_dtype()),
                jax.tree.map(jax.lax.stop_gradient, stats))

    def __repr__(self):
        return (f"ExpertShare(d{self.d_model}x{self.d_ff}, experts "
                f"{self.experts_offset}..+{self.experts_held} of "
                f"{self.experts_total}, k={self.top_k})")


def moe_aux_total(mstate):
    """Sum of every MoE layer's load-balancing aux loss in a module
    state tree (traced — this is the term ``set_expert_parallel`` folds
    into the training objective; gradients flow to the gates through
    it). Zero when the model carries no MoE layers."""
    total = jnp.zeros((), jnp.float32)

    def walk(tree):
        nonlocal total
        if isinstance(tree, dict):
            if "moe_aux" in tree:
                total = total + tree["moe_aux"]
                return
            for sub in tree.values():
                walk(sub)

    walk(mstate)
    return total


def moe_state_stats(mstate) -> dict:
    """Walk a module-state tree for MoE layer states (``MoE``'s and
    ``ExpertShare``'s alike) and return ``{path: {stat: device array}}``
    — one ``jax.device_get`` away from host values (the caller batches
    the readback)."""
    found = {}

    def walk(tree, path):
        if isinstance(tree, dict):
            keys = [key for key in MOE_STATE_KEYS + SHARE_STATE_KEYS
                    + BALANCE_STATE_KEYS if key in tree]
            if keys:
                found["/".join(path) or "moe"] = {
                    key: tree[key] for key in keys}
                return
            for key, sub in tree.items():
                walk(sub, path + [str(key)])

    walk(mstate, [])
    return found


def publish_moe_metrics(mstate, registry=None) -> dict:
    """Publish every MoE layer's dispatch telemetry from a module-state
    tree to the metric registry (gauges labeled by layer path; the
    ``moe_dropped_tokens_total``-style exposition names
    docs/OBSERVABILITY.md documents). ONE batched ``jax.device_get`` for
    all layers — call at epoch boundaries or drain points, never
    per step. Returns ``{layer: {stat: float}}``."""
    if registry is None:
        from bigdl_tpu.observability.registry import default_registry
        registry = default_registry()
    staged = moe_state_stats(mstate)
    if not staged:
        return {}
    host = jax.device_get(staged)
    for layer, stats in host.items():
        for key, val in stats.items():
            registry.gauge(
                key, "MoE dispatch telemetry (parallel/expert.py)",
                labelnames=("layer",)).set(float(val), layer=layer)
    return {layer: {key: float(val) for key, val in stats.items()}
            for layer, stats in host.items()}

"""Keye-VL-2.0-30B-A3B's language model on the training path (ISSUE 31):
``nn.SparseSelectAttention`` (learned top-k sparse attention, its
indexer and the indexer's loss) and its Pallas kernels,
``parallel.expert.ExpertShare`` (one chip's share of a routed
mixture-of-experts layer, dropless) and ``KeyeLM``.

The model tests compare the program with the plain float32 reference
(benchmarks/reference/keye.py) on the logits, the loss, the selection
and EVERY gradient leaf — the indexer's under L_I — at 48 tokens with
top-12 keys and 4 of 8 experts held, with every norm weight and bias
perturbed so that it matters.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.builders import keye as builder
from benchmarks.reference import keye as reference
from bigdl_tpu.nn import attention as attention_mod
from bigdl_tpu.parallel import expert as expert_mod
from bigdl_tpu.parallel.expert import ExpertShare
from bigdl_tpu.tensor import DTypePolicy, policy_scope

CFG = dict(vocab_size=50, hidden_size=32, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, num_hidden_layers=2,
           moe_intermediate_size=16, published={"num_experts": 8},
           num_experts_per_tok=2, num_experts=4, num_local_experts=4,
           experts_offset=2, rope_theta=1e4, rms_norm_eps=1e-6,
           sa_config=dict(indexer_num_heads=2, indexer_head_dim=8,
                          topk=12))
HEADS = CFG["num_attention_heads"]
SEQ = 48
TOL = 2e-5          # float32 on both sides, another order of summation
LEAVES = ("ln1_g", "q_w", "k_w", "v_w", "o_w", "qn_g", "kn_g", "iq_w",
          "ik_w", "ik_ln_g", "ik_ln_b", "iw_w", "ln2_g", "router_w",
          "gate_w", "up_w", "down_w")


@pytest.fixture(autouse=True, scope="module")
def _float32_policy():
    """float32 on both sides, at full matmul precision, whatever policy
    an earlier file of this worker left set."""
    f32 = jnp.dtype("float32")
    with policy_scope(DTypePolicy(param_dtype=f32, compute_dtype=f32,
                                  activation_dtype=f32)), \
            jax.default_matmul_precision("highest"):
        yield


def _batch(seq=SEQ, rows=2, seed=0):
    toks = np.random.default_rng(seed).integers(
        1, CFG["vocab_size"] + 1, size=(rows, seq + 1))
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _perturbed(params, seed=1):
    """Every leaf moved off its initial value: a norm weight of one and
    a bias of zero hide a missing norm."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
                           for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def system():
    model = builder.build(CFG)
    params = _perturbed(model.init(jax.random.PRNGKey(0)))
    return model, params, model.init_state()


@pytest.fixture(scope="module")
def both(system):
    """(system loss, system gradients as the reference names them,
    reference loss, reference gradients) on one batch."""
    model, params, state = system
    x, t = _batch()
    crit = builder.criterion()
    loss, grads = jax.value_and_grad(lambda p: crit.apply(
        model.apply(p, state, x, training=True)[0], t))(params)
    w = builder.reference_weights(params, CFG)
    ref_loss, ref_grads = reference.loss_and_grads(w, x - 1, t - 1, HEADS)
    return (float(loss), builder.reference_weights(grads, CFG), ref_loss,
            ref_grads)


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.linalg.norm(jnp.asarray(b)))


def test_logits_match_the_reference(system):
    model, params, state = system
    x, _ = _batch()
    w = builder.reference_weights(params, CFG)
    got = model.apply(params, state, x, training=True)[0]
    want = jnp.stack([reference.logits(w, x[i] - 1, HEADS)
                      for i in range(x.shape[0])])
    assert got.shape == (2, SEQ, CFG["vocab_size"])
    assert float(jnp.abs(got - want).max()) < TOL


def test_loss_matches_the_reference_and_carries_no_indexer_term(both):
    loss, _, ref_loss, _ = both
    assert abs(loss - ref_loss) < TOL * abs(ref_loss)


@pytest.mark.parametrize("layer", range(CFG["num_hidden_layers"]))
@pytest.mark.parametrize("leaf", LEAVES)
def test_every_layer_leafs_gradient_matches_the_reference(both, layer, leaf):
    """The indexer's five leaves get L_I's gradient, every other the
    loss's: one ``jax.grad`` of the program against the reference's
    chain rule by hand."""
    _, grads, _, ref_grads = both
    got, want = grads["layers"][layer][leaf], ref_grads["layers"][layer][leaf]
    assert float(jnp.linalg.norm(jnp.asarray(want))) > 1e-4
    assert _rel(got, want) < TOL


@pytest.mark.parametrize("leaf", ["tok", "lnf_g", "head_w"])
def test_embedding_and_head_gradients_match_the_reference(both, leaf):
    _, grads, _, ref_grads = both
    assert _rel(grads[leaf], ref_grads[leaf]) < TOL


def test_the_indexer_gets_no_gradient_from_the_loss_and_nothing_else_from_l_i(
        system, monkeypatch):
    """With L_I's injection taken out, the indexer's leaves get exactly
    zero and every other leaf what it got before."""
    model, params, state = system
    x, t = _batch()
    crit = builder.criterion()

    def grads():
        return builder.reference_weights(jax.grad(lambda p: crit.apply(
            model.apply(p, state, x, training=True)[0], t))(params), CFG)

    with_l_i = grads()
    monkeypatch.setattr(attention_mod, "_with_gradient_of",
                        lambda y, aux: y)
    without = grads()
    for layer, plain in zip(with_l_i["layers"], without["layers"]):
        for leaf in LEAVES:
            if leaf in reference.INDEXER_LEAVES:
                assert float(jnp.abs(plain[leaf]).max()) == 0.0
                assert float(jnp.abs(layer[leaf]).max()) > 0.0
            else:
                assert _rel(plain[leaf], layer[leaf]) < 1e-6


def _attention_inputs(seq, seed=0, batch=2, h=4, g=2, d=8, j=2, di=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (batch, seq, h, d)),
            jax.random.normal(ks[1], (batch, seq, g, d)),
            jax.random.normal(ks[2], (batch, seq, g, d)),
            jax.random.normal(ks[3], (batch, seq, j, di)),
            jax.random.normal(ks[4], (batch, seq, di)),
            jax.random.normal(ks[5], (batch, seq, j)) * 0.3)


def test_the_selection_is_the_references(system):
    """The indices kept — not just what attention makes of them."""
    model, params, state = system
    x, _ = _batch()
    w = builder.reference_weights(params, CFG)
    block = model.modules[1]
    emb = model.modules[0].apply(params["0"], state["0"], x)[0]
    u = block.modules[0].modules[0].apply(params["1"]["0"]["0"], {}, emb)[0]
    att = block.modules[0].modules[1]
    qi, ki, wi = att.indexer(params["1"]["0"]["1"], u)
    kept, _ = attention_mod.select_topk_xla(
        attention_mod.index_scores_xla(qi, ki, wi), att.topk)
    for i in range(x.shape[0]):
        want = reference.selection(w["layers"][0], u[i], w.spec)
        assert bool(jnp.array_equal(kept[i] > -jnp.inf, want))
        counts = np.asarray(want.sum(-1))
        assert list(counts) == [min(t + 1, att.topk) for t in range(SEQ)]


def test_equal_scores_keep_the_lower_index():
    scores = jnp.zeros((1, 6, 6)).at[0, 5, 4].set(1.0)
    kept, lse = attention_mod.select_topk_xla(scores, 3)
    assert np.asarray(kept[0] > -jnp.inf).tolist() == [
        [1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0], [1, 1, 1, 0, 0, 0],
        [1, 1, 1, 0, 0, 0], [1, 1, 1, 0, 0, 0], [1, 1, 0, 0, 1, 0]]
    assert float(lse[0, 0]) == 0.0
    want = reference.select(scores[0], jnp.arange(6), 3)
    assert bool(jnp.array_equal(kept[0] > -jnp.inf, want))


def test_below_topk_tokens_it_is_dense_causal_gqa_attention():
    q, k, v, qi, ki, wi = _attention_inputs(16)
    o, l_i, kept = attention_mod.sparse_select_xla(q, k, v, qi, ki, wi,
                                                   topk=16)
    sc = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, 2, axis=2)) \
        * 8 ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), sc, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(sc, -1),
                      jnp.repeat(v, 2, axis=2))
    assert float(jnp.abs(o - want).max()) < 1e-5
    assert bool(jnp.array_equal(kept[0] > -jnp.inf,
                                jnp.tril(jnp.ones((16, 16), bool))))
    assert float(l_i) > 0.0


def test_indexer_loss_value_is_the_references(system):
    model, params, state = system
    x, _ = _batch()
    w = builder.reference_weights(params, CFG)
    want = reference.indexer_loss(w, x - 1, HEADS)
    block = model.modules[1]
    emb = model.modules[0].apply(params["0"], state["0"], x)[0]
    p_att = params["1"]["0"]
    u = block.modules[0].modules[0].apply(p_att["0"], {}, emb)[0]
    att = block.modules[0].modules[1]
    l_i = att.indexer_loss(p_att["1"], u)
    assert abs(float(l_i) - want[0]) < TOL * want[0]


# --------------------------------------------------------------------------
# the Pallas kernels, interpreted, against the jnp path
# --------------------------------------------------------------------------

KERNEL = dict(batch=2, h=4, g=2, d=32, j=2, di=16)


def test_index_scores_kernel_matches_on_every_causal_pair():
    from bigdl_tpu.ops.pallas import sparse_attention as sa
    _, _, _, qi, ki, wi = _attention_inputs(256, **KERNEL)
    got = sa.index_scores(qi.transpose(0, 2, 1, 3), ki, wi, interpret=True)
    want = attention_mod.index_scores_xla(qi, ki, wi)
    seen = jnp.tril(jnp.ones((256, 256), bool))
    # float32 as two bfloat16 halves: 2^-16 of scores up to ~8, where
    # ONE bfloat16 pass is off by 2^-8 of them
    assert float(jnp.abs(jnp.where(seen, got - want, 0.0)).max()) < 1e-4
    one_pass = attention_mod.index_scores_xla(
        qi.astype(jnp.bfloat16), ki.astype(jnp.bfloat16), wi)
    assert float(jnp.abs(one_pass - want).max()) > 1e-2


@pytest.mark.parametrize("topk", [48, 128, 300])
@pytest.mark.parametrize("quantised", [False, True], ids=["distinct", "ties"])
def test_select_rows_kernel_is_exact(topk, quantised):
    """Bisection over the float's bits against a sort; with scores
    rounded to halves most thresholds are tied and the lower index must
    win; what lies in a query's future may hold anything."""
    from bigdl_tpu.ops.pallas import sparse_attention as sa
    _, _, _, qi, ki, wi = _attention_inputs(256, seed=topk, **KERNEL)
    scores = attention_mod.index_scores_xla(qi, ki, wi)
    if quantised:
        scores = jnp.round(scores * 2) / 2 * jnp.where(
            jnp.arange(256) % 7 == 0, -1.0, 1.0)       # -0.0 among them
    want, want_lse = attention_mod.select_topk_xla(scores, topk)
    future = jnp.triu(jnp.ones((256, 256), bool), 1)
    got, lse, _, _ = sa.select_rows(jnp.where(future, jnp.nan, scores),
                                    topk, interpret=True)
    assert bool(jnp.array_equal(got, want))
    assert float(jnp.abs(lse[..., 0] - want_lse).max()) < 1e-5
    for b in range(scores.shape[0]):
        ref = reference.select(scores[b], jnp.arange(256), topk)
        assert bool(jnp.array_equal(got[b] > -jnp.inf, ref))


def _tying_inputs(seq):
    """Indexer inputs whose scores are small whole numbers: every row
    with more than ``topk`` causal keys ties at its threshold."""
    j, di = KERNEL["j"], KERNEL["di"]
    qi = jnp.zeros((1, j, seq, di)).at[..., 0].set(1.0)
    ki = jnp.zeros((1, seq, di)).at[..., 0].set(
        (jnp.arange(seq) * 7 % 5 - 1.0)[None])
    return qi, ki, jnp.ones((1, seq, j))


@pytest.mark.parametrize("case,topk", [("short", 2048), ("random", 300),
                                       ("ties", 300)])
def test_the_mask_written_again_is_the_selections_bit_for_bit(case, topk):
    """The backward's masked scores — ``index_scores`` with a row's
    threshold key and tie column as its epilogue — against the array
    ``select_rows`` wrote in place, wherever a key step of ``attend``
    reaches: rows with fewer than ``topk`` causal keys (all 1024 of
    ``short``, the first 300 of the others), random rows, and rows built
    to tie at their threshold (the lower index wins, again). 1024 tokens:
    two q blocks and a key step two score tiles wide, so the tile past
    the diagonal's is read and must be -inf."""
    from bigdl_tpu.ops.pallas import sparse_attention as sa
    seq = 1024
    sched = sa.sparse_schedule(seq, topk)
    assert (sched.index_bk, sched.bk) == (512, 1024)
    if case == "ties":
        qi, ki, wi = _tying_inputs(seq)
    else:
        _, _, _, qi, ki, wi = _attention_inputs(seq, batch=1, h=4, g=2,
                                                d=32, j=2, di=16)
        qi = qi.transpose(0, 2, 1, 3)
    scores = sa.index_scores(qi, ki, wi, interpret=True)
    kept, _, t, m = sa.select_rows(scores + 0.0, topk, interpret=True)
    again = sa.index_scores(qi, ki, wi, keep=(t, m), interpret=True)
    row, col = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    read = col < ((row // sched.bq * sched.bq + sched.bq - 1)
                  // sched.bk * sched.bk + sched.bk)
    assert bool(read[0, seq - 1]) and not bool(jnp.all(kept[0] == -jnp.inf))
    assert bool(jnp.all(jnp.where(read, again[0] == kept[0], True)))
    counts = np.asarray(jnp.sum(again[0] > -jnp.inf, axis=-1))
    assert list(counts) == [min(r + 1, topk) for r in range(seq)]
    if case == "short":
        assert bool(jnp.all(t == -2 ** 31)) and bool(jnp.all(m == seq))
    if case == "ties":
        # whole numbers: most rows past topk have more keys equal to
        # their threshold than still fit, and a tie column cuts them
        assert float(jnp.mean(m[0, topk:, 0] < seq)) > 0.9


@pytest.fixture(scope="module")
def kernel_and_jnp():
    """Outputs and all six gradients of the interpreted kernels and of
    the jnp path, under one random cotangent."""
    from bigdl_tpu.ops.pallas.sparse_attention import sparse_select_attention
    args = _attention_inputs(256, **KERNEL)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def run(fn):
        return fn(*args), jax.grad(lambda *a: jnp.sum(fn(*a) * ct),
                                   argnums=range(6))(*args)

    return (run(lambda *a: sparse_select_attention(*a, topk=48,
                                                   interpret=True)),
            run(lambda *a: attention_mod.sparse_select_xla(*a, topk=48)[0]))


def test_attention_kernel_matches_the_jnp_path(kernel_and_jnp):
    (got, _), (want, _) = kernel_and_jnp
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("arg", range(6),
                         ids=["q", "k", "v", "qi", "ki", "wi"])
def test_kernel_gradients_match_the_jnp_path(kernel_and_jnp, arg):
    """q, k, v: the one-pass backward under the caller's cotangent; qi,
    ki, wi: L_I's gradient through the two indexer-loss kernels."""
    (_, got), (_, want) = kernel_and_jnp
    assert _rel(got[arg], want[arg]) < 1e-5


def test_kernels_split_a_batch_over_the_data_axis():
    """Under a two-device data mesh each shard runs its own sequences
    and L_I stays the mean over the WHOLE batch."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.ops.pallas.sparse_attention import sparse_select_attention
    args = _attention_inputs(128, **KERNEL)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))

    def grads(*a):
        return jax.grad(lambda *b: jnp.sum(sparse_select_attention(
            *b, topk=32, interpret=True) ** 2), argnums=(0, 3))(*a)

    want = grads(*args)
    with jax.set_mesh(mesh):
        sharded = [jax.device_put(a, NamedSharding(mesh, P("data")))
                   for a in args]
        got = jax.jit(grads)(*sharded)
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-5


def test_the_schedule_is_stated_where_the_kernels_are_traced():
    from bigdl_tpu.observability import trace
    from bigdl_tpu.ops.pallas.sparse_attention import (sparse_schedule,
                                                       sparse_select_attention)
    sched = sparse_schedule(16384, 2048)
    assert (sched.bq, sched.bk, sched.index_bq, sched.index_bk, sched.rows,
            sched.chunk) == (512, 1024, 256, 512, 128, 2048)
    # q block i walks i // 2 + 1 key steps of 1024
    assert sched.tiles_computed == 272
    # 2048 * 2049 / 2 + 14336 * 2048 kept pairs, 23.4% of the causal ones
    assert abs(sched.tiles_needed - 31_458_304 / (512 * 1024)) < 1e-9
    with pytest.raises(ValueError, match="multiple of 128"):
        sparse_schedule(200, 48)
    args = _attention_inputs(128, **KERNEL)
    trace.clear()
    trace.enable()
    try:
        jax.eval_shape(lambda *a: sparse_select_attention(
            *a, topk=32, interpret=True), *args)
        events = [e for e in trace.to_dict()["traceEvents"]
                  if e["name"] == "sparse_schedule"]
    finally:
        trace.disable()
    assert len(events) == 1 and events[0]["cat"] == "kernels"
    assert events[0]["args"]["tiles_computed"] == 1


# --------------------------------------------------------------------------
# the expert layer
# --------------------------------------------------------------------------

D, F, TOTAL, TOP = 16, 8, 16, 4


def _share(held, offset, params=None, seed=0):
    layer = ExpertShare(D, F, TOTAL, TOP, experts_held=held,
                        experts_offset=offset)
    whole = ExpertShare(D, F, TOTAL, TOP).init(jax.random.PRNGKey(seed))
    if params is None:
        params = whole
    mine = {k: (v if k == "router_weight" else v[offset:offset + held])
            for k, v in params.items()}
    return layer, mine


def _ref_moe(params, x, offset):
    lw = {"router_w": params["router_weight"], "gate_w": params["gate_weight"],
          "up_w": params["up_weight"], "down_w": params["down_weight"]}
    spec = reference.Spec(kv_heads=1, index_heads=1, topk=1,
                          experts_total=TOTAL, experts_offset=offset,
                          experts_per_token=TOP, rope_theta=1.0, eps=1e-6)
    return reference._moe(lw, x, spec)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of two experts each: their results, with nothing
    computed alike on every chip (no shared expert), sum to the layer
    that holds all sixteen — in the program and in the reference."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, D))
    whole_layer, whole = _share(TOTAL, 0)
    want = whole_layer.apply(whole, whole_layer.init_state(), x)[0]
    total = jnp.zeros_like(want)
    for chip in range(8):
        layer, mine = _share(2, 2 * chip, whole)
        y, state = layer.apply(mine, layer.init_state(), x)
        ref = _ref_moe(mine, x.reshape(-1, D), 2 * chip).reshape(x.shape)
        assert float(jnp.abs(y - ref).max()) < 1e-5
        total = total + y
    assert float(jnp.abs(total - want).max()) < 1e-5
    assert float(jnp.abs(want - _ref_moe(
        whole, x.reshape(-1, D), 0).reshape(x.shape)).max()) < 1e-5


def test_dropless_under_imbalance():
    """A router biased so that ONE held expert is every token's first
    choice: it takes all 64 tokens (a capacity of 1.25 x the mean would
    hold 5), nothing is dropped and the result is the reference's."""
    layer, mine = _share(4, 8)
    mine = dict(mine, router_weight=mine["router_weight"].at[9].set(0.0))
    x = jax.random.normal(jax.random.PRNGKey(4), (64, D))
    x = x.at[:, 0].set(3.0)
    mine["router_weight"] = mine["router_weight"].at[9, 0].set(4.0)
    y, state = layer.apply(mine, layer.init_state(), x)
    assert float(state["moe_held_load_max"]) == 64.0
    assert float(state["moe_tokens_without_local"]) == 0.0
    assert float(jnp.abs(y - _ref_moe(mine, x, 8)).max()) < 1e-5
    top, c = layer.route(mine, x)
    assert bool(jnp.all(top[:, 0] == 9))
    assert float(jnp.abs(c.sum(-1) - 1.0).max()) < 1e-6


def test_a_token_with_no_expert_here_gets_zero_and_telemetry_says_so():
    layer, mine = _share(2, 14)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, D))
    y, state = layer.apply(mine, layer.init_state(), x)
    top, _ = layer.route(mine, x)
    away = ~jnp.any(top >= 14, axis=-1)
    assert 0 < int(away.sum()) < 40
    assert float(jnp.abs(y[away]).max()) == 0.0
    assert float(jnp.abs(y[~away]).min(axis=-1).max()) > 0.0
    assert abs(float(state["moe_tokens_without_local"])
               - float(away.mean())) < 1e-6
    assert abs(float(state["moe_local_assignment_share"])
               - float((top >= 14).mean())) < 1e-6
    stats = expert_mod.moe_state_stats({"blk": {"1": state}})
    assert set(stats["blk/1"]) == set(expert_mod.SHARE_STATE_KEYS)
    assert float(expert_mod.moe_aux_total({"blk": {"1": state}})) == 0.0


def _spans_run(live, first, total):
    """(``moe_chunks_run``, the rows of the spans that ran): the first
    span always, all ``total`` rows once the live ones pass it."""
    return (2, total) if live > first else (1, first)


@pytest.mark.parametrize("rows", [16, 64, 128])
def test_chunks_of_sorted_rows_are_the_same_layer(monkeypatch, rows):
    """The 256 sorted assignments as a first span of ``rows`` and ONE
    second span of the rest behind a ``lax.cond``, each span that ran
    recomputed in the backward pass, here with ~62 landing on the share
    so that the second span runs (16) or does not (64, 128): the same
    result, gradients (the input's too) and telemetry as all 256 rows in
    one span — but for the two keys that say how the rows were cut
    (PR 34), which must be the spans that ran and the live rows' share
    of those."""
    x = jax.random.normal(jax.random.PRNGKey(6), (64, D))

    def run(first):
        monkeypatch.setattr(expert_mod, "_chunk_rows", lambda *a: first)
        layer, mine = _share(4, 4)
        (loss, state), grads = jax.value_and_grad(
            lambda p, x: (lambda y, st: (jnp.sum(y ** 2), st))(
                *layer.apply(p, layer.init_state(), x)), argnums=(0, 1),
            has_aux=True)(mine, x)
        return loss, state, grads

    (a, sa, ga), (b, sb, gb) = run(256), run(rows)
    assert 16 < float(sa["moe_local_assignment_share"]) * 256 < 64
    assert abs(float(a) - float(b)) < 1e-5 * abs(float(a))
    sa, sb = jax.tree.map(float, sa), jax.tree.map(float, sb)
    live = round(sa["moe_local_assignment_share"] * 256)
    for state, first in ((sa, 256), (sb, rows)):
        ran, worked_on = _spans_run(live, first, 256)
        assert ran == (2 if first == 16 else 1)
        assert state.pop("moe_chunks_run") == ran
        assert state.pop("moe_product_row_share") == pytest.approx(
            live / worked_on)
    assert sa == sb
    for name in ga[0]:
        assert _rel(ga[0][name], gb[0][name]) < 1e-5, name
    assert _rel(ga[1], gb[1]) < 1e-5


def _what_the_chunks_are_handed(monkeypatch, layer, mine, x):
    """``_in_chunks``' arguments as ``apply`` makes them for ``x``, as
    concrete arrays: (rows, weights, tokens, cw, where, live)."""
    seen = []

    def keep(chunk, rows, weights, tokens, cw, where, live):
        seen.append((rows, weights, tokens, cw, where, live))
        return jnp.zeros(tokens.shape, jnp.float32)

    with monkeypatch.context() as m:
        m.setattr(expert_mod, "_in_chunks", keep)
        layer.apply(mine, layer.init_state(), x)
    (args,) = seen
    return args


def _riders_in_the_last_held_group(held, grouped=expert_mod.grouped_matmul):
    """``grouped_matmul`` as commit 0aa00c8's ``_chunk`` called it: the
    rows for experts elsewhere put into the LAST held expert's group, so
    every product multiplied all of the chunk's rows."""
    def parents(x, w, group_sizes):
        return grouped(x, w, group_sizes.at[held - 1].add(group_sizes[held])
                       .at[held].set(0))
    return parents


@pytest.mark.parametrize("which", ["wholly live", "partly live",
                                   "past live", "all the rest"])
def test_a_chunks_products_get_the_held_groups_and_the_rest_apart(
        monkeypatch, which):
    """What ``_chunk`` hands ``grouped_matmul``: ``held`` groups that
    sum to the span's LIVE rows, each the held expert's rows that fall
    into the span, and a last group that is the rest of it — so the
    products' work follows the rows the held experts were sent. For a
    span wholly live, partly live and past ``live`` (nothing but the
    last group), and for the second span as ``_in_chunks`` cuts it (all
    the rows past the first 32), all three products alike."""
    first = 32
    monkeypatch.setattr(expert_mod, "_chunk_rows", lambda *a: first)
    layer, mine = _share(4, 4)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, D))
    _, weights, tokens, cw, where, live = _what_the_chunks_are_handed(
        monkeypatch, layer, mine, x)
    live = int(live)
    assert first < live < 256 - first and live % first
    lo, rows = {"wholly live": (0, first),
                "partly live": (live // first * first, first),
                "past live": (256 - first, first),
                "all the rest": (first, 256 - first)}[which]
    handed = []
    real = expert_mod.grouped_matmul

    def record(x, w, group_sizes):
        handed.append(np.asarray(group_sizes))
        return real(x, w, group_sizes)

    monkeypatch.setattr(expert_mod, "grouped_matmul", record)
    y = layer._chunk(weights, tokens, cw, where, lo=lo, rows=rows)
    top, _ = layer.route(mine, x)
    group = np.sort(np.where((top >= 4) & (top < 8), top - 4, 4).reshape(-1))
    want = np.bincount(group[lo:lo + rows], minlength=5)
    assert len(handed) == 3
    for sizes in handed:
        np.testing.assert_array_equal(sizes, want)
    in_live = min(max(live - lo, 0), rows)
    assert want[:4].sum() == in_live and want[4] == rows - in_live
    assert {"wholly live": in_live == rows, "partly live": 0 < in_live < rows,
            "past live": in_live == 0,
            "all the rest": in_live == live - first}[which]
    assert (float(jnp.abs(y).max()) == 0.0) == (which == "past live")


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "megablox-interpreted"])
def test_rows_that_cost_no_product_give_the_parents_layer(monkeypatch,
                                                          interpret):
    """The same mathematics with less multiplication: result, state and
    every gradient — the experts', the router's, the input's, and the
    combine weights' taken alone — equal what the layer gave when the
    rows for experts elsewhere rode in the last held expert's group
    (weight zero), over a first span of 32 rows and the second of 224,
    which the ~62 live rows reach. Only a weight
    that is zero because its expert is elsewhere had a gradient then
    (the row's finite garbage, which ``apply``'s ``where`` discarded)
    and has an exact zero now."""
    monkeypatch.setattr(expert_mod, "_chunk_rows", lambda *a: 32)
    layer, mine = _share(4, 4)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, D))
    grouped = functools.partial(expert_mod.grouped_matmul,
                                interpret=interpret)

    def run(product):
        monkeypatch.setattr(expert_mod, "grouped_matmul", product)
        (loss, (y, state)), grads = jax.value_and_grad(
            lambda p, x: (lambda y, st: (jnp.sum(y ** 2), (y, st)))(
                *layer.apply(p, layer.init_state(), x)), argnums=(0, 1),
            has_aux=True)(mine, x)
        rows, weights, tokens, cw, where, live = \
            _what_the_chunks_are_handed(monkeypatch, layer, mine, x)
        assert 32 < int(live) < 64
        d_cw = jax.grad(lambda cw: jnp.sum(expert_mod._in_chunks(
            layer._chunk, rows, weights, tokens, cw, where, live) ** 2))(cw)
        return y, state, dict(grads[0], x=grads[1], elsewhere=d_cw[cw == 0],
                              combine_weights=jnp.where(cw != 0, d_cw, 0.0))

    y, state, grads = run(grouped)
    was_y, was_state, was = run(_riders_in_the_last_held_group(4, grouped))
    assert float(jnp.abs(y - was_y).max()) <= 1e-6 * float(jnp.abs(y).max())
    assert jax.tree.map(float, state) == jax.tree.map(float, was_state)
    assert float(jnp.abs(grads.pop("elsewhere")).max()) == 0.0
    assert float(jnp.abs(was.pop("elsewhere")).max()) > 0.0
    for name in grads:
        assert float(jnp.abs(was[name]).max()) > 0, name
        assert _rel(grads[name], was[name]) < 1e-6, name


@pytest.mark.parametrize("rows,routing", [
    (None, "as it falls"), (32, "as it falls"), (32, "one expert takes all"),
    (32, "none held is chosen")])
def test_telemetry_says_what_part_of_the_chunks_was_multiplied(
        monkeypatch, rows, routing):
    """``moe_chunks_run``: 1, or 2 when the live rows pass the first
    span and the second runs; ``moe_product_row_share``: the live rows
    over the rows of the spans that ran (about a quarter where the first
    span is four times the balanced share, as this router's is with no
    bias to balance it, and the router is even; 0 when no chosen expert
    is held). ``moe_state_stats`` reads both."""
    if rows is not None:
        monkeypatch.setattr(expert_mod, "_chunk_rows", lambda *a: rows)
    layer, mine = _share(2, 8)
    rows = rows or expert_mod._chunk_rows(64 * TOP, 2, TOTAL, False)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, D))
    steer = {"as it falls": None, "one expert takes all": 4.0,
             "none held is chosen": -40.0}[routing]
    if steer is not None:
        x = x.at[:, 0].set(3.0)
        mine["router_weight"] = mine["router_weight"].at[9, 0].set(steer) \
            .at[8, 0].set(min(steer, 0.0))
    y, state = layer.apply(mine, layer.init_state(), x)
    top, _ = layer.route(mine, x)
    live = int(((top >= 8) & (top < 10)).sum())
    chunks, worked_on = _spans_run(live, rows, 64 * TOP)
    assert float(state["moe_chunks_run"]) == chunks
    assert float(state["moe_product_row_share"]) == pytest.approx(
        live / worked_on)
    if routing == "as it falls":
        assert 0.5 * 32 < live < 1.5 * 32       # 2 of 16 held: an eighth
        assert chunks == (2 if rows == 32 and live > 32 else 1)
        if rows == 128:                         # four times that
            assert 0.125 < float(state["moe_product_row_share"]) < 0.375
    elif routing == "one expert takes all":
        assert live >= 64 and chunks == 2
    else:
        assert live == 0 and chunks == 1
        assert float(state["moe_product_row_share"]) == 0.0
        assert float(jnp.abs(y).max()) == 0.0
    stats = expert_mod.moe_state_stats({"blk": {"1": state}})
    assert {"moe_product_row_share", "moe_chunks_run"} <= set(stats["blk/1"])
    assert stats["blk/1"]["moe_chunks_run"] is state["moe_chunks_run"]


def test_no_loop_encloses_the_experts():
    """A ``while`` around the experts is ONE device operation that spans
    its body's, and the benchmark's scope readers would count both
    (PERF.md section 7, PR 31): forward and backward hold none, and one
    ``cond`` each for the second span."""
    layer, mine = _share(2, 4)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, D))
    text = str(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(layer.apply(
        p, layer.init_state(), x)[0] ** 2)))(mine))
    assert "while" not in text and "scan" not in text
    assert expert_mod._chunk_rows(64 * TOP, 2, TOTAL, False) == 128
    assert text.count(" cond[") == 2       # forward, backward: span 2


def test_the_lowered_layer_holds_two_span_bodies(monkeypatch, kernel_calls):
    """Every span is a body of its own in the compiled step, and a
    body's grouped-product kernels are compiled whatever its size
    (twelve in the jaxpr: three forward, three made again, six backward;
    the TPU's compiled step counts eleven): the module's own cut (128 +
    128 of 256 rows: the two EQUAL chunks this router's layer had until
    PR 36) and the cut a router that balances itself gets (64 + 192)
    lower to exactly the same kernels, twice the one-span layer's."""
    layer, mine = _share(2, 4)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, D))
    monkeypatch.setattr(expert_mod, "grouped_matmul", functools.partial(
        expert_mod.grouped_matmul, interpret=True))
    own_rule = expert_mod._chunk_rows

    def calls(first):
        monkeypatch.setattr(expert_mod, "_chunk_rows",
                            lambda *a: first or own_rule(*a))
        return kernel_calls(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
            layer.apply(p, layer.init_state(), x)[0] ** 2)))(mine).jaxpr)

    own, halves, unequal, one = (calls(first) for first in
                                 (None, 128, 64, 256))
    assert own == halves == unequal == {name: 2 * n
                                        for name, n in one.items()}
    assert sum(own.values()) == 24


@pytest.mark.parametrize("assignments,held,total,first", [
    (16384 * 6, 8, 64, 24576),          # the kimi cell: + one span of 73728
    (16384 * 8, 16, 128, 32768),        # keye's shape, were it balanced
    (16384 * 8, 128, 128, 16384 * 8),   # the whole layer: one span
    (16384 * 8, 64, 128, 16384 * 8),    # twice the share is all of it
    (64 * 4, 2, 16, 64), (9 * 8, 2, 16, 18), (7, 1, 16, 1), (9, 1, 16, 3),
    (10 * 6, 1, 16, 10),                # 8 does not divide 60: 6 does
    (96 * 2, 4, 8, 192), (128 * 3, 4, 8, 384)])
def test_the_first_span_is_twice_the_balanced_share(assignments, held, total,
                                                    first):
    """Where the router balances itself: the nearest whole division of
    the assignments to twice what lands here when the router is
    balanced; the rest is ONE second span, so never more than two; an
    odd count of assignments divides as far as it can. (Until PR 36
    equal chunks of four times the share:
    ``test_a_chunk_is_four_times_the_balanced_share``.)"""
    assert expert_mod._chunk_rows(assignments, held, total, True) == first
    assert assignments % first == 0
    assert first >= min(assignments, 2 * assignments * held // total)


@pytest.mark.parametrize("assignments,held,total,first", [
    (16384 * 8, 16, 128, 65536),        # the keye cell: + one span of 65536
    (16384 * 6, 8, 64, 49152),          # kimi's shape without its bias
    (16384 * 8, 128, 128, 16384 * 8),   # the whole layer: one span
    (16384 * 8, 32, 128, 16384 * 8),    # four times the share is all of it
    (64 * 4, 2, 16, 128), (9 * 8, 2, 16, 36), (7, 1, 16, 7), (9, 1, 16, 3),
    (10 * 6, 1, 32, 10)])               # 8 does not divide 60: 6 does
def test_the_first_span_is_four_times_the_share_with_no_bias(
        assignments, held, total, first):
    """Where nothing holds the router balanced the step's time would
    follow the router's state (PERF.md section 6, PR 36: the keye cell's
    runs spread 4% for 0.9%): the first span stays at four times the
    balanced share, the chunk the layer had until PR 36, and the one
    second span takes the rest."""
    assert expert_mod._chunk_rows(assignments, held, total, False) == first
    assert assignments % first == 0
    assert first >= min(assignments, 4 * assignments * held // total)


SPAN_ROUTINGS = ["live < first", "live == first", "live == first + 1",
                 "everything to one held expert", "nothing here"]


def _first_span_for(routing, live):
    """Where a case cuts the rows: at the live rows or one short of them
    for the two edges, else where the module's own rule does."""
    return {"live == first": live, "live == first + 1": live - 1}.get(routing)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "megablox-interpreted"])
@pytest.mark.parametrize("routing", SPAN_ROUTINGS)
def test_two_spans_are_the_one_span_layer(two_spans_against_one, routing,
                                          interpret):
    """The softmax layer (2 of 16 held, 4 a token, 64 tokens, no bias
    to balance it: a first span of 128 of the 256 rows by the module's
    own rule) against the
    same layer with all rows in one span: result, state and every
    gradient, whether the live rows stay inside the first span, fill it
    to the row, pass it by one, are all one held expert's, or are none;
    the telemetry and the instant say which spans ran."""
    layer, mine = _share(2, 8)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, D))
    if routing in SPAN_ROUTINGS[3:]:
        x = x.at[:, 0].set(3.0)
        to_nine = 4.0 if routing == SPAN_ROUTINGS[3] else -40.0
        mine["router_weight"] = mine["router_weight"].at[9, 0].set(to_nine) \
            .at[8, 0].set(-40.0)
    top, _ = layer.route(mine, x)
    live = int(((top >= 8) & (top < 10)).sum())
    state, ran, found, first = two_spans_against_one(
        layer, mine, layer.init_state(), x, _first_span_for(routing, live),
        interpret)
    assert found == live
    assert first == (_first_span_for(routing, live) or 128)
    assert state["moe_local_assignment_share"] == pytest.approx(live / 256)
    assert (ran, live) == {
        "live < first": (1, live), "live == first": (1, first),
        "live == first + 1": (2, first + 1),
        "everything to one held expert": (1, 64),
        "nothing here": (1, 0)}[routing]
    if routing in SPAN_ROUTINGS[3:]:
        assert state["moe_held_load_max"] == live
    else:
        assert 16 < live < 48


def test_expert_share_refuses_experts_it_cannot_hold():
    with pytest.raises(ValueError, match="not among"):
        ExpertShare(D, F, 8, 2, experts_held=4, experts_offset=6)
    with pytest.raises(ValueError, match="top_k"):
        ExpertShare(D, F, 8, 9)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "megablox-interpreted"])
def test_grouped_products_backward_is_autodiff_of_a_per_expert_loop(interpret):
    """Forward and both gradients of the grouped matrix product over
    ragged groups (one empty, the tail for no expert here) against a
    loop over experts; the TPU's megablox kernels interpreted too."""
    sizes = jnp.asarray([40, 0, 88, 128], jnp.int32)   # last: elsewhere
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 128, 128))
    ct = jax.random.normal(jax.random.PRNGKey(2), (256, 128))

    def loop(x, w):
        out, at = [], 0
        for e, n in enumerate([40, 0, 88]):
            out.append(x[at:at + n] @ w[e].T)
            at += n
        return jnp.concatenate(out + [jnp.zeros((128, 128))])

    def grouped(x, w):
        return expert_mod.grouped_matmul(x, w, sizes, interpret=interpret)

    assert float(jnp.abs(grouped(x, w) - loop(x, w)).max()) < 1e-4
    got = jax.grad(lambda *a: jnp.sum(grouped(*a) * ct), argnums=(0, 1))(x, w)
    want = jax.grad(lambda *a: jnp.sum(loop(*a) * ct), argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-5


def test_expert_share_on_megablox_interpreted_matches_ragged_dot(monkeypatch):
    layer, mine = _share(4, 4)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, D))

    def run():
        return jax.value_and_grad(lambda p: jnp.sum(layer.apply(
            p, layer.init_state(), x)[0] ** 2))(mine)

    (a, ga) = run()
    monkeypatch.setattr(expert_mod, "grouped_matmul", functools.partial(
        expert_mod.grouped_matmul, interpret=True))
    (b, gb) = run()
    assert abs(float(a) - float(b)) < 1e-4 * abs(float(a))
    for name in mine:
        assert _rel(ga[name], gb[name]) < 1e-4, name


def test_old_and_new_layers_share_one_router():
    x = jax.random.normal(jax.random.PRNGKey(7), (10, D))
    gate = jax.random.normal(jax.random.PRNGKey(8), (D, TOTAL))
    probs, top_p, top = expert_mod.route_top_k(x, gate, TOP)
    assert float(jnp.abs(probs.sum(-1) - 1).max()) < 1e-6
    assert bool(jnp.all(jnp.take_along_axis(probs, top, -1) == top_p))
    layer, mine = _share(TOTAL, 0)
    got, c = layer.route(dict(mine, router_weight=gate.T), x)
    assert bool(jnp.array_equal(got, top))
    assert float(jnp.abs(c - top_p / top_p.sum(-1, keepdims=True)).max()) \
        < 1e-6


# --------------------------------------------------------------------------
# the model as built
# --------------------------------------------------------------------------

def test_keye_lm_counts_the_cells_parameters():
    """A layer's share at the published widths is 96,899,456 parameters,
    embedding and head 77,793,280 (ISSUE 31's arithmetic)."""
    from bigdl_tpu.models import KeyeLM
    model = KeyeLM(vocab_size=18992, num_layers=1, experts_held=16,
                   experts_offset=48)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    assert count(shapes["1"]) == 96_899_456
    assert count(shapes["2"]) == 2048                    # final norm
    assert sum(count(shapes[i]) for i in "023") == 77_793_280
    assert model.remat_policy == "per_block"


@pytest.fixture()
def kernels(monkeypatch):
    """``SparseSelectAttention`` on its Pallas kernels, interpreted: the
    path it takes on the TPU."""
    from bigdl_tpu.ops.pallas.sparse_attention import sparse_select_attention
    monkeypatch.setattr(
        attention_mod, "sparse_select_xla",
        lambda *a, topk, scale: (sparse_select_attention(
            *a, topk=topk, scale=scale, interpret=True), None))


def _loss(model, state, x, t):
    crit = builder.criterion()
    return lambda p: crit.apply(model.apply(p, state, x, training=True)[0],
                                t)


def _residuals(model, params, state, x, t):
    return jax.tree.leaves(jax.eval_shape(
        lambda p: jax.vjp(_loss(model, state, x, t), p)[1], params))


def _sequence_squared(leaves, seq):
    """Residual leaves with two axes of the sequence's length."""
    return [leaf.shape for leaf in leaves if leaf.shape.count(seq) >= 2]


def test_no_sequence_squared_array_outlives_a_block(system):
    """What the backward keeps of the forward is the block boundaries:
    with each block recomputed, not one array with two axes of length S
    is among the residuals; without, each layer's masked scores and
    probabilities are (the jnp path, which autodiff differentiates)."""
    model, params, state = system
    x, t = _batch(seq=128, rows=1)
    assert _sequence_squared(_residuals(model, params, state, x, t),
                             128) == []
    plain = builder.build(CFG).set_remat(None)
    assert _sequence_squared(_residuals(plain, params, state, x, t), 128)


def test_on_the_kernels_no_sequence_squared_array_is_a_residual(system,
                                                                 kernels):
    """On the kernels the masked scores are no residual of the layer,
    recomputed or not (the backward writes them again from a row's two
    numbers); under recomputation the residuals hold what the attention
    kernels made, o among them, which is why the bound is on axes and
    not on elements."""
    model, params, state = system
    x, t = _batch(seq=128, rows=1)
    kept = _residuals(model, params, state, x, t)
    assert _sequence_squared(kept, 128) == []
    heads = (CFG["num_key_value_heads"], HEADS // CFG["num_key_value_heads"])
    assert sum(leaf.shape == (*heads, 128, CFG["head_dim"])
               for leaf in kept) == CFG["num_hidden_layers"]
    plain = builder.build(CFG).set_remat(None)
    assert _sequence_squared(_residuals(plain, params, state, x, t),
                             128) == []


def test_a_recomputed_block_runs_selection_and_attention_once(
        system, kernels, kernel_calls):
    """In the gradient's jaxpr under ``per_block`` each layer has ONE
    ``sparse_select_rows`` and ONE ``sparse_attention_fwd`` (what they
    made is named and kept) and TWO ``sparse_index_scores`` (the
    forward's, and the backward's that masks again)."""
    model, params, state = system
    x, t = _batch(seq=128, rows=1)
    assert model.remat_policy == "per_block"
    calls = kernel_calls(jax.make_jaxpr(jax.grad(_loss(
        model, state, x, t)))(params).jaxpr)
    n = CFG["num_hidden_layers"]
    assert calls == dict(
        sparse_index_scores=2 * n, sparse_select_rows=n,
        sparse_attention_fwd=n, sparse_attention_dqdkdv=n,
        sparse_kept_probs=n, sparse_index_backward=n)


def test_on_the_kernels_recomputation_is_bit_identical_to_none(system,
                                                               kernels):
    """Loss and EVERY gradient leaf, ``per_block`` (the named values
    kept, the rest made again) against no recomputation."""
    model, params, state = system
    x, t = _batch(seq=128, rows=1)
    plain = builder.build(CFG).set_remat(None)
    with_remat = jax.value_and_grad(_loss(model, state, x, t))(params)
    without = jax.value_and_grad(_loss(plain, state, x, t))(params)
    leaves = jax.tree.leaves_with_path(with_remat)
    assert len(leaves) > 30
    for (path, a), b in zip(leaves, jax.tree.leaves(without)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(
            path))


def test_what_a_recomputed_block_kept_is_stated_where_it_is_traced(
        system, kernels):
    from bigdl_tpu.observability import trace
    from bigdl_tpu.optim.remat import KEPT_NAMES
    model, params, state = system
    x, t = _batch(seq=128, rows=1)
    trace.clear()
    trace.enable()
    try:
        jax.eval_shape(jax.grad(_loss(model, state, x, t)), params)
        events = trace.to_dict()["traceEvents"]
    finally:
        trace.disable()
    (said,) = [e["args"] for e in events if e["name"] == "remat_kept"]
    assert said["names"] == ",".join(KEPT_NAMES)
    # a layer: o, its row logsumexp, and a row's index logsumexp,
    # threshold key and tie column
    a_layer = 4 * (HEADS * 128 * CFG["head_dim"] + HEADS * 128 + 3 * 128)
    assert said["per_block"] == [[0, 0]] + [[5, a_layer]] * CFG[
        "num_hidden_layers"] + [[0, 0], [0, 0]]
    assert said["values"] == 5 * CFG["num_hidden_layers"]
    assert said["bytes"] == a_layer * CFG["num_hidden_layers"]


def _op_names(fn, *args):
    """Every ``op_name`` of the compiled program's text: what a trace
    of its runs is joined to (``tracing.ProgramScopes``)."""
    import re
    return set(re.findall(r'op_name="([^"]*)"',
                          jax.jit(fn).lower(*args).compile().as_text()))


def _metric_pattern(metric, key="include"):
    import re
    from benchmarks import manifest
    return re.compile(
        manifest.data_file("layer_metrics", metric)["params"][key])


@pytest.fixture(scope="module")
def step_op_names(system):
    """Of loss and gradients on the kernels, interpreted (the
    ``kernels`` fixture's patch, for the length of one compile)."""
    from bigdl_tpu.ops.pallas.sparse_attention import sparse_select_attention
    model, params, state = system
    x, t = _batch(seq=128, rows=1)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            attention_mod, "sparse_select_xla",
            lambda *a, topk, scale: (sparse_select_attention(
                *a, topk=topk, scale=scale, interpret=True), None))
        return _op_names(jax.grad(_loss(model, state, x, t)), params)


def test_what_the_backward_pass_makes_again_by_hand_carries_the_mark(
        step_op_names):
    """``step.recompute_ms`` reads recomputation by ONE pattern:
    ``jax.checkpoint``'s ``rematted_computation`` and the program's own
    ``recompute`` scope at the two places where it makes a forward
    again by hand: a chunk's forward inside ``_in_chunks_bwd`` and the
    masked index scores in ``_core_bwd``. Nothing in the forward pass
    carries it."""
    mark = _metric_pattern("step.recompute_ms")
    marked = {n for n in step_op_names if mark.search(n)}
    # (the CPU's compiler spells a ragged product as plain ones)
    assert any("/moe_experts/recompute/" in n
               and n.endswith("dot_general") for n in marked)
    assert any("/indexer/recompute/" in n for n in marked)
    assert any("/checkpoint/rematted_computation/" in n for n in marked)
    assert not [n for n in marked if "transpose(" not in n]


def test_a_chunks_backward_products_do_not_carry_the_mark(step_op_names):
    """The chunk's backward runs under ``pullback``; a custom_vjp's
    backward rule (the combine's, the row gather's; on the TPU
    megablox's) is named after where its FORWARD was called, so its
    name holds ``recompute`` too, behind ``pullback``: no
    recomputation."""
    mark = _metric_pattern("step.recompute_ms")
    pulled = {n for n in step_op_names if "/moe_experts/pullback/" in n}
    assert any(n.endswith("dot_general") for n in pulled)
    assert any("/recompute/" in n for n in pulled)
    assert not [n for n in pulled if mark.search(n)]
    # the indexer's and the attention's backward kernels neither
    assert not [n for n in step_op_names if mark.search(n)
                and ("/sparse_attention/" in n or "/indexer_loss/" in n)
                and "rematted_computation" not in n]


def test_the_marks_lie_inside_the_scopes_the_other_metrics_read(
        step_op_names):
    moe = _metric_pattern("step.moe_ms")
    sparse = _metric_pattern("step.sparse_attention_ms")
    backward = _metric_pattern("step.backward_ms")
    by_hand = {n for n in step_op_names
               if "/recompute/" in n or "/pullback/" in n}
    assert by_hand
    for n in by_hand:
        assert backward.search(n), n
        assert (moe if "/moe_experts/" in n else sparse).search(n), n


def test_the_marks_do_not_change_the_program(system, kernels, monkeypatch):
    import contextlib
    model, params, state = system
    x, t = _batch(seq=128, rows=1)
    fn = jax.jit(jax.grad(_loss(model, state, x, t)))
    with_scopes = fn.lower(params).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    assert jax.jit(jax.grad(_loss(model, state, x, t))).lower(
        params).as_text() == with_scopes


def test_the_layer_states_its_shapes_where_it_is_traced(system):
    from bigdl_tpu.observability import trace
    model, params, state = system
    x, _ = _batch()
    trace.clear()
    trace.enable()
    try:
        jax.eval_shape(lambda p: model.apply_plain(p, state, x)[0], params)
        events = trace.to_dict()["traceEvents"]
    finally:
        trace.disable()
    sel = [e["args"] for e in events if e["name"] == "sparse_select"]
    moe = [e["args"] for e in events if e["name"] == "moe_share"]
    assert len(sel) == len(moe) == CFG["num_hidden_layers"]
    assert sel[0]["selected_pairs"] == 2 * (12 * 13 // 2 + 36 * 12)
    assert sel[0]["causal_pairs"] == 2 * 48 * 49 // 2
    assert sel[0]["materialised_bytes"] == 2 * 48 * 48 * 4
    assert moe[0] == dict(experts_total=8, experts_held=4, top_k=2,
                          tokens=96, expected_local_assignments=96.0,
                          chunk_rows=192, chunks=1, rest_rows=0,
                          scoring="softmax", shared_width=0,
                          bias_update_rate=0.0)


def test_the_optimizer_trains_the_model_as_built(system):
    """Through ``Optimizer``: the loss falls, and the indexer's leaves
    move although no loss the optimizer sees depends on them."""
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import SGD, Optimizer
    from bigdl_tpu.optim.trigger import max_iteration
    model = builder.build(CFG)
    model.materialize(jax.random.PRNGKey(0))
    before = jax.tree.map(np.asarray, model.params["1"]["0"]["1"])
    x, t = _batch(rows=4)
    data = DataSet.iterator(lambda: iter([MiniBatch(np.asarray(x),
                                                    np.asarray(t))] * 8),
                            size=32)
    losses = []

    class Log:
        def add_scalar(self, name, value, step):
            if name == "Loss":
                losses.append(float(value))

    opt = Optimizer(model, data, builder.criterion())
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_train_summary(Log())
    opt.set_end_when(max_iteration(8))
    opt.optimize()
    assert losses[-1] < losses[0]
    after = model.params["1"]["0"]["1"]
    for leaf in ("iq_weight", "ik_weight", "iw_weight", "ik_norm_weight",
                 "ik_norm_bias"):
        assert float(np.abs(np.asarray(after[leaf]) - before[leaf]).max()) > 0

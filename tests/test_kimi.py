"""Kimi-VL-A3B-Instruct's language model on the training path (ISSUE 33):
``nn.LatentAttention`` (a score of two parts over one shared rotary key,
values of their own width) and its Pallas kernels,
``parallel.expert.ExpertShare``'s sigmoid router with its selection bias
and shared expert, and ``KimiLM`` (a leading dense layer).

The model tests compare the program with the plain float32 reference
(benchmarks/reference/kimi.py) on the logits, the loss and EVERY
gradient leaf at 48 tokens with 4 of 8 experts held, every leaf
perturbed and the selection bias set off zero so that each matters —
and show that the comparison fails when any one piece of the
mathematics is taken out of the program.
"""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.builders import kimi as builder
from benchmarks.reference import kimi as reference
from bigdl_tpu.nn.attention import LatentAttention
from bigdl_tpu.ops.pallas import latent_attention as kernels_mod
from bigdl_tpu.parallel import expert as expert_mod
from bigdl_tpu.parallel.expert import ExpertShare
from bigdl_tpu.tensor import DTypePolicy, policy_scope

CFG = dict(vocab_size=50, hidden_size=32, num_attention_heads=4,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
           kv_lora_rank=16, q_lora_rank=None, num_hidden_layers=3,
           first_k_dense_replace=1, moe_layer_freq=1, intermediate_size=48,
           moe_intermediate_size=16, published={"n_routed_experts": 8},
           n_routed_experts=4, experts_offset=2, num_experts_per_tok=3,
           n_shared_experts=2, routed_scaling_factor=2.446,
           scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
           topk_group=1, norm_topk_prob=True, bias_update_rate=0.05,
           rope_theta=1e4, rope_scaling=None,
           rms_norm_eps=1e-5)
HEADS = CFG["num_attention_heads"]
LAYERS = CFG["num_hidden_layers"]
SEQ = 48
TOL = 2e-5          # float32 on both sides, another order of summation
ALWAYS = ("ln1_g", "q_w", "kva_w", "kvn_g", "kvb_w", "o_w", "ln2_g",
          "gate_w", "up_w", "down_w")
EXPERT_ONLY = ("router_w", "sh_gate_w", "sh_up_w", "sh_down_w")
LEAVES = [(n, leaf) for n in range(LAYERS) for leaf in ALWAYS] \
    + [(n, leaf) for n in range(1, LAYERS) for leaf in EXPERT_ONLY]


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
    """Every leaf moved off its initial value: a norm weight of one
    hides a missing norm."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
                           for a, k in zip(leaves, keys)])


def _expert_layers(model):
    """The model's ``ExpertShare`` modules, in layer order."""
    return [block.modules[1].modules[1] for block in model.modules[1:-2]
            if isinstance(block.modules[1].modules[1], ExpertShare)]


def _with_biases(state, biases):
    """``state`` with each expert layer's selection bias replaced, in
    layer order."""
    left = iter(biases)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: next(left)
        if path[-1].key == expert_mod.BIAS_STATE_KEY else leaf, state)


def _biases(seed=5, scale=0.3):
    """A bias an expert layer, far off zero: it changes many choices."""
    total = CFG["published"]["n_routed_experts"]
    keys = jax.random.split(jax.random.PRNGKey(seed), LAYERS - 1)
    return [scale * jax.random.normal(k, (total,)) for k in keys]


@pytest.fixture(scope="module")
def system():
    model = builder.build(CFG)
    params = _perturbed(model.init(jax.random.PRNGKey(0)))
    return model, params, _with_biases(model.init_state(), _biases())


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
    ref_loss, ref_grads = reference.loss_and_grads(w, x - 1, t - 1, HEADS,
                                                   biases=_biases())
    return (float(loss), builder.reference_weights(grads, CFG), ref_loss,
            ref_grads)


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.linalg.norm(jnp.asarray(b)))


def _reference_logits(params, x, biases):
    w = builder.reference_weights(params, CFG)
    return jnp.stack([reference.logits(w, x[i] - 1, HEADS, biases=biases)
                      for i in range(x.shape[0])])


# --------------------------------------------------------------------------
# the model against the reference
# --------------------------------------------------------------------------

def test_logits_match_the_reference(system):
    model, params, state = system
    x, _ = _batch()
    got = model.apply(params, state, x, training=True)[0]
    assert got.shape == (2, SEQ, CFG["vocab_size"])
    assert float(jnp.abs(got - _reference_logits(params, x, _biases())
                         ).max()) < TOL


def test_loss_matches_the_reference(both):
    loss, _, ref_loss, _ = both
    assert abs(loss - ref_loss) < TOL * abs(ref_loss)


@pytest.mark.parametrize("layer,leaf", LEAVES)
def test_every_layer_leafs_gradient_matches_the_reference(both, layer, leaf):
    """One ``jax.grad`` of the program against the reference's chain
    rule by hand, the dense layer and both expert layers."""
    _, grads, _, ref_grads = both
    got, want = grads["layers"][layer][leaf], ref_grads["layers"][layer][leaf]
    assert float(jnp.linalg.norm(jnp.asarray(want))) > 1e-4
    assert _rel(got, want) < TOL


@pytest.mark.parametrize("leaf", ["tok", "lnf_g", "head_w"])
def test_embedding_and_head_gradients_match_the_reference(both, leaf):
    _, grads, _, ref_grads = both
    assert _rel(grads[leaf], ref_grads[leaf]) < TOL


def test_the_dense_layer_comes_first_and_has_no_router(system):
    model, params, _ = system
    w = builder.reference_weights(params, CFG)
    assert ["router_w" in lw for lw in w["layers"]] == [False, True, True]
    assert len(_expert_layers(model)) == LAYERS - 1
    assert w["layers"][0]["gate_w"].shape == (CFG["intermediate_size"], 32)
    assert w["layers"][1]["sh_gate_w"].shape == (2 * 16, 32)


def _zero_rotary_part(monkeypatch, model):
    real = kernels_mod.latent_attention_xla
    monkeypatch.setattr(kernels_mod, "latent_attention_xla",
                        lambda qn, qr, kn, kr, v: real(
                            qn, jnp.zeros_like(qr), kn, kr, v))


def _values_as_wide_as_keys(monkeypatch, model):
    """Values read over the key's width: the content key's columns
    where the value's should be."""
    real = kernels_mod.latent_attention_xla
    monkeypatch.setattr(kernels_mod, "latent_attention_xla",
                        lambda qn, qr, kn, kr, v: real(qn, qr, kn, kr, kn))


def _no_latent_norm(monkeypatch, model):
    monkeypatch.setattr(LatentAttention, "_latent_norm",
                        lambda self, c, w: c)


def _softmax_router(monkeypatch, model):
    for layer in _expert_layers(model):
        monkeypatch.setattr(layer, "scoring", "softmax")


def _no_bias_in_the_choice(monkeypatch, model):
    real = expert_mod.route_top_k
    monkeypatch.setattr(expert_mod, "route_top_k",
                        lambda *a, bias=None, **k: real(*a, **k))


def _no_route_scale(monkeypatch, model):
    for layer in _expert_layers(model):
        monkeypatch.setattr(layer, "route_scale", 1.0)


def _no_shared_expert(monkeypatch, model):
    for layer in _expert_layers(model):
        monkeypatch.setattr(layer, "shared", None)


def _shared_expert_twice(monkeypatch, model):
    for layer in _expert_layers(model):
        real = layer.shared.apply
        monkeypatch.setattr(
            layer.shared, "apply",
            lambda *a, _real=real, **k: (2 * _real(*a, **k)[0], {}))


@pytest.mark.parametrize("take_out", [
    _zero_rotary_part, _values_as_wide_as_keys, _no_latent_norm,
    _softmax_router, _no_bias_in_the_choice, _no_route_scale,
    _no_shared_expert, _shared_expert_twice],
    ids=lambda f: f.__name__.strip("_"))
def test_the_comparison_fails_when_a_piece_is_taken_out(monkeypatch,
                                                        take_out):
    """Each piece of the layer's mathematics, taken out of the PROGRAM
    alone, moves the logits off the reference's by far more than the
    tolerance: the comparison can fail."""
    model = builder.build(CFG)
    params = _perturbed(model.init(jax.random.PRNGKey(0)))
    state = _with_biases(model.init_state(), _biases())
    x, _ = _batch()
    want = _reference_logits(params, x, _biases())
    assert float(jnp.abs(model.apply(params, state, x, training=True)[0]
                         - want).max()) < TOL
    take_out(monkeypatch, model)
    got = model.apply(params, state, x, training=True)[0]
    assert float(jnp.abs(got - want).max()) > 100 * TOL


# --------------------------------------------------------------------------
# the kernels (interpreted) against the jnp path
# --------------------------------------------------------------------------

def _core_inputs(seq, batch=2, heads=3, dn=128, dr=64, dv=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = [(batch, seq, heads, dn), (batch, seq, heads, dr),
              (batch, seq, heads, dn), (batch, seq, dr),
              (batch, seq, heads, dv)]
    return [0.3 * jax.random.normal(k, s) for k, s in zip(ks, shapes)]


@pytest.fixture(scope="module")
def kernel_and_jnp():
    """Outputs and all five gradients of the interpreted kernels and of
    the jnp path under one random cotangent, at the published widths
    (128 | 64 | 128) and 1024 tokens: two q blocks, so the walk to the
    diagonal takes its loop, and three heads share each rotary key."""
    args = _core_inputs(1024)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[4].shape)

    def run(fn):
        return fn(*args), jax.grad(lambda *a: jnp.sum(fn(*a) * ct),
                                   argnums=range(5))(*args)

    return (run(lambda *a: kernels_mod.latent_attention(*a, interpret=True)),
            run(kernels_mod.latent_attention_xla))


def test_attention_kernel_matches_the_jnp_path(kernel_and_jnp):
    (got, _), (want, _) = kernel_and_jnp
    assert got.shape == (2, 1024, 3, 128)
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("arg", range(5),
                         ids=["qN", "qR", "kN", "kR", "v"])
def test_kernel_gradients_match_the_jnp_path(kernel_and_jnp, arg):
    """The one-pass backward; kR's gradient leaves the kernel summed
    over the heads, one row a position."""
    (_, got), (_, want) = kernel_and_jnp
    assert got[arg].shape == want[arg].shape
    assert _rel(got[arg], want[arg]) < 1e-5
    if arg == 3:
        assert got[arg].shape == (2, 1024, 64)


def _pallas_calls(jaxpr, name, found=None):
    """The ``pallas_call`` equations named ``name`` in a jaxpr, nested
    jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params["name"] == name:
                found.append(eqn)
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _pallas_calls(sub, name, found)
    return found


def test_the_softmax_scale_rides_q_not_the_score_tiles(system):
    """The core takes no scale: ``nn.LatentAttention`` multiplies it
    into W_q, and inside the kernel a score tile is two products and
    their sum, which nothing multiplies."""
    import re
    model, params, _ = system
    att = model.modules[1].modules[0].modules[1]
    assert isinstance(att, LatentAttention)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    qn, qr, *_ = att.core_inputs(params["1"]["0"]["1"], u)
    q = (u @ params["1"]["0"]["1"]["q_weight"].T).reshape(1, 16, HEADS, 12)
    np.testing.assert_allclose(qn, q[..., :8] * 12 ** -0.5, rtol=1e-5,
                               atol=1e-6)
    assert qr.shape == (1, 16, HEADS, 4)
    args = _core_inputs(256, heads=2, dn=16, dr=8, dv=16)
    jaxpr = jax.make_jaxpr(lambda *a: kernels_mod.latent_attention(
        *a, interpret=True))(*args)
    (call,) = _pallas_calls(jaxpr.jaxpr, "latent_attention_fwd")
    upto_exp = str(call.params["jaxpr"]).split(" exp ")[0]
    assert "dot_general" in upto_exp
    assert not re.search(r":f32\[[0-9,]+\] = mul ", upto_exp)


def test_the_schedule_is_stated_where_the_kernels_are_traced():
    from bigdl_tpu.observability import trace
    sched = kernels_mod.latent_schedule(8192, 128, 64, 128, 2)
    assert (sched.bq, sched.bk, sched.bwd_bk, sched.block) \
        == (512, 2048, 1024, 256)
    # kN, kR (64 lanes padded to 128) and v of one head, two buffers each
    assert sched.fwd_resident_bytes == 3 * 2 * 8192 * 128 * 2
    # + as much for the three outputs and three float32 accumulators
    assert sched.bwd_resident_bytes == 36 * 2 ** 20
    assert sched.tiles_computed == 32 * 33 // 2
    with pytest.raises(ValueError, match="does not fit VMEM"):
        kernels_mod.latent_schedule(32768, 128, 64, 128, 2)
    args = _core_inputs(256, heads=2, dn=16, dr=8, dv=16)
    trace.clear()
    trace.enable()
    try:
        jax.eval_shape(lambda *a: kernels_mod.latent_attention(
            *a, interpret=True), *args)
        events = [e for e in trace.to_dict()["traceEvents"]
                  if e["name"] == "latent_schedule"]
    finally:
        trace.disable()
    assert len(events) == 1 and events[0]["cat"] == "kernels"
    assert events[0]["args"]["heads"] == 2
    assert events[0]["args"]["qk_rope"] == 8


# --------------------------------------------------------------------------
# the router, its bias and the shared expert
# --------------------------------------------------------------------------

D, F, TOTAL, TOP = 16, 8, 16, 3


def _share(held, offset, params=None, seed=0, **kw):
    kw = dict(dict(scoring="sigmoid", route_scale=2.446,
                   bias_update_rate=0.01, shared_width=2 * F), **kw)
    layer = ExpertShare(D, F, TOTAL, TOP, experts_held=held,
                        experts_offset=offset, **kw)
    if params is None:
        params = ExpertShare(D, F, TOTAL, TOP, **kw).init(
            jax.random.PRNGKey(seed))
    mine = {k: (v[offset:offset + held] if k.endswith("_weight")
                and k != "router_weight" else v) for k, v in params.items()}
    return layer, mine


def _ref_weights(params):
    return {"router_w": params["router_weight"],
            "gate_w": params["gate_weight"], "up_w": params["up_weight"],
            "down_w": params["down_weight"],
            "sh_gate_w": params["shared"]["gate_weight"],
            "sh_up_w": params["shared"]["up_weight"],
            "sh_down_w": params["shared"]["down_weight"]}


def _spec(offset):
    return reference.Spec(qk_nope=1, qk_rope=1, experts_total=TOTAL,
                          experts_offset=offset, experts_per_token=TOP,
                          route_scale=2.446, rope_theta=1.0, eps=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of two routed experts each, every one computing the
    shared expert whole: the routed parts, and the shared expert counted
    ONCE, sum to the layer that holds all sixteen — in the program and
    in the reference."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, D))
    flat = x.reshape(-1, D)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (TOTAL,))
    whole_layer, whole = _share(TOTAL, 0)
    state = dict(whole_layer.init_state(), moe_bias=bias)
    want = whole_layer.apply(whole, state, x)[0]
    shared = reference.shared_expert(_ref_weights(whole), flat) \
        .reshape(x.shape)
    total = shared
    for chip in range(8):
        layer, mine = _share(2, 2 * chip, whole)
        y, _ = layer.apply(mine, state, x)
        ref = reference.feed_forward(_ref_weights(mine), flat,
                                     _spec(2 * chip), bias).reshape(x.shape)
        assert float(jnp.abs(y - ref).max()) < 1e-5
        total = total + (y - shared)
    assert float(jnp.abs(total - want).max()) < 1e-5
    assert float(jnp.abs(want - reference.feed_forward(
        _ref_weights(whole), flat, _spec(0), bias).reshape(x.shape)
    ).max()) < 1e-5
    # counted on every chip it would be there eight times
    assert float(jnp.abs(shared).max()) > 1e-2


def test_the_bias_moves_the_choice_and_not_the_weights():
    layer, params = _share(TOTAL, 0)
    tokens = jax.random.normal(jax.random.PRNGKey(1), (64, D))
    plain_top, plain_c = layer.route(params, tokens)
    bias = jnp.zeros((TOTAL,)).at[5].set(10.0)
    top, c = layer.route(params, tokens, bias)
    # every token now chooses expert 5 ...
    assert bool(jnp.all(jnp.any(top == 5, axis=-1)))
    assert not bool(jnp.all(jnp.any(plain_top == 5, axis=-1)))
    # ... and weighs its choice by the scores alone: sigmoid, normalised
    # over the chosen, times 2.446 — the bias is in none of it
    z = jax.nn.sigmoid(tokens @ params["router_weight"].T)
    chosen = jnp.take_along_axis(z, top, axis=-1)
    np.testing.assert_allclose(
        c, 2.446 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(c.sum(-1), 2.446, rtol=1e-6)
    ref_top, ref_c = reference.route(_ref_weights(params), tokens, _spec(0),
                                     bias)
    np.testing.assert_array_equal(top, ref_top)
    np.testing.assert_allclose(c, ref_c, rtol=1e-6)


def test_no_gradient_reaches_the_bias():
    layer, params = _share(4, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, D))
    state = dict(layer.init_state(),
                 moe_bias=0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                                  (TOTAL,)))
    g = jax.grad(lambda b: jnp.sum(layer.apply(
        params, dict(state, moe_bias=b), x, training=True)[0] ** 2))(
            state["moe_bias"])
    assert float(jnp.abs(g).max()) == 0.0
    # and the router's weight does get one, through the scores
    gw = jax.grad(lambda p: jnp.sum(layer.apply(
        p, state, x, training=True)[0] ** 2))(params)["router_weight"]
    assert float(jnp.abs(gw).max()) > 0


def test_equal_scores_choose_the_lower_expert_number():
    scores_of = jnp.zeros((D, 6))                 # every logit equal
    _, top_p, top = expert_mod.route_top_k(
        jnp.ones((3, D)), scores_of, 2, scoring="sigmoid",
        bias=jnp.zeros((6,)))
    np.testing.assert_array_equal(top, [[0, 1]] * 3)
    np.testing.assert_allclose(top_p, 0.5)
    with pytest.raises(ValueError, match="scoring"):
        expert_mod.route_top_k(jnp.ones((3, D)), scores_of, 2,
                               scoring="tanh")


def test_a_step_updates_the_bias_from_its_own_counts():
    """Training hands on bias + rate sign(mean - count) over ALL
    experts, held here or not; evaluation hands the bias on unchanged;
    the two balance figures ride beside it."""
    layer, params = _share(4, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, D))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (TOTAL,))
    state = dict(layer.init_state(), moe_bias=bias)
    new = layer.apply(params, state, x, training=True)[1]
    assert set(new) == set(state)
    top = layer.route(params, x.reshape(-1, D), bias)[0]
    counts = np.bincount(np.asarray(top).reshape(-1), minlength=TOTAL)
    assert counts.sum() == 64 * TOP
    np.testing.assert_allclose(
        new["moe_bias"], bias + 0.01 * np.sign(counts.mean() - counts),
        rtol=1e-6)
    np.testing.assert_allclose(new["moe_bias"], reference.bias_update(
        bias, top, 0.01), rtol=1e-6)
    assert float(new["moe_load_max_over_mean"]) == pytest.approx(
        counts.max() / counts.mean())
    assert float(new["moe_bias_abs_max"]) == pytest.approx(
        float(jnp.abs(new["moe_bias"]).max()))
    kept = layer.apply(params, state, x, training=False)[1]
    np.testing.assert_array_equal(kept["moe_bias"], bias)
    stats = expert_mod.moe_state_stats({"3": {"1": new}})
    assert set(stats["3/1"]) == set(expert_mod.SHARE_STATE_KEYS
                                    + expert_mod.BALANCE_STATE_KEYS)


def test_replicas_of_the_one_program_hold_one_bias():
    """The training step is ONE jit program over the global batch: with
    the tokens split over a ``data`` mesh axis the expert counts are
    still the whole batch's, so every replica hands on the bias one
    device would. A shard counted ALONE gives another bias: what a step
    mapped per shard would have to reconcile (ROADMAP B4)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, D))
    layer, params = _share(4, 4)
    state = layer.init_state()
    want = layer.apply(params, state, x, training=True)[1]
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    got = jax.jit(lambda p, s, xb: layer.apply(p, s, xb, training=True)[1])(
        params, state, jax.device_put(x, NamedSharding(mesh, P("data"))))
    np.testing.assert_allclose(got["moe_bias"], want["moe_bias"])
    assert got["moe_bias"].sharding.is_fully_replicated
    assert float(got["moe_load_max_over_mean"]) == pytest.approx(
        float(want["moe_load_max_over_mean"]))
    halves = [layer.apply(params, state, x[i:i + 1], training=True)[1]
              for i in range(2)]
    assert not np.allclose(halves[0]["moe_bias"], halves[1]["moe_bias"])


@pytest.mark.parametrize("rows", [None, 48])
def test_rows_for_experts_elsewhere_cost_no_product_and_change_nothing(
        monkeypatch, rows):
    """This router's layer (sigmoid, a bias in the choice, the shared
    expert beside the routed ones) with a chunk's rows for experts
    elsewhere in the products' last group, as ``_chunk`` hands them
    over, against the same rows riding in the last held expert's group
    (commit 0aa00c8): result, the state a training step hands on and
    every gradient are equal, cut where the module's own rule cuts the
    384 rows (a first span of 192 that holds the live ~96) and cut at
    48, which they pass so that the second span of 336 runs; the two
    state keys say what part of the spans that ran the products
    multiplied."""
    if rows is not None:
        monkeypatch.setattr(expert_mod, "_chunk_rows", lambda *a: rows)
    layer, params = _share(4, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, D))
    state = dict(layer.init_state(),
                 moe_bias=0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                                  (TOTAL,)))
    real = expert_mod.grouped_matmul

    def riders(x, w, group_sizes):
        return real(x, w, group_sizes.at[3].add(group_sizes[4]).at[4].set(0))

    def run(product):
        monkeypatch.setattr(expert_mod, "grouped_matmul", product)
        (_, (y, new)), grads = jax.value_and_grad(
            lambda p, x: (lambda y, st: (jnp.sum(y ** 2), (y, st)))(
                *layer.apply(p, state, x, training=True)), argnums=(0, 1),
            has_aux=True)(params, x)
        return y, new, jax.tree.leaves(grads)

    y, new, grads = run(real)
    was_y, was_new, was_grads = run(riders)
    assert float(jnp.abs(y - was_y).max()) <= 1e-6 * float(jnp.abs(y).max())
    assert new.keys() == was_new.keys()
    for key in new:
        np.testing.assert_array_equal(new[key], was_new[key])
    for a, b in zip(grads, was_grads):
        assert float(jnp.abs(b).max()) > 0 and _rel(a, b) < 1e-6
    top = layer.route(params, x.reshape(-1, D), state["moe_bias"])[0]
    live = int(((top >= 4) & (top < 8)).sum())
    each = rows or expert_mod._chunk_rows(128 * TOP, 4, TOTAL, True)
    assert 64 < live < 128
    ran, worked_on = (2, 128 * TOP) if live > each else (1, each)
    assert ran == (2 if rows else 1)
    assert float(new["moe_chunks_run"]) == ran
    assert float(new["moe_product_row_share"]) == pytest.approx(
        live / worked_on)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "megablox-interpreted"])
@pytest.mark.parametrize("routing", [
    "live < first", "live == first", "live == first + 1",
    "everything to one held expert", "nothing here"])
def test_two_spans_are_the_one_span_layer(two_spans_against_one, routing,
                                          interpret):
    """This router's layer (sigmoid, a bias in the choice that the step
    updates, the shared expert beside the routed ones; 2 of 16 held, 3 a
    token, 64 tokens: a first span of 48 of the 192 rows by the module's
    own rule) against the same layer with all rows in one span: result,
    the state a training step hands on and every gradient, whether the
    live rows stay inside the first span, fill it to the row, pass it by
    one, are all one held expert's (64: the second span runs), or are
    none (the shared expert's part is all there is)."""
    layer, params = _share(2, 8)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, D))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (TOTAL,))
    if routing == "everything to one held expert":
        bias = bias.at[8].set(10.0).at[9].set(-10.0)
    elif routing == "nothing here":
        bias = bias.at[8:10].set(-10.0)
    state = dict(layer.init_state(), moe_bias=bias)
    top = layer.route(params, x.reshape(-1, D), bias)[0]
    live = int(((top >= 8) & (top < 10)).sum())
    cut_at = {"live == first": live, "live == first + 1": live - 1}.get(
        routing)
    new, ran, found, first = two_spans_against_one(
        layer, params, state, x, cut_at, interpret)
    assert found == live and first == (cut_at or 48)
    assert (ran, live) == {
        "live < first": (1, live), "live == first": (1, first),
        "live == first + 1": (2, first + 1),
        "everything to one held expert": (2, 64),
        "nothing here": (1, 0)}[routing]
    assert 0 < live < 48 or routing in ("everything to one held expert",
                                        "nothing here")
    assert new["moe_bias_abs_max"] > 0      # the step's update is in it


def test_keyes_expert_share_lowers_to_the_parents_text():
    """``route_top_k`` gained a scoring and a bias; the softmax router
    without a bias — the keye cell's — must lower, forward and backward,
    to ONE pinned text whatever the other router's arguments grow into.
    The digest is of PR 36's program, the commit that follows 96f00a2,
    made by this very code. It differs from the parent's in the
    telemetry's two scalars alone (``moe_chunks_run`` is 1 + (the live
    rows passed the first span) where it was a rounded-up division):
    with no bias to balance it this router's first span stays at four
    times the balanced share, here all 192 rows in one span as before,
    and every other line of the text is the parent's.
    Through PR 35 it was 208df886...7e541b, PR 34's, the commit that
    follows 0aa00c8 (a chunk's rows for experts elsewhere became the
    products' last group); through PR 33 58ca92f9...af5e0f7, commit
    7dc00b9's."""
    layer = ExpertShare(16, 8, 16, 4, experts_held=4, experts_offset=4)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 24, 16), jnp.float32)

    def loss(p, x):
        y, st = layer.apply(p, layer.init_state(), x, training=True)
        return jnp.sum(y), st

    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
            params, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "e97853ad9ccbd12e9c394982dce521cd58e9b903d4f1f20262720ca36640d974"
    assert set(layer.init_state()) == set(expert_mod.SHARE_STATE_KEYS)


# --------------------------------------------------------------------------
# the model as built: parameters, recomputation, tracing, the optimizer
# --------------------------------------------------------------------------

def test_kimi_lm_counts_the_cells_parameters():
    """At the published widths a layer's share is 100,405,760
    parameters, the dense layer 82,973,184, embedding, head and final
    norm 83,888,128 (ISSUE 33's arithmetic): 668,890,112 in all."""
    from bigdl_tpu.models import KimiLM
    model = KimiLM(vocab_size=20480, num_layers=2, experts_held=8,
                   experts_offset=24)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    assert count(shapes["1"]["0"]["1"]) == 13_763_072       # attention
    assert count(shapes["1"]) == 82_973_184
    assert count(shapes["2"]) == 100_405_760
    assert count(shapes["2"]["1"]["1"]["shared"]) == 17_301_504
    assert sum(count(shapes[i]) for i in "034") == 83_888_128
    assert 82_973_184 + 5 * 100_405_760 + 83_888_128 == 668_890_112
    assert model.remat_policy == "per_block"
    from bigdl_tpu.models.transformer.model import decode_meta
    with pytest.raises(ValueError, match="LatentAttention"):
        decode_meta(model)


@pytest.fixture()
def on_kernels(monkeypatch):
    """``LatentAttention`` on its Pallas kernels, interpreted: the path
    it takes on the TPU."""
    monkeypatch.setattr(
        kernels_mod, "latent_attention_xla",
        lambda *a: kernels_mod.latent_attention(*a, interpret=True))


def _loss(model, state, x, t):
    crit = builder.criterion()
    return lambda p: crit.apply(model.apply(p, state, x, training=True)[0],
                                t)


def _arrays_made(jaxpr, found=None):
    """(primitive, shape) of every value a jaxpr makes, nested jaxprs
    (a checkpoint region, a custom_vjp) included, a Pallas kernel's own
    body — its tiles live in VMEM — not."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found += [(eqn.primitive.name, tuple(v.aval.shape))
                  for v in eqn.outvars if hasattr(v.aval, "shape")]
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _arrays_made(sub, found)
    return found


def test_no_broadcast_key_and_no_sequence_squared_array_on_the_kernels(
        system, on_kernels):
    """The lowered step, forward and backward: the kernels take the
    rotary key as ONE (B, S, 4) row a position and return its gradient
    so; nothing broadcasts it over the heads, no key of width 8 + 4
    exists, and no array has two axes of the sequence's length."""
    model, params, state = system
    x, t = _batch(seq=256, rows=2)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(model, state, x, t)))(params).jaxpr
    made = _arrays_made(jaxpr)
    assert [shape for _, shape in made if shape.count(256) >= 2] == []
    heads, dn, dr = HEADS, 8, 4
    keyed = [(2, 256, heads, dn + dr), (2 * heads, 256, dn + dr),
             (2, heads, 256, dn + dr)]
    # what has that shape is q (the projection's output, reshaped) and
    # q's gradient (its two parts padded and added): nothing puts a
    # rotary part beside a content part, as a broadcast key would be made
    wide = {name for name, shape in made if shape in keyed}
    assert wide == {"reshape", "pad", "add_any"}
    assert not [s for name, s in made if name == "broadcast_in_dim"
                and len(s) == 4 and s[-1] == dr and heads in s[:-1]]
    fwd = _pallas_calls(jaxpr, "latent_attention_fwd")
    bwd = _pallas_calls(jaxpr, "latent_attention_dqdkdv")
    assert len(fwd) == len(bwd) == LAYERS
    assert [tuple(v.aval.shape) for v in fwd[0].invars] == [
        (8, 256, dn), (8, 256, dr), (8, 256, dn), (2, 256, dr),
        (8, 256, 8)]
    assert [tuple(v.aval.shape) for v in bwd[0].outvars] == [
        (8, 256, dn), (8, 256, dr), (8, 256, dn), (2, 256, dr),
        (8, 256, 8)]


def test_a_recomputed_block_runs_the_attention_forward_once(
        system, on_kernels, kernel_calls):
    """In the gradient's jaxpr under ``per_block`` each layer has ONE
    ``latent_attention_fwd`` (what it made is named and kept) and one
    backward kernel; without the names it would run twice."""
    model, params, state = system
    x, t = _batch(seq=128, rows=1)
    assert model.remat_policy == "per_block"
    calls = kernel_calls(jax.make_jaxpr(jax.grad(_loss(
        model, state, x, t)))(params).jaxpr)
    assert calls == dict(latent_attention_fwd=LAYERS,
                         latent_attention_dqdkdv=LAYERS)


def test_on_the_kernels_recomputation_is_bit_identical_to_none(system,
                                                               on_kernels):
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


def test_a_chunks_forward_made_again_carries_the_mark_its_backward_not(
        system):
    """``step.recompute_ms``'s pattern on this model's compiled
    ``op_name``s (tests/test_keye.py has the sparse-attention site): the
    chunk's forward inside ``_in_chunks_bwd`` and what
    ``jax.checkpoint`` makes again carry the mark; the chunk's backward
    products (under ``pullback``), the forward pass and the shared
    expert's own backward do not; both lie where ``step.moe_routed_ms``
    and ``step.backward_ms`` read."""
    import re
    from benchmarks import manifest

    def pattern(metric):
        return re.compile(manifest.data_file(
            "layer_metrics", metric)["params"]["include"])

    model, params, state = system
    x, t = _batch()
    names = set(re.findall(r'op_name="([^"]*)"', jax.jit(jax.grad(_loss(
        model, state, x, t))).lower(params).compile().as_text()))
    mark = pattern("step.recompute_ms")
    marked = {n for n in names if mark.search(n)}
    # (the CPU's compiler spells a ragged product as plain ones)
    assert any("/moe_experts/recompute/" in n and n.endswith("dot_general")
               for n in marked)
    assert any("/checkpoint/rematted_computation/" in n for n in marked)
    assert not [n for n in marked if "transpose(" not in n]
    pulled = {n for n in names if "/moe_experts/pullback/" in n}
    assert any(n.endswith("dot_general") for n in pulled)
    assert any("/recompute/" in n for n in pulled)    # a custom_vjp's rule
    assert not pulled & marked
    assert not [n for n in marked if "/moe_shared/" in n
                and "rematted_computation" not in n]
    routed, backward = pattern("step.moe_routed_ms"), pattern(
        "step.backward_ms")
    for n in pulled | {n for n in names if "/recompute/" in n}:
        assert routed.search(n) and backward.search(n), n


def test_what_the_layers_are_is_stated_where_they_are_traced(system,
                                                             on_kernels):
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
    # a layer keeps o (H x 128 x v_dim) and its row logsumexp
    a_layer = 4 * (HEADS * 128 * CFG["v_head_dim"] + HEADS * 128)
    assert said["per_block"] == [[0, 0]] + [[2, a_layer]] * LAYERS \
        + [[0, 0], [0, 0]]
    mla = [e["args"] for e in events if e["name"] == "latent_attention"]
    assert mla[0] == dict(seq=128, heads=HEADS, qk_nope=8, qk_rope=4,
                          v_dim=8, kv_rank=16, causal_pairs=128 * 129 // 2,
                          materialised_bytes=HEADS * 128 * 128 * 4)
    moe = [e["args"] for e in events if e["name"] == "moe_share"]
    assert moe[0] == dict(experts_total=8, experts_held=4, top_k=3,
                          tokens=128, expected_local_assignments=192.0,
                          chunk_rows=384, chunks=1, rest_rows=0,
                          scoring="sigmoid", shared_width=32,
                          bias_update_rate=0.05)


@pytest.mark.parametrize("backend,written", [
    ("tpu", 0), ("cpu", 2 * HEADS * 128 * 128 * 4)])
def test_the_instant_states_what_the_path_taken_writes(monkeypatch, backend,
                                                       written):
    """``materialised_bytes`` is the path's own: nothing on the kernels
    (no score array, no key copied over the heads), a float32 score for
    every pair of positions a head on the ``jax.numpy`` path."""
    from bigdl_tpu.observability import trace
    layer = LatentAttention(32, HEADS, 8, 4, 8, 16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    trace.clear()
    trace.enable()
    try:
        jax.eval_shape(lambda p, x: layer.apply(p, {}, x)[0], params,
                       jax.ShapeDtypeStruct((2, 128, 32), jnp.float32))
        events = trace.to_dict()["traceEvents"]
    finally:
        trace.disable()
    (said,) = [e["args"] for e in events if e["name"] == "latent_attention"]
    assert said["materialised_bytes"] == written
    assert said["causal_pairs"] == 2 * 128 * 129 // 2


def test_the_optimizer_trains_the_model_and_steps_the_bias():
    """Through ``Optimizer``, as the other models: the loss falls, and
    after three steps at learning rate ZERO (the weights stand still, so
    the reference can follow) every expert layer's bias equals the
    reference's update rule applied three times to its own choices."""
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import SGD, Optimizer
    from bigdl_tpu.optim.trigger import max_iteration
    x, t = _batch(rows=4)

    def train(lr, steps):
        model = builder.build(CFG)
        model.materialize(jax.random.PRNGKey(0))
        data = DataSet.iterator(
            lambda: iter([MiniBatch(np.asarray(x), np.asarray(t))] * steps),
            size=4 * steps)
        losses = []

        class Log:
            def add_scalar(self, name, value, step):
                if name == "Loss":
                    losses.append(float(value))

        opt = Optimizer(model, data, builder.criterion())
        opt.set_optim_method(SGD(learning_rate=lr))
        opt.set_train_summary(Log())
        opt.set_end_when(max_iteration(steps))
        opt.optimize()
        return model, losses

    model, losses = train(0.5, 8)
    assert losses[-1] < losses[0]
    model, _ = train(0.0, 3)
    w = builder.reference_weights(model.params, CFG)
    total = CFG["published"]["n_routed_experts"]
    biases = [jnp.zeros((total,))] * (LAYERS - 1)
    moved = 0
    for _ in range(3):
        chosen = reference.chosen(w, x - 1, HEADS, biases=biases)
        after = [reference.bias_update(b, c, CFG["bias_update_rate"])
                 for b, c in zip(biases, chosen)]
        moved += sum(int(not np.array_equal(
            c, reference.chosen(w, x - 1, HEADS, biases=after)[n]))
            for n, c in enumerate(chosen))
        biases = after
    assert moved > 0          # the bias did change a later step's choice
    got = [model.state[str(1 + n)]["1"]["1"]["moe_bias"]
           for n in range(1, LAYERS)]
    for a, b in zip(got, biases):
        np.testing.assert_allclose(a, b, atol=1e-7)
        assert float(jnp.abs(b).max()) > 0.04

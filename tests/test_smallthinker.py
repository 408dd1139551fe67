"""SmallThinker-21BA3B-Instruct's language model against the plain
float32 reference (benchmarks/reference/smallthinker.py) at small sizes
on the CPU: the sliding-window flash kernels interpreted against the
XLA path's mask, forward and all three gradients, looped and streamed;
their schedule's counts by hand; the ReGLU expert kind against a plain
loop over experts, routing from a second tensor and the shares of a
layer summed; the whole model's loss and every gradient leaf; and each
of the three controls (window dropped, RoPE in a global layer, the
router fed h) failing the comparison.

Tolerances: the kernel and the XLA path share the float32 inputs and
differ in summation order only (online softmax in tiles against one
softmax): 2e-5 absolute on values and gradients of unit-scale inputs.
The model against the reference: both float32 under ``highest``, 1e-5
relative on the loss, 1e-4 on each gradient leaf (tests/test_nemotron.py
has the same two)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, model_setup
from benchmarks.builders import smallthinker as builder
from benchmarks.kinds import train
from benchmarks.reference import smallthinker as ref
from bigdl_tpu.ops.pallas import flash_attention as fa
from bigdl_tpu.parallel import sequence as seq
from bigdl_tpu.parallel.expert import ExpertShare
from bigdl_tpu.tensor import DTypePolicy, policy_scope

FULL = manifest.data_file("configs", "smallthinker-21ba3b-instruct")
CFG = {**FULL, **FULL["rehearsal"], "sliding_window_size": 16}
HEADS = CFG["num_attention_heads"]
VALUE_TOL, GRAD_TOL = 1e-5, 1e-4
KERNEL_TOL = 2e-5


@pytest.fixture(autouse=True)
def _float32_policy():
    f32 = jnp.dtype("float32")
    with policy_scope(DTypePolicy(param_dtype=f32, compute_dtype=f32,
                                  activation_dtype=f32)), \
            jax.default_matmul_precision("highest"):
        yield


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a, jnp.float32) - b)
                 / jnp.linalg.norm(b))


# -- the window kernels ----------------------------------------------------

def _qkv(s, h=2, d=64, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(key, (1, s, h, d), jnp.float32) for key in k]


@pytest.fixture(params=["looped", "streamed"])
def path(request, monkeypatch):
    """The looped kernels (K/V of a head resident, one backward pass) or
    the streamed ones (a banded grid, dq + dk/dv): the second by a
    residency budget nothing fits."""
    fa._fwd_call.cache_clear()
    fa._bwd_calls.cache_clear()
    if request.param == "streamed":
        monkeypatch.setattr(fa, "_RESIDENT_BUDGET", 0)
    yield request.param
    fa._fwd_call.cache_clear()
    fa._bwd_calls.cache_clear()


@pytest.mark.parametrize("s,window", [
    (512, 256),      # the tile's multiple (bq 256 looped)
    (512, 300),      # not: the edge cuts two blocks
    (1024, 512),     # a multiple of the 512 tile, several walks
    (1024, 700),
    (512, 100),      # narrower than a tile
    (512, 1)])       # a row sees itself alone
def test_the_window_kernel_matches_the_xla_mask(path, s, window):
    q, k, v, ct = _qkv(s)
    sched = fa._schedule(True, s, s, 64, 4, window)
    assert sched.kv_resident == (path == "looped")

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  interpret=True)

    def plain(q, k, v):
        return seq.dot_product_attention(q, k, v, causal=True, flash=False,
                                         window=window)

    assert float(jnp.max(jnp.abs(kernel(q, k, v) - plain(q, k, v)))) \
        < KERNEL_TOL
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * ct), (0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        assert float(jnp.max(jnp.abs(g - w))) < KERNEL_TOL, name


def test_a_window_past_the_sequence_is_the_causal_kernel(path):
    q, k, v, ct = _qkv(512, d=128, seed=1)
    wide = fa.flash_attention(q, k, v, causal=True, window=4096,
                              interpret=True)
    causal = fa.flash_attention(q, k, v, causal=True, interpret=True)
    assert float(jnp.max(jnp.abs(wide - causal))) < KERNEL_TOL
    got = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=True, window=512, interpret=True) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=True, interpret=True) * ct), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < KERNEL_TOL


def test_gqa_heads_through_the_module_on_the_kernel(monkeypatch):
    """28 / 4 heads scaled down to 7 / 1: the module repeats the key
    head over its query group before the kernel, windowed with RoPE and
    global without, as the XLA path computes them."""
    from bigdl_tpu import nn

    def run(window, rope, on_kernel):
        mha = nn.MultiHeadAttention(64, 7, causal=True, with_bias=False,
                                    rope=rope, num_kv_heads=1, head_dim=64,
                                    window=window, rope_theta=1.5e6)
        p = mha.init(jax.random.PRNGKey(3))
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 64))
        if on_kernel:
            kernel = fa.flash_attention
            monkeypatch.setattr(seq, "dot_product_attention",
                                lambda q, k, v, causal, window=None: kernel(
                                    q, k, v, causal=causal, window=window,
                                    interpret=True))
        out = jax.value_and_grad(lambda p: jnp.sum(jnp.sin(
            mha.apply(p, {}, x)[0])))(p)
        monkeypatch.undo()
        return out

    for window, rope in ((96, True), (None, False)):
        (a, ga), (b, gb) = run(window, rope, True), run(window, rope, False)
        assert abs(float(a - b)) < 1e-4
        for name in ga:
            assert _rel(ga[name], gb[name]) < 1e-4, name


def test_the_window_is_refused_where_it_is_not_supported():
    q, k, v, _ = _qkv(256)
    with pytest.raises(ValueError, match="causal"):
        seq.dot_product_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa._schedule(True, 256, 512, 64, 4, 8)
    for core in (seq.ring_attention, seq.ulysses_attention):
        with pytest.raises(ValueError, match="ROADMAP B6"):
            core(q, k, v, causal=True, window=8)


def test_the_schedules_counts_by_hand():
    """The looped walk at the cell's shape: 512-row q blocks, 256-sided
    squares, W = 4096 = 8 blocks, so each q block walks 7 blocks inside
    the window, ONE the edge cuts and the diagonal's 3 squares of 4:
    4 x 8 + 3 = 35 squares, fewer for the first 8 q blocks."""
    s = fa._schedule(True, 16384, 16384, 128, 2, 4096)
    assert (s.bq, s.block, s.window) == (512, 256, 4096)
    assert s.kv_resident and not s.one_pass_backward
    squares = sum(3 + 4 * min(qi, 8) for qi in range(32))
    assert s.tiles_computed == squares == 976
    pairs = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert s.tiles_visible == pairs / 256 ** 2
    assert s.tiles_computed / s.tiles_visible == pytest.approx(1.0892, 1e-4)
    # the streamed backward's bands: 5 K blocks of 1024 a q block, 10 q
    # blocks of 512 a K block
    assert fa._bands(16384, s.bwd_bq, s.bwd_bk, 4096) == (5, 10)
    # a window the tile does not divide: the edge cuts two blocks
    assert fa._inside_and_edges(512, 4096) == (7, 1)
    assert fa._inside_and_edges(512, 700) == (0, 2)
    assert fa._inside_and_edges(512, 100) == (0, 1)
    causal = fa._schedule(True, 16384, 16384, 128, 2)
    assert causal.window is None and causal.tiles_visible == \
        16384 * 16385 / 2 / 256 ** 2


def test_the_streamed_count_by_hand(monkeypatch):
    monkeypatch.setattr(fa, "_RESIDENT_BUDGET", 0)
    s = fa._schedule(True, 2048, 2048, 64, 4, 700)
    assert (s.bq, s.bk, s.kv_resident) == (512, 1024, False)
    # q block i sees K blocks (of 1024) max(512 i - 699, 0) // 1024 ..
    # (512 i + 511) // 1024: 0..0, 0..0, 0..1, 0..1
    assert s.tiles_computed == 1 + 1 + 2 + 2
    assert s.tiles_visible == (700 * 701 / 2 + (2048 - 700) * 700) \
        / (512 * 1024)


def test_the_schedule_is_stated_with_its_window():
    from bigdl_tpu.observability import trace
    q, k, v, _ = _qkv(512)
    trace.clear()
    trace.enable()
    try:
        jax.eval_shape(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=128, interpret=True), q, k, v)
        events = trace.to_dict()["traceEvents"]
    finally:
        trace.disable()
    (got,) = [e["args"] for e in events if e["name"] == "flash_schedule"]
    assert got["window"] == 128
    assert got["tiles_visible"] == (128 * 129 / 2 + 384 * 128) / 256 ** 2
    # ONE q block of 512 rows: the diagonal's 3 squares of 256 alone
    assert (got["bq"], got["block"], got["tiles_computed"]) == (512, 256, 3)


# -- the ReGLU expert share --------------------------------------------------

def _share(held, offset, params=None, seed=0):
    layer = ExpertShare(16, 8, 8, 3, experts_held=held,
                        experts_offset=offset, expert_act="relu")
    return layer, (layer.init(jax.random.PRNGKey(seed)) if params is None
                   else params)


def _dense_reglu_moe(params, h, r, offset=0, k=3):
    """Every held expert on every token's h, weighted by a softmax top-k
    over r's logits renormalised over the k: a plain loop over experts."""
    p = jax.nn.softmax(r @ params["router_weight"].T, axis=-1)
    c, top = jax.lax.top_k(p, k)
    c = c / jnp.sum(c, -1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(params["up_weight"].shape[0]):
        g = jnp.sum(jnp.where(top == offset + e, c, 0.0), -1, keepdims=True)
        out = out + g * ((jax.nn.relu(h @ params["gate_weight"][e].T)
                          * (h @ params["up_weight"][e].T))
                         @ params["down_weight"][e].T)
    return out


def test_the_reglu_kind_matches_a_loop_over_experts():
    layer, params = _share(4, 2)
    assert set(params) == {"gate_weight", "up_weight", "down_weight",
                           "router_weight"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))
    y, _ = layer.apply(params, layer.init_state(), x)
    flat = x.reshape(-1, 16)
    assert _rel(y.reshape(-1, 16), _dense_reglu_moe(params, flat, flat,
                                                    offset=2)) < VALUE_TOL
    g = jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply(p, layer.init_state(), x)[0])))(params)
    g_ref = jax.grad(lambda p: jnp.sum(jnp.sin(_dense_reglu_moe(
        p, flat, flat, offset=2))))(params)
    for (path, a), b in zip(jax.tree.leaves_with_path(g),
                            jax.tree.leaves(g_ref)):
        assert _rel(a, b) < GRAD_TOL, jax.tree_util.keystr(path)


def test_routing_from_a_second_tensor():
    """The router reads r, the experts h: the choice follows r alone,
    and both tensors get their own gradients."""
    layer, params = _share(8, 0, seed=2)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 20, 16))
    r = jax.random.normal(jax.random.PRNGKey(4), (1, 20, 16))
    state = layer.init_state()

    def share(h, r):
        return layer.apply(params, state, h, route_from=r)[0]

    want = _dense_reglu_moe(params, h[0], r[0])
    assert _rel(share(h, r)[0], want) < VALUE_TOL
    # routed from h it is another layer
    assert _rel(layer.apply(params, state, h)[0][0], want) > 0.05
    gh, gr = jax.grad(lambda h, r: jnp.sum(jnp.sin(share(h, r))),
                      (0, 1))(h, r)
    wh, wr = jax.grad(lambda h, r: jnp.sum(jnp.sin(_dense_reglu_moe(
        params, h[0], r[0]))), (0, 1))(h, r)
    assert _rel(gh, wh) < GRAD_TOL and _rel(gr, wr) < GRAD_TOL
    with pytest.raises(ValueError, match="route_from"):
        layer.apply(params, state, h, route_from=r[:, :10])


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's share test: eight chips of two experts each of a
    16-expert toy layer; summed, the shares give the uncut reference
    layer's experts."""
    whole_layer = ExpertShare(16, 8, 16, 3, expert_act="relu")
    whole = whole_layer.init(jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 40, 16))
    r = jax.random.normal(jax.random.PRNGKey(7), (1, 40, 16))
    total = 0.0
    for chip in range(8):
        layer = ExpertShare(16, 8, 16, 3, experts_held=2,
                            experts_offset=2 * chip, expert_act="relu")
        params = dict(whole, **{k: v[2 * chip:2 * chip + 2]
                                for k, v in whole.items()
                                if k != "router_weight"})
        total = total + layer.apply(params, layer.init_state(), h,
                                    route_from=r)[0]
    spec = ref.Spec(layout=((True, True),), num_kv_heads=1, window=4,
                    rope_theta=1e4, experts_total=16, experts_offset=0,
                    experts_per_token=3, eps=1e-6)
    lw = {"router_w": whole["router_weight"], "gate_w": whole["gate_weight"],
          "up_w": whole["up_weight"], "down_w": whole["down_weight"]}
    assert _rel(total[0], ref.experts(lw, h[0], r[0], spec)) < VALUE_TOL
    uncut = whole_layer.apply(whole, whole_layer.init_state(), h,
                              route_from=r)[0]
    assert _rel(total, uncut) < VALUE_TOL


# -- the model against the reference ---------------------------------------

@pytest.fixture(scope="module")
def system():
    """The program's model in float32 at rehearsal size with a window of
    16 over 64 tokens, every leaf off its initial value, a (2, 64)
    batch."""
    f32 = jnp.dtype("float32")
    with policy_scope(DTypePolicy(param_dtype=f32, compute_dtype=f32,
                                  activation_dtype=f32)):
        model = builder.build(CFG)
        model_setup.materialize_lean(model, 5)
        flat, tree = jax.tree_util.tree_flatten_with_path(model.params)
        keys = jax.random.split(jax.random.PRNGKey(9), len(flat))
        model.sync(jax.tree.unflatten(tree, [
            x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
            for (_, x), k in zip(flat, keys)]), model.init_state())
    toks = np.random.default_rng(0).integers(1, CFG["vocab_size"] + 1,
                                             size=(2, 65))
    return model, toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def _loss(model, data, labels):
    crit = builder.criterion()
    return lambda p: crit.apply(model.apply(p, model.state,
                                            jnp.asarray(data),
                                            training=True)[0],
                                jnp.asarray(labels))


@pytest.fixture(scope="module")
def both(system):
    model, data, labels = system
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(_loss(*system))(model.params)
        w = builder.reference_weights(model.params, CFG)
        ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
        ref_value, ref_grads = ref.loss_and_grads(w, ids, tgt, HEADS)
    return (float(value), builder.reference_weights(grads, CFG),
            ref_value, ref_grads)


def test_the_loss_matches_the_reference(both):
    value, _, ref_value, _ = both
    assert abs(value - ref_value) / ref_value < VALUE_TOL


def _leaves(grads):
    return [(jax.tree_util.keystr(path), g) for path, g in
            jax.tree_util.tree_flatten_with_path(grads)[0]]


def test_every_gradient_leaf_matches_the_reference(both):
    _, grads, _, ref_grads = both
    got, want = _leaves(grads), _leaves(ref_grads)
    # 8 layers of 10 leaves, embedding, head and final norm
    assert len(got) == len(want) == 8 * 10 + 3
    for (name, g), (_, r) in zip(got, want):
        assert _rel(g, r) < GRAD_TOL, name


def _fails(system, both, build_bad):
    """Whether the comparison ``kinds/train.check`` makes (the loss
    within TOL_LOSS_REL, every gradient leaf within TOL_GRAD_REL) fails
    for the program with a control planted: its loss or a gradient leaf
    leaves the reference's."""
    model, data, labels = system
    _, _, ref_value, ref_grads = both
    bad = build_bad()
    model_setup.materialize_lean(bad, 5)
    bad.sync(model.params, bad.init_state())
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(_loss(bad, data, labels))(
            bad.params)
    if abs(float(value) - ref_value) / ref_value > train.TOL_LOSS_REL:
        return True
    got = _leaves(builder.reference_weights(grads, CFG))
    return max(_rel(g, r) for (_, g), (_, r) in
               zip(got, _leaves(ref_grads))) > train.TOL_GRAD_REL


def _controlled(**over):
    def build():
        with policy_scope(DTypePolicy(
                param_dtype=jnp.dtype("float32"),
                compute_dtype=jnp.dtype("float32"),
                activation_dtype=jnp.dtype("float32"))):
            return builder.build(dict(CFG, **over))
    return build


def test_dropping_the_window_fails_the_comparison(system, both):
    n = CFG["num_hidden_layers"]
    assert _fails(system, both, _controlled(
        sliding_window_layout=[0] * n))


def test_rope_in_a_global_layer_fails_the_comparison(system, both):
    n = CFG["num_hidden_layers"]
    assert _fails(system, both, _controlled(rope_layout=[1] * n))


def test_the_router_fed_h_fails_the_comparison(system, both, monkeypatch):
    real = ExpertShare.apply
    monkeypatch.setattr(ExpertShare, "apply",
                        lambda self, *a, route_from=None, **k:
                        real(self, *a, **k))
    assert _fails(system, both, _controlled())


def test_the_model_as_built(system):
    model, *_ = system
    assert model.remat_policy == "per_block"
    blocks = model.modules[1:-2]
    assert [b.scope for b in blocks] == ["attention_global",
                                         "attention_window",
                                         "attention_window",
                                         "attention_window"] * 2
    att = [b.modules[1] for b in blocks]
    assert [(a.window, a.rope) for a in att[:4]] == [
        (None, False), (16, True), (16, True), (16, True)]
    assert att[1].rope_theta == 1.5e6 and att[1].num_kv_heads == 2
    assert {b.modules[3].expert_act for b in blocks} == {"relu"}
    from bigdl_tpu.models.transformer.model import decode_meta
    with pytest.raises(ValueError, match="ROADMAP B6"):
        decode_meta(model)


@pytest.fixture()
def on_kernels(monkeypatch):
    """The model's attention on the flash kernels, interpreted: the path
    it takes on the TPU."""
    kernel = fa.flash_attention
    monkeypatch.setattr(seq, "dot_product_attention",
                        lambda q, k, v, causal, window=None: kernel(
                            q, k, v, causal=causal, window=window,
                            interpret=True))


def _tokens():
    """One row of 128 tokens: the shortest sequence the kernels tile."""
    toks = np.random.default_rng(1).integers(1, CFG["vocab_size"] + 1,
                                             size=(1, 129))
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def test_a_recomputed_block_runs_the_attention_forward_once(
        system, on_kernels, kernel_calls):
    """In the gradient's jaxpr under ``per_block`` each window layer has
    ONE ``flash_window_fwd`` and each global layer ONE
    ``flash_attention_fwd`` (what the kernel made is named and kept),
    beside one backward kernel each; without the names the forward
    would run twice."""
    model, *_ = system
    assert model.remat_policy == "per_block"
    calls = kernel_calls(jax.make_jaxpr(jax.grad(_loss(
        model, *_tokens())))(model.params).jaxpr)
    assert calls == dict(flash_window_fwd=6, flash_window_dqdkdv=6,
                         flash_attention_fwd=2, flash_attention_dqdkdv=2)


def test_on_the_kernels_recomputation_is_bit_identical_to_none(system,
                                                               on_kernels):
    model, *_ = system
    plain = builder.build(CFG).set_remat(None)
    plain.sync(model.params, model.state)
    data, labels = _tokens()
    with_remat = jax.value_and_grad(_loss(model, data, labels))(
        model.params)
    without = jax.value_and_grad(_loss(plain, data, labels))(model.params)
    leaves = jax.tree.leaves_with_path(with_remat)
    assert len(leaves) == 1 + 8 * 10 + 3
    for (path, a), b in zip(leaves, jax.tree.leaves(without)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(
            path))


def test_the_layout_is_stated_once_at_build():
    from bigdl_tpu.observability import trace
    trace.clear()
    trace.enable()
    try:
        builder.build(CFG)
        events = trace.to_dict()["traceEvents"]
    finally:
        trace.disable()
    (got,) = [e["args"] for e in events if e["name"] == "attention_layout"]
    assert got["window_layers"] == [1, 2, 3, 5, 6, 7]
    assert got["global_layers"] == [0, 4]
    assert got["rope_layers"] == [1, 2, 3, 5, 6, 7]
    assert got["window"] == 16


def test_smallthinker_counts_the_cells_parameters():
    """The configuration's arithmetic: a layer's share 68,326,400 parameters,
    embedding + head + final norm 97,241,600: 643,852,800."""
    from benchmarks import shapes_smallthinker
    model = builder.build(FULL)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    assert count(shapes["1"]) == 68_326_400
    assert count(shapes) == 643_852_800 == \
        shapes_smallthinker.param_count(FULL)

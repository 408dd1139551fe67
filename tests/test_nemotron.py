"""NVIDIA-Nemotron-3-Nano-30B-A3B's language model (ISSUE 37) against
the plain float32 reference (benchmarks/reference/nemotron.py) at small
sizes on the CPU: the Mamba-2 scan three ways (the Pallas kernel
interpreted, ``ssd_chunked_xla`` and the reference's per-position
recurrence), the causal conv, the gated group norm, the mixer, the relu^2
expert share and its shares summed, the whole model's loss and every
gradient leaf, the pattern, recomputation and what the trace is told.

Tolerances: both sides float32 under ``highest`` matmul precision, so
what is left is summation order — the chunked form sums a chunk's
positions in one product and passes states between chunks where the
recurrence adds one position at a time: 1e-5 relative on values, 1e-4
on gradients (a gradient sums over more terms: through the states of
every later position)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, model_setup
from benchmarks.builders import nemotron as builder
from benchmarks.reference import nemotron as ref
from bigdl_tpu.nn import linear, state_space
from bigdl_tpu.ops.pallas import flash_attention as fa
from bigdl_tpu.ops.pallas import ssd as kernels
from bigdl_tpu.parallel import sequence as seq
from bigdl_tpu.parallel.expert import ExpertShare
from bigdl_tpu.tensor import DTypePolicy, policy_scope

FULL = manifest.data_file("configs", "nemotron-3-nano-30b-a3b")
CFG = dict(FULL, **FULL["rehearsal"])
HEADS = CFG["num_attention_heads"]
VALUE_TOL, GRAD_TOL = 1e-5, 1e-4


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


# -- the scan, three ways ------------------------------------------------

def _scan_inputs(b=2, s=64, h=4, p=8, g=2, n=16, seed=0):
    """Delta over two decades (0.02 .. 2.6) and A over [1, 7]: decays
    from next to nothing to most of a state gone in one step."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, s, h, p)),
            jax.nn.softplus(2.0 * jax.random.normal(k[1], (b, s, h)) - 1.5),
            -jnp.exp(jax.random.uniform(k[2], (h,), maxval=2.0)),
            0.5 * jax.random.normal(k[3], (b, s, g, n)),
            0.5 * jax.random.normal(k[4], (b, s, g, n)),
            jax.random.normal(k[5], (h,)))


def _recurrence(x, dt, a, bm, cm, d):
    return jnp.stack([ref.ssd_recurrent(x[i], dt[i], a, bm[i], cm[i], d)
                      for i in range(x.shape[0])])


SCANS = {
    "kernel": lambda *v: kernels.ssd(*v, chunk=16, interpret=True),
    "chunked_xla": lambda *v: state_space.ssd_chunked_xla(*v, chunk=16),
}


@pytest.mark.parametrize("scan", sorted(SCANS))
def test_the_scan_matches_the_recurrence(scan):
    """Four chunks, states crossing three chunk boundaries, two rows."""
    args = _scan_inputs()
    want = _recurrence(*args)
    assert _rel(SCANS[scan](*args), want) < VALUE_TOL


@pytest.mark.parametrize("scan", sorted(SCANS))
def test_the_scans_gradients_match_the_recurrence(scan):
    args = _scan_inputs(s=48, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)

    def grads(fn):
        return jax.grad(lambda *v: jnp.sum(fn(*v) * w),
                        argnums=tuple(range(6)))(*args)

    for name, got, want in zip(("x", "Delta", "A", "B", "C", "D"),
                               grads(SCANS[scan]), grads(_recurrence)):
        assert _rel(got, want) < GRAD_TOL, name


def test_the_state_crosses_the_chunk_boundary():
    """A token before the boundary still reaches an output after it: cut
    the carried state and the later outputs change."""
    x, dt, a, bm, cm, d = _scan_inputs(b=1, s=32)
    y = kernels.ssd(x, dt, a, bm, cm, d, chunk=16, interpret=True)
    alone = kernels.ssd(x[:, 16:], dt[:, 16:], a, bm[:, 16:], cm[:, 16:], d,
                        chunk=16, interpret=True)
    assert _rel(y[:, 16:], alone) > 1e-3
    assert _rel(y[:, :16], _recurrence(x[:, :16], dt[:, :16], a,
                                       bm[:, :16], cm[:, :16], d)) < 1e-5


@pytest.mark.parametrize("scan", sorted(SCANS))
def test_a_length_off_the_chunk_is_refused_by_name(scan):
    args = _scan_inputs(s=40)
    with pytest.raises(ValueError, match="not a multiple of the chunk 16"):
        SCANS[scan](*args)


def test_the_tpu_kernel_refuses_a_chunk_mosaic_does_not_tile():
    with pytest.raises(ValueError, match="multiple of 128"):
        kernels.ssd_schedule(8192, 64, 8, 64)
    assert kernels.ssd_schedule(8192, 64, 8, 128) == kernels.SsdSchedule(
        chunk=128, chunks=64, heads_per_step=8, head_blocks=8)


# -- the mixer's other parts ---------------------------------------------

def test_the_causal_conv_by_hand():
    """out[t] = b + sum_i w[:, i] x[t + i - 3]: the first three positions
    see only the sequence's own start."""
    x = jnp.arange(1.0, 13.0).reshape(1, 6, 2)        # x[t] = (2t+1, 2t+2)
    w = jnp.array([[1.0, 10.0, 100.0, 1000.0], [2.0, 0.0, 0.0, 1.0]])
    b = jnp.array([0.5, -1.0])
    y = state_space.causal_conv1d(x, w, b)
    assert y[0, 0].tolist() == [0.5 + 1000 * 1, -1 + 2]
    assert y[0, 1].tolist() == [0.5 + 100 * 1 + 1000 * 3, -1 + 4]
    assert y[0, 2].tolist() == [0.5 + 10 * 1 + 100 * 3 + 1000 * 5, -1 + 6]
    assert y[0, 3].tolist() == [0.5 + 1 + 10 * 3 + 100 * 5 + 1000 * 7,
                                -1 + 2 * 2 + 8]


def test_the_gated_group_norm_against_its_formula():
    """The gate BEFORE the norm, each of the groups normed alone."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    y, z = jax.random.normal(k[0], (3, 12)), jax.random.normal(k[1], (3, 12))
    w = jax.random.normal(k[2], (12,))
    got = state_space.gated_rms_norm(y, z, w, groups=3, eps=1e-5)
    g = np.asarray(y * z / (1 + np.exp(-np.asarray(z))))
    for i in range(3):
        part = g[:, 4 * i:4 * i + 4]
        want = part / np.sqrt(np.mean(part ** 2, -1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(got[:, 4 * i:4 * i + 4],
                                   want * np.asarray(w[4 * i:4 * i + 4]),
                                   rtol=1e-5)


def _mixer(seed=0):
    m = state_space.Mamba2Mixer(CFG["hidden_size"], CFG["mamba_num_heads"],
                                CFG["mamba_head_dim"], CFG["n_groups"],
                                CFG["ssm_state_size"], CFG["conv_kernel"],
                                CFG["chunk_size"])
    params = m.init(jax.random.PRNGKey(seed))
    # every leaf off its initial value (D and the norm's weight start at 1)
    params = jax.tree.map(lambda p: p + 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), p.shape), params)
    return m, params


def _mixer_weights(params):
    return {"in_w": params["in_weight"], "conv_w": params["conv_weight"],
            "conv_b": params["conv_bias"], "dt_bias": params["dt_bias"],
            "A_log": params["A_log"], "D": params["D"],
            "norm_g": params["norm_weight"], "out_w": params["out_weight"]}


def test_the_mixer_and_its_gradients_match_the_recurrence():
    m, params = _mixer()
    spec = builder._spec(CFG)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, CFG["hidden_size"]))
    got, grads = jax.value_and_grad(
        lambda p: jnp.sum(jnp.sin(m.apply(p, {}, x)[0])))(params)
    want, ref_grads = jax.value_and_grad(
        lambda w: jnp.sum(jnp.sin(ref.mamba_mixer(w, x[0], spec))))(
        _mixer_weights(params))
    assert abs(got - want) / abs(want) < VALUE_TOL
    for name, leaf in _mixer_weights(grads).items():
        assert _rel(leaf, ref_grads[name]) < GRAD_TOL, name


def test_the_mixers_parameters_at_the_published_widths():
    """ISSUE 37's arithmetic: W_in 27,697,152; W_out 11,010,048; conv
    30,720; Delta/A/D 192; gated norm 4,096."""
    m = state_space.Mamba2Mixer(2688, 64, 64, 8, 128)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    sizes = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert sizes == {"in_weight": 27_697_152, "out_weight": 11_010_048,
                     "conv_weight": 24_576, "conv_bias": 6_144,
                     "dt_bias": 64, "A_log": 64, "D": 64,
                     "norm_weight": 4_096}


def test_mamba2s_initialisation():
    """A in [1, 16], Delta = softplus(dt_bias) log-uniform in [1e-3,
    0.1], D one."""
    m = state_space.Mamba2Mixer(32, 64, 8, 8, 16)
    p = m.init(jax.random.PRNGKey(0))
    a = np.exp(np.asarray(p["A_log"]))
    delta = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1 <= a.min() and a.max() <= 16
    assert 1e-3 * 0.999 <= delta.min() and delta.max() <= 0.1 * 1.001
    assert np.all(np.asarray(p["D"]) == 1)


# -- the relu^2 expert share ---------------------------------------------

def _share(held, offset, params=None, seed=0):
    layer = ExpertShare(16, 8, 8, 3, experts_held=held,
                        experts_offset=offset, scoring="sigmoid",
                        route_scale=2.5, bias_update_rate=0.01,
                        shared_width=12, expert_act="relu2")
    return layer, (layer.init(jax.random.PRNGKey(seed)) if params is None
                   else params)


def _dense_relu2_moe(params, x, offset=0, k=3, scale=2.5):
    """Every held expert on every token, weighted by a sigmoid top-k
    router normalised over the k; plus the shared expert."""
    z = jax.nn.sigmoid(x @ params["router_weight"].T)
    top = jax.lax.top_k(z, k)[1]
    c = jnp.take_along_axis(z, top, -1)
    c = scale * c / jnp.sum(c, -1, keepdims=True)
    out = jnp.square(jax.nn.relu(x @ params["shared"]["up_weight"].T)) \
        @ params["shared"]["down_weight"].T
    for e in range(params["up_weight"].shape[0]):
        g = jnp.sum(jnp.where(top == offset + e, c, 0.0), -1, keepdims=True)
        out = out + g * (jnp.square(jax.nn.relu(
            x @ params["up_weight"][e].T)) @ params["down_weight"][e].T)
    return out


def test_the_relu2_expert_share_matches_a_dense_relu2_moe():
    layer, params = _share(4, 2)
    assert set(params) == {"up_weight", "down_weight", "router_weight",
                           "shared"}
    assert set(params["shared"]) == {"up_weight", "down_weight"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))
    y, _ = layer.apply(params, layer.init_state(), x)
    want = _dense_relu2_moe(params, x.reshape(-1, 16), offset=2)
    assert _rel(y.reshape(-1, 16), want) < VALUE_TOL
    g = jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply(p, layer.init_state(), x)[0])))(params)
    g_ref = jax.grad(lambda p: jnp.sum(jnp.sin(_dense_relu2_moe(
        p, x.reshape(-1, 16), offset=2))))(params)
    for (path, a), b in zip(jax.tree.leaves_with_path(g),
                            jax.tree.leaves(g_ref)):
        assert _rel(a, b) < GRAD_TOL, jax.tree_util.keystr(path)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's share test: four chips of two experts each; summed,
    with the shared expert (every chip computes it whole) counted once,
    the shares give the uncut reference layer."""
    _, whole = _share(8, 0, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 16))
    total = 0.0
    for chip in range(4):
        held = {k: v[2 * chip:2 * chip + 2] for k, v in whole.items()
                if k in ("up_weight", "down_weight")}
        layer, params = _share(2, 2 * chip, dict(whole, **held))
        total = total + layer.apply(params, layer.init_state(), x)[0]
    shared = jnp.square(jax.nn.relu(x @ whole["shared"]["up_weight"].T)) \
        @ whole["shared"]["down_weight"].T
    spec = ref.Spec(kinds=("moe",), mamba_heads=1, groups=1, num_kv_heads=1,
                    experts_total=8, experts_offset=0, experts_per_token=3,
                    route_scale=2.5, eps=1e-5)
    lw = {"router_w": whole["router_weight"], "up_w": whole["up_weight"],
          "down_w": whole["down_weight"],
          "sh_up_w": whole["shared"]["up_weight"],
          "sh_down_w": whole["shared"]["down_weight"]}
    want = ref.experts(lw, x[0], spec)
    assert _rel((total - 3 * shared)[0], want) < VALUE_TOL


def test_the_relu2_ffns_own_backward_is_autodiffs():
    ffn = linear.Relu2FFN(16, 24)
    p = ffn.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 16))

    def plain(p):
        return jnp.sum(jnp.sin(jnp.square(jax.nn.relu(
            x @ p["up_weight"].T)) @ p["down_weight"].T))

    got = jax.grad(lambda p: jnp.sum(jnp.sin(ffn.apply(p, {}, x)[0])))(p)
    for name, g in jax.grad(plain)(p).items():
        assert _rel(got[name], g) < 1e-6, name


def test_the_expert_kind_is_chosen_at_construction():
    with pytest.raises(ValueError, match="expert_act"):
        ExpertShare(16, 8, 8, 3, expert_act="gelu")
    gated = ExpertShare(16, 8, 8, 3, shared_width=12)
    assert set(gated.init(jax.random.PRNGKey(0))) == {
        "gate_weight", "up_weight", "down_weight", "router_weight", "shared"}
    assert isinstance(gated.shared, linear.GatedFFN)


# -- the model -----------------------------------------------------------

def test_the_published_pattern():
    from bigdl_tpu.models.transformer.model import hybrid_pattern
    kinds = hybrid_pattern(FULL["hybrid_override_pattern"])
    assert len(kinds) == 52
    assert [kinds.count(k) for k in ("mamba", "moe", "attention")] == \
        [23, 23, 6]
    assert builder.pattern(FULL) == "MEMEM*EME"
    with pytest.raises(ValueError, match=r"\['-'\]"):
        hybrid_pattern("M-M*")


@pytest.fixture(scope="module")
def system():
    """The program's model in float32 at rehearsal size, every leaf off
    its initial value, a (2, 64) batch."""
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


def _loss(model, data, labels, state=None):
    crit = builder.criterion()
    state = model.state if state is None else state
    return lambda p: crit.apply(model.apply(p, state, jnp.asarray(data),
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
    return value, builder.reference_weights(grads, CFG), ref_value, ref_grads


def test_the_loss_matches_the_reference(both):
    value, _, ref_value, _ = both
    assert abs(value - ref_value) / ref_value < VALUE_TOL


def _leaves(grads):
    return [(jax.tree_util.keystr(path), g) for path, g in
            jax.tree_util.tree_flatten_with_path(grads)[0]]


def test_every_gradient_leaf_matches_the_reference(both):
    _, grads, _, ref_grads = both
    got, want = _leaves(grads), _leaves(ref_grads)
    # 4 Mamba layers of 9 leaves, 4 expert layers of 6, 1 attention of 5,
    # embedding, head and final norm
    assert len(got) == len(want) == 4 * 9 + 4 * 6 + 5 + 3
    for (name, g), (_, r) in zip(got, want):
        assert _rel(g, r) < GRAD_TOL, name


def test_the_model_as_built(system):
    model, *_ = system
    assert model.remat_policy == "per_block"
    kinds = [type(block.modules[1]).__name__ for block in model.modules[1:-2]]
    assert kinds == ["Mamba2Mixer", "ExpertShare", "Mamba2Mixer",
                     "ExpertShare", "Mamba2Mixer", "MultiHeadAttention",
                     "ExpertShare", "Mamba2Mixer", "ExpertShare"]
    att = model.modules[6].modules[1]
    assert (att.num_heads, att.num_kv_heads, att.head_dim, att.rope,
            att.with_bias) == (4, 2, 8, False, False)
    from bigdl_tpu.models.transformer.model import decode_meta
    with pytest.raises(ValueError, match="ROADMAP B7"):
        decode_meta(model)


def test_nemotron_counts_the_cells_parameters():
    """ISSUE 37: a Mamba layer 38,744,896 parameters, an expert layer's
    share 100,125,440 with its bias of 128 (state, not a parameter),
    attention 23,399,040, embedding + head + final norm 88,083,072:
    666,963,456."""
    from benchmarks import shapes_nemotron
    model = builder.build(FULL)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    assert count(shapes["1"]) == 38_744_896
    assert count(shapes["2"]) == 100_125_440 - 128
    assert count(shapes["6"]) == 23_399_040
    assert count(shapes) + 4 * 128 == 666_963_456 == \
        shapes_nemotron.param_count(FULL)


@pytest.fixture()
def on_kernel(monkeypatch):
    """``Mamba2Mixer`` on the Pallas scan, interpreted: the TPU's path."""
    monkeypatch.setattr(
        state_space, "ssd_chunked_xla",
        lambda *a, chunk: kernels.ssd(*a, chunk=chunk, interpret=True))


def test_a_recomputed_block_runs_the_scan_forward_once(system, on_kernel,
                                                        kernel_calls):
    """Under ``per_block`` each Mamba layer has ONE ``ssd_fwd`` (its
    output and states are named and kept) and one ``ssd_bwd``."""
    model, data, labels = system
    jaxpr = jax.make_jaxpr(jax.grad(_loss(model, data[:1],
                                          labels[:1])))(model.params).jaxpr
    assert kernel_calls(jaxpr) == dict(ssd_fwd=4, ssd_bwd=4)


def test_a_recomputed_block_runs_the_flash_forward_once(system, on_kernel,
                                                         monkeypatch,
                                                         kernel_calls):
    """The attention layer on the flash kernels, interpreted, at 128
    tokens (the shortest sequence they tile): under ``per_block`` ONE
    ``flash_attention_fwd`` (its output and row statistics are named
    and kept) and one backward kernel, beside the scan's."""
    kernel = fa.flash_attention
    monkeypatch.setattr(seq, "dot_product_attention",
                        lambda q, k, v, causal, window=None: kernel(
                            q, k, v, causal=causal, window=window,
                            interpret=True))
    model, *_ = system
    toks = np.random.default_rng(1).integers(1, CFG["vocab_size"] + 1,
                                             size=(1, 129))
    data, labels = toks[:, :-1], toks[:, 1:]
    jaxpr = jax.make_jaxpr(jax.grad(_loss(model, data,
                                          labels)))(model.params).jaxpr
    assert kernel_calls(jaxpr) == dict(
        ssd_fwd=4, ssd_bwd=4, flash_attention_fwd=1,
        flash_attention_dqdkdv=1)


def test_on_the_kernel_the_model_matches_the_chunked_form(system, both,
                                                          on_kernel):
    model, data, labels = system
    value, grads = jax.value_and_grad(_loss(*system))(model.params)
    want_value, want, *_ = both
    assert abs(value - want_value) / want_value < VALUE_TOL
    for (name, g), (_, r) in zip(
            _leaves(builder.reference_weights(grads, CFG)), _leaves(want)):
        assert _rel(g, r) < GRAD_TOL, name


def test_what_the_mixer_runs_is_stated_where_it_is_traced(system,
                                                          on_kernel):
    from bigdl_tpu.observability import trace
    model, data, _ = system
    trace.clear()
    trace.enable()
    try:
        jax.eval_shape(lambda p: model.apply_plain(
            p, model.state, jnp.asarray(data))[0], model.params)
        events = trace.to_dict()["traceEvents"]
    finally:
        trace.disable()
    mixers = [e["args"] for e in events if e["name"] == "mamba_mixer"]
    assert len(mixers) == 4
    # the pointwise passes' bytes at their bound: 128 tokens, 32 inner,
    # 64 conv channels, float32 here
    assert mixers[0] == dict(seq=64, heads=4, head_dim=8, groups=2, state=8,
                             chunk=16, conv_kernel=4,
                             scan="ssd_chunked_xla", pointwise="xla",
                             pointwise_bytes=4 * 128 * (7 * 64 + 11 * 32))
    sched = [e["args"] for e in events if e["name"] == "ssd_schedule"]
    assert len(sched) == 4 and sched[0]["grid"] == [2, 2, 4]
    assert sched[0]["state_bytes"] == 2 * 4 * 4 * 8 * 8 * 4
    moe = [e["args"] for e in events if e["name"] == "moe_share"]
    assert len(moe) == 4 and moe[0]["expert_act"] == "relu2"

"""EvaByte on the training path (ISSUE 27): ``nn.RMSNorm``,
``nn.GatedFFN``, ``nn.EvaAttention`` and its Pallas kernel,
``nn.MultiBytePredictionCriterion``, the block builder, ``EvaByteLM`` and
recomputation as a property of the model as built.

The model tests compare the program with the plain float32 reference
(benchmarks/reference/evabyte.py) on the loss and on EVERY gradient leaf,
at window 32 / chunk 4, with the query and key projections scaled so that
attention scores have a standard deviation above 1: under a near-uniform
softmax a wrong mask moves nothing. Each comparison is then made to FAIL
by a named mutant of the program.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.builders import evabyte as builder
from benchmarks.reference import evabyte as reference
from bigdl_tpu import nn
from bigdl_tpu.models import TransformerLM
from bigdl_tpu.models.transformer import model as model_mod
from bigdl_tpu.nn import attention as attention_mod
from bigdl_tpu.tensor import DTypePolicy, policy_scope

CFG = dict(vocab_size=320, hidden_size=32, num_attention_heads=4,
           num_hidden_layers=2, intermediate_size=64, window_size=32,
           chunk_size=4, num_pred_heads=8, rope_theta=100000,
           rms_norm_eps=1e-5)
HEADS = CFG["num_attention_heads"]
TOL = 2e-4          # float32 on both sides, another order of summation


@pytest.fixture(autouse=True, scope="module")
def _float32_policy():
    """float32 on both sides unless a test says otherwise, whatever
    policy an earlier file of this worker left set."""
    f32 = jnp.dtype("float32")
    with policy_scope(DTypePolicy(param_dtype=f32, compute_dtype=f32,
                                  activation_dtype=f32)):
        yield


def _sharp(params):
    """q and k projections x 4: scores with a standard deviation >= 1."""
    def scale(path, a):
        name = jax.tree_util.keystr(path)
        return a * 4 if "q_weight" in name or "k_weight" in name else a
    return jax.tree_util.tree_map_with_path(scale, params)


def _batch(seq, rows=2, seed=0):
    toks = np.random.default_rng(seed).integers(1, 321, size=(rows, seq + 1))
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


@pytest.fixture(scope="module")
def system():
    model = builder.build(CFG)
    params = _sharp(model.init(jax.random.PRNGKey(0)))
    data, labels = _batch(4 * CFG["window_size"])
    w = builder.reference_weights(params, CFG)
    ref_loss, ref_grads = reference.loss_and_grads(w, data - 1, labels - 1,
                                                   HEADS)
    return model, params, data, labels, ref_loss, ref_grads


def _disagreement(model, params, data, labels, ref_loss, ref_grads,
                  crit=None):
    """(relative loss error, worst gradient leaf's relative L2 error) of
    the program against the reference."""
    crit = crit or builder.criterion()
    state = model.init_state()

    def loss(p):
        return crit.apply(model.apply(p, state, data, training=True)[0],
                          labels)

    value, grads = jax.value_and_grad(loss)(params)
    grads = builder.reference_weights(grads, CFG)
    worst = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                for a, b in zip(jax.tree.leaves(grads),
                                jax.tree.leaves(ref_grads)))
    return abs(float(value) - ref_loss) / ref_loss, worst


def test_scores_are_sharp_enough_to_see_a_mask(system):
    model, params, data, *_ = system
    att = model.modules[1].modules[0].modules[1]
    p = params["1"]["0"]["1"]
    x, _ = model.modules[0].apply(params["0"], {}, data)
    h, _ = model.modules[1].modules[0].modules[0].apply(
        params["1"]["0"]["0"], {}, x)
    q = (h @ p["q_weight"].T).reshape(*data.shape, HEADS, -1)
    k = (h @ p["k_weight"].T).reshape(*data.shape, HEADS, -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * att.head_dim ** -0.5
    assert float(jnp.std(s)) >= 1.0


def test_loss_and_every_gradient_leaf_match_the_reference(system):
    loss_err, grad_err = _disagreement(*system)
    assert loss_err < 1e-5 and grad_err < TOL


def test_a_sequence_that_is_no_multiple_of_the_window_is_refused(system):
    model, params, *_ = system
    data, _ = _batch(3 * CFG["window_size"] + CFG["window_size"] // 2)
    with pytest.raises(ValueError, match="EvaAttention: sequence length "
                                         "112 is not a multiple of window"):
        model.apply(params, model.init_state(), data)
    w = builder.reference_weights(params, CFG)
    with pytest.raises(ValueError, match="not a multiple of the window"):
        reference.loss(w, data - 1, data - 1, HEADS)


def _xla_mutant(remote_mask):
    """``eva_attention_xla`` with another rule for which summaries a
    window sees: ``remote_mask(summary's window, query's window)``."""
    def mutant(q, k, v, ks, vs, *, window, chunk, scale=None):
        b, s, h, d = q.shape
        nw, per = s // window, window // chunk
        scale = scale if scale is not None else d ** -0.5
        qw, kw, vw = (x.reshape(b, nw, window, h, d) for x in (q, k, v))
        local = jnp.einsum("bnqhd,bnkhd->bnhqk", qw, kw) * scale
        pos = jnp.arange(window)
        local = jnp.where(pos[None, :] > pos[:, None], -1e9, local)
        remote = jnp.einsum("bnqhd,bchd->bnhqc", qw, ks) * scale
        seen = remote_mask(jnp.arange(nw * per)[None, :] // per,
                           jnp.arange(nw)[:, None])
        remote = jnp.where(seen[None, :, None, None, :], remote, -1e9)
        p = jax.nn.softmax(jnp.concatenate([local, remote], -1), axis=-1)
        o = (jnp.einsum("bnhqk,bnkhd->bnqhd", p[..., :window], vw)
             + jnp.einsum("bnhqc,bchd->bnqhd", p[..., window:], vs))
        return o.reshape(b, s, h, d)
    return mutant


REMOTE_DROPPED = _xla_mutant(lambda c, n: (c < n) & False)
OWN_WINDOW_TWICE = _xla_mutant(lambda c, n: c <= n)


def test_the_mutant_mask_agrees_with_the_module_when_it_is_the_rule():
    """The harness of the two mask mutants is itself right."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q, k, v = (jax.random.normal(kk, (1, 64, 2, 8)) for kk in ks[:3])
    sk, sv = (jax.random.normal(ky, (1, 16, 2, 8)) for ky in ks[3:])
    want = attention_mod.eva_attention_xla(q, k, v, sk, sv, window=32,
                                           chunk=4)
    got = _xla_mutant(lambda c, n: c < n)(q, k, v, sk, sv, window=32,
                                          chunk=4)
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("mutant", ["remote_set_dropped",
                                    "own_windows_chunks_counted_twice",
                                    "mu_left_out",
                                    "head_i_scored_against_byte_t_plus_1"])
def test_each_named_mutant_fails_the_comparison(system, mutant, monkeypatch):
    crit = None
    if mutant == "remote_set_dropped":
        monkeypatch.setattr(attention_mod, "eva_attention_xla",
                            REMOTE_DROPPED)
    elif mutant == "own_windows_chunks_counted_twice":
        monkeypatch.setattr(attention_mod, "eva_attention_xla",
                            OWN_WINDOW_TWICE)
    elif mutant == "mu_left_out":
        real = attention_mod.eva_chunk_summaries
        monkeypatch.setattr(
            attention_mod, "eva_chunk_summaries",
            lambda k, v, phi, mu, chunk: real(k, v, phi, mu * 0, chunk))
    else:
        class EveryHeadNextByte(nn.MultiBytePredictionCriterion):
            def apply(self, x, target):
                h, v = self.num_heads, self.vocab
                logits = x.reshape(*x.shape[:2], h, v)
                one = nn.CrossEntropyCriterion()
                return sum(one.apply(logits[:, :, i], target)
                           for i in range(h)) / h
        crit = EveryHeadNextByte(8, 320)
    loss_err, grad_err = _disagreement(*system, crit=crit)
    assert grad_err > 100 * TOL, (mutant, loss_err, grad_err)


def _what_the_blocks_added(model, params, data):
    """The residual stream after the last block less the embedding."""
    x0, _ = model.modules[0].apply(params["0"], {}, data)
    x = x0
    for i in range(1, 1 + CFG["num_hidden_layers"]):
        x, _ = model.modules[i].apply(params[str(i)],
                                      model.modules[i].init_state(), x)
    return x.astype(jnp.float32) - x0.astype(jnp.float32)


def test_a_bf16_residual_stream_fails_where_the_float32_one_passes(system):
    """Under the cell's policy (float32 parameters, bf16 compute and
    activations) and an embedding 1000 x larger than what the blocks add
    to it — the stream of a deep model — the float32 stream keeps what
    they add to within bf16 matmul rounding of the float32 reference; the
    mutant that rounds the stream to bf16 at every add loses it."""
    _, params, data, *_ = system
    params = dict(params, **{"0": {"tok": params["0"]["tok"] * 1000}})
    w = builder.reference_weights(params, CFG)
    want = jnp.stack([reference.hidden(w, row - 1, HEADS)
                      - w["tok"][row - 1] for row in data])
    policy = DTypePolicy(param_dtype=jnp.float32,
                         compute_dtype=jnp.bfloat16,
                         activation_dtype=jnp.bfloat16)
    with policy_scope(policy):
        good = builder.build(CFG)
        bad = builder.build(CFG)
        bad.modules[0].out_dtype = None
        for block in bad.modules[1:1 + CFG["num_hidden_layers"]]:
            for residual in block.modules:
                residual.residual_dtype = None
        err_good, err_bad = (
            float(jnp.linalg.norm(_what_the_blocks_added(m, params, data)
                                  - want) / jnp.linalg.norm(want))
            for m in (good, bad))
    assert err_good < 0.04 and err_bad > 5 * err_good, (err_good, err_bad)


# -- the kernel ----------------------------------------------------------

def _kernel_case(dtype, seed=0):
    b, h, d, window, chunk, s = 1, 2, 128, 384, 8, 1152
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(kk, (b, s, h, d)) * 1.5 for kk in ks[:2])
    v, do = (jax.random.normal(ky, (b, s, h, d)) for ky in ks[2:4])
    phi, mu = (jax.random.normal(kz, (h, d)) * 0.1 for kz in ks[4:])
    sk, sv = attention_mod.eva_chunk_summaries(k, v, phi, mu, chunk)
    args = tuple(t.astype(dtype) for t in (q, k, v, sk, sv))
    return args, do, dict(window=window, chunk=chunk)


def _fwd_and_grads(core, args, do, **kw):
    f32 = jnp.float32

    def scalar(*a):
        return jnp.sum(core(*a, **kw).astype(f32) * do)

    out = core(*args, **kw).astype(f32)
    grads = jax.grad(scalar, argnums=tuple(range(5)))(*args)
    return out, [g.astype(f32) for g in grads]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_kernel_interpreted_matches_the_jnp_path_at_d128(dtype, tol):
    """Three windows of three q blocks each (wide steps 3 forward, 2
    backward), 48 summaries a window, D=128: forward and the five
    gradients; then the two mask mutants of the jnp path must NOT match."""
    from bigdl_tpu.ops.pallas.eva_attention import eva_attention
    args, do, kw = _kernel_case(dtype)
    out, grads = _fwd_and_grads(
        lambda *a, **k: eva_attention(*a, interpret=True, **k), args, do,
        **kw)
    wide = tuple(a.astype(jnp.float32) for a in args)

    def worst(core):
        ref_out, ref_grads = _fwd_and_grads(core, wide, do, **kw)
        return max([float(jnp.max(jnp.abs(out - ref_out)))]
                   + [float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
                      for g, r in zip(grads, ref_grads)])

    assert worst(attention_mod.eva_attention_xla) < tol
    assert worst(REMOTE_DROPPED) > 10 * tol
    assert worst(OWN_WINDOW_TWICE) > 10 * tol


def test_schedule_at_the_cells_shape_and_what_it_refuses():
    from bigdl_tpu.ops.pallas.eva_attention import eva_schedule
    s = eva_schedule(16384, 2048, 16, 128, 2)
    assert (s.local.bq, s.local.bk, s.local.bwd_bk, s.local.block) == (
        512, 2048, 1024, 256)
    assert (s.windows, s.q_per_window, s.per_window) == (8, 4, 128)
    # 36 squares of 256 a window for the 32.02 the causal half needs
    assert s.tiles_computed == 288
    assert s.tiles_needed == pytest.approx(8 * 2048 * 2049 / 2 / 256 ** 2)
    # 4 q blocks a window x (0 + 1 + ... + 7) earlier windows, none masked
    assert s.remote_tiles_computed == s.remote_tiles_needed == 112
    with pytest.raises(ValueError, match="not a multiple of window"):
        eva_schedule(3 * 2048 + 1024, 2048, 16, 128, 2)
    with pytest.raises(ValueError, match="sublane tile"):
        eva_schedule(4096, 2048, 256, 128, 2)
    with pytest.raises(ValueError, match="does not fit VMEM"):
        eva_schedule(65536, 32768, 16, 128, 2)


def test_the_schedule_is_stated_where_the_kernel_is_traced():
    from bigdl_tpu.observability import trace
    from bigdl_tpu.ops.pallas.eva_attention import eva_attention
    args, _, kw = _kernel_case(jnp.float32)
    seen = []
    trace._TRACER._taps.append(seen.append)
    try:
        jax.eval_shape(lambda *a: eva_attention(*a, interpret=True, **kw),
                       *args)
    finally:
        trace._TRACER._taps.remove(seen.append)
    stated = [e for e in seen if e["name"] == "eva_schedule"]
    assert len(stated) == 1 and stated[0]["cat"] == "kernels"
    assert {"sq", "window", "chunk", "d", "bq", "bk", "tiles_computed",
            "tiles_needed", "remote_tiles_computed",
            "remote_tiles_needed"} <= set(stated[0]["args"])


# -- the modules ---------------------------------------------------------

def test_rms_norm_is_the_formula():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16)) * 3
    g = jax.random.normal(jax.random.PRNGKey(1), (16,)) * 0.1
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    unit = nn.RMSNorm(16, unit_offset=True)
    assert float(jnp.max(jnp.abs(unit.init(None)["weight"]))) == 0.0
    np.testing.assert_allclose(unit.apply({"weight": g}, {}, x)[0],
                               want * (1 + g), rtol=1e-5)
    plain = nn.RMSNorm(16)
    np.testing.assert_allclose(
        plain.apply(plain.init(None), {}, x)[0], want, rtol=1e-5)


def test_rms_norm_rounds_a_float32_stream_once():
    policy = DTypePolicy(param_dtype=jnp.float32,
                         compute_dtype=jnp.bfloat16,
                         activation_dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 3
    with policy_scope(policy):
        norm = nn.RMSNorm(64, unit_offset=True)
        y, _ = norm.apply(norm.init(None), {}, x)
        wide, _ = nn.RMSNorm(64, unit_offset=True, fp32=True).apply(
            norm.init(None), {}, x)
    assert y.dtype == jnp.bfloat16 and wide.dtype == jnp.float32
    np.testing.assert_array_equal(y, wide.astype(jnp.bfloat16))


def test_gated_ffn_is_three_bias_free_matrices():
    ffn = nn.GatedFFN(8, 24)
    p = ffn.init(jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in p.items()} == {
        "gate_weight": (24, 8), "up_weight": (24, 8),
        "down_weight": (8, 24)}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
    want = (jax.nn.silu(x @ p["gate_weight"].T) * (x @ p["up_weight"].T)
            ) @ p["down_weight"].T
    np.testing.assert_allclose(ffn.apply(p, {}, x)[0], want, rtol=1e-5,
                               atol=1e-6)
    relu = nn.GatedFFN(8, 24, act=jax.nn.relu)
    assert float(jnp.max(jnp.abs(relu.apply(p, {}, x)[0] - want))) > 1e-3


def _ffn_case(act, compute, remat):
    """``(loss of the module, loss of the plain formula, params, x)``:
    one ``GatedFFN`` in a ``Sequential``, recomputed as ``set_remat``
    does it where asked, against ``(act(h Wg^T) * (h Wu^T)) Wd^T`` in the
    same compute dtype, both weighted by one random cotangent."""
    cd = jnp.dtype(compute)
    ffn = nn.GatedFFN(16, 48, act=act)
    model = nn.Sequential().add(ffn).set_remat(
        "per_block" if remat else None)
    params = {"0": ffn.init(jax.random.PRNGKey(0))}
    x, cot = (jax.random.normal(jax.random.PRNGKey(k), (2, 24, 16))
              for k in (1, 2))

    def weighted(y):
        return jnp.sum(y.astype(jnp.float32) * cot)

    def system(p, x):
        return weighted(model.apply(p, {"0": {}}, x, training=True)[0])

    def formula(p, x, f=ffn.act):
        h = x.astype(cd)
        w = {k: v.astype(cd) for k, v in p["0"].items()}
        return weighted((f(h @ w["gate_weight"].T) * (h @ w["up_weight"].T))
                        @ w["down_weight"].T)

    return system, formula, params, x


@pytest.mark.parametrize("remat", [False, True], ids=["grad", "checkpoint"])
@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
@pytest.mark.parametrize("act", [
    "silu", "relu",
    pytest.param(lambda g: g * jax.nn.sigmoid(1.702 * g), id="callable")])
def test_gated_ffn_backward_is_autodiff_of_the_plain_formula(
        act, compute, remat):
    """``GatedFFN`` defines its own backward: the gradients of h and of
    the three weights are autodiff's of the plain formula.

    float32: the same five matmuls and three products, so only the
    order of a matmul's summation differs; 1e-6 is 8 ulp (1.2e-7) of
    the leaf's largest element, where a missing or wrong term moves it
    by its own size. bf16: ``act(gate) * up``, ``dgate`` and ``dup`` are
    bf16 tensors in autodiff's program too, each rounded once, so the
    bound is one bf16 ulp (2**-8) of the leaf's largest element; this
    backend reads 0."""
    system, formula, params, x = _ffn_case(act, compute, remat)
    cd = jnp.dtype(compute)
    with policy_scope(DTypePolicy(param_dtype=jnp.float32, compute_dtype=cd,
                                  activation_dtype=cd)):
        value, got = jax.value_and_grad(system, argnums=(0, 1))(params, x)
    want_value, want = jax.value_and_grad(formula, argnums=(0, 1))(params, x)
    other = jax.grad(formula, argnums=(0, 1))(
        params, x, jax.nn.silu if act == "relu" else jax.nn.relu)
    np.testing.assert_allclose(value, want_value, rtol=1e-5)
    tol = 1e-6 if compute == "float32" else 2.0 ** -8
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(leaves) == 4
    for (path, g), w, o in zip(leaves, jax.tree.leaves(want),
                               jax.tree.leaves(other)):
        name = jax.tree_util.keystr(path)
        assert g.dtype == jnp.float32 and g.shape == w.shape, name
        top = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g - w))) <= tol * top, name
        # the comparison can fail: another activation's gradients
        assert float(jnp.max(jnp.abs(o - w))) > 0.05 * top, name


def test_gated_ffn_states_its_backward_once_a_trace_and_is_reverse_only():
    """The instant that counts the layers on the path (PERF.md section
    3), and the docstring's contract: reverse mode only."""
    from bigdl_tpu.observability import trace
    system, _, params, x = _ffn_case("silu", "float32", True)
    seen = []
    trace._TRACER._taps.append(seen.append)
    try:
        jax.eval_shape(system, params, x)       # the forward says nothing
        assert not [e for e in seen if e["name"] == "gated_ffn_backward"]
        jax.eval_shape(jax.grad(system), params, x)
    finally:
        trace._TRACER._taps.remove(seen.append)
    stated = [e for e in seen if e["name"] == "gated_ffn_backward"]
    assert len(stated) == 1 and stated[0]["cat"] == "nn"
    assert stated[0]["args"] == {
        "tokens": 48, "d_model": 16, "d_ff": 48,
        "materialised_bytes": 48 * (4 * 48 + 2 * 16) * 4}
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda x: system(params, x), (x,), (x,))


def test_eva_attention_names_its_parameters_as_mha_does():
    att = nn.EvaAttention(32, 4, window=32, chunk=4, rope_theta=1e5)
    p = att.init(jax.random.PRNGKey(0))
    mha = nn.MultiHeadAttention(32, 4, with_bias=False).init(
        jax.random.PRNGKey(0))
    assert set(p) == set(mha) | {"phi", "mu"}
    assert p["phi"].shape == p["mu"].shape == (4, 8)
    assert att.rope_theta == 1e5
    with pytest.raises(ValueError, match="not a multiple of chunk"):
        nn.EvaAttention(32, 4, window=30, chunk=4)


def test_the_first_window_is_plain_causal_attention():
    from bigdl_tpu.parallel.sequence import dot_product_attention
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q, k, v = (jax.random.normal(kk, (2, 32, 2, 8)) for kk in ks[:3])
    sk, sv = (jax.random.normal(ky, (2, 8, 2, 8)) for ky in ks[3:])
    got = attention_mod.eva_attention_xla(q, k, v, sk, sv, window=32,
                                          chunk=4)
    want = dot_product_attention(q, k, v, causal=True, flash=False)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_multi_byte_criterion_against_a_loop_over_heads():
    b, s, h, v = 2, 12, 4, 7
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, h * v))
    toks = np.random.default_rng(0).integers(1, v + 1, size=(b, s + 1))
    target = jnp.asarray(toks[:, 1:])
    logp = jax.nn.log_softmax(x.reshape(b, s, h, v), axis=-1)
    per_head = []
    for i in range(1, h + 1):          # head i against byte t + i
        nll = [-logp[r, t, i - 1, toks[r, t + i] - 1]
               for r in range(b) for t in range(s) if t + i <= s]
        assert len(nll) == b * (s - i + 1)
        per_head.append(sum(nll) / len(nll))
    want = sum(per_head) / h
    got = nn.MultiBytePredictionCriterion(h, v).apply(x, target)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    # one head is next-token cross-entropy
    one = nn.MultiBytePredictionCriterion(1, h * v).apply(x, target * 0 + 3)
    assert float(one) == pytest.approx(float(
        nn.CrossEntropyCriterion().apply(x, target * 0 + 3)), rel=1e-6)


def test_the_embedding_clips_ids_outside_the_vocabulary():
    """``_TokenAndPosition`` reads the nearest row for an id outside
    1..vocab and does not raise (shared with ``TransformerLM``)."""
    emb = model_mod._TokenAndPosition(320, 8, 0, with_pos=False,
                                      out_dtype=jnp.float32)
    p = emb.init(jax.random.PRNGKey(0))
    y, _ = emb.apply(p, {}, jnp.asarray([[0, 1, 320, 321, 999]]))
    assert y.dtype == jnp.float32
    np.testing.assert_array_equal(y[0, 0], p["tok"][0])
    np.testing.assert_array_equal(y[0, 1], p["tok"][0])
    np.testing.assert_array_equal(y[0, 2], p["tok"][319])
    np.testing.assert_array_equal(y[0, 3], p["tok"][319])
    np.testing.assert_array_equal(y[0, 4], p["tok"][319])


# -- the block builder ---------------------------------------------------

class _OldResidual(nn.Container):
    """``_Residual`` as it was before the block builder took its norm."""

    def __init__(self, d_model, inner):
        super().__init__(nn.LayerNorm(d_model), inner)

    def apply(self, params, state, x, *, training=False, rng=None):
        h, s0 = self.modules[0].apply(params["0"], state["0"], x,
                                      training=training)
        h, s1 = self.modules[1].apply(params["1"], state["1"], h,
                                      training=training, rng=rng)
        return x + h, {"0": s0, "1": s1}


def _old_transformer_lm(vocab, d_model, num_heads, num_layers, max_len):
    model = nn.Sequential().add(
        model_mod._TokenAndPosition(vocab, d_model, max_len))
    for _ in range(num_layers):
        mha = nn.MultiHeadAttention(d_model, num_heads, causal=True)
        ffn = (nn.Sequential().add(nn.Linear(d_model, 4 * d_model))
               .add(nn.ReLU()).add(nn.Linear(4 * d_model, d_model)))
        model.add(nn.Sequential().add(_OldResidual(d_model, mha))
                  .add(_OldResidual(d_model, ffn)))
    model.add(nn.LayerNorm(d_model))
    model.add(nn.Linear(d_model, vocab, init_method=nn.init.Xavier))
    return model


@pytest.mark.parametrize("bf16", [False, True], ids=["float32", "bf16"])
def test_transformer_lm_is_bit_for_bit_what_it_was(bf16):
    policy = DTypePolicy(
        param_dtype=jnp.float32,
        compute_dtype=jnp.bfloat16 if bf16 else jnp.float32,
        activation_dtype=jnp.bfloat16 if bf16 else jnp.float32)
    with policy_scope(policy):
        new = TransformerLM(50, d_model=16, num_heads=2, num_layers=2,
                            max_len=12, with_log_softmax=False)
        old = _old_transformer_lm(50, 16, 2, 2, 12)
        key = jax.random.PRNGKey(3)
        p_new, p_old = new.init(key), old.init(key)
        assert jax.tree.structure(p_new) == jax.tree.structure(p_old)
        for a, b in zip(jax.tree.leaves(p_new), jax.tree.leaves(p_old)):
            np.testing.assert_array_equal(a, b)
        x = jnp.asarray(np.random.default_rng(0).integers(1, 51, (2, 12)))

        def loss(m, p):
            y, _ = m.apply(p, m.init_state(), x, training=True)
            return jnp.sum(y.astype(jnp.float32) ** 2), y

        (_, y_new), g_new = jax.value_and_grad(
            lambda p: loss(new, p), has_aux=True)(p_new)
        (_, y_old), g_old = jax.value_and_grad(
            lambda p: loss(old, p), has_aux=True)(p_old)
    assert y_new.dtype == y_old.dtype
    np.testing.assert_array_equal(y_new, y_old)
    for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_array_equal(a, b)
    assert new.lm_meta["num_layers"] == 2 and new.remat_policy is None


def test_decode_paths_refuse_a_model_without_one_by_name():
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.models.transformer.serving import ContinuousBatcher
    model = builder.build(CFG).materialize(jax.random.PRNGKey(0))
    assert not hasattr(model, "lm_meta")
    for call in (lambda: generate(model, jnp.ones((1, 4), jnp.int32)),
                 lambda: ContinuousBatcher(model, max_batch=2, num_pages=8)):
        with pytest.raises(ValueError, match="no decode path for "
                                             "EvaAttention: ROADMAP B7"):
            call()


# -- recomputation belongs to the model as built -------------------------

def _checkpoint_regions(jaxpr, depth=0):
    """[(nesting depth)] of every checkpoint region in a jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        inner = depth + (eqn.primitive.name in ("checkpoint", "remat2",
                                                "remat"))
        if inner > depth:
            found.append(inner)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _checkpoint_regions(sub, inner)
    return found


def _loss_of(fwd, model, data, labels):
    crit = builder.criterion()
    state = model.init_state()
    return lambda p: crit.apply(fwd(p, state, data, training=True)[0],
                                labels)


def test_recomputation_in_the_model_is_bit_identical_to_none(system):
    model, params, data, labels, *_ = system
    assert model.remat_policy == "per_block"
    plain = builder.build(CFG).set_remat(None)
    assert plain.remat_policy is None
    with_remat = jax.value_and_grad(_loss_of(model.apply, model, data,
                                             labels))(params)
    without = jax.value_and_grad(_loss_of(plain.apply, plain, data,
                                          labels))(params)
    for a, b in zip(jax.tree.leaves(with_remat), jax.tree.leaves(without)):
        np.testing.assert_array_equal(a, b)
    n = len(model.modules)
    regions = _checkpoint_regions(jax.make_jaxpr(jax.grad(_loss_of(
        model.apply, model, data, labels)))(params).jaxpr)
    assert regions.count(1) >= n and max(regions) == 1
    assert _checkpoint_regions(jax.make_jaxpr(jax.grad(_loss_of(
        plain.apply, plain, data, labels)))(params).jaxpr) == []


@pytest.fixture()
def on_the_kernel(monkeypatch):
    """``EvaAttention`` on its Pallas kernel, interpreted (the path it
    takes on the TPU), at a window the kernel takes: (model with
    ``per_block``, the same without, params, data, labels)."""
    import functools

    from bigdl_tpu.ops.pallas.eva_attention import eva_attention
    monkeypatch.setattr(attention_mod, "eva_attention_xla",
                        functools.partial(eva_attention, interpret=True))
    cfg = dict(CFG, window_size=128, chunk_size=16)
    model = builder.build(cfg)
    data, labels = _batch(2 * cfg["window_size"])
    return (model, builder.build(cfg).set_remat(None),
            _sharp(model.init(jax.random.PRNGKey(0))), data, labels)


def test_a_recomputed_block_runs_the_attention_kernel_once(on_the_kernel,
                                                           kernel_calls):
    """``o`` and its row statistics are named where the kernel made them
    and ``per_block`` keeps what is named: the gradient's jaxpr holds ONE
    ``eva_attention_fwd`` a layer, not a second in the recomputation."""
    model, plain, params, data, labels = on_the_kernel
    assert model.remat_policy == "per_block"
    for m in (model, plain):
        calls = kernel_calls(jax.make_jaxpr(jax.grad(_loss_of(
            m.apply, m, data, labels)))(params).jaxpr)
        assert calls == dict(eva_attention_fwd=CFG["num_hidden_layers"],
                             eva_attention_dqdkdv=CFG["num_hidden_layers"])


def test_on_the_kernel_recomputation_is_bit_identical_to_none(on_the_kernel):
    model, plain, params, data, labels = on_the_kernel
    with_remat = jax.value_and_grad(_loss_of(model.apply, model, data,
                                             labels))(params)
    without = jax.value_and_grad(_loss_of(plain.apply, plain, data,
                                          labels))(params)
    leaves = jax.tree.leaves_with_path(with_remat)
    assert len(leaves) > 20
    for (path, a), b in zip(leaves, jax.tree.leaves(without)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(
            path))


@pytest.mark.parametrize("policy", ["per_block", "nothing_saveable"])
def test_an_optimizers_policy_wins_and_nothing_is_recomputed_twice(
        system, policy):
    from bigdl_tpu.optim.remat import remat_forward
    model, params, data, labels, *_ = system
    # no policy given: the model's own, whatever differentiates it
    assert remat_forward(model, None) == model.apply
    fwd = remat_forward(model, policy)
    grads = jax.grad(_loss_of(fwd, model, data, labels))(params)
    own = jax.grad(_loss_of(model.apply, model, data, labels))(params)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(own)):
        np.testing.assert_array_equal(a, b)
    regions = _checkpoint_regions(jax.make_jaxpr(jax.grad(_loss_of(
        fwd, model, data, labels)))(params).jaxpr)
    assert regions and max(regions) == 1, regions


def test_per_block_names_the_same_scopes_as_sequential(system):
    model, params, data, labels, *_ = system
    text = jax.jit(_loss_of(model.apply, model, data, labels)).lower(
        params).as_text(debug_info=True)
    for scope in ("embed", "block_0", "block_1", "final_norm", "lm_head",
                  "eva_prep_kv", "eva_attention"):
        assert f"{scope}/" in text or f"/{scope}" in text, scope


def test_the_optimizer_trains_the_model_as_built(system):
    """``Optimizer(model, ...)`` with no policy of its own compiles the
    model's recomputation into the step: checkpoint regions are in the
    step it differentiates, once."""
    from bigdl_tpu.optim.accumulation import make_train_step
    from bigdl_tpu.optim.optim_method import AdamW
    from bigdl_tpu.optim.remat import remat_forward
    model, params, data, labels, *_ = system
    method = AdamW(learning_rate=1e-3)
    step = make_train_step(fwd=remat_forward(model, "none"),
                           criterion=builder.criterion(),
                           update_fn=method.update)
    args = (params, model.init_state(), method.init_state(params), None,
            data, labels, jnp.ones((), jnp.int32))
    regions = _checkpoint_regions(jax.make_jaxpr(step)(*args).jaxpr)
    assert regions and max(regions) == 1
    new_params, _, _, loss = jax.jit(step)(*args)
    assert np.isfinite(float(loss))
    assert float(jnp.max(jnp.abs(new_params["4"]["weight"]
                                 - params["4"]["weight"]))) > 0

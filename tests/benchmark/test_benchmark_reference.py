"""The plain OPT reference against the program's TransformerLM at toy
size (logits, loss, gradients), and the comparison shown able to fail:
a dropped layer, a wrong mask, wrong positions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import model_setup
from benchmarks.builders import opt as builder
from benchmarks.reference import opt as ref

CFG = dict(hidden_size=32, ffn_dim=128, num_attention_heads=4,
           num_hidden_layers=3, vocab_size=97, max_position_embeddings=24,
           dropout=0.0)
HEADS = CFG["num_attention_heads"]


@pytest.fixture(scope="module")
def system():
    """The program's model in float32 with NON-trivial biases and norms
    (its initializers leave them at 0 and 1, which would hide a dropped
    bias)."""
    model = builder.build(CFG)
    model_setup.materialize_lean(model, 5)
    leaves, tree = jax.tree.flatten(model.params)
    keys = jax.random.split(jax.random.PRNGKey(9), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
              for x, k in zip(leaves, keys)]
    model.sync(jax.tree.unflatten(tree, leaves), model.init_state())
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, CFG["vocab_size"] + 1, size=(2, 17))
    return model, tokens[:, :-1].astype(np.int32), \
        tokens[:, 1:].astype(np.int32)


def _sys_logits(model, data):
    out, _ = model.apply(model.params, model.state, jnp.asarray(data),
                         training=False)
    return np.asarray(out, np.float32)


def test_reference_logits_match_the_system(system):
    model, data, _ = system
    w = builder.reference_weights(model.params, CFG)
    got = np.asarray(ref.logits(w, jnp.asarray(data - 1), HEADS))
    np.testing.assert_allclose(got, _sys_logits(model, data), atol=2e-5)
    pos = np.array([[3, 15], [0, 7]])
    at = np.asarray(ref.logits_at(w, jnp.asarray(data - 1),
                                  jnp.asarray(pos), HEADS))
    np.testing.assert_allclose(at[0, 1], got[0, 15], atol=1e-6)
    np.testing.assert_allclose(at[1, 0], got[1, 0], atol=1e-6)


def test_reference_loss_and_gradients_match_the_system(system):
    model, data, labels = system
    w = builder.reference_weights(model.params, CFG)
    crit = builder.criterion()

    def sys_loss(p):
        y, _ = model.apply(p, model.state, jnp.asarray(data),
                           training=True)
        return crit.apply(y, jnp.asarray(labels))

    want, g_sys = jax.value_and_grad(sys_loss)(model.params)
    ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
    assert ref.loss(w, ids, tgt, HEADS) == pytest.approx(float(want),
                                                         rel=1e-5)
    got, g_ref = ref.loss_and_grads(w, ids, tgt, HEADS)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    g_sys = builder.reference_weights(g_sys, CFG)
    for a, b in zip(jax.tree.leaves(g_sys), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("fault", ["dropped_layer", "no_mask",
                                   "shifted_positions", "bf16_weights"])
def test_the_comparison_can_fail(system, fault, monkeypatch):
    """What the benchmark's tolerances must catch, at toy size: each
    fault moves the loss by more than TOL_LOSS_REL, the gradients by
    more than TOL_GRAD_REL on some leaf, and the logits by more than the
    serving check would forgive."""
    from benchmarks.kinds import train
    model, data, labels = system
    w = builder.reference_weights(model.params, CFG)
    ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
    good = ref.loss(w, ids, tgt, HEADS)
    good_logits = ref.logits(w, ids, HEADS)
    _, good_grads = ref.loss_and_grads(w, ids, tgt, HEADS)
    if fault == "dropped_layer":
        w = dict(w, layers=w["layers"][:-1])
    elif fault == "no_mask":
        monkeypatch.setattr(ref.jnp, "tril", lambda x: jnp.ones_like(x))
        ref._layer_jit.clear_cache() if hasattr(
            ref._layer_jit, "clear_cache") else None
        monkeypatch.setattr(ref, "_layer_jit",
                            jax.jit(ref._highest(ref.layer),
                                    static_argnums=2))
    elif fault == "shifted_positions":
        w = dict(w, pos=jnp.roll(w["pos"], 1, axis=0))
    elif fault == "bf16_weights":
        # f32 -> bf16 where the configuration says f32: the loss barely
        # moves (that is what the dtype check is for), so this case
        # asserts the DTYPE comparison, not the loss
        cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), model.params)
        assert not train.params_dtype_ok(cast, "float32")
        assert train.params_dtype_ok(model.params, "float32")
        return
    from benchmarks.kinds import serve
    bad = ref.loss(w, ids, tgt, HEADS)
    assert abs(bad - good) / good > train.TOL_LOSS_REL
    bad_logits = ref.logits(w, ids, HEADS)
    assert float(jnp.max(jnp.abs(bad_logits - good_logits))) \
        > serve.TOL_LOGIT
    if fault != "dropped_layer":          # same tree: compare leaf by leaf
        ref.loss_and_grads.clear_cache()
        _, bad_grads = ref.loss_and_grads(w, ids, tgt, HEADS)
        worst = max(
            float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for a, b in zip(jax.tree.leaves(bad_grads),
                            jax.tree.leaves(good_grads))
            if float(jnp.linalg.norm(b)) > 0)
        assert worst > train.TOL_GRAD_REL

"""nn.Remat + the remat policy registry: gradient equivalence, pytree
transparency, and the static memory receipt.

Remat is a TPU memory lever (jax.checkpoint over a block); it must be
semantically invisible — same outputs, same grads, same param/state tree
(so checkpoints, golden fixtures, and name-matched Caffe/Torch imports
are unaffected by wrapping). The Inception measurement that keeps
``remat=False`` the default is in docs/PERF.md. ISSUE 10 adds NAMED
policies applied at step-construction time (optim/remat.py): gradients
stay bit-identical across policies, saved-residual bytes move, and the
policy keys the AOT executable cache.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn


def _block():
    return (nn.Sequential()
            .add(nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1))
            .add(nn.SpatialBatchNormalization(8))
            .add(nn.ReLU()))


def test_remat_same_tree_outputs_and_grads():
    plain = nn.Sequential().add(_block())
    remat = nn.Sequential().add(nn.Remat(_block()))
    plain.materialize(jax.random.PRNGKey(0))
    remat.materialize(jax.random.PRNGKey(0))
    assert (jax.tree.structure(plain.params)
            == jax.tree.structure(remat.params))

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 3, 8, 8)).astype(np.float32))

    def loss(m, p):
        y, _ = m.apply(p, m.state, x, training=True)
        return jnp.sum(y ** 2)

    ga = jax.grad(lambda p: loss(plain, p))(plain.params)
    gb = jax.grad(lambda p: loss(remat, p))(remat.params)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remat_threads_rng_and_state():
    """Dropout inside Remat: same key -> same mask; BN state updates
    propagate out of the checkpointed region."""
    m = nn.Remat(nn.Sequential().add(nn.SpatialBatchNormalization(3))
                 .add(nn.Dropout(0.5)))
    m.materialize(jax.random.PRNGKey(1))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (8, 3, 4, 4)).astype(np.float32))
    y1, s1 = m.apply(m.params, m.state, x, training=True,
                     rng=jax.random.PRNGKey(7))
    y2, s2 = m.apply(m.params, m.state, x, training=True,
                     rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    rm = np.asarray(s1["0"]["running_mean"])
    assert not np.allclose(rm, 0.0)  # BN stats moved


def _stack(depth=3, d=16):
    m = nn.Sequential()
    for _ in range(depth):
        m.add(nn.Sequential().add(nn.Linear(d, d)).add(nn.Tanh()))
    m.materialize(jax.random.PRNGKey(0))
    m.training()
    return m


class TestPolicyRegistry:
    def test_known_policies_and_validation(self):
        from bigdl_tpu.optim.remat import (check_remat_policy,
                                           known_remat_policies)
        assert set(known_remat_policies()) == {
            "none", "dots_saveable", "per_block", "nothing_saveable"}
        assert check_remat_policy(None) == "none"
        with pytest.raises(ValueError, match="unknown remat policy"):
            check_remat_policy("everything_saveable")

    def test_none_is_the_unwrapped_forward(self):
        from bigdl_tpu.optim.remat import remat_forward
        m = _stack()
        # bound-method identity: same function, same instance (a fresh
        # bound-method object is created per attribute access)
        assert remat_forward(m, "none") == m.apply
        assert remat_forward(m, None) == m.apply

    def test_grads_bit_identical_across_policies(self):
        """The recomputed forward is the same program — gradients must
        not move by a single bit under any policy."""
        from bigdl_tpu.optim.remat import remat_forward
        m = _stack()
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (8, 16)).astype(np.float32))

        def grads(policy):
            fwd = remat_forward(m, policy)

            def loss(p):
                y, _ = fwd(p, m.state, x, training=True, rng=None)
                return jnp.sum(y ** 2)

            return jax.jit(jax.grad(loss))(m.params)

        g0 = grads("none")
        for pol in ("dots_saveable", "per_block", "nothing_saveable"):
            for a, b in zip(jax.tree.leaves(g0),
                            jax.tree.leaves(grads(pol))):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b), err_msg=pol)

    def test_per_block_threads_rng_like_sequential(self):
        """Dropout draws must land exactly where Sequential.apply's
        per-child rng folds put them — per_block mirrors the fold."""
        from bigdl_tpu.optim.remat import remat_forward
        m = nn.Sequential(nn.Linear(8, 8), nn.Dropout(0.5),
                          nn.Linear(8, 8), nn.Dropout(0.5))
        m.materialize(jax.random.PRNGKey(1))
        m.training()
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            (4, 8)).astype(np.float32))
        key = jax.random.PRNGKey(7)
        y0, _ = m.apply(m.params, m.state, x, training=True, rng=key)
        fwd = remat_forward(m, "per_block")
        y1, _ = fwd(m.params, m.state, x, training=True, rng=key)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))

    def test_saved_residual_bytes_move_with_policy(self):
        """The static receipt: heavier policies save strictly fewer
        residual bytes; nothing_saveable well past the 1.5x acceptance
        bar on a deep stack."""
        from bigdl_tpu.optim.remat import (remat_forward,
                                           saved_residual_bytes)
        # batch >> width so activations dominate the saved set (at tiny
        # batch the params the backward reads dominate and every policy
        # converges — the interesting regime is the activation-bound one)
        m = _stack(depth=6, d=32)
        x = jnp.asarray(np.random.default_rng(2).standard_normal(
            (256, 32)).astype(np.float32))

        def resid(policy):
            fwd = remat_forward(m, policy)

            def loss(p):
                y, _ = fwd(p, m.state, x, training=True, rng=None)
                return jnp.sum(y ** 2)

            return saved_residual_bytes(loss, m.params)

        r = {p: resid(p) for p in ("none", "dots_saveable", "per_block",
                                   "nothing_saveable")}
        assert r["none"] > r["dots_saveable"]
        assert r["none"] > r["per_block"] > r["nothing_saveable"]
        assert r["none"] / r["nothing_saveable"] >= 1.5


class _NamesItsSquare(nn.Module):
    """A parameter-free layer that names one value it makes, as an
    attention kernel's module names its output."""

    def init(self, rng):
        return {}

    def apply(self, params, state, x, *, training=False, rng=None):
        from jax.ad_checkpoint import checkpoint_name
        return jnp.sin(checkpoint_name(x * x, "attention_out")), state


class TestWhatPerBlockKeeps:
    """``per_block`` keeps the block boundary and what a module names
    (``KEPT_NAMES``); a model that names nothing is the program it was."""

    def _lm(self):
        from bigdl_tpu.models import TransformerLM
        model = TransformerLM(32, d_model=16, num_heads=2, num_layers=2,
                              max_len=8, with_log_softmax=False)
        model.materialize(jax.random.PRNGKey(0))
        data = jnp.asarray(np.random.default_rng(0).integers(
            1, 33, size=(2, 8)))
        return model, data

    @staticmethod
    def _loss(fwd, model, data):
        def loss(p):
            y, _ = fwd(p, model.state, data, training=True, rng=None)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return loss

    def test_a_model_that_names_nothing_keeps_what_it_kept(self,
                                                           monkeypatch):
        """``TransformerLM``: every residual leaf of the loss's backward
        (shape and dtype, in order) and the lowered gradient program are
        what a bare ``jax.checkpoint(block)`` — the spelling before the
        policy — gives."""
        from bigdl_tpu.optim import remat
        model, data = self._lm()

        def receipt():
            loss = self._loss(remat.remat_forward(model, "per_block"),
                              model, data)
            kept = jax.eval_shape(lambda p: jax.vjp(loss, p)[1],
                                  model.params)
            return ([(leaf.shape, leaf.dtype)
                     for leaf in jax.tree.leaves(kept)],
                    remat.saved_residual_bytes(loss, model.params),
                    jax.jit(jax.grad(loss)).lower(model.params).as_text())

        now = receipt()
        policy = remat._checkpoint_policy
        monkeypatch.setattr(
            remat, "_checkpoint_policy",
            lambda name: None if name == "per_block" else policy(name))
        before = receipt()
        assert now[0] == before[0] and now[1] == before[1] > 0
        assert now[2] == before[2]

    def test_a_named_value_is_kept_and_its_maker_runs_once(self):
        from bigdl_tpu.optim.remat import (KEPT_NAMES, remat_forward,
                                           saved_residual_bytes)
        assert KEPT_NAMES == ("attention_out", "attention_stats",
                              "attention_selection")
        model = nn.Sequential(nn.Linear(8, 8), _NamesItsSquare(),
                              nn.Linear(8, 8))
        model.materialize(jax.random.PRNGKey(0))
        x = jnp.ones((4, 8))

        def loss(policy):
            return self._loss(remat_forward(model, policy), model, x)

        kept = saved_residual_bytes(loss("per_block"), model.params)
        bare = saved_residual_bytes(loss("nothing_saveable"), model.params)
        assert kept > bare
        g = jax.grad(loss("per_block"))(model.params)
        g0 = jax.grad(loss("none"))(model.params)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g0)):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("named", [True, False])
    def test_the_instant_states_the_names_and_the_bytes(self, named):
        """One ``bigdl:optim:remat_kept`` instant a traced forward, with
        tracing on: per block, the values and bytes kept beyond the
        block's inputs."""
        from bigdl_tpu.observability import trace
        from bigdl_tpu.optim.remat import KEPT_NAMES, remat_forward
        model = nn.Sequential(
            nn.Linear(8, 8), _NamesItsSquare() if named else nn.Tanh(),
            nn.Linear(8, 8))
        model.materialize(jax.random.PRNGKey(0))
        loss = self._loss(remat_forward(model, "per_block"), model,
                          jnp.ones((4, 8)))
        jax.eval_shape(jax.grad(loss), model.params)       # tracing off
        trace.clear()
        trace.enable()
        try:
            jax.eval_shape(jax.grad(loss), model.params)
            events = trace.to_dict()["traceEvents"]
        finally:
            trace.disable()
        (said,) = [e for e in events if e["name"] == "remat_kept"]
        assert said["cat"] == "optim"
        middle = [1, 4 * 8 * 4] if named else [0, 0]
        assert said["args"] == dict(
            model="Sequential", names=",".join(KEPT_NAMES), blocks=3,
            values=middle[0], bytes=middle[1],
            per_block=[[0, 0], middle, [0, 0]])

    def test_the_containers_checkpoint_keeps_the_same_names(self):
        """``nn.Remat`` with no policy of its own and the pipeline's
        ``per_block`` take the policy from optim/remat.py."""
        from bigdl_tpu.optim.remat import saved_residual_bytes
        inner = nn.Sequential(nn.Linear(8, 8), _NamesItsSquare())
        wrapped = nn.Remat(inner)
        wrapped.materialize(jax.random.PRNGKey(0))
        x = jnp.ones((4, 8))

        def loss(module):
            return lambda p: jnp.sum(module.apply(
                p, wrapped.state, x, training=True)[0])

        bare = nn.Remat(inner, policy=jax.checkpoint_policies
                        .nothing_saveable)
        assert (saved_residual_bytes(loss(wrapped), wrapped.params)
                - saved_residual_bytes(loss(bare), wrapped.params)
                == 4 * 8 * 4)


class TestOptimizerWiring:
    def _run(self, policy):
        import bigdl_tpu.optim as optim
        from bigdl_tpu.dataset import Sample, SampleToBatch, array
        from bigdl_tpu.utils.random import RandomGenerator
        RandomGenerator.set_seed(7)
        np.random.seed(3)
        rs = np.random.RandomState(0)
        x = rs.rand(64, 4).astype(np.float32)
        t = (x[:, 0] > 0.5).astype(np.int64) + 1
        ds = array([Sample(x[i], t[i]) for i in range(len(x))]) \
            >> SampleToBatch(32)
        model = nn.Sequential(nn.Linear(4, 16), nn.Tanh(),
                              nn.Linear(16, 2), nn.LogSoftMax())
        o = optim.Optimizer(model=model, dataset=ds,
                            criterion=nn.ClassNLLCriterion(),
                            remat_policy=policy)
        o.set_optim_method(optim.SGD(learning_rate=0.5))
        o.set_end_when(optim.max_iteration(3))
        losses = []
        orig = o._emit_step

        def spy(e, loss):
            losses.append(loss)
            orig(e, loss)

        o._emit_step = spy
        m = o.optimize()
        return m.params, losses

    @pytest.mark.parametrize("policy", ["per_block", "nothing_saveable"])
    def test_trained_trajectory_matches_none(self, policy):
        """End-to-end through the compiled donated step: trajectories
        match within XLA fusion rounding (the checkpoint boundary can
        change which ops fuse into an FMA — ulp-level, pinned tight;
        the gradient math itself is bit-identical, see
        TestPolicyRegistry)."""
        p0, l0 = self._run(None)
        p1, l1 = self._run(policy)
        np.testing.assert_allclose(l0, l1, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)

    def test_policy_keys_the_aot_cache(self):
        import bigdl_tpu.optim as optim
        from bigdl_tpu.dataset import Sample, SampleToBatch, array
        rs = np.random.RandomState(0)
        ds = array([Sample(rs.rand(4).astype(np.float32), 1)
                    for _ in range(8)]) >> SampleToBatch(4)
        mk = lambda: optim.Optimizer(
            model=nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax()),
            dataset=ds, criterion=nn.ClassNLLCriterion())
        o_none, o_pb = mk(), mk()
        o_pb.set_remat_policy("per_block")
        assert o_none._step_key_extra() != o_pb._step_key_extra()
        # "none" and never-configured share a key (plain step identity)
        o_explicit = mk()
        o_explicit.set_remat_policy("none")
        assert o_none._step_key_extra() == o_explicit._step_key_extra()

    def test_unknown_policy_refused_eagerly(self):
        import bigdl_tpu.optim as optim
        with pytest.raises(ValueError, match="unknown remat policy"):
            optim.Optimizer(model=nn.Linear(2, 2), dataset=None,
                            criterion=None, remat_policy="fp8")


def test_inception_remat_flag_is_transparent():
    from bigdl_tpu.models import Inception_v1_NoAuxClassifier
    a = Inception_v1_NoAuxClassifier(10)
    b = Inception_v1_NoAuxClassifier(10, remat=True)
    a.materialize(jax.random.PRNGKey(0))
    b.materialize(jax.random.PRNGKey(0))
    assert jax.tree.structure(a.params) == jax.tree.structure(b.params)
    a.evaluate(), b.evaluate()
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 3, 224, 224)).astype(np.float32))
    ya, _ = a.apply(a.params, a.state, x)
    yb, _ = b.apply(b.params, b.state, x)
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))

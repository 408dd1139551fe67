"""Criterion golden tests vs torch (reference test strategy SURVEY §4.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

import bigdl_tpu.nn as nn


def assert_close(a, b, tol=1e-4):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


RS = np.random.RandomState(7)
logits = RS.randn(6, 5).astype(np.float32)
labels1 = RS.randint(1, 6, (6,)).astype(np.int64)  # 1-based


class TestClassNLL:
    def test_loss_and_grad(self):
        logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits)))
        c = nn.ClassNLLCriterion()
        loss = c.forward(jnp.asarray(logp), jnp.asarray(labels1))
        ref = F.nll_loss(torch.from_numpy(logp),
                         torch.from_numpy(labels1 - 1))
        assert_close(loss, ref.item())
        g = c.backward(jnp.asarray(logp), jnp.asarray(labels1))
        t = torch.from_numpy(logp).requires_grad_(True)
        F.nll_loss(t, torch.from_numpy(labels1 - 1)).backward()
        assert_close(g, t.grad.numpy())

    def test_weighted(self):
        w = np.arange(1, 6, dtype=np.float32)
        logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits)))
        c = nn.ClassNLLCriterion(weights=w)
        loss = c.forward(jnp.asarray(logp), jnp.asarray(labels1))
        ref = F.nll_loss(torch.from_numpy(logp),
                         torch.from_numpy(labels1 - 1),
                         weight=torch.from_numpy(w))
        assert_close(loss, ref.item())


class TestCrossEntropy:
    def test_matches_torch(self):
        c = nn.CrossEntropyCriterion()
        loss = c.forward(jnp.asarray(logits), jnp.asarray(labels1))
        ref = F.cross_entropy(torch.from_numpy(logits),
                              torch.from_numpy(labels1 - 1))
        assert_close(loss, ref.item())


class TestMSE:
    def test_loss_and_grad(self):
        x = RS.randn(4, 3).astype(np.float32)
        y = RS.randn(4, 3).astype(np.float32)
        c = nn.MSECriterion()
        assert_close(c.forward(jnp.asarray(x), jnp.asarray(y)),
                     F.mse_loss(torch.from_numpy(x),
                                torch.from_numpy(y)).item())
        g = c.backward(jnp.asarray(x), jnp.asarray(y))
        t = torch.from_numpy(x).requires_grad_(True)
        F.mse_loss(t, torch.from_numpy(y)).backward()
        assert_close(g, t.grad.numpy())


class TestBCE:
    def test_matches_torch(self):
        p = RS.rand(5, 2).astype(np.float32)
        y = RS.randint(0, 2, (5, 2)).astype(np.float32)
        c = nn.BCECriterion()
        assert_close(c.forward(jnp.asarray(p), jnp.asarray(y)),
                     F.binary_cross_entropy(torch.from_numpy(p),
                                            torch.from_numpy(y)).item(),
                     tol=1e-3)


class TestAbsSmoothL1:
    def test_abs(self):
        x = RS.randn(4, 3).astype(np.float32)
        y = RS.randn(4, 3).astype(np.float32)
        assert_close(nn.AbsCriterion().forward(jnp.asarray(x), jnp.asarray(y)),
                     F.l1_loss(torch.from_numpy(x),
                               torch.from_numpy(y)).item())

    def test_smooth_l1(self):
        x = RS.randn(4, 3).astype(np.float32)
        y = RS.randn(4, 3).astype(np.float32)
        assert_close(nn.SmoothL1Criterion().forward(jnp.asarray(x),
                                                    jnp.asarray(y)),
                     F.smooth_l1_loss(torch.from_numpy(x),
                                      torch.from_numpy(y)).item())


class TestDistKLDiv:
    def test_matches_torch(self):
        x = np.asarray(jax.nn.log_softmax(jnp.asarray(logits)))
        t = np.asarray(jax.nn.softmax(jnp.asarray(RS.randn(6, 5)
                                                  .astype(np.float32))))
        c = nn.DistKLDivCriterion()
        ref = F.kl_div(torch.from_numpy(x), torch.from_numpy(t),
                       reduction="batchmean")
        assert_close(c.forward(jnp.asarray(x), jnp.asarray(t)), ref.item(),
                     tol=1e-3)


class TestMargin:
    def test_margin(self):
        x = RS.randn(8).astype(np.float32)
        y = np.sign(RS.randn(8)).astype(np.float32)
        ours = nn.MarginCriterion().forward(jnp.asarray(x), jnp.asarray(y))
        ref = F.hinge_embedding_loss  # not the same; compute manually
        expected = np.maximum(0, 1 - x * y).mean()
        assert_close(ours, expected)

    def test_multi_margin(self):
        c = nn.MultiMarginCriterion()
        loss = c.forward(jnp.asarray(logits), jnp.asarray(labels1))
        ref = F.multi_margin_loss(torch.from_numpy(logits),
                                  torch.from_numpy(labels1 - 1))
        assert_close(loss, ref.item())

    def test_multilabel_soft_margin(self):
        x = RS.randn(4, 5).astype(np.float32)
        y = RS.randint(0, 2, (4, 5)).astype(np.float32)
        c = nn.MultiLabelSoftMarginCriterion()
        ref = F.multilabel_soft_margin_loss(torch.from_numpy(x),
                                            torch.from_numpy(y))
        assert_close(c.forward(jnp.asarray(x), jnp.asarray(y)), ref.item(),
                     tol=1e-3)

    def test_soft_margin(self):
        x = RS.randn(6).astype(np.float32)
        y = np.sign(RS.randn(6)).astype(np.float32)
        c = nn.SoftMarginCriterion()
        ref = F.soft_margin_loss(torch.from_numpy(x), torch.from_numpy(y))
        assert_close(c.forward(jnp.asarray(x), jnp.asarray(y)), ref.item())

    def test_margin_ranking(self):
        a = RS.randn(5).astype(np.float32)
        b = RS.randn(5).astype(np.float32)
        y = np.sign(RS.randn(5)).astype(np.float32)
        c = nn.MarginRankingCriterion(margin=0.5)
        ref = F.margin_ranking_loss(torch.from_numpy(a), torch.from_numpy(b),
                                    torch.from_numpy(y), margin=0.5)
        assert_close(c.forward((jnp.asarray(a), jnp.asarray(b)),
                               jnp.asarray(y)), ref.item())

    def test_hinge_embedding(self):
        x = RS.randn(6).astype(np.float32)
        y = np.sign(RS.randn(6)).astype(np.float32)
        c = nn.HingeEmbeddingCriterion()
        ref = F.hinge_embedding_loss(torch.from_numpy(x),
                                     torch.from_numpy(y))
        assert_close(c.forward(jnp.asarray(x), jnp.asarray(y)), ref.item())

    def test_cosine_embedding(self):
        a = RS.randn(4, 6).astype(np.float32)
        b = RS.randn(4, 6).astype(np.float32)
        y = np.sign(RS.randn(4)).astype(np.float32)
        c = nn.CosineEmbeddingCriterion(margin=0.2)
        ref = F.cosine_embedding_loss(torch.from_numpy(a),
                                      torch.from_numpy(b),
                                      torch.from_numpy(y), margin=0.2)
        assert_close(c.forward((jnp.asarray(a), jnp.asarray(b)),
                               jnp.asarray(y)), ref.item())

    def test_multilabel_margin(self):
        x = RS.randn(3, 5).astype(np.float32)
        t = np.zeros((3, 5), np.int64)
        t[0, :2] = [2, 4]
        t[1, :1] = [1]
        t[2, :3] = [5, 3, 1]
        c = nn.MultiLabelMarginCriterion()
        ref = F.multilabel_margin_loss(torch.from_numpy(x),
                                       torch.from_numpy(t - 1))
        assert_close(c.forward(jnp.asarray(x), jnp.asarray(t)), ref.item(),
                     tol=1e-3)


class TestComposite:
    def test_multi_criterion(self):
        x = RS.randn(4, 3).astype(np.float32)
        y = RS.randn(4, 3).astype(np.float32)
        mc = nn.MultiCriterion().add(nn.MSECriterion(), 0.5) \
                                .add(nn.AbsCriterion(), 2.0)
        expected = 0.5 * nn.MSECriterion().forward(jnp.asarray(x),
                                                   jnp.asarray(y)) + \
            2.0 * nn.AbsCriterion().forward(jnp.asarray(x), jnp.asarray(y))
        assert_close(mc.forward(jnp.asarray(x), jnp.asarray(y)), expected)

    def test_parallel_criterion(self):
        x1 = RS.randn(4, 3).astype(np.float32)
        y1 = RS.randn(4, 3).astype(np.float32)
        pc = nn.ParallelCriterion().add(nn.MSECriterion()) \
                                   .add(nn.AbsCriterion(), 0.1)
        loss = pc.forward((jnp.asarray(x1), jnp.asarray(x1)),
                          (jnp.asarray(y1), jnp.asarray(y1)))
        expected = nn.MSECriterion().forward(jnp.asarray(x1),
                                             jnp.asarray(y1)) + \
            0.1 * nn.AbsCriterion().forward(jnp.asarray(x1), jnp.asarray(y1))
        assert_close(loss, expected)

    def test_time_distributed(self):
        x = RS.randn(2, 3, 4).astype(np.float32)
        y = RS.randn(2, 3, 4).astype(np.float32)
        c = nn.TimeDistributedCriterion(nn.MSECriterion(), size_average=True)
        manual = np.mean([float(nn.MSECriterion().forward(
            jnp.asarray(x[:, t]), jnp.asarray(y[:, t]))) for t in range(3)])
        assert_close(c.forward(jnp.asarray(x), jnp.asarray(y)), manual)

    def test_l1_penalty_and_cost(self):
        x = RS.randn(3, 3).astype(np.float32)
        assert_close(nn.L1Cost().forward(jnp.asarray(x), None),
                     np.abs(x).sum())
        m = nn.L1Penalty(0.1)
        g = m.backward(jnp.asarray(x), jnp.ones((3, 3)))
        assert_close(g, 1.0 + 0.1 * np.sign(x))


def test_time_distributed_vmap_matches_explicit_loop():
    """The vmapped TimeDistributedCriterion (docs/PERF.md 10.4x fix) must
    equal the reference's explicit per-timestep sum for inner criteria
    with and without size averaging."""
    import jax.numpy as jnp
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((4, 6, 10)).astype(np.float32))
    logp = jax.nn.log_softmax(x, axis=-1)
    t = jnp.asarray(rng.integers(1, 11, size=(4, 6)))
    for inner in (nn.ClassNLLCriterion(),
                  nn.ClassNLLCriterion(size_average=False)):
        for size_average in (False, True):
            c = nn.TimeDistributedCriterion(inner, size_average)
            got = float(c.apply(logp, t))
            want = sum(float(inner.apply(logp[:, i], t[:, i]))
                       for i in range(6))
            if size_average:
                want /= 6
            np.testing.assert_allclose(got, want, rtol=1e-5)
    # MSE inner over (N, T, D) regression targets
    y = jnp.asarray(rng.standard_normal((4, 6, 3)).astype(np.float32))
    p = jnp.asarray(rng.standard_normal((4, 6, 3)).astype(np.float32))
    c = nn.TimeDistributedCriterion(nn.MSECriterion())
    want = sum(float(nn.MSECriterion().apply(p[:, i], y[:, i]))
               for i in range(6))
    np.testing.assert_allclose(float(c.apply(p, y)), want, rtol=1e-5)


def test_weighted_cross_entropy_matches_torch():
    """The lse-form CrossEntropyCriterion's weighted reduction (review
    r2: previously delegated to ClassNLL, now shared via _nll_reduce)."""
    rng = np.random.default_rng(12)
    x = rng.standard_normal((8, 5)).astype(np.float32)
    t = rng.integers(1, 6, size=(8,))
    w = rng.uniform(0.5, 2.0, size=(5,)).astype(np.float32)
    for size_average, red in ((True, "mean"), (False, "sum")):
        c = nn.CrossEntropyCriterion(weights=w, size_average=size_average)
        got = float(c.apply(jnp.asarray(x), jnp.asarray(t)))
        want = F.cross_entropy(torch.tensor(x), torch.tensor(t - 1),
                               weight=torch.tensor(w), reduction=red)
        np.testing.assert_allclose(got, float(want), rtol=1e-5)


def test_label_smoothing_matches_torch():
    rng = np.random.default_rng(13)
    x = rng.standard_normal((10, 7)).astype(np.float32)
    t = rng.integers(1, 8, size=(10,))
    w = rng.uniform(0.5, 2.0, size=(7,)).astype(np.float32)
    for eps in (0.1, 0.3):
        for weights in (None, w):
            c = nn.CrossEntropyCriterion(weights=weights,
                                         label_smoothing=eps)
            got = float(c.apply(jnp.asarray(x), jnp.asarray(t)))
            want = F.cross_entropy(
                torch.tensor(x), torch.tensor(t - 1),
                weight=None if weights is None else torch.tensor(w),
                label_smoothing=eps)
            np.testing.assert_allclose(got, float(want), rtol=1e-5)
    with pytest.raises(ValueError, match="label_smoothing"):
        nn.CrossEntropyCriterion(label_smoothing=1.0)


class _ParentCrossEntropy(nn.CrossEntropyCriterion):
    """The formula ``CrossEntropyCriterion`` computed before it stopped
    widening the logits to gather from them (PERF.md section 6, PR 30):
    the float32 cast of the whole input, ``logsumexp`` of it and a
    gather of the label's logit; the yardstick of the test below."""

    def apply(self, x, target):
        t = target.astype(jnp.int32).reshape(-1) - 1
        logits = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        per = lse - jnp.take_along_axis(logits, t[:, None], axis=1)[:, 0]
        eps, w = self.label_smoothing, self.weights
        w_t = jnp.ones_like(per) if w is None else jnp.take(w, t)
        if eps > 0.0 and w is not None:
            smooth = (lse * jnp.sum(w) - logits @ w) / logits.shape[-1]
            total = jnp.sum((1.0 - eps) * w_t * per + eps * smooth)
        else:
            if eps > 0.0:
                per = (1.0 - eps) * per + eps * (
                    lse - jnp.mean(logits, axis=-1))
            total = jnp.sum(w_t * per)
        return total / jnp.sum(w_t) if self.size_average else total


_CE_CLASSES = 11
_CE_OPTIONS = {
    "plain": {},
    "weights": {"weights": np.linspace(0.5, 2.0, _CE_CLASSES,
                                       dtype=np.float32)},
    "label_smoothing": {"label_smoothing": 0.1},
    "both": {"weights": np.linspace(2.0, 0.5, _CE_CLASSES,
                                    dtype=np.float32),
             "label_smoothing": 0.3},
    "sum": {"size_average": False},
}


@pytest.mark.parametrize("masked", [False, True], ids=["bare", "masked"])
@pytest.mark.parametrize("options", sorted(_CE_OPTIONS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_cross_entropy_is_the_parent_formula(dtype, options, masked):
    """One pass over the logits as they arrive gives the loss and the
    gradient of the parent's cast-then-gather formula.

    float32: 1e-6 of the gradient's largest element (the weighted
    smoothing term sums in another order). bfloat16: the cotangent is
    computed in float32 and rounded once to the input's dtype, as
    autodiff's transposed cast rounds the parent's, so each element is
    within one bf16 ulp (2**-7 of itself at most)."""
    rng = np.random.default_rng(30)
    x = jnp.asarray(3.0 * rng.standard_normal((4, 6, _CE_CLASSES)),
                    dtype)
    t = rng.integers(1, _CE_CLASSES + 1, size=(4, 6))
    t[0, :2] = 1, _CE_CLASSES            # the first class and the last
    t = jnp.asarray(t)
    system = nn.CrossEntropyCriterion(**_CE_OPTIONS[options])
    parent = _ParentCrossEntropy(**_CE_OPTIONS[options])
    mask = jnp.asarray([True, True, False, True])
    if masked:
        def loss(c, x, t=t):
            return nn.MaskedCriterion(c).apply(x, t, mask)
    else:
        def loss(c, x, t=t):
            return c.apply(x, t)

    value, got = jax.value_and_grad(loss, argnums=1)(system, x)
    want_value, want = jax.value_and_grad(loss, argnums=1)(parent, x)
    assert value.dtype == jnp.float32
    np.testing.assert_allclose(value, want_value, rtol=1e-6)
    assert got.dtype == x.dtype and got.shape == x.shape
    want32 = np.asarray(want, np.float32)

    def agrees(g):
        off = np.abs(np.asarray(g, np.float32) - want32)
        if dtype == "float32":
            return np.max(off) <= 1e-6 * np.max(np.abs(want32))
        return np.all(off <= 2.0 ** -7 * np.abs(want32))

    assert agrees(got)
    if masked:
        assert not np.any(got[2]) and np.any(got[3])
    # the comparison can fail: another label moves the gradient
    assert not agrees(jax.grad(loss, argnums=1)(
        parent, x, jnp.roll(t, 1, axis=1)))
    # the gradient traces under jit and under vmap
    grad = jax.grad(lambda x: loss(system, x))
    assert agrees(jax.jit(grad)(x))
    assert agrees(jax.vmap(grad)(jnp.stack([x + 1, x]))[1])

"""Criterion (loss) library.

Reference parity (dl/.../bigdl/nn/): ClassNLLCriterion, MSECriterion,
BCECriterion, CrossEntropyCriterion, ClassSimplexCriterion, AbsCriterion,
CosineEmbeddingCriterion, DistKLDivCriterion, HingeEmbeddingCriterion,
L1Cost, L1HingeEmbeddingCriterion, MarginCriterion, MarginRankingCriterion,
MultiCriterion, MultiLabelMarginCriterion, MultiLabelSoftMarginCriterion,
MultiMarginCriterion, SmoothL1Criterion, SmoothL1CriterionWithWeights,
SoftMarginCriterion, SoftmaxWithCriterion, ParallelCriterion,
TimeDistributedCriterion, CriterionTable.

Conventions: class targets are **1-based** like the reference/Torch; losses
are pure scalar functions, gradients via autodiff (the reference hand-writes
``updateGradInput`` per criterion).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Criterion

__all__ = ["ClassNLLCriterion", "MSECriterion", "BCECriterion",
           "CrossEntropyCriterion", "ClassSimplexCriterion", "AbsCriterion",
           "CosineEmbeddingCriterion", "DistKLDivCriterion",
           "HingeEmbeddingCriterion", "L1Cost", "L1HingeEmbeddingCriterion",
           "MarginCriterion", "MarginRankingCriterion", "MultiCriterion",
           "MultiLabelMarginCriterion", "MultiLabelSoftMarginCriterion",
           "MultiMarginCriterion", "SmoothL1Criterion",
           "MultiBytePredictionCriterion",
           "SmoothL1CriterionWithWeights", "SoftMarginCriterion",
           "SoftmaxWithCriterion", "ParallelCriterion",
           "TimeDistributedCriterion", "CriterionTable", "MaskedCriterion"]


def _avg(v, n, size_average):
    return v / n if size_average else v


def _nll_reduce(per, t, weights, size_average):
    """Shared NLL reduction: ``per`` is the per-sample loss, ``t`` the
    0-based class index (for per-class weights)."""
    if weights is not None:
        w = jnp.take(weights, t)
        total = jnp.sum(w * per)
        return total / jnp.sum(w) if size_average else total
    return _avg(jnp.sum(per), t.shape[0], size_average)


class ClassNLLCriterion(Criterion):
    """NLL over log-probabilities; 1-based integer targets
    (reference nn/ClassNLLCriterion.scala, threaded per sample)."""

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def apply(self, x, target):
        t = target.astype(jnp.int32).reshape(-1) - 1
        logp = x.reshape(-1, x.shape[-1])
        picked = jnp.take_along_axis(logp, t[:, None], axis=1)[:, 0]
        return _nll_reduce(-picked, t, self.weights, self.size_average)


class MSECriterion(Criterion):
    """(reference nn/MSECriterion.scala)"""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def apply(self, x, target):
        return _avg(jnp.sum(jnp.square(x - target)), x.size,
                    self.size_average)


class AbsCriterion(Criterion):
    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def apply(self, x, target):
        return _avg(jnp.sum(jnp.abs(x - target)), x.size, self.size_average)


class BCECriterion(Criterion):
    """(reference nn/BCECriterion.scala; eps clamp like Torch)"""

    eps = 1e-12

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def apply(self, x, target):
        l = target * jnp.log(x + self.eps) + \
            (1 - target) * jnp.log(1 - x + self.eps)
        if self.weights is not None:
            l = l * self.weights
        return _avg(-jnp.sum(l), x.size, self.size_average)


class CrossEntropyCriterion(Criterion):
    """LogSoftMax + ClassNLL fused (reference nn/CrossEntropyCriterion.scala).

    TPU note: computed as ``logsumexp(x) - x[target]`` rather than
    composing ``log_softmax`` + NLL: the composition materializes the
    (N, V) log-prob tensor in f32 as a saved residual, while the lse
    form's backward is ``softmax(x) - onehot``, which XLA evaluates
    inside the consumers of the one cotangent. The label's logit is a
    masked row sum beside the sum of exponentials, not a gather: every
    reduction reads the logits where and as they arrived (bf16 from an
    LM head) and widens them on the way, so no float32 array of the
    logits' size is written in either direction. A gather wants its
    operand in memory, rows by classes: at LM vocabulary sizes that was
    a float32 copy of all the logits to pick one number a row
    (PERF.md section 6, PR 30)."""

    def __init__(self, weights=None, size_average: bool = True,
                 label_smoothing: float = 0.0):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average
        if not 0.0 <= label_smoothing < 1.0:
            raise ValueError(f"label_smoothing must be in [0, 1), got "
                             f"{label_smoothing}")
        self.label_smoothing = label_smoothing

    def apply(self, x, target):
        from bigdl_tpu.observability import trace
        t = target.astype(jnp.int32).reshape(-1) - 1
        logits = x.reshape(-1, x.shape[-1]).astype(
            jnp.promote_types(x.dtype, jnp.float32))
        # python runs this where the criterion is traced for a compile,
        # never in a step
        trace.instant("cross_entropy", cat="nn", rows=logits.shape[0],
                      classes=logits.shape[1], logits_dtype=x.dtype.name,
                      materialised_bytes=0)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        column = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        picked = jnp.sum(jnp.where(column == t[:, None], logits, 0.0),
                         axis=-1)
        per = lse - picked
        eps = self.label_smoothing
        if eps > 0.0 and self.weights is not None:
            # torch convention with class weights: the target term is
            # weighted by w[t] but the smoothing term by each class's
            # own weight (-(logp * w).sum / K); mean divides by sum w[t]
            w = self.weights.astype(logits.dtype)
            w_t = jnp.take(w, t)
            smooth = (lse * jnp.sum(w) - jnp.sum(logits * w, axis=-1)) \
                / logits.shape[-1]
            total = jnp.sum((1.0 - eps) * w_t * per + eps * smooth)
            return total / jnp.sum(w_t) if self.size_average else total
        if eps > 0.0:
            # (1-eps)*CE(target) + eps*mean_c CE(c)
            per = (1.0 - eps) * per + eps * (lse - jnp.mean(logits,
                                                            axis=-1))
        return _nll_reduce(per, t, self.weights, self.size_average)


class ClassSimplexCriterion(Criterion):
    """MSE against a regular-simplex embedding of the classes
    (reference nn/ClassSimplexCriterion.scala)."""

    def __init__(self, n_classes: int):
        super().__init__()
        self.n_classes = n_classes
        self.simplex = jnp.asarray(self._regular_simplex(n_classes))
        self.mse = MSECriterion()

    @staticmethod
    def _regular_simplex(n):
        """n unit vertices in R^n with pairwise dot -1/(n-1) — the regular
        simplex the reference embeds classes into."""
        a = np.zeros((n, n), np.float32)
        for k in range(n - 1):
            a[k, k] = np.sqrt(max(1.0 - np.sum(a[k, :k] ** 2), 0.0))
            for j in range(k + 1, n):
                a[j, k] = (-1.0 / (n - 1) - np.dot(a[j, :k], a[k, :k])) \
                    / a[k, k]
        return a

    def apply(self, x, target):
        t = target.astype(jnp.int32).reshape(-1) - 1
        goal = jnp.take(self.simplex, t, axis=0)
        return self.mse.apply(x, goal)


class CosineEmbeddingCriterion(Criterion):
    """(reference nn/CosineEmbeddingCriterion.scala; y=1 similar, y=-1
    dissimilar with margin)"""

    def __init__(self, margin: float = 0.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def apply(self, x, target):
        a, b = x
        y = target.reshape(-1)
        cos = jnp.sum(a * b, -1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        l = jnp.where(y > 0, 1 - cos, jnp.maximum(0.0, cos - self.margin))
        return _avg(jnp.sum(l), y.shape[0], self.size_average)


class DistKLDivCriterion(Criterion):
    """KL(target || exp(input)) with log-prob input
    (reference nn/DistKLDivCriterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def apply(self, x, target):
        l = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-30))
                                            - x), 0.0)
        n = x.shape[0] if x.ndim > 1 else 1
        return _avg(jnp.sum(l), x.size if x.ndim == 1 else n,
                    self.size_average)


class HingeEmbeddingCriterion(Criterion):
    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def apply(self, x, target):
        l = jnp.where(target > 0, x, jnp.maximum(0.0, self.margin - x))
        return _avg(jnp.sum(l), x.size, self.size_average)


class L1Cost(Criterion):
    """(reference nn/L1Cost.scala)"""

    def apply(self, x, target=None):
        return jnp.sum(jnp.abs(x))


class L1HingeEmbeddingCriterion(Criterion):
    """Hinge on L1 distance of a pair (reference
    nn/L1HingeEmbeddingCriterion.scala)."""

    def __init__(self, margin: float = 1.0):
        super().__init__()
        self.margin = margin

    def apply(self, x, target):
        a, b = x
        d = jnp.sum(jnp.abs(a - b))
        y = jnp.reshape(target, ())
        return jnp.where(y > 0, d, jnp.maximum(0.0, self.margin - d))


class MarginCriterion(Criterion):
    """Hinge loss (reference nn/MarginCriterion.scala; squared option)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True,
                 squared: bool = False):
        super().__init__()
        self.margin, self.size_average, self.squared = \
            margin, size_average, squared

    def apply(self, x, target):
        l = jnp.maximum(0.0, self.margin - x * target)
        if self.squared:
            l = jnp.square(l)
        return _avg(jnp.sum(l), x.size, self.size_average)


class MarginRankingCriterion(Criterion):
    """(reference nn/MarginRankingCriterion.scala)"""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin, self.size_average = margin, size_average

    def apply(self, x, target):
        a, b = x
        y = jnp.reshape(target, -1)
        l = jnp.maximum(0.0, -y * (a.reshape(-1) - b.reshape(-1))
                        + self.margin)
        return _avg(jnp.sum(l), l.size, self.size_average)


class MultiCriterion(Criterion):
    """Weighted sum of criterions on the same (input, target)
    (reference nn/MultiCriterion.scala)."""

    def __init__(self):
        super().__init__()
        self.criterions: list[Criterion] = []
        self.weights: list[float] = []

    def add(self, criterion: Criterion, weight: float = 1.0):
        self.criterions.append(criterion)
        self.weights.append(weight)
        return self

    def apply(self, x, target):
        return sum(w * c.apply(x, target)
                   for c, w in zip(self.criterions, self.weights))


class ParallelCriterion(Criterion):
    """i-th criterion on (input[i], target[i]) weighted sum
    (reference nn/ParallelCriterion.scala; repeatTarget broadcasts)."""

    def __init__(self, repeat_target: bool = False):
        super().__init__()
        self.repeat_target = repeat_target
        self.criterions: list[Criterion] = []
        self.weights: list[float] = []

    def add(self, criterion: Criterion, weight: float = 1.0):
        self.criterions.append(criterion)
        self.weights.append(weight)
        return self

    def apply(self, x, target):
        total = 0.0
        for i, (c, w) in enumerate(zip(self.criterions, self.weights)):
            t = target if self.repeat_target else target[i]
            total = total + w * c.apply(x[i], t)
        return total


class MultiLabelMarginCriterion(Criterion):
    """(reference nn/MultiLabelMarginCriterion.scala; targets are 1-based
    label lists padded with 0)"""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def apply(self, x, target):
        x2 = jnp.atleast_2d(x)
        t2 = jnp.atleast_2d(target).astype(jnp.int32)
        n, c = x2.shape

        def per_sample(xi, ti):
            valid = ti > 0
            idx = jnp.clip(ti - 1, 0, c - 1)
            # padding entries scatter out-of-range and are dropped
            is_target = jnp.zeros((c,), bool).at[
                jnp.where(valid, idx, c)].set(True, mode="drop")
            tgt_scores = jnp.where(valid, xi[idx], 0.0)
            # sum over target j, non-target k of max(0, 1 - (x_j - x_k))
            margins = 1.0 - (tgt_scores[:, None] - xi[None, :])
            mask = valid[:, None] & (~is_target)[None, :]
            return jnp.sum(jnp.where(mask, jnp.maximum(margins, 0.0), 0.0)) / c

        l = jax.vmap(per_sample)(x2, t2)
        return _avg(jnp.sum(l), n, self.size_average)


class MultiLabelSoftMarginCriterion(Criterion):
    """Sigmoid + BCE per label (reference
    nn/MultiLabelSoftMarginCriterion.scala)."""

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def apply(self, x, target):
        l = target * jax.nn.log_sigmoid(x) + \
            (1 - target) * jax.nn.log_sigmoid(-x)
        if self.weights is not None:
            l = l * self.weights
        n = x.shape[0] if x.ndim > 1 else 1
        per = -jnp.sum(l) / x.shape[-1]
        return _avg(per, n, self.size_average)


class MultiMarginCriterion(Criterion):
    """Multi-class hinge (reference nn/MultiMarginCriterion.scala)."""

    def __init__(self, p: int = 1, weights=None, margin: float = 1.0,
                 size_average: bool = True):
        super().__init__()
        self.p, self.margin, self.size_average = p, margin, size_average
        self.weights = None if weights is None else jnp.asarray(weights)

    def apply(self, x, target):
        x2 = jnp.atleast_2d(x)
        t = jnp.reshape(target, -1).astype(jnp.int32) - 1
        n, c = x2.shape
        tgt = jnp.take_along_axis(x2, t[:, None], axis=1)
        m = jnp.maximum(0.0, self.margin - tgt + x2)
        if self.p == 2:
            m = jnp.square(m)
        if self.weights is not None:
            m = m * jnp.take(self.weights, t)[:, None]
        onehot = jax.nn.one_hot(t, c, dtype=bool)
        per = jnp.sum(jnp.where(onehot, 0.0, m), axis=1) / c
        return _avg(jnp.sum(per), n, self.size_average)


class SmoothL1Criterion(Criterion):
    """Huber (reference nn/SmoothL1Criterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def apply(self, x, target):
        d = jnp.abs(x - target)
        l = jnp.where(d < 1.0, 0.5 * jnp.square(d), d - 0.5)
        return _avg(jnp.sum(l), x.size, self.size_average)


class SmoothL1CriterionWithWeights(Criterion):
    """Fast-RCNN bbox regression loss with inside/outside weights
    (reference nn/SmoothL1CriterionWithWeights.scala).

    Target is (t, inside_w, outside_w); sigma scales the transition point.
    """

    def __init__(self, sigma: float = 1.0, num: int = 0):
        super().__init__()
        self.sigma2 = sigma * sigma
        self.num = num

    def apply(self, x, target):
        t, w_in, w_out = target
        d = w_in * (x - t)
        ad = jnp.abs(d)
        l = jnp.where(ad < 1.0 / self.sigma2,
                      0.5 * self.sigma2 * jnp.square(d),
                      ad - 0.5 / self.sigma2)
        total = jnp.sum(w_out * l)
        return total / self.num if self.num > 0 else total


class SoftMarginCriterion(Criterion):
    """log(1 + exp(-y*x)) (reference nn/SoftMarginCriterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def apply(self, x, target):
        l = jnp.log1p(jnp.exp(-x * target))
        return _avg(jnp.sum(l), x.size, self.size_average)


class SoftmaxWithCriterion(Criterion):
    """Caffe-style SoftmaxWithLoss over NCHW logits with optional
    ignore_label and normalization modes (reference
    nn/SoftmaxWithCriterion.scala)."""

    def __init__(self, ignore_label: int | None = None,
                 normalize_mode: str = "valid"):
        super().__init__()
        self.ignore_label = ignore_label
        self.normalize_mode = normalize_mode

    def apply(self, x, target):
        # x: (N, C, ...); target 1-based labels (N, ...)
        logp = jax.nn.log_softmax(x, axis=1)
        t = target.astype(jnp.int32) - 1
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(jnp.clip(t, 0, x.shape[1] - 1), 1),
            axis=1).squeeze(1)
        if self.ignore_label is not None:
            mask = (target.astype(jnp.int32) != self.ignore_label)
            picked = jnp.where(mask, picked, 0.0)
            count = jnp.sum(mask)
        else:
            count = picked.size
        total = -jnp.sum(picked)
        if self.normalize_mode == "valid":
            return total / jnp.maximum(count, 1)
        if self.normalize_mode == "full":
            return total / picked.size
        if self.normalize_mode == "batch_size":
            return total / x.shape[0]
        return total  # "none"


class TimeDistributedCriterion(Criterion):
    """Apply a criterion at every timestep of (N, T, ...) input
    (reference nn/TimeDistributedCriterion.scala)."""

    def __init__(self, critrn: Criterion, size_average: bool = False):
        super().__init__()
        self.critrn = critrn
        self.size_average = size_average

    def apply(self, x, target):
        # vmap over the time axis instead of a Python loop: identical
        # per-timestep semantics for any inner criterion, but ONE fused
        # graph — the unrolled loop put T separate gathers in the HLO
        # (T=2048 made the transformer LM step 9x slower and the compile
        # pathological; docs/PERF.md)
        T = x.shape[1]
        losses = jax.vmap(self.critrn.apply, in_axes=1)(x, target)
        total = jnp.sum(losses)
        return total / T if self.size_average else total


class CriterionTable(Criterion):
    """Adapt a criterion to table input (x, target)
    (reference nn/CriterionTable.scala)."""

    def __init__(self, critrn: Criterion):
        super().__init__()
        self.critrn = critrn

    def apply(self, x, target=None):
        inp, t = x
        return self.critrn.apply(inp, t)


class MaskedCriterion(Criterion):
    """Row-validity mask around any per-sample-decomposable criterion.

    The input-pipeline's partial-batch padding
    (``dataset.prefetch.PadPartialBatches``) keeps the train step at ONE
    compiled signature by padding short batches to the full shape; this
    wrapper guarantees the padded rows contribute exactly zero to the
    loss AND its gradient: the base criterion is vmapped over the batch
    axis (each row evaluated as its own batch of one — valid for any
    criterion whose batch loss is a mean/sum of per-row terms), the
    per-row losses are multiplied by ``mask``, and the reduction honors
    the base's ``size_average`` (mean over VALID rows, or masked sum).
    """

    def __init__(self, criterion: Criterion):
        super().__init__()
        self.criterion = criterion

    def apply(self, x, target, mask):
        total, count = self.masked_sum(x, target, mask)
        if getattr(self.criterion, "size_average", True):
            return total / jnp.maximum(count, 1.0)
        return total

    def masked_sum(self, x, target, mask):
        """Unnormalized ``(masked loss sum, valid-row count)`` — the
        accumulation seam (optim/accumulation.py): gradient accumulation
        sums numerator and denominator across microbatches separately
        and divides ONCE, so a short batch split into microbatches with
        uneven valid counts still reproduces the full batch's masked
        mean exactly."""
        per_row = jax.vmap(
            lambda xi, ti: self.criterion.apply(xi[None], ti[None]))(
                x, target)
        m = mask.astype(per_row.dtype)
        return jnp.sum(per_row * m), jnp.sum(m)


class MultiBytePredictionCriterion(Criterion):
    """Cross-entropy of ``num_heads`` prediction heads, head i (1-based)
    at position t scored against the token i steps ahead (multi-byte
    prediction, as EvaByte's ``num_pred_heads``; head 1 alone is
    next-token cross-entropy).

    ``x``: (B, S, num_heads * vocab) logits, head-major in the last axis;
    ``target``: the (B, S) 1-based NEXT-token labels the input pipeline
    yields (target[t] = token t + 1), shifted here: head i reads
    target[t + i - 1] and has no target in the last i - 1 positions,
    which are left out. Each head's loss is the mean over its own
    positions and the heads weigh equally."""

    def __init__(self, num_heads: int, vocab: int):
        super().__init__()
        self.num_heads, self.vocab = num_heads, vocab

    def apply(self, x, target):
        b, s, _ = x.shape
        h = self.num_heads
        logits = x.reshape(b, s, h, self.vocab).astype(
            jnp.promote_types(x.dtype, jnp.float32))
        ahead = jnp.arange(s)[:, None] + jnp.arange(h)[None, :]   # (S, H)
        valid = ahead < s
        t = (target.astype(jnp.int32) - 1)[:, jnp.minimum(ahead, s - 1)]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        per_head = jnp.sum(jnp.where(valid, lse - picked, 0.0),
                           axis=(0, 1)) / (b * jnp.sum(valid, axis=0))
        return jnp.mean(per_head)

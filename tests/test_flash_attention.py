"""Pallas flash-attention kernel vs the XLA attention path.

Runs the kernel in interpret mode on CPU (same convention as the LRN
kernel tests in test_perf_paths.py). Tolerances are ~1e-3 because BOTH
paths round matmul operands to bf16 under JAX's default matmul precision
— measured: a 128-deep f32 dot differs from f64 by ~6e-3 at default
precision and ~3e-7 at "highest" — so the comparison pins algorithmic
equivalence, not operand precision (inputs are scaled to keep the
softmax temperate, as peaked softmaxes amplify logit rounding).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops.pallas.flash_attention import flash_attention
from bigdl_tpu.parallel.sequence import dot_product_attention

INTERP = jax.default_backend() != "tpu"


def _qkv(rng, b, s, h, d, skv=None):
    skv = s if skv is None else skv
    q = jnp.asarray(0.2 * rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(0.2 * rng.standard_normal((b, skv, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, h, d)), jnp.float32)
    return q, k, v


def _naive(q, k, v, causal):
    return dot_product_attention(q, k, v, causal=causal, flash=False)


# (causal, sq, skv, d). Causal self-attention takes the looped schedule
# (K/V resident, one backward pass): one uncut block at 256, two q blocks
# cut in two on the diagonal at 1024, the benchmark's four of them and a
# last step of up to three blocks beside the diagonal at 2048; the rest
# stream rectangular tiles.
CASES = [(False, 256, 256, 128), (True, 256, 256, 128),
         (True, 256, 256, 64), (True, 512, 512, 64), (True, 512, 512, 128),
         (True, 1024, 1024, 64), (True, 2048, 2048, 64),
         (False, 256, 384, 64),
         (False, 384, 128, 128)]
CASE_IDS = [f"{'causal' if c else 'full'}-{sq}x{skv}-d{d}"
            for c, sq, skv, d in CASES]


@pytest.mark.parametrize("causal,sq,skv,d", CASES, ids=CASE_IDS)
def test_forward_matches_xla_path(causal, sq, skv, d):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, 2, sq, 2, d, skv=skv)
    o_fl = flash_attention(q, k, v, causal=causal, interpret=INTERP)
    o_nv = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o_fl), np.asarray(o_nv),
                               rtol=2e-2, atol=2e-3)


def _assert_grads_match(loss_fl, loss_nv, args, argnums=(0, 1, 2)):
    g_fl = jax.grad(loss_fl, argnums=argnums)(*args)
    g_nv = jax.grad(loss_nv, argnums=argnums)(*args)
    for a, b in zip(g_fl, g_nv):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("causal,sq,skv,d", CASES, ids=CASE_IDS)
def test_gradients_match_xla_path(causal, sq, skv, d):
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, 1, sq, 2, d, skv=skv)
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    _assert_grads_match(
        lambda q, k, v: jnp.vdot(flash_attention(
            q, k, v, causal=causal, interpret=INTERP), ct),
        lambda q, k, v: jnp.vdot(_naive(q, k, v, causal), ct), (q, k, v))


@pytest.fixture
def store():
    """An empty tuning-record store for the test, then the default."""
    from bigdl_tpu.tuning.records import TuningRecords, set_default_records
    s = TuningRecords()
    set_default_records(s)
    yield s
    set_default_records(None)


# (sq, record, VMEM budget): tiles the menu does not pick, through a
# tuning record. The looped kernels take a nested pair as (q rows a grid
# step, K rows a loop step): two and four blocks a step run the loop AND
# every static body of what is left beside the diagonal; 256 rows a grid
# step are cut into two squares on the diagonal, 128 are not. No budget
# streams the record's tiles over the grid, where the index maps clamp
# above the diagonal.
@pytest.mark.parametrize("sq,record,budget", [
    (512, (256, 128), None), (512, (128, 256), None),
    (1024, (128, 512), None), (1024, (256, 512), None),
    (512, (128, 256), 0), (512, (256, 128), 0)])
def test_causal_gradients_at_other_tiles(store, monkeypatch, sq, record,
                                         budget):
    from bigdl_tpu.ops.pallas import flash_attention as fa
    store.record("flash_attention", {"sq": sq, "skv": sq},
                 {"bq": record[0], "bk": record[1]})
    if budget is not None:
        monkeypatch.setattr(fa, "_RESIDENT_BUDGET", budget)
    sched = fa._schedule(True, sq, sq, 64, 4)
    assert sched.kv_resident == sched.one_pass_backward == (budget is None)
    side, step = sorted(record)
    assert sched[:5] == ((side, step, side, step, 128) if budget is None
                         else record * 2 + (0,))
    rng = np.random.default_rng(10)
    q, k, v = _qkv(rng, 1, sq, 1, 64)
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    _assert_grads_match(
        lambda q, k, v: jnp.vdot(flash_attention(
            q, k, v, causal=True, interpret=INTERP), ct),
        lambda q, k, v: jnp.vdot(_naive(q, k, v, True), ct), (q, k, v))


def test_schedule_wastes_the_diagonal_only():
    """Pure arithmetic: at the benchmark cells' shape the causal schedule
    computes at most 1.13 of the causal half (the parent's 512 x 1024
    grid computed 1.5 of it)."""
    from bigdl_tpu.ops.pallas.flash_attention import _schedule
    s = _schedule(True, 2048, 2048, 64, 2)
    assert s.kv_resident and s.one_pass_backward
    # whole q blocks a loop step, whole squares a q block
    assert s.bk % s.bq == 0 and s.bwd_bk % s.bwd_bq == 0
    assert s.bq % s.block == 0
    n = 2048 // s.block
    assert s.tiles_computed == n * (n + 1) // 2
    assert 1.0 < s.tiles_computed / s.tiles_visible <= 1.13
    # what streams keeps the rectangular menu; no mask, no waste
    r = _schedule(False, 2048, 2048, 64, 2)
    assert (r.bq, r.bk, r.block) == (512, 1024, 0) and not r.kv_resident
    assert r.tiles_computed == r.tiles_visible == 8
    # causal cross-length calls stream too: the parent's 1.5
    x = _schedule(True, 2048, 4096, 64, 2)
    assert not x.kv_resident and not x.one_pass_backward
    assert x.tiles_computed / x.tiles_visible == pytest.approx(1.5, abs=1e-3)


def test_schedule_follows_the_vmem_budget():
    """What a head holds in VMEM decides residency and the number of
    backward passes: K, V, the dk/dv blocks and their f32 accumulators of
    a 16k-token head exceed the budget, so the backward keeps two kernels
    and rectangular tiles while K/V alone still fit; at 32k K/V stream
    too."""
    from bigdl_tpu.ops.pallas.flash_attention import (_RESIDENT_BUDGET,
                                                      _schedule)
    fits = _schedule(True, 8192, 8192, 128, 2)
    assert fits.kv_resident and fits.one_pass_backward
    long = _schedule(True, 16384, 16384, 128, 2)
    assert 2 * 16384 * 128 * 4 > _RESIDENT_BUDGET // 2   # the f32 dk, dv
    assert long.kv_resident and not long.one_pass_backward
    assert (long.bwd_bq, long.bwd_bk) == (512, 1024)
    assert long.bk % long.bq == 0 and long.block
    longer = _schedule(True, 32768, 32768, 128, 2)
    assert not longer.kv_resident and not longer.one_pass_backward
    assert (longer.bq, longer.bk, longer.block) == (512, 1024, 0)
    # f32 operands are twice the bytes
    assert not _schedule(True, 16384, 16384, 128, 4).kv_resident


def test_schedule_is_stated_once_a_trace():
    """``flash_schedule`` is an instant of the tracer (and so a profiler
    annotation) emitted where the kernel is TRACED: a call served by the
    compiled program states nothing."""
    from bigdl_tpu.observability import trace
    seen = []

    def tap(ev):
        if ev["name"] == "flash_schedule":
            seen.append(ev)

    rng = np.random.default_rng(11)
    q, k, v = _qkv(rng, 1, 512, 1, 64)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 interpret=INTERP))
    tracer = trace.get_tracer()
    tracer.add_tap(tap)
    try:
        fn(q, k, v)
        assert len(seen) == 1
        fn(q, k, v)
        assert len(seen) == 1
    finally:
        tracer.remove_tap(tap)
    ev = seen[0]
    assert ev["cat"] == "kernels" and ev["ph"] == "i"
    a = ev["args"]
    assert (a["sq"], a["skv"], a["d"], a["causal"]) == (512, 512, 64, True)
    from bigdl_tpu.ops.pallas.flash_attention import _schedule
    sched = _schedule(True, 512, 512, 64, 4)
    assert {f: a[f] for f in sched._fields} == sched._asdict()
    assert a["kv_resident"] and a["one_pass_backward"]
    n = 512 // a["block"]
    assert a["tiles_computed"] == n * (n + 1) // 2
    assert a["tiles_computed"] / a["tiles_visible"] == pytest.approx(
        a["tiles_computed"] * a["block"] ** 2 / (512 * 513 / 2))


def test_cross_attention_shapes():
    """S_q != S_kv (cross attention) with uneven block pick (384 = 3*128)."""
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, 1, 128, 2, 128, skv=384)
    o_fl = flash_attention(q, k, v, interpret=INTERP)
    o_nv = _naive(q, k, v, False)
    np.testing.assert_allclose(np.asarray(o_fl), np.asarray(o_nv),
                               rtol=2e-2, atol=2e-3)


def test_causal_first_row_attends_only_itself():
    """Row 0 under causal masking = v[0] exactly (softmax over one key)."""
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, 1, 128, 1, 128)
    o = flash_attention(q, k, v, causal=True, interpret=INTERP)
    np.testing.assert_allclose(np.asarray(o[0, 0, 0]),
                               np.asarray(v[0, 0, 0]), rtol=1e-5, atol=1e-5)


def test_bf16_io_f32_internals():
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, 1, 256, 2, 128)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    o = flash_attention(qb, kb, vb, causal=True, interpret=INTERP)
    assert o.dtype == jnp.bfloat16
    o_nv = _naive(qb, kb, vb, True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_nv, np.float32),
                               rtol=5e-2, atol=2e-2)


def test_auto_dispatch_falls_back_off_tpu_or_bad_shapes():
    """dot_product_attention(flash="auto") must not require the kernel:
    odd shapes (here head_dim 32) always take the XLA path."""
    rng = np.random.default_rng(5)
    q, k, v = _qkv(rng, 2, 96, 2, 32)
    o = dot_product_attention(q, k, v, causal=True)  # flash="auto"
    o_ref = _naive(q, k, v, True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref))


def test_flash_inside_multihead_attention_module():
    """MultiHeadAttention's local core goes through dot_product_attention
    — auto dispatch must keep module semantics identical."""
    from bigdl_tpu import nn
    m = nn.MultiHeadAttention(256, 2, causal=True)
    m.materialize(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, 128, 256)).astype(np.float32))
    y, _ = m.apply(m.params, {}, x)
    assert y.shape == (2, 128, 256)
    assert np.isfinite(np.asarray(y, np.float32)).all()


@pytest.mark.parametrize("causal,s,d", [(False, 256, 128), (True, 512, 64),
                                        (True, 256, 128)])
def test_with_lse_cotangent_math(causal, s, d):
    """(o, lse) are both differentiable: the gradients of sum(lse) must
    match the XLA logsumexp path (the lse cotangent folds into delta' =
    delta - g_lse in the backward kernels, one pass or two)."""
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention_with_lse
    rng = np.random.default_rng(7)
    q, k, v = _qkv(rng, 1, s, 2, d)

    def lse_flash(q, k, v):
        _, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                          interpret=INTERP)
        return jnp.sum(lse)

    def lse_xla(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
        if causal:
            sc = jnp.where(jnp.triu(jnp.ones((s, s), bool), 1), -1e9, sc)
        return jnp.sum(jax.nn.logsumexp(sc, axis=-1))

    np.testing.assert_allclose(float(lse_flash(q, k, v)),
                               float(lse_xla(q, k, v)), rtol=1e-4)
    _assert_grads_match(lse_flash, lse_xla, (q, k, v), argnums=(0, 1))


# interpret-mode flash over a 512-token ring costs ~70s total on the
# single-core tier-1 box; the flash kernel itself and the plain ring
# core stay pinned in tier-1 by the tests above
@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_body_matches_full(causal):
    """Ring attention with the per-step flash kernel (interpret mode on a
    4-way seq mesh) == unsharded full attention, values and grads."""
    import jax as _jax
    from bigdl_tpu.parallel.engine import Engine
    from bigdl_tpu.parallel.sequence import ring_attention
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, 2, 512, 2, 128)
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    Engine.reset()
    mesh = Engine.init(axes={"seq": 4}, devices=_jax.devices()[:4])
    try:
        with mesh:
            o = ring_attention(q, k, v, causal=causal, flash=True,
                               interpret=True)
            o_ref = _naive(q, k, v, causal)
            np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                       rtol=2e-3, atol=2e-4)
            g = _jax.grad(lambda q, k, v: jnp.vdot(
                ring_attention(q, k, v, causal=causal, flash=True,
                               interpret=True), ct),
                argnums=(0, 1, 2))(q, k, v)
            g_ref = _jax.grad(lambda q, k, v: jnp.vdot(
                _naive(q, k, v, causal), ct), argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(g, g_ref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-3, atol=2e-4)
    finally:
        Engine.reset()


@pytest.mark.slow  # ~15s mesh compile; sequence_parallel ring tests pin tier-1
def test_ring_flash_guards():
    """Review r2: causal cross-length and undersized K/V shards must not
    take the flash ring body; flash=True raises, auto falls back."""
    import jax as _jax
    from bigdl_tpu.parallel.engine import Engine
    from bigdl_tpu.parallel.sequence import ring_attention
    rng = np.random.default_rng(9)
    q, _, _ = _qkv(rng, 1, 1024, 2, 128)
    _, k, v = _qkv(rng, 1, 512, 2, 128)
    ct_q = q
    Engine.reset()
    mesh = Engine.init(axes={"seq": 4}, devices=_jax.devices()[:4])
    try:
        with mesh:
            # causal cross-length: forced flash raises...
            with pytest.raises(ValueError, match="equal q/kv"):
                ring_attention(q, k, v, causal=True, flash=True,
                               interpret=True)
            # ...auto falls back to the XLA body and matches the oracle
            o = ring_attention(q, k, v, causal=True)   # flash="auto"
            o_ref = _naive(q, k, v, True)
            np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                       rtol=2e-3, atol=2e-4)
            # kv shard 64 (< min tile): forced flash raises instead of
            # crashing inside _pick_blocks
            _, k2, v2 = _qkv(rng, 1, 256, 2, 128)
            with pytest.raises(ValueError, match="kv=64"):
                ring_attention(q[:, :512], k2, v2, causal=False,
                               flash=True, interpret=True)
            # non-causal cross-length IS flash-eligible and correct
            o2 = ring_attention(q, k, v, causal=False, flash=True,
                                interpret=True)
            o2_ref = _naive(q, k, v, False)
            np.testing.assert_allclose(np.asarray(o2), np.asarray(o2_ref),
                                       rtol=2e-3, atol=2e-4)
    finally:
        Engine.reset()

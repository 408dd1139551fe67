"""The Mamba-2 mixer's pointwise passes (``ops/pallas/ssm_pointwise.py``)
interpreted on the CPU against their ``jax.numpy`` forms in
``nn.state_space``: the causal conv + silu and the gated group norm,
forward values and every input's and weight's cotangent; token counts
off the tile, several sequences, widths at the cell's and small; the
shapes the TPU kernels refuse; the whole mixer on the fused path against
the unfused one.

Both sides float32 under ``highest`` matmul precision except where a
test says bf16: what is left is summation order (1e-6 relative)."""
import functools

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.nn import state_space
from bigdl_tpu.ops.pallas import ssd as kernels
from bigdl_tpu.ops.pallas import ssm_pointwise as pointwise
from bigdl_tpu.tensor import DTypePolicy, policy_scope

TOL = 1e-6


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _xla_conv(xbc, w, b, widths):
    """``Mamba2Mixer``'s conv, silu and split on the CPU's path."""
    h, p, g, n = widths
    a = jax.nn.silu(state_space.causal_conv1d(xbc, w, b)).astype(xbc.dtype)
    lead = xbc.shape[:2]
    return (a[..., :h * p].reshape(*lead, h, p),
            a[..., h * p:h * p + g * n].reshape(*lead, g, n),
            a[..., h * p + g * n:].reshape(*lead, g, n))


def _conv_args(batch, seq, widths, seed=0, dtype=jnp.float32):
    h, p, g, n = widths
    channels = h * p + 2 * g * n
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (batch, seq, channels), dtype),
            0.5 * jax.random.normal(k[1], (channels, 4)),
            0.5 * jax.random.normal(k[2], (channels,)),
            (jax.random.normal(k[3], (batch, seq, h, p), dtype),
             jax.random.normal(k[4], (batch, seq, g, n), dtype),
             jax.random.normal(k[5], (batch, seq, g, n), dtype)))


def _fused_conv(widths):
    h, p, g, n = widths
    return functools.partial(pointwise.conv_silu_split, heads=h, head_dim=p,
                             groups=g, state=n, interpret=True)


@pytest.fixture()
def tiles(monkeypatch):
    """Tiles of ``block`` tokens where a test asks for them: several
    tiles, and a last one cut short, at small sizes."""
    def use(block):
        if block:
            monkeypatch.setattr(pointwise, "BLOCK_TOKENS", block)
            monkeypatch.setattr(pointwise, "BLOCK_ROWS", block)
    return use


# (batch, tokens, (heads, head dim, groups, state), tokens a block): off
# the tile and on it, one tile and many; the cell's widths (6144 conv
# channels), 4096 channels, and small ones
CONV_CASES = [(2, 40, (4, 8, 2, 8), 16), (3, 64, (8, 16, 2, 32), 32),
              (1, 7, (2, 32, 1, 32), None), (2, 56, (64, 64, 8, 128), 16),
              (2, 48, (32, 64, 8, 128), 32)]


@pytest.mark.parametrize("batch,seq,widths,block", CONV_CASES)
def test_the_conv_pass_matches_causal_conv1d_silu_and_the_split(
        batch, seq, widths, block, tiles):
    tiles(block)
    x, w, b, cts = _conv_args(batch, seq, widths)
    out, pull = jax.vjp(_fused_conv(widths), x, w, b)
    want, want_pull = jax.vjp(lambda *a: _xla_conv(*a, widths), x, w, b)
    for name, got, ref in zip(("x", "B", "C"), out, want):
        assert got.shape == ref.shape, name
        assert _rel(got, ref) < TOL, name
    for name, got, ref in zip(("xBC", "w", "b"), pull(cts), want_pull(cts)):
        assert got.shape == ref.shape and got.dtype == ref.dtype, name
        assert _rel(got, ref) < TOL, name


@pytest.mark.parametrize("block", [16, 32])
def test_the_conv_window_never_crosses_a_sequence(block, tiles):
    """Row 1 is zero: whatever its outputs and its input's cotangent
    hold past silu(b) came from row 0 across the batch boundary."""
    tiles(block)
    widths = (4, 32, 1, 64)
    x, w, b, cts = _conv_args(2, 48, widths, seed=3)
    x = x.at[1].set(0.0)
    out, pull = jax.vjp(_fused_conv(widths), x, w, b)
    flat = jnp.concatenate([o[1].reshape(48, -1) for o in out], axis=1)
    assert float(jnp.max(jnp.abs(flat - jax.nn.silu(b)))) < 1e-6
    dx = pull(tuple(ct.at[0].set(0.0) for ct in cts))[0]
    assert float(jnp.max(jnp.abs(dx[0]))) == 0.0
    # and a tile boundary inside a row does carry the window
    for got, ref in zip(out, _xla_conv(x, w, b, widths)):
        assert _rel(got[0], ref[0]) < TOL


def test_the_conv_pass_keeps_bf16_at_its_boundaries(tiles):
    """bf16 in and out, float32 taps and bias gradients: the dtypes
    ``Mamba2Mixer`` stored before; the values within a bf16 rounding of
    the float32 form's."""
    tiles(16)
    widths = (2, 64, 1, 64)
    x, w, b, cts = _conv_args(2, 40, widths, seed=5, dtype=jnp.bfloat16)
    out, pull = jax.vjp(_fused_conv(widths), x, w, b)
    want, want_pull = jax.vjp(lambda *a: _xla_conv(*a, widths), x, w, b)
    for got, ref in zip(out, want):
        assert got.dtype == jnp.bfloat16 and _rel(got, ref) < 1e-2
    dx, dw, db = pull(cts)
    assert (dx.dtype, dw.dtype, db.dtype) == (jnp.bfloat16, jnp.float32,
                                              jnp.float32)
    for got, ref in zip((dx, dw, db), want_pull(cts)):
        assert _rel(got, ref) < 1e-2


def _norm_args(lead, heads, head_dim, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    width = heads * head_dim
    return (jax.random.normal(k[0], (*lead, heads, head_dim), dtype),
            jax.random.normal(k[1], (*lead, width), dtype),
            1.0 + 0.3 * jax.random.normal(k[2], (width,)),
            jax.random.normal(k[3], (*lead, width), dtype))


def _xla_norm(y, z, w, groups):
    return state_space.gated_rms_norm(y.reshape(z.shape), z, w, groups,
                                      1e-5).astype(y.dtype)


# ((batch, tokens), heads, head dim, groups, tokens a block): the cell's
# 64 heads of 64 in eight groups of 512 and small ones, token counts off
# the tile
NORM_CASES = [((2, 20), 6, 8, 3, 16), ((2, 32), 8, 32, 2, 32),
              ((1, 7), 2, 64, 1, None), ((2, 28), 64, 64, 8, 16)]


@pytest.mark.parametrize("lead,heads,head_dim,groups,block", NORM_CASES)
def test_the_norm_pass_matches_gated_rms_norm(lead, heads, head_dim, groups,
                                              block, tiles):
    tiles(block)
    y, z, w, dout = _norm_args(lead, heads, head_dim)
    fused = functools.partial(pointwise.gated_norm, groups=groups, eps=1e-5,
                              interpret=True)
    out, pull = jax.vjp(fused, y, z, w)
    want, want_pull = jax.vjp(functools.partial(_xla_norm, groups=groups),
                              y, z, w)
    assert _rel(out, want) < TOL
    for name, got, ref in zip(("y", "z", "w"), pull(dout), want_pull(dout)):
        assert got.shape == ref.shape and got.dtype == ref.dtype, name
        assert _rel(got, ref) < TOL, name


def test_the_norm_pass_keeps_bf16_at_its_boundaries(tiles):
    tiles(16)
    y, z, w, dout = _norm_args((2, 24), 4, 64, seed=2, dtype=jnp.bfloat16)
    fused = functools.partial(pointwise.gated_norm, groups=2, eps=1e-5,
                              interpret=True)
    out, pull = jax.vjp(fused, y, z, w)
    want, want_pull = jax.vjp(functools.partial(_xla_norm, groups=2), y, z,
                              w)
    assert out.dtype == jnp.bfloat16 and _rel(out, want) < 1e-2
    dy, dz, dw = pull(dout)
    assert (dy.dtype, dz.dtype, dw.dtype) == (jnp.bfloat16, jnp.bfloat16,
                                              jnp.float32)
    for got, ref in zip((dy, dz, dw), want_pull(dout)):
        assert _rel(got, ref) < 1e-2


W = pointwise.Widths


@pytest.mark.parametrize("call,match", [
    (lambda: pointwise.conv_schedule(8192, W(64, 64, 8, 120), 4),
     "whole pieces of 120; got 960"),
    (lambda: pointwise.conv_schedule(8192, W(64, 64, 8, 128), 18),
     "18 taps"),
    (lambda: pointwise.norm_schedule(8192, W(64, 64, 64, 1)),
     "multiple of 128 columns; got 64"),
    (lambda: pointwise.norm_schedule(8192, W(64, 64, 3, 1)),
     "64 heads do not split into 3 groups"),
    (lambda: pointwise.conv_silu_split(*_conv_args(1, 32, (2, 4, 2, 8))[:3],
                                       heads=2, head_dim=4, groups=2,
                                       state=8), "got 8"),
    (lambda: pointwise.conv_silu_split(*_conv_args(1, 32, (2, 4, 2, 8))[:3],
                                       heads=2, head_dim=4, groups=1,
                                       state=8), "channels are not"),
])
def test_shapes_the_tpu_passes_do_not_take_are_refused_by_name(call, match):
    with pytest.raises(ValueError, match=match):
        call()


def test_the_schedules_at_the_cells_shape():
    assert pointwise.conv_schedule(8192, W(64, 64, 8, 128), 4) == \
        pointwise.ConvSchedule(block_tokens=pointwise.BLOCK_TOKENS,
                               token_blocks=8192 // pointwise.BLOCK_TOKENS,
                               chunk_x=512, chunk_g=512)
    assert pointwise.norm_schedule(8192, W(64, 64, 8, 1)).width == 512
    # the bytes at the bound, a layer-step: ~2.89 GB, 3.52 ms at 819 GB/s
    assert pointwise.pointwise_bytes(16384, 4096, 6144, 2) == 2_885_681_152


# -- the whole mixer on the fused path ---------------------------------------

@pytest.fixture()
def fused_path(monkeypatch):
    """``Mamba2Mixer`` as it runs on the TPU, its three kernels
    interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mod, name in ((kernels, "ssd"), (pointwise, "conv_silu_split"),
                      (pointwise, "gated_norm")):
        monkeypatch.setattr(mod, name, functools.partial(
            getattr(mod, name), interpret=True))


def _mixer_run(x, params, mixer):
    def loss(p, v):
        return jnp.sum(jnp.sin(mixer.apply(p, {}, v)[0].astype(jnp.float32)))
    return jax.value_and_grad(loss, argnums=(0, 1))(params, x)


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_the_mixer_on_the_fused_path_matches_the_unfused(compute, request):
    """Values and every gradient leaf, the input's too: float32 to
    summation order, bf16 compute within its rounding."""
    f32, cd = jnp.dtype("float32"), jnp.dtype(compute)
    tol = (1e-5, 1e-4) if compute == "float32" else (2e-2, 3e-2)
    with policy_scope(DTypePolicy(param_dtype=f32, compute_dtype=cd,
                                  activation_dtype=f32)):
        mixer = state_space.Mamba2Mixer(32, 4, 8, 2, 16, chunk=16)
        params = mixer.init(jax.random.PRNGKey(0))
        params = jax.tree.map(lambda p: p + 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), p.shape), params)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 32))
        want, (want_p, want_x) = _mixer_run(x, params, mixer)
        request.getfixturevalue("fused_path")
        got, (got_p, got_x) = _mixer_run(x, params, mixer)
    assert abs(float(got - want)) / abs(float(want)) < tol[0]
    assert _rel(got_x, want_x) < tol[1]
    for name in params:
        assert _rel(got_p[name], want_p[name]) < tol[1], name


def test_the_fused_path_is_stated_where_the_mixer_is_traced(fused_path):
    from bigdl_tpu.observability import trace
    mixer = state_space.Mamba2Mixer(32, 4, 8, 2, 16, chunk=16)
    params = mixer.init(jax.random.PRNGKey(0))
    trace.clear()
    trace.enable()
    try:
        jax.eval_shape(lambda p: mixer.apply(p, {}, jnp.zeros((2, 32, 32)))
                       [0], params)
        events = trace.to_dict()["traceEvents"]
    finally:
        trace.disable()
    (stated,) = [e["args"] for e in events if e["name"] == "mamba_mixer"]
    itemsize = jnp.dtype(state_space.compute_dtype()).itemsize
    assert stated["scan"] == "ssd_kernel"
    assert stated["pointwise"] == "fused_kernel"
    assert stated["pointwise_bytes"] == pointwise.pointwise_bytes(
        64, 32, 32 + 2 * 32, itemsize)

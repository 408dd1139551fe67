"""Cross-lowering of every live Pallas kernel to the TPU from the CPU.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas TPU lowering without a chip, so a block shape the lowering refuses
(the last two block dims must be divisible by (8, 128) or span the array)
fails here in seconds. This does NOT replace the Mosaic compile on the
chip: Mosaic itself — VMEM limits, unsupported reshapes, unaligned slices —
only runs when ``chip_smoke.py`` compiles the same kernels on a TPU. The
geometries are exactly the smoke's.
"""
import itertools
import math
import re
import types

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops.pallas import lrn as plrn
from bigdl_tpu.ops.pallas.flash_attention import (flash_attention,
                                                  flash_supported)
from bigdl_tpu.ops.pallas.fused_ce import _linear_ce, linear_ce_supported
from bigdl_tpu.ops.pallas.paged_attention import (dense_cache_attention,
                                                  dense_cache_supported,
                                                  paged_attention,
                                                  paged_supported)

BF16 = jnp.bfloat16


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lowers(fn, *args) -> str:
    """StableHLO text of ``fn`` lowered for the TPU (raises where the
    Pallas TPU lowering refuses)."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _scalar_grads(fn, n_args):
    """fwd + bwd in one program: sum(fn) and its grads w.r.t. every
    array (the value keeps a forward kernel alive whose backward
    recomputes instead of reading it)."""
    return jax.value_and_grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                              argnums=tuple(range(n_args)))


# linear_cross_entropy gates the compiled kernel on the backend; lower
# the custom-vjp kernel entry itself (interpret=False), fwd + bwd
_CE_GRADS = jax.value_and_grad(
    lambda h, w, b, t: _linear_ce(h, w, b, t, False).sum(),
    argnums=(0, 1, 2))


@pytest.fixture
def on_tpu(monkeypatch):
    """The ``*_supported`` predicates as they answer on a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _paged_args(b, t, h, kv, d, s, pages_per_seq, dtype=BF16):
    n_pool = b * pages_per_seq + 1
    return (_sds((b, t, h, d), dtype), _sds((n_pool, s, kv, d), dtype),
            _sds((n_pool, s, kv, d), dtype),
            _sds((b, pages_per_seq), jnp.int32), _sds((b,), jnp.int32))


class TestSmokeGeometriesLower:
    @pytest.mark.parametrize("shape", [(2, 2048, 4, 128),
                                       (2, 320, 4, 128)])
    def test_flash_fwd_bwd(self, shape):
        fn = _scalar_grads(
            lambda q, k, v: flash_attention(q, k, v, causal=True), 3)
        text = _lowers(fn, *[_sds(shape)] * 3)
        # causal self-attention that fits VMEM: forward and ONE backward
        assert text.count("tpu_custom_call") == 2

    def test_flash_streamed_fwd_bwd(self):
        # no diagonal: forward, dq and fused dk/dv over the grid
        fn = _scalar_grads(lambda q, k, v: flash_attention(q, k, v), 3)
        text = _lowers(fn, _sds((2, 512, 4, 128)),
                       *[_sds((2, 2048, 4, 128))] * 2)
        assert text.count("tpu_custom_call") == 3

    def test_lrn_fwd_bwd(self):
        fn = _scalar_grads(lambda x: plrn.lrn(x, 5, 1e-4, 0.75, 1.0), 1)
        assert _lowers(fn, _sds((256, 64, 56, 56))).count(
            "tpu_custom_call") == 2

    def test_fused_ce_fwd_bwd(self):
        n, d, v = 8192, 1024, 32768
        text = _lowers(_CE_GRADS, _sds((n, d)), _sds((v, d)), _sds((v,)),
                       _sds((n,), jnp.int32))
        # forward, dh and dw/db
        assert text.count("tpu_custom_call") == 3

    @pytest.mark.parametrize("kv,t,s", list(itertools.product(
        (1, 8), (1, 64), (16, 128))))
    def test_paged_attention(self, kv, t, s):
        args = _paged_args(4, t, 8, kv, 128, s, 16)
        assert _lowers(paged_attention, *args).count(
            "tpu_custom_call") == 1


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: compiling for it runs XLA:TPU
    and Mosaic — VMEM limits, unaligned slices, unsupported loops — with
    no chip. Described inside the fixture, never at import: only the
    worker that runs this file may load the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


class TestFlashCompilesForTheV5e:
    """Mosaic's own verdict on the flash kernels, forward and backward in
    one program, at the shapes that decide the schedule."""

    # (B, S, H, D) -> (K/V resident, one backward pass, Mosaic calls)
    @pytest.mark.parametrize("shape,resident,one_pass,calls", [
        ((4, 2048, 32, 64), True, True, 2),     # the benchmark cells', a chip
        ((2, 2048, 32, 128), True, True, 2),    # opt-6.7b's head width
        ((1, 8192, 4, 128), True, True, 2),     # the most a head holds
        ((1, 16384, 2, 128), True, False, 3),   # dq accumulator too large
        ((1, 32768, 1, 128), False, False, 3),  # a long ring shard: streamed
    ], ids=["cell-s2048-d64", "s2048-d128", "s8192-d128", "s16384-d128",
            "s32768-d128"])
    def test_causal_fwd_bwd(self, one_chip, shape, resident, one_pass,
                            calls):
        from bigdl_tpu.ops.pallas.flash_attention import _schedule
        sched = _schedule(True, shape[1], shape[1], shape[3], 2)
        assert (sched.kv_resident, sched.one_pass_backward) == (
            resident, one_pass)
        fn = _scalar_grads(
            lambda q, k, v: flash_attention(q, k, v, causal=True), 3)
        x = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
        text = jax.jit(fn).lower(x, x, x).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == calls

    def test_streamed_cross_attention(self, one_chip):
        fn = _scalar_grads(lambda q, k, v: flash_attention(q, k, v), 3)
        q = jax.ShapeDtypeStruct((4, 512, 32, 64), BF16, sharding=one_chip)
        kv = jax.ShapeDtypeStruct((4, 2048, 32, 64), BF16,
                                  sharding=one_chip)
        jax.jit(fn).lower(q, kv, kv).compile()


class TestEvaCompilesForTheV5e:
    """Mosaic's verdict on the EVA kernels at the benchmark cell's shape
    (evabyte-6.5b.train.long: 32 heads of 16384 x 128, window 2048, chunk
    16), forward and backward in one program: VMEM for a window's K/V and
    dk/dv, the head's summaries and their gradients, the static slices of
    the summaries."""

    @pytest.mark.parametrize("seq", [16384, 8192], ids=["s16384", "s8192"])
    def test_window_and_summaries_fwd_bwd(self, one_chip, seq):
        from bigdl_tpu.ops.pallas.eva_attention import eva_attention
        fn = _scalar_grads(
            lambda q, k, v, ks, vs: eva_attention(q, k, v, ks, vs,
                                                  window=2048, chunk=16), 5)
        x = jax.ShapeDtypeStruct((1, seq, 32, 128), BF16, sharding=one_chip)
        s = jax.ShapeDtypeStruct((1, seq // 16, 32, 128), BF16,
                                 sharding=one_chip)
        text = jax.jit(fn).lower(x, x, x, s, s).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert "eva_attention_fwd" in text
        assert "eva_attention_dqdkdv" in text


class TestGatedFFNBackwardCompilesForTheV5e:
    """XLA:TPU's verdict on ``nn.GatedFFN``'s own backward at the
    benchmark cell's widths (evabyte-6.5b.train.long: 4096 / 11008 over
    16384 tokens, bf16 compute, the block recomputed): no matmul of the
    backward pass reads SiLU's exponential through a producer fused into
    its operand, which it would evaluate again on every pass over it
    (PERF.md section 6, PR 28; autodiff's backward has five a layer)."""

    def test_no_backward_matmul_recomputes_the_activation(self, one_chip,
                                                          on_tpu):
        from bigdl_tpu import nn
        from bigdl_tpu.models.transformer.model import PreNormBlock
        from bigdl_tpu.observability.tracing import matmuls_fed_by
        from bigdl_tpu.tensor import DTypePolicy, policy_scope
        d, seq = 4096, 16384
        block = PreNormBlock(
            lambda: nn.RMSNorm(d, unit_offset=True),
            nn.EvaAttention(d, 32, 2048, 16, 1e5), nn.GatedFFN(d, 11008),
            residual_dtype=jnp.float32).set_name("block_0")
        model = nn.Sequential().add(block).set_remat("per_block")

        def loss(p, x):
            with jax.named_scope("model"):
                y, _ = model.apply(p, model.init_state(), x, training=True)
            return jnp.sum(y)

        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        x = jax.ShapeDtypeStruct((1, seq, d), jnp.float32, sharding=one_chip)
        with policy_scope(DTypePolicy(param_dtype=jnp.float32,
                                      compute_dtype=BF16,
                                      activation_dtype=BF16)):
            text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                params, x).compile().as_text()
        # the custom backward keeps the block's scope, which is what
        # step.backward_ms and step.update_fused_ms read it by
        assert ("transpose(jvp(model))/block_0/jvp(model)/block_0/"
                "checkpoint/1__Residual/dot_general") in text
        fed = matmuls_fed_by(text, "exponential")
        assert not [name for name, scope in fed.items()
                    if "transpose(" in scope], fed


class TestHeadAndLossCompileForTheV5e:
    """XLA:TPU's verdict on the head and the loss at the benchmark
    cells' widths (opt-1.3b: 4 x 2048 tokens, 2048 wide, 50272 classes,
    bf16 logits): ``final_norm`` + ``lm_head`` +
    ``CrossEntropyCriterion`` + AdamW as ``make_train_step`` assembles
    them. The criterion reads the logits where the matmul left them: no
    float32 array of their size is written, nothing copies them and
    nothing gathers from them (PERF.md section 6, PR 30; the parent's
    cast-then-gather formula cost a 1.65 GB float32 copy, 3.7 ms a
    step on the chip)."""

    TOKENS, D, V = (4, 2048), 2048, 50272

    @staticmethod
    def _parent_formula(x, target):
        t = target.astype(jnp.int32).reshape(-1) - 1
        logits = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=1)[:, 0]
        return jnp.mean(lse - picked)

    def _step(self, one_chip, criterion):
        """The compiled step. The hidden state arrives as a parameter,
        so that the head's input-gradient matmul is in the program."""
        from bigdl_tpu import nn, optim
        from bigdl_tpu.nn import init as init_mod
        from bigdl_tpu.optim.accumulation import make_train_step
        from bigdl_tpu.tensor import DTypePolicy, policy_scope
        model = nn.Sequential()
        model.add(nn.LayerNorm(self.D).set_name("final_norm"))
        model.add(nn.Linear(self.D, self.V, init_method=init_mod.Xavier)
                  .set_name("lm_head"))
        method = optim.AdamW(learning_rate=1e-4, beta1=0.9, beta2=0.95,
                             weight_decay=0.1)

        def fwd(p, mstate, data, training, rng):
            return model.apply(p["model"], mstate, p["hidden"].astype(BF16),
                               training=training, rng=rng)

        def on_chip(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)

        params = on_chip({
            "model": jax.eval_shape(model.init, jax.random.PRNGKey(0)),
            "hidden": jax.ShapeDtypeStruct((*self.TOKENS, self.D),
                                           jnp.float32)})
        opt_state = on_chip(jax.eval_shape(method.init_state, params))
        key, epoch, ids = on_chip((
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct(self.TOKENS, jnp.int32)))
        step = make_train_step(fwd=fwd, criterion=criterion,
                               update_fn=method.update)
        with policy_scope(DTypePolicy(param_dtype=jnp.float32,
                                      compute_dtype=BF16,
                                      activation_dtype=BF16)):
            return jax.jit(step, donate_argnums=(0, 1, 2)).lower(
                params, model.init_state(), opt_state, key, ids, ids,
                epoch).compile()

    def _written(self, text):
        """``[(opcode, dtype)]`` of the logits-sized arrays that the
        entry computation's instructions write."""
        from bigdl_tpu.observability.tracing import _HLO_NO_OP
        size = math.prod(self.TOKENS) * self.V
        out = []
        for line in text[text.index("\nENTRY "):].splitlines():
            m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(",
                         line)
            if not m or m.group(2) in _HLO_NO_OP:
                continue
            out += [(m.group(2), dtype)
                    for dtype, dims in re.findall(r"([a-z]\w*)\[([\d,]+)\]",
                                                  m.group(1))
                    if math.prod(map(int, dims.split(","))) == size]
        return out

    def test_nothing_widens_copies_or_gathers_the_logits(self, one_chip):
        from bigdl_tpu import nn
        step = self._step(one_chip, nn.CrossEntropyCriterion())
        text = step.as_text()
        # the logits themselves, out of the head's matmul, and no other
        # array of their size in any dtype
        assert self._written(text) == [("fusion", "bf16")]
        assert not [line for line in text.splitlines()
                    if " gather(" in line and f",{self.V}]" in line]
        assert "/lm_head/dot_general" in text and "jvp(criterion)/" in text
        parent = self._step(one_chip, types.SimpleNamespace(
            apply=self._parent_formula, size_average=True))
        # the comparison can fail: the parent's formula
        written = self._written(parent.as_text())
        assert ("copy", "f32") in written and ("fusion", "bf16") in written
        freed = (parent.memory_analysis().temp_size_in_bytes
                 - step.memory_analysis().temp_size_in_bytes)
        assert freed >= 1.3e9, freed


class TestPredicatesMatchTheLowering:
    """Whatever a ``*_supported`` predicate accepts on a TPU lowers; what
    the lowering refuses reads as unsupported."""

    def test_paged_accepted_geometries_lower(self, on_tpu):
        seen = 0
        for dtype, kv, d, s in itertools.product(
                (BF16, jnp.float32), (1, 8), (64, 128, 256), (2, 12, 128)):
            if not paged_supported(d, s, kv, dtype):
                continue
            seen += 1
            _lowers(paged_attention,
                    *_paged_args(2, 8, 8, kv, d, s, 4, dtype))
        assert seen == 30      # all but KV=8 at D=64

    def test_paged_refused_geometries_read_unsupported(self, on_tpu):
        # more than one kv head needs a 128-multiple head dim: the kv
        # head is a lane block of the (num_pages, S, KV*D) pool view
        assert not paged_supported(64, 16, 4, BF16)
        assert not paged_supported(64, 16, 8, jnp.float32)
        with pytest.raises(ValueError, match="last two dimensions"):
            _lowers(paged_attention, *_paged_args(2, 1, 8, 4, 64, 16, 4))
        # one kv head spans the lane dim at any width
        assert paged_supported(64, 16, 1, BF16)
        # a page fills at least one 32-bit sublane row; it need not be a
        # whole tile (bf16 pages of 8 rows run on the chip)
        assert paged_supported(128, 8, 8, BF16)
        assert paged_supported(128, 1, 8, jnp.float32)
        assert not paged_supported(128, 1, 8, BF16)
        # Mosaic on the v5e compiles no float16
        assert not paged_supported(128, 16, 8, jnp.float16)

    def test_dense_cache_view_follows_paged(self, on_tpu):
        for m, kv, d in ((2048, 8, 128), (640, 1, 64), (96, 2, 128)):
            ok = dense_cache_supported(d, m, kv, BF16)
            args = (_sds((2, 1, 8, d)), _sds((2, m, kv, d)),
                    _sds((2, m, kv, d)), _sds((2,), jnp.int32))
            if ok:
                _lowers(dense_cache_attention, *args)
        assert dense_cache_supported(128, 2048, 8, BF16)
        assert not dense_cache_supported(64, 2048, 4, BF16)

    def test_flash_accepted_geometries_lower(self, on_tpu):
        fn = _scalar_grads(
            lambda q, k, v: flash_attention(q, k, v, causal=True), 3)
        for s, d in itertools.product((128, 320, 640, 2048), (64, 128)):
            q = _sds((1, s, 2, d))
            assert flash_supported(q, q)
            _lowers(fn, q, q, q)
        assert not flash_supported(_sds((1, 100, 2, 64)),
                                   _sds((1, 100, 2, 64)))
        assert not flash_supported(_sds((1, 128, 2, 32)),
                                   _sds((1, 128, 2, 32)))

    def test_lrn_accepted_geometries_lower(self, on_tpu):
        fn = _scalar_grads(lambda x: plrn.lrn(x, 5, 1e-4, 0.75, 1.0), 1)
        for shape, dtype in (((256, 192, 56, 56), BF16),
                             ((64, 64, 28, 28), BF16),
                             ((128, 8, 14, 14), jnp.float32)):
            x = _sds(shape, dtype)
            assert plrn.lrn_supported(x)
            _lowers(fn, x)
        # bf16 packs 16 rows a sublane tile; a small batch leaves the
        # lane axis mostly empty
        assert not plrn.lrn_supported(_sds((256, 8, 14, 14), BF16))
        assert not plrn.lrn_supported(_sds((32, 64, 28, 28), BF16))

    def test_fused_ce_accepted_geometries_lower(self, on_tpu):
        for n, d, v in ((256, 128, 512), (1024, 256, 4096)):
            h, w = _sds((n, d)), _sds((v, d))
            assert linear_ce_supported(h, w)
            _lowers(_CE_GRADS, h, w, _sds((v,)), _sds((n,), jnp.int32))
        assert not linear_ce_supported(_sds((100, 128)), _sds((512, 128)))
        assert not linear_ce_supported(_sds((256, 96)), _sds((512, 96)))


class TestSparseAttentionCompilesForTheV5e:
    """Mosaic's verdict on the learned-sparse-attention kernels at the
    benchmark cell's shape (keye-vl-2.0-30b-a3b.train.seq16384: 32 query
    and 4 key/value heads of 16384 x 128, 16 indexer heads of 64,
    top-2048), forward and backward in one program: VMEM for 128 whole
    rows of index scores and their integer keys, for a key/value head's
    dk/dv, the bisection's loops, the float32 index matmuls."""

    S, TOPK = 16384, 2048

    @pytest.fixture(scope="class")
    def compiled(self, one_chip):
        from bigdl_tpu.ops.pallas.sparse_attention import (
            sparse_select_attention)

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        s = self.S
        fn = _scalar_grads(lambda *a: sparse_select_attention(
            *a, topk=self.TOPK), 6)
        return jax.jit(fn).lower(
            sds((1, s, 32, 128), BF16), sds((1, s, 4, 128), BF16),
            sds((1, s, 4, 128), BF16), sds((1, s, 16, 64), jnp.float32),
            sds((1, s, 64), jnp.float32),
            sds((1, s, 16), jnp.float32)).compile()

    def test_six_kernels_and_their_names(self, compiled):
        """Seven calls of six kernels: the scores kernel once more in
        the backward pass, where it writes the masked scores again."""
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 7
        assert len(re.findall(r"%sparse_index_scores[.\d]* = ", text)) == 2
        for name in ("sparse_index_scores", "sparse_select_rows",
                     "sparse_attention_fwd", "sparse_attention_dqdkdv",
                     "sparse_kept_probs", "sparse_index_backward"):
            assert name in text, name

    def test_no_key_or_value_row_is_gathered_per_query(self, compiled):
        """K and V reach the kernels whole and unrepeated: no gather, and
        no array of (heads, S, top-k) elements or more."""
        text = compiled.as_text()
        assert not re.search(r"= \S+ gather\(", text)
        assert f"[32,{self.S},{self.TOPK}" not in text

    def test_one_sequence_squared_array_at_a_time(self, compiled):
        """Scores, masked scores and d L_I / d I are ONE float32 (S, S)
        buffer written in place: the program's temporaries hold it once
        (1.07 GB), not three times."""
        square = self.S * self.S * 4
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert square <= temp < 2 * square, temp / 1e9


class TestGroupedMatmulCompilesForTheV5e:
    """Mosaic's verdict on megablox's grouped matrix products as
    ``parallel.expert.grouped_matmul`` tiles them, at one chunk of the
    Keye cell's sorted rows: 32768 rows of 2048 against 16 experts
    of 768, both products' shapes, forward and backward."""

    @pytest.mark.parametrize("n,k", [(768, 2048), (2048, 768)],
                             ids=["gate-up", "down"])
    def test_fwd_bwd(self, one_chip, monkeypatch, n, k):
        from bigdl_tpu.parallel import expert
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fn = jax.value_and_grad(
            lambda x, w, sizes: expert.grouped_matmul(x, w, sizes)
            .astype(jnp.float32).sum(), argnums=(0, 1))
        text = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((32768, k), BF16, sharding=one_chip),
            jax.ShapeDtypeStruct((16, n, k), BF16, sharding=one_chip),
            jax.ShapeDtypeStruct((17,), jnp.int32, sharding=one_chip)
        ).compile().as_text()
        # gmm forward, gmm for d rows, tgmm for d weights
        assert text.count('custom_call_target="tpu_custom_call"') == 3


class TestLatentAttentionCompilesForTheV5e:
    """Mosaic's verdict on the latent-attention kernels at the benchmark
    cell's shape (kimi-vl-a3b-instruct.train.seq8192: 2 x 8192 tokens, 16
    heads, 128 | 64 | 128 wide), forward and backward in one program:
    36 MiB resident in the one-pass backward (over flash's budget: the
    kernels ask for 100 MiB of VMEM), 64-lane blocks, the shared key's
    accumulator over a batch row's heads."""

    B, S, H = 2, 8192, 16

    @pytest.fixture(scope="class")
    def compiled(self, one_chip):
        from bigdl_tpu.ops.pallas.latent_attention import latent_attention

        def sds(*shape):
            return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

        b, s, h = self.B, self.S, self.H
        return jax.jit(_scalar_grads(latent_attention, 5)).lower(
            sds(b, s, h, 128), sds(b, s, h, 64), sds(b, s, h, 128),
            sds(b, s, 64), sds(b, s, h, 128)).compile()

    def test_two_kernels_and_their_names(self, compiled):
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        for name in ("latent_attention_fwd", "latent_attention_dqdkdv"):
            assert name in text, name

    def test_the_shared_key_is_never_copied_over_the_heads(self, compiled):
        """No array of (B, S, 16, 192) or (B x 16, S, 192) elements — a
        key with the rotary part beside the content part — and none
        with two axes of the sequence's length; the shared key's
        gradient leaves the kernel as (B, S, 64)."""
        text = compiled.as_text()
        b, s, h = self.B, self.S, self.H
        assert not re.search(r"[\[,]192[,\]]", text)
        assert not re.search(rf"\[(\d+,)*{s},{s}[,\]]", text)
        assert re.search(rf"bf16\[{b},{s},64\]", text)

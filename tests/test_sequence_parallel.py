"""Sequence/context parallelism tests on the 8-virtual-device CPU mesh.

Pins the first-class long-context capability (ring + Ulysses attention,
parallel/sequence.py): sequence-sharded attention must match full local
attention in both values and gradients, causal and not.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu import nn
from bigdl_tpu.parallel import (Engine, dot_product_attention,
                                ring_attention, ulysses_attention)


def _qkv(b=2, s=32, h=8, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d))
                             .astype(np.float32))
    return mk(), mk(), mk()


def _seq_mesh():
    return Engine.init(axes={"seq": 8})


class TestLocalAttention:
    def test_matches_torch_sdpa(self):
        import torch
        q, k, v = _qkv()
        out = dot_product_attention(q, k, v)
        tq, tk, tv = (torch.tensor(np.asarray(t)).permute(0, 2, 1, 3)
                      for t in (q, k, v))
        ref = torch.nn.functional.scaled_dot_product_attention(tq, tk, tv)
        np.testing.assert_allclose(np.asarray(out),
                                   ref.permute(0, 2, 1, 3).numpy(),
                                   rtol=2e-5, atol=2e-5)

    def test_causal_matches_torch(self):
        import torch
        q, k, v = _qkv(seed=1)
        out = dot_product_attention(q, k, v, causal=True)
        tq, tk, tv = (torch.tensor(np.asarray(t)).permute(0, 2, 1, 3)
                      for t in (q, k, v))
        ref = torch.nn.functional.scaled_dot_product_attention(
            tq, tk, tv, is_causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   ref.permute(0, 2, 1, 3).numpy(),
                                   rtol=2e-5, atol=2e-5)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_local(self, causal):
        mesh = _seq_mesh()
        q, k, v = _qkv(seed=2)
        out = ring_attention(q, k, v, causal=causal, mesh=mesh)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    # grad-of-ring compiles ~70s total on the single-core tier-1 box;
    # forward parity above keeps the ring core pinned in tier-1
    @pytest.mark.slow
    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_local(self, causal):
        mesh = _seq_mesh()
        q, k, v = _qkv(b=1, s=16, h=2, d=8, seed=3)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, causal=causal,
                                          mesh=mesh) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v,
                                                 causal=causal) ** 2)

        gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_jit_compatible(self):
        mesh = _seq_mesh()
        q, k, v = _qkv(seed=4)
        f = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=True,
                                                   mesh=mesh))
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)),
            np.asarray(dot_product_attention(q, k, v, causal=True)),
            rtol=2e-5, atol=2e-5)

    def test_rejects_indivisible_sequence(self):
        mesh = _seq_mesh()
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, 30, 2, 8), np.float32))
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention(q, q, q, mesh=mesh)

    def test_a_recomputed_block_keeps_nothing_of_the_flash_hops(self):
        """Ring attention's per-hop ``flash_attention_with_lse`` names
        nothing: under ``per_block``'s policy a recomputed block saves
        what a checkpoint that keeps nothing saves, not each hop's
        output. The whole attention on the kernel keeps its output and
        row statistics. Abstract: nothing runs."""
        from bigdl_tpu.ops.pallas.flash_attention import flash_attention
        from bigdl_tpu.optim import remat
        mesh = _seq_mesh()
        q, k, v = _qkv(b=1, s=1024, h=2, d=64, seed=6)
        kept = jax.checkpoint_policies.save_only_these_names(
            *remat.KEPT_NAMES)
        nothing = jax.checkpoint_policies.nothing_saveable

        def held(core, policy):
            fn = jax.checkpoint(lambda q, k, v: jnp.sum(core(q, k, v)),
                                policy=policy)
            return remat.saved_residual_bytes(fn, q, k, v)

        def ring(q, k, v):
            return ring_attention(q, k, v, causal=True, mesh=mesh,
                                  flash=True, interpret=True)

        def whole(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=True)

        assert held(ring, kept) == held(ring, nothing)
        # o (2 heads, 1024, 64) and lse (2 heads, 1024), float32
        assert held(whole, kept) - held(whole, nothing) == \
            2 * 1024 * 64 * 4 + 2 * 1024 * 4


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_local(self, causal):
        mesh = _seq_mesh()
        q, k, v = _qkv(seed=5)
        out = ulysses_attention(q, k, v, causal=causal, mesh=mesh)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_indivisible_heads(self):
        mesh = _seq_mesh()
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, 32, 6, 8), np.float32))
        with pytest.raises(ValueError, match="heads"):
            ulysses_attention(q, q, q, mesh=mesh)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gqa_narrow_kv_rides_the_wire(self, causal):
        """kv heads divisible by the axis: k/v cross the all_to_all at
        kv width and widen locally — values must equal the pre-widened
        reference (chunk-local head t -> kv head t // groups alignment).
        """
        Engine.reset()
        mesh = Engine.init(axes={"seq": 4},
                           devices=jax.devices()[:4])
        q, _, _ = _qkv(s=32, h=8, seed=8)
        _, k, v = _qkv(s=32, h=4, seed=9)       # narrow: 4 kv heads
        out = ulysses_attention(q, k, v, causal=causal, mesh=mesh,
                                kv_groups=2)
        wide_k = jnp.repeat(k, 2, axis=2)
        wide_v = jnp.repeat(v, 2, axis=2)
        ref = dot_product_attention(q, wide_k, wide_v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        Engine.reset()

    def test_gqa_fallback_when_kv_heads_underdivide(self):
        """MQA (1 kv head) on a 4-way axis can't head-split narrow k/v:
        the pre-widen fallback must still be exact."""
        Engine.reset()
        mesh = Engine.init(axes={"seq": 4},
                           devices=jax.devices()[:4])
        q, _, _ = _qkv(s=32, h=8, seed=10)
        _, k, v = _qkv(s=32, h=1, seed=11)      # multi-query
        out = ulysses_attention(q, k, v, causal=True, mesh=mesh,
                                kv_groups=8)
        ref = dot_product_attention(q, jnp.repeat(k, 8, axis=2),
                                    jnp.repeat(v, 8, axis=2), causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        Engine.reset()

    def test_gqa_narrow_gradients_match(self):
        Engine.reset()
        mesh = Engine.init(axes={"seq": 4},
                           devices=jax.devices()[:4])
        q, _, _ = _qkv(b=1, s=16, h=4, d=8, seed=12)
        _, k, v = _qkv(b=1, s=16, h=2, d=8, seed=13)

        def par_loss(q, k, v):
            return jnp.sum(ulysses_attention(q, k, v, causal=True,
                                             mesh=mesh, kv_groups=2) ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(dot_product_attention(
                q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
                causal=True) ** 2)

        gp = jax.grad(par_loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        Engine.reset()


class TestMultiHeadAttentionModule:
    def test_local_forward_and_train_step(self):
        m = nn.MultiHeadAttention(32, 4, causal=True)
        m.materialize(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(6).standard_normal(
            (2, 16, 32)).astype(np.float32))
        y, _ = m.apply(m.params, m.state, x)
        assert y.shape == (2, 16, 32)
        g = jax.grad(lambda p: jnp.sum(
            m.apply(p, m.state, x)[0] ** 2))(m.params)
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree.leaves(g))

    # the ring variant re-traces per hop (~14s); ulysses keeps the
    # module-level sequence-parallel seam in tier-1
    @pytest.mark.parametrize(
        "sp", [pytest.param("ring", marks=pytest.mark.slow), "ulysses"])
    def test_sequence_parallel_matches_local(self, sp):
        mesh = _seq_mesh()
        local = nn.MultiHeadAttention(32, 8, causal=True)
        local.materialize(jax.random.PRNGKey(1))
        par = nn.MultiHeadAttention(32, 8, causal=True,
                                    sequence_parallel=sp)
        x = jnp.asarray(np.random.default_rng(7).standard_normal(
            (2, 32, 32)).astype(np.float32))
        y_local, _ = local.apply(local.params, {}, x)
        y_par, _ = par.apply(local.params, {}, x)
        np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_local),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("sp", ["ring", "ulysses"])
    def test_gqa_sequence_parallel_matches_local(self, sp):
        """GQA composes with both sequence-parallel cores (Ulysses rides
        narrow kv heads over the wire when they divide the axis)."""
        Engine.reset()
        mesh = Engine.init(axes={"seq": 4},
                           devices=jax.devices()[:4])
        local = nn.MultiHeadAttention(32, 8, causal=True, num_kv_heads=4)
        local.materialize(jax.random.PRNGKey(2))
        par = nn.MultiHeadAttention(32, 8, causal=True, num_kv_heads=4,
                                    sequence_parallel=sp)
        x = jnp.asarray(np.random.default_rng(8).standard_normal(
            (2, 32, 32)).astype(np.float32))
        y_local, _ = local.apply(local.params, {}, x)
        y_par, _ = par.apply(local.params, {}, x)
        np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_local),
                                   rtol=2e-5, atol=2e-5)
        Engine.reset()

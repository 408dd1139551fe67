"""Distributed training tests on an 8-virtual-device CPU mesh.

Mirrors the reference's strategy (SURVEY §4.3): Spark local[1] with 4
logical partitions → here a real Mesh over 8 XLA CPU devices, exercising the
same pjit/collective code paths as a TPU slice.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import Sample, array, SampleToBatch
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel import Engine, get_mesh, data_sharding


@pytest.fixture(autouse=True)
def fresh_engine():
    Engine.reset()
    yield
    Engine.reset()


def make_dataset(n=512, seed=0, num_shards=None):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, 2).astype(np.float32)
    y = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(np.int64) + 1
    samples = [Sample(x[i], y[i]) for i in range(n)]
    return array(samples, num_shards=num_shards)


def make_mlp():
    return nn.Sequential(nn.Linear(2, 32), nn.Tanh(),
                         nn.Linear(32, 2), nn.LogSoftMax())


class TestEngine:
    def test_mesh_default_data_axis(self):
        mesh = Engine.init()
        assert mesh.shape["data"] == 8
        assert Engine.node_number() == 8

    def test_multi_axis_mesh(self):
        mesh = Engine.init(axes={"data": 4, "model": 2})
        assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2

    def test_axes_must_cover_devices(self):
        with pytest.raises(AssertionError):
            Engine.init(axes={"data": 3})


class TestDistriOptimizer:
    def test_factory_dispatch_through_transform(self):
        ds = make_dataset(num_shards=1) >> SampleToBatch(64)
        o = optim.Optimizer(model=make_mlp(), dataset=ds,
                            criterion=nn.ClassNLLCriterion())
        from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
        assert isinstance(o, DistriOptimizer)

    def test_convergence_on_mesh(self):
        # the epoch shuffles draw from the process-wide host RNG stream:
        # seed it so the trajectory is the same standalone and mid-suite
        # (unseeded, the recipe landed at 0.88 in some orders — a hard
        # seed, not a distributed-math bug: the local loop scored the
        # same, and both clear 0.9 with the seeded 60-epoch recipe)
        from bigdl_tpu.utils.random import RandomGenerator
        RandomGenerator.set_seed(0)
        Engine.init()
        ds = make_dataset(num_shards=1) >> SampleToBatch(64)
        model = make_mlp()
        o = optim.Optimizer(model=model, dataset=ds,
                            criterion=nn.ClassNLLCriterion())
        o.set_optim_method(optim.SGD(learning_rate=0.5, momentum=0.9)) \
         .set_end_when(optim.max_epoch(60))
        trained = o.optimize()
        res = optim.LocalValidator(
            trained, make_dataset(seed=5) >> SampleToBatch(64)
        ).test([optim.Top1Accuracy()])
        acc = res[0][0].result()[0]
        assert acc > 0.9, f"accuracy {acc}"

    def test_batch_not_divisible_raises(self):
        Engine.init()
        ds = make_dataset(n=100, num_shards=1) >> SampleToBatch(
            20, drop_remainder=True)  # 20 % 8 != 0
        o = optim.Optimizer(model=make_mlp(), dataset=ds,
                            criterion=nn.ClassNLLCriterion())
        o.set_end_when(optim.max_iteration(2))
        with pytest.raises(ValueError, match="not divisible"):
            o.optimize()

    def test_matches_local_optimizer_losses(self):
        """SPMD data-parallel step must be numerically equivalent to the
        single-device step (the reference checks DistriOptimizer against
        RefLocalOptimizer the same way, SURVEY §4.4)."""
        samples_ds = make_dataset(n=256)
        batches = list((samples_ds >> SampleToBatch(64)).data(train=False))

        def run(dist: bool):
            model = make_mlp()
            model.materialize(jax.random.PRNGKey(7))
            crit = nn.ClassNLLCriterion()
            sgd = optim.SGD(learning_rate=0.1)
            params, mstate = model.params, model.state
            opt_state = sgd.init_state(params)
            losses = []
            if dist:
                Engine.init()
                from bigdl_tpu.parallel import replicated
                repl = replicated()
                shard = data_sharding()
                params = jax.device_put(params, repl)

            def step(params, opt_state, data, labels):
                def loss_fn(p):
                    y, _ = model.apply(p, mstate, data)
                    return crit.apply(y, labels)
                loss, g = jax.value_and_grad(loss_fn)(params)
                params, opt_state = sgd.update(g, params, opt_state)
                return params, opt_state, loss

            jstep = jax.jit(step)
            for b in batches:
                data, labels = jnp.asarray(b.data), jnp.asarray(b.labels)
                if dist:
                    data = jax.device_put(np.asarray(b.data), shard)
                    labels = jax.device_put(np.asarray(b.labels), shard)
                params, opt_state, loss = jstep(params, opt_state, data,
                                                labels)
                losses.append(float(loss))
            return losses

        local_losses = run(False)
        dist_losses = run(True)
        np.testing.assert_allclose(local_losses, dist_losses, rtol=1e-4)

    def test_collective_stacked_contract(self):
        """Eager collectives take stacked per-shard contributions so sums
        are honest (regression: replicated in_specs summed N identical
        copies, inflating values by mesh size)."""
        Engine.init()
        from bigdl_tpu.parallel import collective as C
        mesh = get_mesh()
        n = mesh.shape["data"]
        contrib = jnp.stack([jnp.full((4,), float(i)) for i in range(n)])
        out = C.all_reduce(contrib, "data", mesh)
        np.testing.assert_allclose(np.asarray(out),
                                   np.full(4, sum(range(n))))
        out_mean = C.all_reduce(contrib, "data", mesh, mean=True)
        np.testing.assert_allclose(np.asarray(out_mean),
                                   np.full(4, sum(range(n)) / n))
        wide = jnp.stack([jnp.full((2 * n,), float(i)) for i in range(n)])
        rs = C.reduce_scatter(wide, "data", mesh)
        np.testing.assert_allclose(np.asarray(rs),
                                   np.full(2 * n, sum(range(n))))
        with pytest.raises(ValueError, match="stacked per-shard"):
            C.all_reduce(jnp.ones(4), "data", mesh)

    def test_all_reduce_parameter_roundtrip(self):
        """put_gradients -> get_weights round trip pins exact values on the
        8-device mesh (each shard owns the SUM of its slice)."""
        Engine.init()
        from bigdl_tpu.parameters import AllReduceParameter
        mesh = get_mesh()
        n = mesh.shape["data"]
        p = AllReduceParameter(mesh=mesh)
        tree = {"w": jnp.zeros((3, 5)), "b": jnp.zeros(7)}
        p.init(tree)
        grads = [jax.tree.map(lambda v: jnp.full(v.shape, float(i + 1)),
                              tree) for i in range(n)]
        sharded = p.put_gradients(grads)
        full = p.get_weights(sharded)
        expect = sum(range(1, n + 1))
        np.testing.assert_allclose(np.asarray(full["w"]),
                                   np.full((3, 5), expect))
        np.testing.assert_allclose(np.asarray(full["b"]),
                                   np.full(7, expect))
        with pytest.raises(ValueError, match="per-shard"):
            p.put_gradients(jnp.ones(22))

    def test_gradient_allreduce_semantics(self):
        """Sharded-batch gradient == full-batch gradient (the property the
        reference's AllReduceParameter provides)."""
        Engine.init()
        model = make_mlp()
        model.materialize(jax.random.PRNGKey(0))
        crit = nn.ClassNLLCriterion()
        rs = np.random.RandomState(3)
        x = rs.rand(64, 2).astype(np.float32)
        t = rs.randint(1, 3, (64,))

        def loss_fn(p, data, labels):
            y, _ = model.apply(p, model.state, data)
            return crit.apply(y, labels)

        g_local = jax.grad(loss_fn)(model.params, jnp.asarray(x),
                                    jnp.asarray(t))
        shard = data_sharding()
        xd = jax.device_put(x, shard)
        td = jax.device_put(t, shard)
        g_dist = jax.jit(jax.grad(loss_fn))(model.params, xd, td)
        for a, b in zip(jax.tree.leaves(g_local), jax.tree.leaves(g_dist)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestCollectiveAccounting:
    """The second BASELINE metric: allreduce bytes/GB-s instrumentation
    (VERDICT r2 missing #1; reference AllReduceParameter.scala:134-228)."""

    def test_distri_metrics_report_collective_bytes(self):
        mesh = Engine.init(axes={"data": 8})
        model = make_mlp()
        ds = make_dataset() >> SampleToBatch(64, drop_remainder=True)
        o = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                                  mesh=mesh)
        o.set_end_when(optim.max_iteration(3))
        o.optimize()
        logical = o.metrics.get("collective logical bytes per step")
        wire = o.metrics.get("collective wire bytes per chip per step")
        # the gradient allreduce moves at least the full f32 param tree
        n_params = sum(np.prod(p.shape) for p in
                       jax.tree.leaves(model.params))
        assert logical >= 4 * n_params, (logical, n_params)
        # ring wire estimate: 2*(N-1)/N per all-reduced byte
        assert wire == pytest.approx(logical * 2 * 7 / 8, rel=0.5)
        summary = o.metrics.summary()
        assert "collective wire bytes per chip per step" in summary
        assert "allreduce GB/s" in summary

    def test_single_device_reports_zero(self):
        mesh = Engine.init(axes={"data": 1}, devices=jax.devices()[:1])
        model = make_mlp()
        ds = make_dataset() >> SampleToBatch(64, drop_remainder=True)
        o = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                                  mesh=mesh)
        o.set_end_when(optim.max_iteration(2))
        o.optimize()
        assert o.metrics.get("collective logical bytes per step") == 0
        assert "allreduce GB/s" not in o.metrics.summary()

    def test_allreduce_bench_runs_and_accounts(self):
        from bigdl_tpu.parallel.collective_bench import allreduce_bench
        mesh = Engine.init(axes={"data": 8})
        out = allreduce_bench(size_mb=0.5, iters=3, warmup=1, mesh=mesh)
        assert out["devices"] == 8
        assert out["payload_mb"] >= 0.5
        assert out["bus_gbps"] > 0 and out["alg_gbps"] > 0
        # bus = alg * 2*(N-1)/N for a ring allreduce
        assert out["bus_gbps"] == pytest.approx(
            out["alg_gbps"] * 2 * 7 / 8, rel=0.01)

    def test_collective_bytes_parser(self):
        from bigdl_tpu.parallel.collective_bench import collective_bytes
        # realistic single-line HLO instruction forms (XLA prints one
        # instruction per line); shapes kept small to stay readable
        hlo = "\n".join([
            "ENTRY %main {",
            "  %p0 = f32[1024,8]{1,0} parameter(0)",
            "  %ar = f32[1024,8]{1,0} all-reduce(%p0),"
            " replica_groups={{0,1,2,3}}, to_apply=%add",
            "  %g = (f32[8]{0}, f32[32]{0}) all-gather-start(%x),"
            " replica_groups=[1,4]<=[4], dimensions={0}",
            "  %gd = f32[32]{0} all-gather-done(%g)",
            "}",
        ])
        acct = collective_bytes(hlo, 4)
        assert acct["ops"] == 2
        ar_bytes = 1024 * 8 * 4
        assert acct["by_kind"]["all-reduce"] == [1, ar_bytes]
        # the async all-gather-start tuple holds (operand, result); only
        # the gathered result (the largest element) is payload
        assert acct["by_kind"]["all-gather"] == [1, 32 * 4]
        assert acct["wire_bytes_per_chip"] == pytest.approx(
            ar_bytes * 2 * 3 / 4 + 32 * 4 * 3 / 4)

    def test_async_allreduce_start_not_double_counted(self):
        from bigdl_tpu.parallel.collective_bench import collective_bytes
        hlo = "\n".join([
            "ENTRY %main {",
            "  %s = (f32[1000]{0}, f32[1000]{0}) all-reduce-start(%p),"
            " replica_groups={{0,1}}, to_apply=%add",
            "  %d = f32[1000]{0} all-reduce-done(%s)",
            "}",
        ])
        acct = collective_bytes(hlo, 99)   # default must NOT be used
        assert acct["ops"] == 1
        assert acct["logical_bytes"] == 4000       # not 8000
        assert acct["wire_bytes_per_chip"] == pytest.approx(4000.0)


def test_distri_partial_final_batch_recompiles():
    """Review r3: the AOT step executable must handle a final batch whose
    shape differs (SampleToBatch drop_remainder=False default)."""
    mesh = Engine.init(axes={"data": 8})
    model = make_mlp()
    # 96 samples, batch 64 -> batches of 64 and 32 (both divisible by 8)
    ds = make_dataset(n=96) >> SampleToBatch(64)
    o = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), mesh=mesh)
    o.set_end_when(optim.max_iteration(4))
    trained = o.optimize()
    assert trained is model
    assert np.isfinite(
        np.asarray(model.forward(np.zeros((4, 2), np.float32)))).all()


def test_spatial_bn_cross_device_unbiased_running_var():
    """Round-3: the fused-moment spatial BN computes the GLOBAL variance
    across the mesh, so Bessel must use the global sample count."""
    from jax.sharding import PartitionSpec as P
    mesh = Engine.init(axes={"data": 8})
    sbn = nn.SpatialBatchNormalization(3, axis_name="data")
    sbn.materialize(jax.random.PRNGKey(0))
    xg = np.random.default_rng(1).standard_normal(
        (16, 3, 4, 4)).astype(np.float32)

    def body(xs):
        _, st = sbn.apply(sbn.params, sbn.state, xs, training=True)
        return st["running_var"]

    from jax import shard_map
    with mesh:
        rv = shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P())(jnp.asarray(xg))
    want = 0.9 + 0.1 * np.var(xg, axis=(0, 2, 3), ddof=1)
    np.testing.assert_allclose(np.asarray(rv), want, rtol=1e-4)


class TestComposedMeshAxes:
    """dp x tp x seq in ONE jitted train step (VERDICT r3 #3): batch on
    'data', params on 'model' (GSPMD), sequence on 'seq' (ring
    attention) — trajectory parity with a plain single-device step."""

    def _losses_via_log(self, run):
        import logging
        losses = []

        class Grab(logging.Handler):
            def emit(self, rec):
                msg = rec.getMessage()
                if "loss is" in msg:
                    losses.append(float(
                        msg.split("loss is ")[1].split(",")[0]))
        lg = logging.getLogger("bigdl_tpu.optim")
        prev = lg.level
        lg.setLevel(logging.INFO)
        h = Grab()
        lg.addHandler(h)
        try:
            run()
        finally:
            lg.removeHandler(h)
            lg.setLevel(prev)
        return losses

    def test_dp_tp_seq_transformer_trajectory_parity(self):
        from bigdl_tpu.dataset import dataset as dsmod
        from bigdl_tpu.dataset.sample import MiniBatch
        from bigdl_tpu.models import TransformerLM

        V, S, B, iters = 32, 8, 4, 3
        rs = np.random.default_rng(0)
        data = rs.integers(1, V + 1, size=(B, S))
        labels = np.roll(data, -1, axis=1)
        batches = [MiniBatch(data, labels)] * iters
        crit = lambda: nn.TimeDistributedCriterion(  # noqa: E731
            nn.ClassNLLCriterion(), size_average=True)

        def build(sp):
            model = TransformerLM(V, d_model=32, num_heads=4,
                                  num_layers=2, max_len=S,
                                  sequence_parallel=sp)
            model.materialize(jax.random.PRNGKey(3))
            return model

        def run_mesh():
            mesh = Engine.init(axes={"data": 2, "model": 2, "seq": 2})
            ds = dsmod.iterator_source(lambda: iter(batches), size=B)
            o = DistriOptimizer(build("ring"), ds, crit(), mesh=mesh,
                                tensor_parallel=True,
                                sequence_parallel=True)
            o.set_optim_method(optim.SGD(learning_rate=0.1))
            o.set_end_when(optim.max_iteration(iters))
            o.optimize()

        def run_local():
            Engine.reset()
            ds = dsmod.iterator_source(lambda: iter(batches), size=B)
            from bigdl_tpu.optim.optimizer import LocalOptimizer
            o = LocalOptimizer(build(None), ds, crit())
            o.set_optim_method(optim.SGD(learning_rate=0.1))
            o.set_end_when(optim.max_iteration(iters))
            o.optimize()

        mesh_losses = self._losses_via_log(run_mesh)
        local_losses = self._losses_via_log(run_local)
        assert len(mesh_losses) == len(local_losses) == iters
        assert mesh_losses[-1] < mesh_losses[0]
        np.testing.assert_allclose(mesh_losses, local_losses, rtol=2e-4)

    def test_sequence_parallel_rank1_labels(self):
        """Sequence classification under dp x seq: data (B, S, D) shards
        P('data','seq'); rank-1 labels must shard over 'data' alone
        (review finding: the data spec crashed on rank-1 labels)."""
        from bigdl_tpu.dataset import dataset as dsmod
        from bigdl_tpu.dataset.sample import MiniBatch

        mesh = Engine.init(axes={"data": 2, "seq": 4})
        rs = np.random.default_rng(0)
        B, S, D = 4, 8, 32
        data = rs.standard_normal((B, S, D)).astype(np.float32)
        labels = rs.integers(1, 3, size=(B,))
        ds = dsmod.iterator_source(
            lambda: iter([MiniBatch(data, labels)] * 2), size=B)
        model = nn.Sequential(
            nn.MultiHeadAttention(D, 4, causal=True,
                                  sequence_parallel="ring"),
            nn.Mean(dimension=1),
            nn.Linear(D, 2), nn.LogSoftMax())
        model.materialize(jax.random.PRNGKey(0))
        o = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), mesh=mesh,
                            sequence_parallel=True)
        o.set_optim_method(optim.SGD(learning_rate=0.05))
        o.set_end_when(optim.max_iteration(2))
        o.optimize()   # must run, not crash on label placement

    def test_sequence_parallel_bad_seq_length_raises(self):
        from bigdl_tpu.dataset import dataset as dsmod
        from bigdl_tpu.dataset.sample import MiniBatch
        from bigdl_tpu.models import TransformerLM

        mesh = Engine.init(axes={"data": 4, "seq": 2})
        rs = np.random.default_rng(0)
        data = rs.integers(1, 17, size=(4, 7))     # 7 % 2 != 0
        ds = dsmod.iterator_source(
            lambda: iter([MiniBatch(data, np.roll(data, -1, 1))]), size=4)
        lm = TransformerLM(16, d_model=32, num_heads=4, num_layers=1,
                           max_len=7, sequence_parallel="ring")
        o = DistriOptimizer(
            lm, ds, nn.TimeDistributedCriterion(nn.ClassNLLCriterion()),
            mesh=mesh, sequence_parallel=True)
        o.set_end_when(optim.max_iteration(1))
        with pytest.raises(ValueError, match="sequence length"):
            o.optimize()

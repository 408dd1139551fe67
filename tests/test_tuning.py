"""Autotuner + persistent AOT executable cache (ISSUE 8).

Pins the tentpole contracts:

- tuning records: JSON round trip, device-kind keying, corrupt-file
  tolerance, canonical signatures.
- ``tune``: measured winner, VMEM pruning WITHOUT building, cost-model
  ordering cut keeps the baseline, failing candidates are skipped, the
  tie-with-static verdict is reported, winners persist.
- kernel pickers: records override the static menus (legal records
  only); the flash divisor fallback accepts sequences outside the menu
  and ``flash_supported`` agrees exactly with ``_pick_blocks``.
- AOT cache: key stable across processes for the same program+mesh;
  jaxlib version / device kind / donation mask / mesh shape changes
  each miss; store/load round trips bit-identically; a corrupt blob
  falls back to fresh compilation with a counted
  ``tuning_cache_miss``; a warm LocalOptimizer run replays the cold
  run's loss series bit-identically while loading (not compiling) its
  step.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.tuning import (AOTCache, StepCompiler, TuningRecords,
                              cache_key, tune)
from bigdl_tpu.tuning import records as records_mod
from bigdl_tpu.tuning.aot_cache import mesh_descriptor, stable_repr
from bigdl_tpu.tuning.autotuner import (bucket_mb_candidates,
                                        flash_candidates,
                                        flash_est_vmem, lrn_candidates,
                                        tile_divisors)


@pytest.fixture
def store(tmp_path):
    """An isolated default record store (kernel pickers consult it)."""
    r = TuningRecords(str(tmp_path / "tuning.json"))
    records_mod.set_default_records(r)
    yield r
    records_mod.set_default_records(None)


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

class TestRecords:
    def test_round_trip_and_persistence(self, tmp_path):
        path = str(tmp_path / "t.json")
        r = TuningRecords(path)
        assert r.lookup("k", {"a": 1}) is None
        r.record("k", {"a": 1}, {"bq": 256}, score=0.5)
        assert r.lookup("k", {"a": 1}) == {"bq": 256}
        # a fresh instance (another process) reads the same winner
        assert TuningRecords(path).lookup("k", {"a": 1}) == {"bq": 256}

    def test_device_kind_keying(self, tmp_path):
        r = TuningRecords(str(tmp_path / "t.json"))
        r.record("k", {"a": 1}, {"bq": 256}, device="TPU v5e")
        assert r.lookup("k", {"a": 1}, device="TPU v5e") == {"bq": 256}
        # a different chip generation must not import these tiles
        assert r.lookup("k", {"a": 1}, device="TPU v4") is None

    def test_corrupt_file_tolerated(self, tmp_path):
        path = str(tmp_path / "t.json")
        with open(path, "w") as f:
            f.write("{not json")
        r = TuningRecords(path)
        assert r.lookup("k", {"a": 1}) is None     # no raise
        r.record("k", {"a": 1}, {"x": 2})
        assert TuningRecords(path).lookup("k", {"a": 1}) == {"x": 2}

    def test_signature_canonical(self):
        from bigdl_tpu.tuning import signature_str
        assert signature_str({"b": 2, "a": 1}) == "a=1,b=2"
        assert signature_str((("b", 2), ("a", 1))) == "a=1,b=2"
        assert signature_str({"a": 1, "b": 2}) == \
            signature_str((("a", 1), ("b", 2)))


# ---------------------------------------------------------------------------
# tune()
# ---------------------------------------------------------------------------

class TestTune:
    def _build(self, built):
        def build(cfg):
            built.append(dict(cfg))

            def fn():
                time.sleep(cfg["s"])
                return cfg["s"]
            return fn
        return build

    def test_measured_winner_persists(self, store):
        built = []
        res = tune(self._build(built),
                   [{"s": 0.03}, {"s": 0.001}, {"s": 0.02}],
                   key=("k", {"g": 1}), records=store, iters=1)
        assert res.config == {"s": 0.001}
        assert store.lookup("k", {"g": 1}) == {"s": 0.001}
        assert len(built) == 3

    def test_vmem_prune_skips_without_building(self, store):
        built = []
        res = tune(self._build(built),
                   [{"s": 0.001, "vm": 1}, {"s": 0.0005, "vm": 10 ** 9}],
                   key=("k", {"g": 2}), records=store, iters=1,
                   est_vmem=lambda c: c["vm"])
        # the faster candidate was never built: pruned by the model
        assert built == [{"s": 0.001, "vm": 1}]
        assert res.config == {"s": 0.001, "vm": 1}
        skipped = [m for m in res.measurements if m.skipped]
        assert len(skipped) == 1 and "VMEM" in skipped[0].skipped

    def test_tie_with_static_reported(self, store, caplog):
        import logging
        with caplog.at_level(logging.INFO, "bigdl_tpu.tuning"):
            res = tune(self._build([]), [{"s": 0.02}, {"s": 0.001}],
                       key=("k", {"g": 3}), records=store, iters=1,
                       baseline={"s": 0.001})
        assert res.tie is True
        assert any("TIE" in r.message for r in caplog.records)

    def test_failing_candidate_skipped(self, store):
        def build(cfg):
            if cfg.get("boom"):
                raise RuntimeError("mosaic says no")
            return lambda: None
        res = tune(build, [{"boom": True}, {"boom": False}],
                   key=("k", {"g": 4}), records=store, iters=1)
        assert res.config == {"boom": False}
        assert any(m.skipped and "mosaic" in m.skipped
                   for m in res.measurements)

    def test_cost_cut_keeps_baseline(self, store):
        built = []
        res = tune(self._build(built),
                   [{"s": 0.001}, {"s": 0.002}, {"s": 0.003}],
                   key=("k", {"g": 5}), records=store, iters=1,
                   est_cost=lambda c, stats: c["s"], max_candidates=1,
                   baseline={"s": 0.003})
        # cut to 1 + the baseline; the dropped middle is logged/recorded
        assert {tuple(b.items()) for b in built} == \
            {(("s", 0.001),), (("s", 0.003),)}
        assert res.baseline_time_s is not None
        assert res.config == {"s": 0.001}

    def test_candidate_generators(self):
        assert tile_divisors(512, 512) == [512, 256, 128]
        assert tile_divisors(320, 512) == [320, 160]
        assert tile_divisors(127, 512) == []
        cands = flash_candidates(320, 512)
        assert {"bq": 320, "bk": 512} in cands
        assert {"bq": 160, "bk": 128} in cands
        est = flash_est_vmem(d=64)
        assert est({"bq": 512, "bk": 1024}) > est({"bq": 128, "bk": 128})
        # causal self-attention of a length: what the looped kernels keep
        # of a head (K, V, the dk/dv outputs double-buffered, the f32
        # dk/dv scratch) rides on every candidate — until it exceeds the
        # kernels' own budget and they stream
        held = flash_est_vmem(d=64, sq=2048)
        assert held({"bq": 512, "bk": 1024}) - est({"bq": 512, "bk": 1024}) \
            == 4 * 2 * 2048 * 128 * 2 + 2 * 2048 * 128 * 4
        streamed = flash_est_vmem(d=128, sq=32768)
        assert streamed({"bq": 512, "bk": 1024}) \
            == flash_est_vmem(d=128)({"bq": 512, "bk": 1024})
        assert {"bucket_mb": 4.0} in bucket_mb_candidates()

    def test_step_memory_candidates_and_est(self):
        """ISSUE 10: the (remat_policy, num_microbatches) search axes —
        every known policy crossed with batch-dividing power-of-two k,
        and the static HBM estimator scaling the residual term 1/k."""
        from bigdl_tpu.tuning.autotuner import (step_memory_candidates,
                                                step_memory_est_hbm)
        cands = step_memory_candidates(32)
        assert {"remat_policy": "none", "num_microbatches": 1} in cands
        assert {"remat_policy": "nothing_saveable",
                "num_microbatches": 8} in cands
        ks = {c["num_microbatches"] for c in cands}
        assert ks == {1, 2, 4, 8}             # powers of two dividing 32
        pols = {c["remat_policy"] for c in cands}
        assert pols == {"none", "dots_saveable", "per_block",
                        "nothing_saveable"}
        # k legality follows the batch: 24 admits 1/2/4/8, 6 only 1/2
        assert {c["num_microbatches"]
                for c in step_memory_candidates(6)} == {1, 2}
        est = step_memory_est_hbm({"none": 1000, "nothing_saveable": 100},
                                  persistent_bytes=50)
        assert est({"remat_policy": "none", "num_microbatches": 1}) == 1050
        assert est({"remat_policy": "none", "num_microbatches": 4}) == 300
        assert est({"remat_policy": "nothing_saveable",
                    "num_microbatches": 1}) == 150
        # ordering: heavier policy + more microbatches = smaller estimate
        assert est({"remat_policy": "nothing_saveable",
                    "num_microbatches": 4}) < \
            est({"remat_policy": "none", "num_microbatches": 4})


# ---------------------------------------------------------------------------
# kernel pickers consult records / flash divisor fallback
# ---------------------------------------------------------------------------

class TestKernelPickers:
    def test_flash_divisor_fallback(self, store):
        from bigdl_tpu.ops.pallas.flash_attention import (_blocks_or_none,
                                                          _pick_blocks)
        # outside the static menu: the largest multiple-of-16 divisor
        assert _pick_blocks(320, 320) == (320, 320)
        assert _pick_blocks(160, 192) == (160, 192)
        # menu shapes unchanged
        assert _pick_blocks(512, 2048) == (512, 1024)
        # nothing tiles a prime-ish length
        assert _blocks_or_none(127, 512) is None
        with pytest.raises(ValueError, match="tile divisor"):
            _pick_blocks(127, 512)

    def test_flash_supported_agrees_with_picker(self, store, monkeypatch):
        from bigdl_tpu.ops.pallas import flash_attention as fa
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for sq in (128, 160, 192, 320, 512, 520, 127, 2048):
            q = jnp.zeros((1, sq, 4, 64))
            supported = fa.flash_supported(q, q)
            picked = fa._blocks_or_none(sq, sq)
            assert supported == (picked is not None), sq
            if supported:
                fa._pick_blocks(sq, sq)     # must not raise

    def test_flash_record_overrides_menu(self, store):
        from bigdl_tpu.ops.pallas.flash_attention import _pick_blocks
        store.record("flash_attention", {"sq": 256, "skv": 256},
                     {"bq": 128, "bk": 128})
        assert _pick_blocks(256, 256) == (128, 128)
        # an illegal record (not dividing the sequence) is ignored
        store.record("flash_attention", {"sq": 512, "skv": 512},
                     {"bq": 100, "bk": 100})
        assert _pick_blocks(512, 512) == (512, 512)

    def test_fused_ce_record_overrides_menu(self, store):
        from bigdl_tpu.ops.pallas.fused_ce import _pick_tiles
        assert _pick_tiles(512, 1024) == (512, 1024)
        store.record("fused_ce", {"n": 512, "v": 1024},
                     {"bt": 128, "bv": 256})
        assert _pick_tiles(512, 1024) == (128, 256)
        store.record("fused_ce", {"n": 256, "v": 512},
                     {"bt": 100, "bv": 100})        # illegal -> menu
        assert _pick_tiles(256, 512) == (256, 512)

    def test_lrn_and_maxpool_records(self, store):
        from bigdl_tpu.ops.pallas.lrn import _pick_hw_tile
        from bigdl_tpu.ops.pallas.maxpool import _pick_tiles
        assert _pick_hw_tile(192, 256) == 8      # static sweep
        store.record("lrn", {"c": 192, "n": 256}, {"ht": 2})
        assert _pick_hw_tile(192, 256) == 2
        store.record("lrn", {"c": 64, "n": 64}, {"ht": 0})   # illegal
        assert _pick_hw_tile(64, 64) == 8
        assert _pick_tiles(28, 256) == (4, 256)  # static default
        store.record("maxpool3x3s1", {"h": 28, "n": 256},
                     {"h_t": 7, "n_t": 128})
        assert _pick_tiles(28, 256) == (7, 128)
        store.record("maxpool3x3s1", {"h": 14, "n": 128},
                     {"h_t": 3, "n_t": 128})     # 14 % 3 != 0 -> static
        assert _pick_tiles(14, 128) == (2, 128)

    def test_flash_nonmenu_shape_runs_and_matches_reference(self, store):
        """The divisor fallback is not just accepted — the kernel at a
        non-menu shape (S=320 -> 320-tile) produces reference attention
        output (interpret mode)."""
        from bigdl_tpu.ops.pallas.flash_attention import flash_attention
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 320, 2, 64).astype(np.float32))
        k = jnp.asarray(rs.randn(1, 320, 2, 64).astype(np.float32))
        v = jnp.asarray(rs.randn(1, 320, 2, 64).astype(np.float32))
        out = flash_attention(q, k, v, interpret=True)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (64 ** -0.5)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                         v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_tuned_bucket_mb(self, store):
        from bigdl_tpu.optim.sharded_update import (DEFAULT_BUCKET_MB,
                                                    tuned_bucket_mb)
        assert tuned_bucket_mb(10 ** 6, 8) == DEFAULT_BUCKET_MB
        store.record("sharded_update", {"params": 10 ** 6, "shards": 8},
                     {"bucket_mb": 2.0})
        assert tuned_bucket_mb(10 ** 6, 8) == 2.0
        store.record("sharded_update", {"params": 5, "shards": 2},
                     {"bucket_mb": -1})           # illegal -> default
        assert tuned_bucket_mb(5, 2) == DEFAULT_BUCKET_MB


# ---------------------------------------------------------------------------
# the measured microbench: tune a real Pallas kernel on CPU (interpret)
# ---------------------------------------------------------------------------

class TestKernelMicrobench:
    def test_tune_lrn_tile_and_adopt(self, store):
        """End-to-end acceptance shape: a measured search over the LRN
        spatial tile in interpret mode, candidates flowing through the
        record store the kernel's own picker consults; the winner beats
        the static default or ties (the tie is reported), and the tuned
        kernel's output matches the static configuration's."""
        from bigdl_tpu.ops.pallas.lrn import _pick_hw_tile, lrn
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.rand(8, 16, 8, 8).astype(np.float32))
        c, n = 16, 8
        static = {"ht": _pick_hw_tile(c, n)}
        y_static = np.asarray(lrn(x, interpret=True))

        def build(cfg):
            # the kernel picks tiles through the default record store —
            # staging each candidate there exercises the real consult
            # path during measurement
            store.record("lrn", {"c": c, "n": n}, cfg)
            return lambda: lrn(x, interpret=True)

        res = tune(build, lrn_candidates(64), key=("lrn", {"c": c,
                                                           "n": n}),
                   records=store, iters=1, baseline=static)
        assert res.tie or res.time_s <= res.baseline_time_s
        # the winner is persisted and the picker adopts it
        assert store.lookup("lrn", {"c": c, "n": n}) == res.config
        assert _pick_hw_tile(c, n) == res.config["ht"]
        y_tuned = np.asarray(lrn(x, interpret=True))
        np.testing.assert_allclose(y_tuned, y_static, rtol=1e-6,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# AOT executable cache
# ---------------------------------------------------------------------------

_FP = {"jax": "0.4.37", "jaxlib": "0.4.36", "backend": "cpu",
       "device_kind": "cpu", "processes": 1}


class _FakeDev:
    def __init__(self, kind):
        self.device_kind = kind
        self.platform = "tpu"


class _FakeMesh:
    def __init__(self, axes, kinds=("TPU v5e",)):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)

        class _D:
            def __init__(self, devs):
                self.flat = devs
        self.devices = _D([_FakeDev(k) for k in kinds])


class TestCacheKey:
    def test_stable_across_processes(self):
        sig = (("arg0", "float32[8,8]"), ("arg1", "int32[8]"))
        here = cache_key("step", sig, donate_argnums=(0, 2), fp=_FP)
        code = (
            "from bigdl_tpu.tuning import cache_key;"
            f"print(cache_key('step', {sig!r}, donate_argnums=(0, 2), "
            f"fp={_FP!r}))")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == here

    def test_each_component_misses(self):
        sig = (("arg0", "float32[8,8]"),)
        base = cache_key("step", sig, donate_argnums=(0,), fp=_FP,
                         mesh=_FakeMesh({"data": 8}))
        # jaxlib upgrade
        assert cache_key("step", sig, donate_argnums=(0,),
                         fp=dict(_FP, jaxlib="9.9.9"),
                         mesh=_FakeMesh({"data": 8})) != base
        # different chip generation
        assert cache_key("step", sig, donate_argnums=(0,),
                         fp=dict(_FP, device_kind="TPU v4"),
                         mesh=_FakeMesh({"data": 8})) != base
        # donation mask
        assert cache_key("step", sig, donate_argnums=(), fp=_FP,
                         mesh=_FakeMesh({"data": 8})) != base
        # mesh shape
        assert cache_key("step", sig, donate_argnums=(0,), fp=_FP,
                         mesh=_FakeMesh({"data": 4})) != base
        # signature
        assert cache_key("step", (("arg0", "float32[16,8]"),),
                         donate_argnums=(0,), fp=_FP,
                         mesh=_FakeMesh({"data": 8})) != base
        # same everything == same key
        assert cache_key("step", sig, donate_argnums=(0,), fp=_FP,
                         mesh=_FakeMesh({"data": 8})) == base

    def test_mesh_descriptor_ignores_device_ids(self):
        a = mesh_descriptor(_FakeMesh({"data": 2}, ("TPU v5e",
                                                    "TPU v5e")))
        b = mesh_descriptor(_FakeMesh({"data": 2}, ("TPU v5e",)))
        assert a == b          # kinds set, not per-device identity

    def test_stable_repr_strips_addresses(self):
        class Thing:
            pass
        assert "0x" not in stable_repr(Thing())
        assert stable_repr(Thing()) == stable_repr(Thing())


class TestAOTCache:
    def _compiled(self, scale=3.0):
        def f(x, y):
            return (x * scale + y).sum()
        x = jnp.ones((64, 64))
        return jax.jit(f).lower(x, x).compile(), x

    def test_store_load_bit_identical(self, tmp_path):
        cache = AOTCache(str(tmp_path))
        comp, x = self._compiled()
        key = cache_key("t", "sig", fp=_FP)
        assert cache.store(key, comp)
        loaded = cache.load(key, name="t")
        assert loaded is not None
        assert float(loaded(x, x)) == float(comp(x, x))
        assert cache.hits == 1 and cache.misses == 0

    def test_loads_onto_the_executables_own_devices(self, tmp_path):
        """With eight devices visible, a one-device executable (on a
        device that is not the default one) and a four-device sharded
        one both load onto the devices they were compiled for and run.
        Loading onto every device of the backend — the library default
        — made the first call fail with "Expected args ... to have 8
        shards"."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        devs = jax.devices()
        assert len(devs) == 8
        cache = AOTCache(str(tmp_path))
        x1 = jax.device_put(jnp.arange(8.0), devs[3])
        one = jax.jit(lambda x: x * 2 + 1).lower(x1).compile()
        sh = NamedSharding(Mesh(np.array(devs[2:6]), ("data",)),
                           P("data"))
        x4 = jax.device_put(jnp.arange(8.0), sh)
        four = jax.jit(lambda x: x * 3, in_shardings=sh,
                       out_shardings=sh).lower(x4).compile()
        for name, comp, x in (("one", one, x1), ("four", four, x4)):
            key = cache_key(name, "sig", fp=_FP)
            assert cache.store(key, comp, name=name)
            loaded = cache.load(key, name=name)
            assert loaded is not None
            out = loaded(x)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(comp(x)))
            assert out.sharding.device_set == x.sharding.device_set
        assert cache.hits == 2 and cache.misses == 0

    def test_absent_and_corrupt_are_counted_misses(self, tmp_path):
        from bigdl_tpu.observability.compile_watch import CompileWatch
        from bigdl_tpu.observability.registry import MetricRegistry
        reg = MetricRegistry()
        watch = CompileWatch(registry=reg)
        cache = AOTCache(str(tmp_path), watch=watch)
        key = cache_key("t", "sig", fp=_FP)
        assert cache.load(key, name="t") is None          # absent
        with open(cache._file(key), "wb") as f:
            f.write(b"not a pickle")
        assert cache.load(key, name="t") is None          # corrupt
        assert cache.misses == 2 and cache.hits == 0
        t = watch.table()["t"]
        assert t["cache_misses"] == 2
        assert reg.get("tuning_cache_misses_total").value(name="t") == 2

    def test_step_compiler_backstop_recompiles(self, tmp_path):
        """A corrupt blob must not break step construction: the
        pipeline logs the miss, compiles fresh, and repairs the
        entry."""
        cache = AOTCache(str(tmp_path))

        def f(x):
            return x * 2

        x = jnp.arange(8.0)
        sc = StepCompiler(jax.jit(f), name="t", cache=cache, extra="v1")
        key = sc.key_for((x,))
        with open(cache._file(key), "wb") as g:
            g.write(b"garbage")
        compiled, was_compile = sc.get("k", (x,))
        assert was_compile is True
        np.testing.assert_array_equal(np.asarray(compiled(x)),
                                      np.asarray(x) * 2)
        # the entry was repaired: a fresh pipeline loads it
        sc2 = StepCompiler(jax.jit(f), name="t", cache=AOTCache(
            str(tmp_path)), extra="v1")
        _, was_compile2 = sc2.get("k", (x,))
        assert was_compile2 is False

    def test_extra_key_material_separates_programs(self, tmp_path):
        """Same shapes, different jit-constant (the learning-rate
        trap): the extra material must key them apart."""
        cache = AOTCache(str(tmp_path))
        x = jnp.arange(8.0)

        def mk(scale):
            return jax.jit(lambda v: v * scale)

        a, _ = StepCompiler(mk(2.0), name="t", cache=cache,
                            extra=("lr", 2.0)).get("k", (x,))
        b, _ = StepCompiler(mk(3.0), name="t", cache=cache,
                            extra=("lr", 3.0)).get("k", (x,))
        assert float(a(x)[1]) != float(b(x)[1])
        assert len(os.listdir(tmp_path)) == 2

    def test_env_cache(self, tmp_path, monkeypatch):
        from bigdl_tpu.tuning.aot_cache import env_cache
        monkeypatch.delenv("BIGDL_TPU_AOT_CACHE_DIR", raising=False)
        assert env_cache() is None
        monkeypatch.setenv("BIGDL_TPU_AOT_CACHE_DIR", str(tmp_path))
        c = env_cache()
        assert c is not None and c.path == str(tmp_path)


# ---------------------------------------------------------------------------
# the training-loop contract: warm restart == cold run, bitwise
# ---------------------------------------------------------------------------

class _LossCap:
    def __init__(self):
        self.losses = []

    def add_scalar(self, name, v, step):
        if name == "Loss":
            self.losses.append(v)

    def close(self):
        pass


def _train_local(cache, iters=4):
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import Sample, SampleToBatch, array
    from bigdl_tpu.utils.random import RandomGenerator
    RandomGenerator.set_seed(0)
    rs = np.random.RandomState(0)
    x = rs.rand(64, 32).astype(np.float32)
    y = rs.randint(1, 5, size=(64,)).astype(np.int64)
    ds = array([Sample(x[i], y[i]) for i in range(64)]) \
        >> SampleToBatch(32)
    model = nn.Sequential(nn.Linear(32, 64), nn.Tanh(),
                          nn.Linear(64, 4), nn.LogSoftMax())
    o = optim.Optimizer(model=model, dataset=ds,
                        criterion=nn.ClassNLLCriterion())
    o.set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
    o.set_aot_cache(cache)
    o.set_end_when(optim.max_iteration(iters))
    cap = _LossCap()
    o.set_train_summary(cap)
    trained = o.optimize()
    return cap.losses, jax.tree.map(np.asarray, trained.params)


class TestWarmRestartParity:
    def test_loss_series_bit_identical_and_loaded(self, tmp_path):
        cold_cache = AOTCache(str(tmp_path / "aot"))
        cold_losses, cold_params = _train_local(cold_cache)
        assert cold_cache.misses >= 1 and cold_cache.hits == 0
        warm_cache = AOTCache(str(tmp_path / "aot"))
        warm_losses, warm_params = _train_local(warm_cache)
        # the warm "restarted worker" LOADED its step...
        assert warm_cache.hits >= 1 and warm_cache.misses == 0
        # ...and replayed the cold run exactly, bit for bit
        assert warm_losses == cold_losses
        for a, b in zip(jax.tree.leaves(cold_params),
                        jax.tree.leaves(warm_params)):
            np.testing.assert_array_equal(a, b)

    def test_disabled_cache_writes_nothing(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_TPU_AOT_CACHE_DIR",
                           str(tmp_path / "env"))
        _train_local(None)      # set_aot_cache(None) beats the env var
        assert not os.path.exists(str(tmp_path / "env"))


# ---------------------------------------------------------------------------
# bench row wiring lives in test_bench_contract.py; the probe itself is
# exercised there on the fast geometry.
# ---------------------------------------------------------------------------


class TestPipelineScheduleCandidates:
    """ISSUE 11: the pipeline schedule search axis — candidates legal by
    construction, HBM estimator follows the schedule's exact stash."""

    def test_candidates_grid_and_legality(self):
        from bigdl_tpu.tuning.autotuner import \
            pipeline_schedule_candidates
        cands = pipeline_schedule_candidates(32, n_layers=8,
                                             stage_counts=(2, 4, 3))
        assert cands, "empty candidate grid"
        for c in cands:
            assert c["schedule"] in ("gpipe", "1f1b",
                                     "interleaved_1f1b")
            assert 32 % c["num_microbatches"] == 0
            assert 8 % (c["stages"] * c["virtual_stages"]) == 0
            if c["schedule"] == "interleaved_1f1b":
                assert c["virtual_stages"] > 1
                assert c["num_microbatches"] % c["stages"] == 0
            else:
                assert c["virtual_stages"] == 1
        # stage count 3 does not divide 8 layers -> never emitted
        assert all(c["stages"] != 3 for c in cands)
        # every schedule family present
        assert {c["schedule"] for c in cands} == {
            "gpipe", "1f1b", "interleaved_1f1b"}

    def test_est_hbm_tracks_schedule_stash(self):
        from bigdl_tpu.tuning.autotuner import pipeline_est_hbm
        est = pipeline_est_hbm(act_bytes_full_batch=8 << 20,
                               persistent_bytes=4 << 20)
        gp = est({"schedule": "gpipe", "num_microbatches": 8,
                  "stages": 4, "virtual_stages": 1})
        fb = est({"schedule": "1f1b", "num_microbatches": 8,
                  "stages": 4, "virtual_stages": 1})
        # gpipe stashes all M microbatches, 1f1b ~S: at M=8, S=4 the
        # activation term halves
        assert fb < gp
        act = (8 << 20) // 8
        assert gp == (4 << 20) // 4 + 8 * act
        assert fb == (4 << 20) // 4 + 4 * act
        # more microbatches shrink the per-microbatch term for 1f1b
        fb16 = est({"schedule": "1f1b", "num_microbatches": 16,
                    "stages": 4, "virtual_stages": 1})
        assert fb16 < fb

    def test_est_hbm_prunes_in_tune_without_building(self):
        from bigdl_tpu.tuning.autotuner import (pipeline_est_hbm,
                                                tune)
        from bigdl_tpu.tuning.records import TuningRecords

        built = []

        def build(c):
            built.append(c["schedule"])
            return lambda: 0.0

        # gpipe stashes 4 microbatches -> 1 GiB, over the 512 MiB
        # budget; 1f1b stashes 2 -> exactly at budget, survives
        est = pipeline_est_hbm(act_bytes_full_batch=1 << 30)
        res = tune(build,
                   [{"schedule": "gpipe", "num_microbatches": 4,
                     "stages": 2, "virtual_stages": 1},
                    {"schedule": "1f1b", "num_microbatches": 4,
                     "stages": 2, "virtual_stages": 1}],
                   key=("pipeline_schedule", "test"),
                   records=TuningRecords(), est_vmem=est,
                   vmem_budget=(1 << 29),
                   persist=False)
        assert res.config["schedule"] == "1f1b"
        assert built == ["1f1b"]        # gpipe never compiled
        skipped = [m for m in res.measurements if m.skipped]
        assert any("pruned" in m.skipped for m in skipped)

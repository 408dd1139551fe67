"""Async-dispatch training-loop contract (CPU-pinned, ISSUE 3).

The train loops keep ``loss`` on device and drain the in-flight window
with ONE packed ``jax.device_get``. These tests pin the contract the
way PR 1 pinned no-sync tracing:

- N steps under ``max_iteration`` with ``max_in_flight=2`` cost
  <= ceil(N/2)+2 host readbacks (vs. N before);
- a loss-reading trigger (``min_loss``) forces lockstep — a readback
  every step — and preserves exact stopping semantics;
- trajectories (per-step losses, final params, optimizer state) are
  bit-identical to the synchronous (``max_in_flight=1``) loop for both
  LocalOptimizer and DistriOptimizer;
- deferred drains stamp summaries/logs with the step's ORIGINAL
  ``neval``.

Readbacks are counted by wrapping ``jax.device_get`` — the loops'
only sanctioned readback path (the L-BFGS reads in optim_method.py are
not exercised here).
"""
import math
import threading

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import Sample, SampleToBatch, array
from bigdl_tpu.observability import SummaryReader, TrainSummary
from bigdl_tpu.utils import file as bfile
from bigdl_tpu.utils.random import RandomGenerator

BATCH = 32
N_SAMPLES = 128          # 4 batches per epoch


def _samples(n=N_SAMPLES, seed=3):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, 2).astype(np.float32)
    y = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(np.int64) + 1
    return [Sample(x[i], y[i]) for i in range(n)]


def _mlp():
    return nn.Sequential(nn.Linear(2, 16), nn.Tanh(),
                         nn.Linear(16, 2), nn.LogSoftMax())


@pytest.fixture
def count_device_get(monkeypatch):
    """Count host readbacks going through the sanctioned batched path."""
    calls = {"n": 0}
    orig = jax.device_get

    def wrapped(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", wrapped)
    return calls


def _run(end_when, *, max_in_flight=None, mesh=None, ckpt_dir=None,
         summary=None, configure=None):
    """One deterministic training run (host RNG + init key pinned, so two
    runs differing only in the dispatch window see identical data order
    and identical initial params). ``configure(o)`` has the last word on
    the optimizer before it runs."""
    RandomGenerator.set_seed(11)
    ds = array(_samples()) >> SampleToBatch(BATCH)
    model = _mlp()
    if mesh is not None:
        from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
        o = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), mesh=mesh)
    else:
        o = optim.Optimizer(model=model, dataset=ds,
                            criterion=nn.ClassNLLCriterion())
        assert isinstance(o, optim.LocalOptimizer)
    o.set_optim_method(optim.SGD(learning_rate=0.5, momentum=0.9))
    o.set_end_when(end_when)
    if max_in_flight is not None:
        o.set_async_dispatch(max_in_flight=max_in_flight)
    if ckpt_dir is not None:
        o.set_checkpoint(str(ckpt_dir), optim.every_epoch())
        o.overwrite_checkpoint()
    if summary is not None:
        o.set_train_summary(summary)
    if configure is not None:
        configure(o)
    trained = o.optimize()
    return trained, o


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def data_mesh():
    from bigdl_tpu.parallel import Engine
    Engine.reset()
    yield Engine.init(axes={"data": 8})
    Engine.reset()


class TestTransferCount:
    """The acceptance criterion: readback count, async vs lockstep."""

    def test_local_window_halves_readbacks(self, count_device_get):
        n = 8
        _run(optim.max_iteration(n), max_in_flight=2)
        assert count_device_get["n"] <= math.ceil(n / 2) + 2, \
            count_device_get["n"]
        assert count_device_get["n"] < n      # strictly fewer than before

    def test_local_odd_n_final_drain(self, count_device_get):
        n = 7
        _run(optim.max_iteration(n), max_in_flight=2)
        assert count_device_get["n"] <= math.ceil(n / 2) + 2

    def test_local_min_loss_syncs_every_step(self, count_device_get):
        n = 8
        # threshold never reached -> exactly max_iteration steps, each
        # drained individually because min_loss reads the loss
        _run(optim.or_trigger(optim.max_iteration(n),
                              optim.min_loss(1e-12)))
        assert count_device_get["n"] == n

    def test_local_window_one_is_lockstep(self, count_device_get):
        n = 8
        _run(optim.max_iteration(n), max_in_flight=1)
        assert count_device_get["n"] == n

    def test_distri_window_halves_readbacks(self, count_device_get,
                                            data_mesh):
        n = 8
        _run(optim.max_iteration(n), max_in_flight=2, mesh=data_mesh)
        assert count_device_get["n"] <= math.ceil(n / 2) + 2
        assert count_device_get["n"] < n

    def test_distri_min_loss_syncs_every_step(self, count_device_get,
                                              data_mesh):
        n = 8
        _run(optim.or_trigger(optim.max_iteration(n),
                              optim.min_loss(1e-12)), mesh=data_mesh)
        assert count_device_get["n"] == n


class TestBitIdentical:
    """Deferring the readback must not change a single bit of the
    trajectory — same steps, same order, same arithmetic."""

    def _compare(self, tmp_path, mesh=None):
        n = 8
        runs = {}
        for name, window in (("sync", 1), ("async", 2)):
            ts = TrainSummary(str(tmp_path), name +
                              ("_d" if mesh is not None else "_l"))
            ckpt = tmp_path / (name + ("_d" if mesh is not None else "_l"))
            trained, _ = _run(optim.max_iteration(n), max_in_flight=window,
                              mesh=mesh, ckpt_dir=ckpt, summary=ts)
            state = bfile.load(str(ckpt / "state"))
            runs[name] = (jax.tree.map(np.asarray, trained.params),
                          SummaryReader(ts.path).scalars("Loss"),
                          state["opt_state"])
        p_sync, loss_sync, opt_sync = runs["sync"]
        p_async, loss_async, opt_async = runs["async"]
        _assert_tree_equal(p_sync, p_async)                 # final params
        _assert_tree_equal(opt_sync, opt_async)             # opt state
        assert [s[0] for s in loss_sync] == list(range(1, n + 1))
        assert [s[0] for s in loss_async] == list(range(1, n + 1))
        sync_vals = [s[2] for s in loss_sync]
        async_vals = [s[2] for s in loss_async]
        assert sync_vals == async_vals                      # bit-identical

    def test_local(self, tmp_path):
        self._compare(tmp_path)

    def test_distri(self, tmp_path, data_mesh):
        self._compare(tmp_path, mesh=data_mesh)


class TestStoppingSemantics:
    def test_min_loss_stops_at_same_step_regardless_of_window(self,
                                                              tmp_path):
        """min_loss(10) is satisfied after the very first step; a loop
        that let the window run ahead on a stale loss would overshoot."""
        steps = {}
        for window in (1, 8):
            ts = TrainSummary(str(tmp_path), f"w{window}")
            _run(optim.or_trigger(optim.max_iteration(50),
                                  optim.min_loss(10.0)),
                 max_in_flight=window, summary=ts)
            steps[window] = [s[0] for s in
                             SummaryReader(ts.path).scalars("Loss")]
        assert steps[1] == steps[8] == [1]


class TestDeferredEmission:
    def test_drain_stamps_original_neval(self, tmp_path,
                                         count_device_get):
        """Window larger than the run: everything drains once at training
        end, yet every summary scalar carries its own step number in
        order."""
        ts = TrainSummary(str(tmp_path), "deferred")
        _, o = _run(optim.max_iteration(3), max_in_flight=8, summary=ts)
        assert count_device_get["n"] == 1       # one packed drain
        series = SummaryReader(ts.path).scalars("Loss")
        assert [s[0] for s in series] == [1, 2, 3]
        assert all(np.isfinite(s[2]) for s in series)
        # the dispatch-depth gauge saw the full window
        assert o.metrics.get("dispatch depth") == 3

    def test_drain_trace_span_annotates_sync(self, tmp_path):
        from bigdl_tpu.observability import trace
        trace.clear()
        trace.enable()
        try:
            _run(optim.max_iteration(4), max_in_flight=2)
        finally:
            trace.disable()
        events = trace.to_dict()["traceEvents"]
        trace.clear()
        drains = [e for e in events if e["name"] == "loss drain"]
        assert drains, "no loss drain span recorded"
        assert all(e["args"]["host_sync"] == "packed loss readback"
                   for e in drains)
        assert sum(e["args"]["depth"] for e in drains) == 4
        # the device step span is dispatch-only now — no sync annotation
        dsteps = [e for e in events if e["name"] == "device step"]
        assert len(dsteps) == 4
        assert all("host_sync" not in e.get("args", {}) for e in dsteps)


class TestOneLoop:
    """Both optimizers train through the ONE loop of the base
    ``Optimizer`` (ISSUE 29): a subclass prepares the run and writes no
    iteration of its own. Six steps of the same job over 4-batch epochs,
    validation and a checkpoint after steps 2 and 5, the epoch's turn in
    step 4."""

    KINDS = {"steady": 3, "epoch turn": 4, "validation and checkpoint": 5}

    @pytest.fixture(scope="class")
    def both(self, tmp_path_factory):
        """Per optimizer: the leaf spans of the loop's thread by
        iteration, in order, and every entry handed to ``_emit_step``."""
        from bigdl_tpu.observability import trace
        from bigdl_tpu.parallel import Engine
        tmp = tmp_path_factory.mktemp("one_loop")
        loop_thread = threading.get_ident()
        runs = {}
        Engine.reset()
        try:
            for name, mesh in (("local", None),
                               ("distri", Engine.init(axes={"data": 8}))):
                spans, iterations, entries = [], {}, []

                def tap(ev):
                    if ev["ph"] != "X" or ev["tid"] != loop_thread:
                        return
                    if ev["name"] != "train iteration":
                        spans.append(ev)
                        return
                    # a span ends after the spans inside it: a leaf is
                    # one that holds none of those that ended before it
                    iterations[ev["args"]["step"]] = [
                        e["name"] for i, e in enumerate(spans)
                        if not any(e["ts"] <= c["ts"] and c["ts"] + c["dur"]
                                   <= e["ts"] + e["dur"]
                                   for c in spans[:i])]
                    spans.clear()

                def configure(o):
                    val = array(_samples(64, seed=4)) >> SampleToBatch(BATCH)
                    o.set_validation(optim.several_iteration(3), val,
                                     [optim.Top1Accuracy()])
                    o.set_checkpoint(str(tmp / name),
                                     optim.several_iteration(3))
                    emit = o._emit_step
                    o._emit_step = lambda e, loss: (
                        entries.append(dict(e)), emit(e, loss))

                trace.get_tracer().add_tap(tap)
                try:
                    _run(optim.max_iteration(6), mesh=mesh,
                         configure=configure)
                finally:
                    trace.get_tracer().remove_tap(tap)
                runs[name] = (iterations, entries)
        finally:
            Engine.reset()
        return runs

    @pytest.mark.parametrize("kind", sorted(KINDS))
    def test_iteration_has_the_same_leaf_spans_in_both(self, both, kind):
        step = self.KINDS[kind]
        local, distri = both["local"][0], both["distri"][0]
        assert sorted(local) == sorted(distri) == [1, 2, 3, 4, 5, 6, 7]
        assert local[step] == distri[step]
        lookup_and_step = ["input wait", "step lookup", "device step"]
        assert local[step][:3] == lookup_and_step
        rest = local[step][3:]
        if kind == "steady":
            assert rest == []
        elif kind == "epoch turn":
            assert rest == ["loss drain", "emit steps"]
        else:
            assert rest[:3] == ["loss drain", "emit steps", "model sync"]
            assert "checkpoint handoff" in rest[3:]
        # and every other iteration, the compiling first and the seventh
        # that only ends the run among them
        assert local == distri

    def test_emit_step_is_handed_the_same_entry_by_both(self, both):
        local, distri = both["local"][1], both["distri"][1]
        assert [e["neval"] for e in local] == [1, 2, 3, 4, 5, 6]
        assert [e["neval"] for e in distri] == [1, 2, 3, 4, 5, 6]
        assert {frozenset(e) for e in local} \
            == {frozenset(e) for e in distri}
        # the first step compiled; "compiled" is what keeps a compile's
        # wall time out of DistriOptimizer's link-bandwidth estimate
        assert [e["compiled"] for e in local] \
            == [e["compiled"] for e in distri] == [True] + [False] * 5

    @pytest.mark.parametrize("name", ["LocalOptimizer", "DistriOptimizer"])
    def test_subclass_writes_no_loop_of_its_own(self, name):
        import inspect
        from bigdl_tpu.optim import distri_optimizer
        cls = getattr(optim, name, None) or getattr(distri_optimizer, name)
        assert "_optimize_impl" not in vars(cls)
        assert "_prepare_run" in vars(cls)
        assert cls._optimize_impl is optim.Optimizer._optimize_impl
        assert "while True" not in inspect.getsource(cls)


class TestBuilderAPI:
    def test_set_async_dispatch_validates(self):
        o = optim.Optimizer(model=_mlp(),
                            dataset=array(_samples()) >>
                            SampleToBatch(BATCH),
                            criterion=nn.ClassNLLCriterion())
        assert o.max_in_flight == 2             # async by default
        assert o.set_async_dispatch(max_in_flight=4) is o
        assert o.max_in_flight == 4
        with pytest.raises(ValueError, match="max_in_flight"):
            o.set_async_dispatch(max_in_flight=0)

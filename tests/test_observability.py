"""Observability subsystem tests (bigdl_tpu/observability/).

The load-bearing invariants:

- registry semantics: counters monotonic, gauges last-write-wins,
  histograms land in FIXED buckets; Prometheus text + JSON exposition
  are well-formed;
- summary JSONL round-trips write -> read with per-tag series intact;
- trace export is valid Chrome trace JSON (``ph``/``ts``/``name`` on
  every event);
- a DistriOptimizer LeNet run and a ContinuousBatcher session each
  produce a valid trace AND a replayable scalar event log;
- instrumentation sits OUTSIDE the compiled step path: enabling it
  changes neither the compile count nor the one-dispatch-per-step
  burst loop, and never adds a device sync.
"""
import glob
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import Sample, SampleToBatch, array
from bigdl_tpu.observability import (MetricRegistry, Summary,
                                     SummaryReader, TrainSummary,
                                     Tracer, ValidationSummary,
                                     sanitize_name, trace)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def live_trace():
    """Enable the global tracer for one test, always restore."""
    trace.clear()
    trace.enable()
    yield trace
    trace.disable()
    trace.clear()


@pytest.fixture
def fresh_engine():
    from bigdl_tpu.parallel import Engine
    Engine.reset()
    yield
    Engine.reset()


# ---------------------------------------------------------------------------
# metric registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_and_labels(self):
        reg = MetricRegistry()
        c = reg.counter("req_total", "requests", labelnames=("code",))
        c.inc(code="200")
        c.inc(2, code="200")
        c.inc(code="500")
        assert c.value(code="200") == 3
        assert c.value(code="500") == 1
        assert c.value(code="404") == 0

    def test_counter_rejects_decrease(self):
        c = MetricRegistry().counter("n_total")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_label_mismatch_raises(self):
        c = MetricRegistry().counter("n_total", labelnames=("a",))
        with pytest.raises(ValueError, match="expects labels"):
            c.inc()

    def test_gauge_last_write_wins(self):
        g = MetricRegistry().gauge("depth")
        g.set(5)
        g.set(2)
        g.inc()
        g.dec(3)
        assert g.value() == 0

    def test_histogram_fixed_buckets(self):
        reg = MetricRegistry()
        h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 0.5, 50.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(50.605)
        # cumulative per upper bound, +Inf catches the outlier
        assert snap["buckets"] == {"0.01": 1, "0.1": 3, "1": 4,
                                   "+Inf": 5}

    def test_histogram_rejects_bad_buckets(self):
        reg = MetricRegistry()
        with pytest.raises(ValueError, match="strictly increasing"):
            reg.histogram("h", buckets=(1.0, 0.5))
        with pytest.raises(ValueError, match="strictly increasing"):
            reg.histogram("h2", buckets=(1.0, math.inf))

    def test_get_or_create_idempotent_and_typed(self):
        reg = MetricRegistry()
        a = reg.counter("x_total")
        assert reg.counter("x_total") is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")
        with pytest.raises(ValueError, match="labelnames"):
            reg.counter("x_total", labelnames=("z",))

    def test_exposition_text(self):
        reg = MetricRegistry()
        reg.counter("a_total", "things").inc(3)
        reg.gauge("depth", labelnames=("q",)).set(2, q="main")
        reg.histogram("lat", buckets=(0.5,)).observe(0.1)
        text = reg.expose()
        assert "# TYPE a_total counter" in text
        assert "a_total 3" in text
        assert 'depth{q="main"} 2' in text
        assert 'lat_bucket{le="0.5"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_count 1" in text

    def test_histogram_exemplars(self):
        """ISSUE 19 pin: an ``observe(v, exemplar=rid)`` remembers the
        bucket's last trace id; exposition carries it OpenMetrics-style
        and ``dump()`` keys it by bucket bound, while ``snapshot()``
        stays exemplar-free (merges unchanged)."""
        reg = MetricRegistry()
        h = reg.histogram("lat", buckets=(0.01, 1.0))
        h.observe(0.005, exemplar="r1")
        h.observe(0.007, exemplar="r2")        # same bucket: last wins
        h.observe(0.5)                         # exemplar-free stays so
        text = reg.expose()
        assert '# {trace_id="r2"} 0.007' in text
        assert 'le="1"' in text and 'trace_id="r1"' not in text
        sample = reg.dump()["lat"]["samples"][0]
        assert sample["exemplars"]["0.01"]["trace_id"] == "r2"
        assert "1" not in sample["exemplars"]  # no exemplar, no entry
        assert "exemplars" not in h.snapshot()

    def test_json_dump_roundtrips(self, tmp_path):
        reg = MetricRegistry()
        reg.counter("a_total").inc()
        reg.histogram("h", buckets=(1.0,)).observe(2.0)
        path = str(tmp_path / "m.json")
        reg.dump_json(path)
        with open(path) as f:
            data = json.load(f)
        assert data["a_total"]["type"] == "counter"
        assert data["a_total"]["samples"][0]["value"] == 1
        assert data["h"]["samples"][0]["buckets"]["+Inf"] == 1

    def test_dump_json_creates_parent_dirs(self, tmp_path):
        reg = MetricRegistry()
        reg.counter("a_total").inc()
        path = str(tmp_path / "fresh" / "dir" / "m.json")
        reg.dump_json(path)
        with open(path) as f:
            assert json.load(f)["a_total"]["samples"][0]["value"] == 1

    def test_sanitize_name(self):
        assert sanitize_name("device step time") == "device_step_time"
        assert sanitize_name("allreduce GB/s (x)") \
            == "allreduce_GB_s__x_"
        assert sanitize_name("9lives").startswith("_")


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_export_is_valid_chrome_trace(self, tmp_path):
        t = Tracer(enabled=True)
        with t.span("device step", host_sync="loss readback"):
            with t.span("inner", cat="nest"):
                pass
        t.instant("epoch end")
        path = t.export(str(tmp_path / "trace.json"))
        with open(path) as f:
            data = json.load(f)
        events = data["traceEvents"]
        assert len(events) == 3
        for ev in events:
            assert "ph" in ev and "ts" in ev and "name" in ev
            assert "pid" in ev and "tid" in ev
        complete = [e for e in events if e["ph"] == "X"]
        assert len(complete) == 2
        assert all(e["dur"] >= 0 for e in complete)
        outer = next(e for e in complete if e["name"] == "device step")
        assert outer["args"]["host_sync"] == "loss readback"

    def test_disabled_records_nothing(self):
        t = Tracer(enabled=False)
        with t.span("x"):
            pass
        t.instant("y")
        assert t.to_dict()["traceEvents"] == []

    def test_bounded_buffer_counts_drops(self):
        t = Tracer(max_events=2, enabled=True)
        for _ in range(5):
            t.instant("e")
        d = t.to_dict()
        assert len(d["traceEvents"]) == 2
        assert d["otherData"]["dropped_events"] == 3

    def test_global_tracer_module_api(self, live_trace, tmp_path):
        with trace.span("step"):
            pass
        data = json.loads(
            open(trace.export(str(tmp_path / "t.json"))).read())
        assert data["traceEvents"][0]["name"] == "step"

    def test_export_creates_parent_dirs(self, tmp_path):
        """Satellite: a postmortem/export path under a fresh run dir
        must not fail on the missing parent."""
        t = Tracer(enabled=True)
        t.instant("e")
        path = str(tmp_path / "new" / "run" / "trace.json")
        assert t.export(path) == path
        with open(path) as f:
            assert len(json.load(f)["traceEvents"]) == 1


# ---------------------------------------------------------------------------
# summaries
# ---------------------------------------------------------------------------

class TestSummary:
    def test_train_summary_roundtrip(self, tmp_path):
        s = TrainSummary(str(tmp_path), "app")
        for i in range(1, 4):
            s.add_scalar("Loss", 1.0 / i, i)
            s.add_scalar("Throughput", 100.0 * i, i)
        got = s.read_scalar("Loss")
        assert [g[0] for g in got] == [1, 2, 3]
        assert [g[2] for g in got] == [1.0, 0.5, pytest.approx(1 / 3)]
        assert all(g[1] > 0 for g in got)          # wall_time
        assert s.tags() == ["Loss", "Throughput"]
        s.close()

    def test_reader_replays_jsonl(self, tmp_path):
        s = ValidationSummary(str(tmp_path), "app")
        s.add_scalar("Top1Accuracy", 0.5, 10)
        s.close()
        assert s.path.endswith("validation.jsonl")
        r = SummaryReader(s.path)
        assert r.scalars("Top1Accuracy") == [(10, pytest.approx(
            r.records()[0]["wall_time"]), 0.5)]
        assert r.steps("Top1Accuracy") == [10]
        assert r.values("Top1Accuracy") == [0.5]

    def test_lines_are_plain_json(self, tmp_path):
        s = Summary(str(tmp_path), "app")
        s.add_scalar("t", 1.5, 0)
        s.close()
        with open(s.path) as f:
            rec = json.loads(f.readline())
        assert set(rec) == {"step", "wall_time", "tag", "value"}

    def test_closed_summary_raises(self, tmp_path):
        s = Summary(str(tmp_path), "app")
        s.close()
        with pytest.raises(ValueError, match="closed"):
            s.add_scalar("t", 1.0, 0)

    def test_corrupt_line_is_loud(self, tmp_path):
        s = Summary(str(tmp_path), "app")
        s.add_scalar("t", 1.0, 0)
        s.close()
        with open(s.path, "a") as f:
            f.write("not json\n")
        with pytest.raises(ValueError, match="corrupt"):
            SummaryReader(s.path).records()

    def test_live_tail_skips_incomplete_final_line(self, tmp_path):
        """Satellite: tailing a LIVE log can catch the writer mid-line;
        an unterminated final line is skipped — and only that one."""
        s = Summary(str(tmp_path), "app")
        s.add_scalar("t", 1.0, 1)
        s.add_scalar("t", 2.0, 2)
        s.close()
        with open(s.path, "a") as f:
            f.write('{"step": 3, "wall_time": 1.0, "tag": "t", "va')
        r = SummaryReader(s.path)
        assert r.values("t") == [1.0, 2.0]
        assert r.steps("t") == [1, 2]
        # a corrupt line in the MIDDLE still fails loudly even when the
        # file also ends mid-write
        with open(s.path, "w") as f:
            f.write('{"step": 1, "wall_time": 1.0, "tag": "t", '
                    '"value": 1.0}\n')
            f.write("garbage\n")
            f.write('{"step": 2, "wall_time": 1.0, "tag": "t", "val')
        with pytest.raises(ValueError, match="corrupt"):
            SummaryReader(s.path).records()


# ---------------------------------------------------------------------------
# Metrics shim (optim/metrics.py rides the registry)
# ---------------------------------------------------------------------------

class TestMetricsShim:
    def test_metrics_exports_through_registry(self):
        from bigdl_tpu.optim.metrics import Metrics
        reg = MetricRegistry()
        m = Metrics(registry=reg)
        m.set("collective ops per step", 5)
        m.add("x y", 2.0)
        m.record("device step time", 0.01)
        m.record("device step time", 0.02)
        g = reg.get("bigdl_collective_ops_per_step")
        assert g is not None and g.value() == 5
        c = reg.get("bigdl_x_y_total")
        assert c is not None and c.value() == 2.0
        h = reg.get("bigdl_device_step_time")
        assert h is not None and h.snapshot()["count"] == 2
        # the Metrics-side API is unchanged by the shim
        assert m.get("collective ops per step") == 5
        assert m.stats("device step time")["n"] == 2

    def test_aggregated_single_process_is_copy(self):
        from bigdl_tpu.optim.metrics import Metrics
        reg = MetricRegistry()
        m = Metrics(registry=reg)
        m.set("s", 3.0)
        m.add("a", 1.0)
        m.add("a", 2.0)
        for v in (0.1, 0.2, 0.3):
            m.record("t", v)
        agg = m.aggregated()
        assert agg is not m
        assert agg.get("s") == 3.0
        assert agg.get("a") == 3.0
        assert agg.stats("t")["n"] == 3
        assert agg.stats("t")["max"] == pytest.approx(0.3)
        # originals untouched by the merge
        m.record("t", 9.0)
        assert agg.stats("t")["n"] == 3
        assert "a : 1.5 s" in agg.summary()   # mean of add()s

    def test_summary_reports_series_distribution(self):
        from bigdl_tpu.optim.metrics import Metrics
        m = Metrics(registry=MetricRegistry())
        for v in (0.1, 0.2):
            m.record("step", v)
        text = m.summary()
        assert "step : mean=0.15" in text


# ---------------------------------------------------------------------------
# training loops produce traces + event logs (acceptance criterion)
# ---------------------------------------------------------------------------

def _lenet_samples(n=32, seed=0, flat=False):
    rs = np.random.RandomState(seed)
    shape = (n, 784) if flat else (n, 1, 28, 28)
    x = rs.rand(*shape).astype(np.float32)
    y = rs.randint(1, 11, size=(n,)).astype(np.int64)
    return [Sample(x[i], y[i]) for i in range(n)]


class TestOptimizerIntegration:
    def test_distri_lenet_trace_and_event_log(self, tmp_path,
                                              fresh_engine, live_trace):
        """LeNet-sized DistriOptimizer.optimize(): valid Chrome trace +
        replayable per-step scalar series + validation scalars."""
        from bigdl_tpu import models
        from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
        from bigdl_tpu.parallel import Engine
        Engine.init()
        ds = array(_lenet_samples(), num_shards=1) >> SampleToBatch(16)
        val_ds = array(_lenet_samples(seed=5, n=16)) >> SampleToBatch(16)
        model = models.LeNet5(10)
        o = optim.Optimizer(model=model, dataset=ds,
                            criterion=nn.ClassNLLCriterion())
        assert isinstance(o, DistriOptimizer)
        ts = TrainSummary(str(tmp_path), "lenet")
        vs = ValidationSummary(str(tmp_path), "lenet")
        o.set_optim_method(optim.SGD(learning_rate=0.01)) \
         .set_train_summary(ts).set_val_summary(vs) \
         .set_validation(optim.every_epoch(), val_ds,
                         [optim.Top1Accuracy()]) \
         .set_end_when(optim.max_iteration(3))
        o.optimize()
        # (a) valid Chrome-trace JSON
        path = trace.export(str(tmp_path / "trace.json"))
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        assert events
        for ev in events:
            assert "ph" in ev and "ts" in ev and "name" in ev
        names = {e["name"] for e in events}
        # ISSUE 5: the host input phase is split into the consumer's
        # "input wait" (a queue pop under prefetch) and the worker-side
        # "input produce" (assembly + placement)
        assert {"input wait", "input produce", "compile step",
                "device step", "loss drain", "validation"} <= names
        # async dispatch: the device step span is dispatch-only; the
        # intentional sync lives in the packed "loss drain" span
        dstep = [e for e in events if e["name"] == "device step"]
        assert len(dstep) == 3
        assert all("host_sync" not in e.get("args", {}) for e in dstep)
        drains = [e for e in events if e["name"] == "loss drain"]
        assert all(e["args"]["host_sync"] == "packed loss readback"
                   for e in drains)
        assert sum(e["args"]["depth"] for e in drains) == 3
        # (b) the reader returns the recorded per-step series
        for tag in ("Loss", "Throughput", "HostInputTime",
                    "DeviceStepTime"):
            series = SummaryReader(ts.path).scalars(tag)
            assert [s[0] for s in series] == [1, 2, 3], tag
        losses = SummaryReader(ts.path).values("Loss")
        assert all(np.isfinite(v) for v in losses)
        # validation fired at the epoch boundary (2 batches/epoch)
        acc = SummaryReader(vs.path).scalars("Top1Accuracy")
        assert len(acc) == 1 and 0.0 <= acc[0][2] <= 1.0
        assert SummaryReader(vs.path).scalars("ValidationThroughput")

    def test_instrumentation_adds_no_compiles(self, tmp_path):
        """Tracer + summaries sit outside the jitted step: the traced
        step function compiles the SAME number of times with
        observability on as off."""
        def run(instrument: bool, sub: str) -> int:
            samples = _lenet_samples(n=64, seed=1, flat=True)
            ds = array(samples) >> SampleToBatch(32)
            model = nn.Sequential(nn.Linear(784, 16), nn.Tanh(),
                                  nn.Linear(16, 10), nn.LogSoftMax())
            traces = []
            orig = model.apply
            model.apply = lambda *a, **k: (traces.append(1),
                                           orig(*a, **k))[1]
            o = optim.Optimizer(model=model, dataset=ds,
                                criterion=nn.ClassNLLCriterion())
            o.set_optim_method(optim.SGD(learning_rate=0.1)) \
             .set_end_when(optim.max_iteration(4))
            if instrument:
                o.set_train_summary(
                    TrainSummary(str(tmp_path), sub))
                trace.enable()
            try:
                o.optimize()
            finally:
                trace.disable()
                trace.clear()
            return len(traces)

        assert run(False, "off") == run(True, "on")


# ---------------------------------------------------------------------------
# serving: batcher session metrics, event log, no-sync contract
# ---------------------------------------------------------------------------

V = 32


def _lm(seed=0):
    from bigdl_tpu.models import TransformerLM
    m = TransformerLM(V, d_model=32, num_heads=4, num_layers=2,
                      max_len=64)
    m.materialize(jax.random.PRNGKey(seed))
    m.evaluate()
    return m


def _prompts(lengths, seed=1):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(1, V + 1, size=(n,))) for n in lengths]


class TestBatcherObservability:
    def test_session_metrics_trace_and_event_log(self, tmp_path,
                                                 live_trace):
        from bigdl_tpu.models.transformer.serving import (
            ContinuousBatcher)
        reg = MetricRegistry()
        summ = Summary(str(tmp_path), "serving")
        model = _lm(seed=6)
        cb = ContinuousBatcher(model, max_batch=2, num_pages=32,
                               page_size=4, max_new_tokens=6,
                               max_burst=4, registry=reg, summary=summ)
        for i, p in enumerate(_prompts([3, 7, 5], seed=4)):
            cb.submit(i, p)
        assert reg.get("serving_queue_depth").value() == 3
        results = dict(cb.run_to_completion(burst=4))
        assert set(results) == {0, 1, 2}
        # counters / gauges tell the session's story
        assert reg.get("serving_admissions_total").value() == 3
        assert reg.get("serving_retirements_total").value() == 3
        assert reg.get("serving_ttft_seconds").snapshot()["count"] == 3
        assert reg.get("serving_queue_depth").value() == 0
        assert reg.get("serving_active_slots").value() == 0
        # pool back to scratch-page-only utilization
        assert reg.get("serving_kv_page_utilization").value() \
            == pytest.approx(1 / 32)
        steps = reg.get("serving_decode_token_seconds") \
                   .snapshot()["count"]
        assert steps >= 2
        assert reg.get("serving_generated_tokens_total").value() > 0
        # (b) per-step scalar event log round-trips through the reader
        r = SummaryReader(summ.path)
        for tag in ("QueueDepth", "ActiveSlots", "KVPageUtilization",
                    "DecodeTokensPerSec"):
            series = r.scalars(tag)
            assert [s[0] for s in series] == list(
                range(1, steps + 1)), tag
        assert all(0.0 <= v <= 1.0
                   for v in r.values("KVPageUtilization"))
        # (a) valid Chrome-trace JSON with serving spans
        path = trace.export(str(tmp_path / "serve_trace.json"))
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        for ev in events:
            assert "ph" in ev and "ts" in ev and "name" in ev
        names = {e["name"] for e in events}
        assert {"prefill", "decode burst"} <= names
        bursts = [e for e in events if e["name"] == "decode burst"]
        assert len(bursts) == steps
        assert all(e["args"]["host_sync"] == "token readback"
                   for e in bursts)

    def test_no_new_compiles_one_dispatch_per_step(self, tmp_path,
                                                   monkeypatch):
        """The instrumented burst loop stays one paged_decode dispatch
        per step() and compiles nothing the bare loop didn't."""
        from bigdl_tpu.models.transformer import serving as sv
        model = _lm(seed=6)
        prompts = _prompts([3, 7, 5], seed=4)

        def run(**kw):
            cb = sv.ContinuousBatcher(model, max_batch=2, num_pages=32,
                                      page_size=4, max_new_tokens=6,
                                      max_burst=4, **kw)
            for i, p in enumerate(prompts):
                cb.submit(i, p)
            cb.run_to_completion(burst=4)
            return cb

        run()                                    # warm: compile shapes
        decode_c = sv._paged_decode_impl._cache_size()
        prefill_c = sv._paged_prefill_impl._cache_size()
        dispatches = []
        orig = sv.paged_decode
        monkeypatch.setattr(
            sv, "paged_decode",
            lambda *a, **k: (dispatches.append(1), orig(*a, **k))[1])
        reg = MetricRegistry()
        trace.clear()
        trace.enable()
        try:
            run(registry=reg,
                summary=Summary(str(tmp_path), "serving2"))
        finally:
            trace.disable()
            trace.clear()
        assert sv._paged_decode_impl._cache_size() == decode_c
        assert sv._paged_prefill_impl._cache_size() == prefill_c
        steps = reg.get("serving_decode_token_seconds") \
                   .snapshot()["count"]
        assert len(dispatches) == steps > 0

    def test_default_burst_respects_small_max_burst(self):
        """Satellite: max_burst < 8 must work with no-arg step() /
        run_to_completion() (burst=None -> min(8, max_burst))."""
        from bigdl_tpu.models.transformer.generate import (
            GenerationConfig, generate)
        from bigdl_tpu.models.transformer.serving import (
            ContinuousBatcher)
        model = _lm(seed=6)
        p = _prompts([5], seed=4)[0]
        cb = ContinuousBatcher(model, max_batch=1, num_pages=32,
                               page_size=4, max_new_tokens=6,
                               max_burst=2, registry=MetricRegistry())
        cb.submit("r", p)
        assert cb.step() == 1                    # no-arg, burst -> 2
        results = dict(cb.run_to_completion())   # no-arg drives home
        want = np.asarray(generate(
            model, np.asarray([p], np.int32),
            GenerationConfig(max_new_tokens=6, temperature=0.0)))[0]
        np.testing.assert_array_equal(results["r"], want)
        with pytest.raises(ValueError, match="max_burst"):
            cb.step(burst=3)


class TestSpeculativeAcceptance:
    def test_denominator_counts_active_rows_only(self):
        """Satellite: proposals from rows that already hit their budget
        no longer deflate acceptance_rate (ADVICE.md)."""
        from bigdl_tpu.models.transformer.serving import (
            speculative_generate)
        target, draft = _lm(seed=0), _lm(seed=7)
        # single row: every round it is active until done
        _, st = speculative_generate(target, draft, _prompts([5]),
                                     max_new_tokens=16, gamma=3)
        assert st["proposed"] == st["rounds"] * 3
        assert st["acceptance_rate"] == pytest.approx(
            st["accepted"] / st["proposed"])
        # mixed progress: rows finish at different rounds, so fewer
        # proposals count than the old rounds*gamma*B denominator
        _, st = speculative_generate(target, draft,
                                     _prompts([3, 6, 9]),
                                     max_new_tokens=16, gamma=3)
        assert st["proposed"] < st["rounds"] * 3 * 3
        assert 0.0 <= st["acceptance_rate"] <= 1.0

    def test_perfect_draft_rate_is_one(self):
        from bigdl_tpu.models.transformer.serving import (
            speculative_generate)
        target = _lm(seed=0)
        _, st = speculative_generate(target, target, _prompts([3, 6]),
                                     max_new_tokens=12, gamma=3)
        assert st["acceptance_rate"] == 1.0
        assert st["accepted"] == st["proposed"]


# ---------------------------------------------------------------------------
# satellites: payload guard, lint host-only rule
# ---------------------------------------------------------------------------

def test_allgather_payload_size_guard():
    from bigdl_tpu.parallel.collective import _check_payload_size
    _check_payload_size(10)                      # small: fine
    _check_payload_size(2 ** 31 - 1)             # at the edge: fine
    with pytest.raises(ValueError, match="int32 size-gather limit"):
        _check_payload_size(2 ** 31)


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "bigdl_lint", os.path.join(REPO, "dev", "lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestLintHostOnlyRule:
    """OBS1 became jaxlint rule JX5 (dev/analysis/,
    docs/STATIC_ANALYSIS.md) — same contract, configurable prefixes."""

    def _jaxlint(self):
        _load_lint()            # puts dev/ on sys.path
        from analysis import jaxlint
        return jaxlint

    def test_detects_toplevel_jax_imports(self):
        jaxlint = self._jaxlint()
        bad = ("import jax\n"
               "from jax import numpy\n"
               "from jax.sharding import Mesh\n"
               "import numpy\n"
               "def f():\n"
               "    import jax\n")
        found = jaxlint.analyze_source(
            bad, "bigdl_tpu/observability/bad.py")
        assert [f.line for f in found] == [1, 2, 3]
        assert all(f.rule == "JX5" for f in found)

    def test_observability_package_is_clean(self):
        jaxlint = self._jaxlint()
        files = glob.glob(os.path.join(
            REPO, "bigdl_tpu", "observability", "*.py"))
        assert files, "observability package missing?"
        for path in files:
            found = jaxlint.analyze_file(path, REPO)
            assert [f for f in found if f.rule == "JX5"] == [], path

    def test_lint_file_applies_rule_to_package(self):
        lint = _load_lint()
        path = os.path.join(REPO, "bigdl_tpu", "observability",
                            "registry.py")
        findings, _ = lint.run_jaxlint([path])
        assert all("JX5" not in msg for _, _, msg in findings)


# ---------------------------------------------------------------------------
# standalone validators record scalars
# ---------------------------------------------------------------------------

def test_local_validator_records_summary(tmp_path):
    samples = _lenet_samples(n=16, seed=2, flat=True)
    ds = array(samples) >> SampleToBatch(16)
    model = nn.Sequential(nn.Linear(784, 8), nn.Tanh(),
                          nn.Linear(8, 10), nn.LogSoftMax())
    model.materialize(jax.random.PRNGKey(0))
    vs = ValidationSummary(str(tmp_path), "val")
    optim.LocalValidator(model, ds).test(
        [optim.Top1Accuracy()], summary=vs, step=7)
    got = SummaryReader(vs.path).scalars("Top1Accuracy")
    assert len(got) == 1 and got[0][0] == 7
    assert 0.0 <= got[0][2] <= 1.0

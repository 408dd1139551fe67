"""bench.py output-contract tests.

One JSON line per row and a final aggregate line that carries every row,
ok or failed. The parent process reads its own backend (no child is
started to probe it: the process that touches jax holds the chip). A
backend that cannot start gives one error row per requested metric and
exit code 3; ANY requested row that errors gives exit code 2; a device
kind missing from the peak table is an error, not a silent MFU of 0.
"""
import json

import pytest

import bench


def _parse_lines(captured: str):
    return [json.loads(line) for line in captured.strip().splitlines()
            if line.startswith("{")]


def _no_backend(why: str):
    """A ``_backend_info`` whose backend cannot start."""
    def raiser():
        raise RuntimeError(why)
    return raiser


def test_backend_info_is_read_in_this_process(monkeypatch):
    """One process per chip: the backend line comes from this process's
    own jax, and no child process is started to ask."""
    import subprocess

    def no_children(*a, **k):
        raise AssertionError("bench started a child to read the backend")
    monkeypatch.setattr(subprocess, "run", no_children)
    monkeypatch.setattr(subprocess, "Popen", no_children)
    platform, kind, count = bench._backend_info().split("|")
    assert (platform, kind, int(count)) == ("cpu", "cpu", 8)


def test_backend_info_propagates_init_failure(monkeypatch):
    import jax

    def dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", dead)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        bench._backend_info()


def test_main_emits_aggregate_with_all_rows(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
    head_row = {"metric": "inception_v1_train_images_per_sec_per_chip",
                "value": 123.0, "unit": "images/sec/chip",
                "vs_baseline": 0.8}
    monkeypatch.setattr(bench, "bench_convnet_synthetic",
                        lambda name, headline=False: dict(head_row))

    def boom():
        raise RuntimeError("no tokens today")
    monkeypatch.setattr(bench, "bench_transformer_lm", boom)

    with pytest.raises(SystemExit) as ei:
        bench.main(["--rows", "headline,transformer"])
    assert ei.value.code == 2       # a failed row fails the run
    lines = _parse_lines(capsys.readouterr().out)
    # per-row line for the ok row, then the aggregate (failed rows appear
    # only in the aggregate)
    assert lines[0]["value"] == 123.0
    agg = lines[-1]
    assert agg["metric"] == head_row["metric"]    # headline fields hoisted
    assert agg["value"] == 123.0 and agg["vs_baseline"] == 0.8
    assert len(agg["rows"]) == 2
    assert agg["rows"][0]["value"] == 123.0
    assert "RuntimeError" in agg["rows"][1]["error"]


def test_main_headline_failure_exits_2_with_aggregate(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")

    def boom(name, headline=False):
        raise RuntimeError("compile exploded")
    monkeypatch.setattr(bench, "bench_convnet_synthetic", boom)

    with pytest.raises(SystemExit) as ei:
        bench.main(["--headline-only"])
    assert ei.value.code == 2
    agg = _parse_lines(capsys.readouterr().out)[-1]
    assert "compile exploded" in agg["rows"][0]["error"]
    # a failed headline must NOT be papered over by hoisting another row
    assert agg["metric"] == "aggregate" and agg["value"] == 0.0


def test_main_backend_init_failure_exits_3_with_structured_row(
        monkeypatch, capsys):
    monkeypatch.setattr(bench, "_backend_info",
                        _no_backend("backend wedged"))
    with pytest.raises(SystemExit) as ei:
        bench.main([])
    assert ei.value.code == 3
    lines = _parse_lines(capsys.readouterr().out)
    assert "RuntimeError: backend wedged" in lines[0]["error"]
    assert lines[0]["value"] == 0.0
    agg = lines[-1]
    assert "backend wedged" in agg["rows"][0]["error"]


def test_backend_init_failure_emits_row_per_requested_metric(
        monkeypatch, capsys):
    """A backend that cannot start must report EVERY requested row as a
    structured error immediately, not just the headline."""
    monkeypatch.setattr(bench, "_backend_info",
                        _no_backend("init timed out"))
    with pytest.raises(SystemExit) as ei:
        bench.main(["--rows", "headline,transformer,decode"])
    assert ei.value.code == 3
    lines = _parse_lines(capsys.readouterr().out)
    agg = lines[-1]
    assert [r["metric"] for r in agg["rows"]] == [
        "inception_v1_train_images_per_sec_per_chip", "transformer",
        "decode"]
    assert all("timed out" in r["error"] for r in agg["rows"])
    # the per-row error lines were emitted immediately, before the
    # aggregate
    assert len(lines) == 4
    assert all("error" in line for line in lines[:-1])


def test_probe_timeout_flag_and_env_names_are_gone(monkeypatch):
    """The subprocess probe existed for a backend that could hang at
    init; with it went its flag and its two environment names."""
    monkeypatch.setenv("BIGDL_TPU_BENCH_INIT_TIMEOUT", "7.5")
    monkeypatch.setenv("BENCH_PROBE_TIMEOUT_S", "333")
    with pytest.raises(SystemExit) as ei:
        bench.main(["--probe-timeout", "2.5"])
    assert ei.value.code == 2       # argparse: unrecognized argument
    src = open(bench.__file__, encoding="utf-8").read()
    for gone in ("_probe_backend", "_BACKEND_DEATH_MARKERS",
                 "BIGDL_TPU_BENCH_INIT_TIMEOUT", "BENCH_PROBE_TIMEOUT_S"):
        assert gone not in src


def test_any_failed_row_exits_2_after_running_the_rest(monkeypatch,
                                                       capsys):
    """Not only the headline: a failed row anywhere is exit code 2, and
    the rows after it still run and are reported."""
    monkeypatch.setattr(bench, "_backend_info", lambda: "cpu|test|1")
    ran = []
    monkeypatch.setattr(bench, "bench_decode",
                        lambda: ran.append("decode") or {
                            "metric": "decode", "value": 1.0,
                            "unit": "t/s"})

    def boom():
        raise RuntimeError("no tokens today")
    monkeypatch.setattr(bench, "bench_transformer_lm", boom)
    monkeypatch.setattr(bench, "bench_decode_ragged",
                        lambda: ran.append("ragged") or {
                            "metric": "decode_ragged", "value": 2.0,
                            "unit": "t/s"})
    with pytest.raises(SystemExit) as ei:
        bench.main(["--rows", "decode,transformer,decode_ragged"])
    assert ei.value.code == 2
    assert ran == ["decode", "ragged"]
    agg = _parse_lines(capsys.readouterr().out)[-1]
    assert [("error" in r) for r in agg["rows"]] == [False, True, False]


def test_all_rows_ok_exits_0(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_backend_info", lambda: "cpu|test|1")
    monkeypatch.setattr(bench, "bench_decode",
                        lambda: {"metric": "decode", "value": 1.0,
                                 "unit": "t/s"})
    assert bench.main(["--rows", "decode"]) is None
    assert _parse_lines(capsys.readouterr().out)[-1]["rows"][0][
        "value"] == 1.0


class TestInputPipelineOverlapRow:
    """ISSUE 5 satellite: the input_pipeline_overlap metric — fraction
    of step wall time spent in `input wait` at prefetch depth 0 vs
    depth 2 — rides the standard row/registry contract."""

    def test_row_shape_and_registry_export(self, tmp_path):
        row = bench.bench_input_pipeline_overlap(iters=5)
        assert row["metric"] == "input_pipeline_overlap"
        assert row["unit"] == "fraction of step wall time"
        for k in ("input_wait_frac_depth0", "input_wait_frac_depth2"):
            assert 0.0 <= row[k] <= 1.0, (k, row)
        # the overlap won is the difference of the two fractions
        # (clamped at 0 — scheduling noise must not go negative)
        assert 0.0 <= row["value"] <= 1.0

    def test_main_wires_row_into_metrics_out(self, monkeypatch, capsys,
                                             tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        fake = {"metric": "input_pipeline_overlap", "value": 0.25,
                "unit": "fraction of step wall time",
                "input_wait_frac_depth0": 0.3,
                "input_wait_frac_depth2": 0.05, "iters": 4}
        monkeypatch.setattr(bench, "bench_input_pipeline_overlap",
                            lambda iters=12, batch=64: dict(fake))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "input_pipeline", "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "input_pipeline_overlap"
        assert lines[-1]["rows"][0]["value"] == 0.25
        with open(out) as f:
            text = f.read()
        assert "bench_input_pipeline_overlap 0.25" in text


class TestServingRows:
    """ISSUE 6 satellite: serving_ttft (p50/p99) and
    serving_tokens_per_sec at a fixed SLO through the router, riding
    the standard row/known/all contract."""

    def test_rows_registered_and_wired(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        ttft = {"metric": "serving_ttft", "value": 0.05,
                "unit": "seconds", "ttft_p50_s": 0.05,
                "ttft_p99_s": 0.25, "within_slo": True,
                "prefix_prefill_skips": 2, "disagg_prefills": 1}
        tps = {"metric": "serving_tokens_per_sec", "value": 512.0,
               "unit": "tokens/sec", "within_slo": True}
        monkeypatch.setattr(bench, "bench_serving_ttft",
                            lambda **kw: dict(ttft))
        monkeypatch.setattr(bench, "bench_serving_tokens_per_sec",
                            lambda **kw: dict(tps))
        bench.main(["--rows", "serving_ttft,serving_tokens_per_sec"])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "serving_ttft"
        assert lines[1]["metric"] == "serving_tokens_per_sec"
        agg = lines[-1]
        assert [r["metric"] for r in agg["rows"]] == [
            "serving_ttft", "serving_tokens_per_sec"]
        # mirrored into the process registry like every other row
        from bigdl_tpu.observability.registry import default_registry
        assert default_registry().get(
            "bench_serving_tokens_per_sec").value() == 512.0

    def test_rows_in_all(self, monkeypatch, capsys):
        """`--rows all` must include the serving rows (regression gate:
        a silently dropped row reads as healthy). The probe-failure
        path emits one structured error row per REQUESTED metric, so it
        exposes exactly what "all" expands to."""
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        metrics = [r["metric"] for r in agg["rows"]]
        assert "serving_ttft" in metrics
        assert "serving_tokens_per_sec" in metrics

    @pytest.fixture
    def _restore_dtype_policy(self):
        """The real bench row sets the global bf16 policy (as every
        bench row does); the suite's later torch-parity/golden tests
        need it back."""
        from bigdl_tpu.tensor import get_policy, set_policy
        old = get_policy()
        yield
        set_policy(old)

    @pytest.mark.parametrize("row", ["serving_ttft",
                                     "serving_tokens_per_sec"])
    def test_real_row_tiny_geometry(self, row, _restore_dtype_policy):
        """A REAL 2-replica router run (tiny model) produces a sane
        row: the shared workload is cached, so the pair costs one
        run."""
        fn = getattr(bench, f"bench_{row}")
        out = fn(n_requests=6, d_model=32, num_layers=2)
        assert out["metric"] == row
        assert out["value"] >= 0
        assert out["replicas"] == 2 and out["n_requests"] == 6
        assert out["slo"]["long_prefill_tokens"] == 128
        assert isinstance(out["within_slo"], bool)
        if row == "serving_ttft":
            assert out["ttft_p99_s"] >= out["ttft_p50_s"] >= 0
            assert out["prefix_prefill_skips"] >= 1
            assert out["disagg_prefills"] >= 1


class TestTrainMfuRow:
    """ISSUE 7 satellite: train_mfu rides the headline synthetic run
    (one training run serves both rows) and reports fraction-of-peak."""

    def test_row_shares_headline_run(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        calls = []

        def fake(name, headline=False):
            calls.append(name)
            return {"metric": "inception_v1_train_images_per_sec_per_chip",
                    "value": 5000.0, "unit": "images/sec/chip",
                    "vs_baseline": 33.3, "achieved_tflops": 63.4,
                    "mfu": 0.23, "chip_peak_tflops_bf16": 275.0}
        monkeypatch.setattr(bench, "bench_convnet_synthetic", fake)
        bench.main(["--rows", "headline,train_mfu"])
        lines = _parse_lines(capsys.readouterr().out)
        assert calls == ["inception_v1"]      # ONE run for both rows
        assert lines[0]["value"] == 5000.0
        assert lines[1]["metric"] == "train_mfu"
        assert lines[1]["value"] == 0.23
        assert lines[1]["unit"] == "fraction of bf16 peak"
        assert lines[1]["images_per_sec_per_chip"] == 5000.0
        agg = lines[-1]
        assert [r["metric"] for r in agg["rows"]] == [
            "inception_v1_train_images_per_sec_per_chip", "train_mfu"]

    def test_unknown_device_kind_is_an_error(self, monkeypatch):
        """A device kind missing from the peak table raises (with the
        kind and the known kinds in the message) instead of dropping
        ``mfu`` / reporting 0.0 with exit code 0."""
        import jax

        class Dev:
            device_kind = "TPU v9 imaginary"
        monkeypatch.setattr(jax, "devices", lambda: [Dev()])
        with pytest.raises(RuntimeError, match="TPU v9 imaginary"):
            bench._chip_peak_tflops()
        Dev.device_kind = "TPU v5 lite"
        assert bench._chip_peak_tflops() == 197.0

    def test_unknown_peak_fails_the_row_and_the_run(self, monkeypatch,
                                                    capsys):
        monkeypatch.setattr(bench, "_backend_info", lambda: "cpu|cpu|1")

        def headline_on_unknown_device(name, headline=False):
            bench._chip_peak_tflops()       # the cpu is not in the table
        monkeypatch.setattr(bench, "bench_convnet_synthetic",
                            headline_on_unknown_device)
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "train_mfu"])
        assert ei.value.code == 2
        row = _parse_lines(capsys.readouterr().out)[-1]["rows"][0]
        assert "no bf16 peak recorded for device kind 'cpu'" in \
            row["error"]


class TestCollectiveWireBytesRow:
    """ISSUE 7: static wire accounting for the sharded-update step at
    fp32 vs bf16 vs int8 — and the acceptance ratio (int8 >= 3x)."""

    def test_real_subprocess_probe(self):
        row = bench.bench_collective_wire_bytes()
        assert row["metric"] == "collective_wire_bytes_per_step"
        assert row["value"] == row["wire_bytes_per_chip_int8"] > 0
        assert row["wire_bytes_per_chip_fp32"] > \
            row["wire_bytes_per_chip_bf16"] > \
            row["wire_bytes_per_chip_int8"]
        assert row["reduction_int8_vs_fp32"] >= 3.0
        assert row["reduction_bf16_vs_fp32"] >= 1.9
        assert row["n_shards"] == 8

    def test_rows_in_all(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        metrics = [r["metric"] for r in agg["rows"]]
        assert "train_mfu" in metrics
        assert "collective_wire_bytes_per_step" in metrics


class TestBenchRecovery:
    """ISSUE 7 satellites: round-4 (backend death mid-run must yield
    structured rows + postmortem, not a raw rc=1 traceback) and round-5
    (probe failure dumps a flight-recorder postmortem)."""

    @pytest.mark.slow  # full inception trace is ~15s on the tier-1 box
    def test_inception_step_traces_on_cpu(self):
        """Regression for the BENCH_r04 crash signature: the inception
        row's train step TRACES cleanly on CPU — the
        convert_element_type failure was the dead backend surfacing
        through the row's first eager op, not a dtype bug in the step.
        This pins the step itself stays traceable (bf16 policy, int64
        labels and all) so any future r04-style crash is environmental
        by elimination."""
        import numpy as np

        import jax
        import jax.numpy as jnp
        from bigdl_tpu.tensor import get_policy, set_policy
        old = get_policy()
        try:
            bench._set_bf16_policy()
            pieces = bench._convnet_pieces("inception_v1")
            model, params, mstate, opt_state, train_step = pieces
            host = np.random.default_rng(0)
            data = jnp.asarray(host.standard_normal((4, 3, 224, 224),
                                                    np.float32))
            labels = jnp.asarray(host.integers(1, 1001, size=(4,)))
            jax.jit(train_step, donate_argnums=(0, 1, 2)).lower(
                params, mstate, opt_state, jax.random.PRNGKey(0),
                data, labels)      # raises on any trace-time dtype bug
        finally:
            set_policy(old)

    def test_backend_error_in_a_row_is_an_ordinary_row_failure(
            self, monkeypatch, capsys):
        """No error text is special: a row that dies with a backend
        error is reported like any failed row, the remaining rows still
        run on their own merits, and the run exits 2."""
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")

        def dead(name, headline=False):
            raise RuntimeError(
                "Unable to initialize backend 'tpu': UNAVAILABLE: TPU "
                "backend setup/compile error (Unavailable).")
        ran = []
        monkeypatch.setattr(bench, "bench_convnet_synthetic", dead)
        monkeypatch.setattr(bench, "bench_decode",
                            lambda: ran.append(1) or {
                                "metric": "decode", "value": 1.0,
                                "unit": "t/s"})
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "headline,decode"])
        assert ei.value.code == 2
        assert ran == [1]
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert agg["metric"] == "aggregate"     # failed headline not hoisted
        assert "Unable to initialize backend" in agg["rows"][0]["error"]
        assert agg["rows"][1]["value"] == 1.0

    def test_ordinary_row_failure_runs_later_rows_and_exits_2(
            self, monkeypatch, capsys):
        """A row exception does not lose the others: later rows still
        run and are reported — and the run exits non-zero, whichever
        row it was."""
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")

        def boom():
            raise RuntimeError("no tokens today")
        ran = []
        monkeypatch.setattr(bench, "bench_transformer_lm", boom)
        monkeypatch.setattr(bench, "bench_decode",
                            lambda: ran.append(1) or {
                                "metric": "decode", "value": 1.0,
                                "unit": "t/s"})
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "transformer,decode"])
        assert ei.value.code == 2
        assert ran == [1]
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "no tokens today" in agg["rows"][0]["error"]
        assert agg["rows"][1]["value"] == 1.0

    def test_backend_init_failure_names_the_cause_in_every_row(
            self, monkeypatch, capsys):
        monkeypatch.setattr(
            bench, "_backend_info",
            _no_backend("Unable to initialize backend 'tpu'"))
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "headline,decode"])
        assert ei.value.code == 3
        lines = _parse_lines(capsys.readouterr().out)
        assert len(lines[-1]["rows"]) == 2
        for r in lines[-1]["rows"]:
            assert "jax backend init failed: RuntimeError: Unable to " \
                "initialize backend 'tpu'" in r["error"]


class TestCompileColdStartRow:
    """ISSUE 8 satellite: compile_cold_start — wall-clock to first step
    with a cold vs warmed AOT executable cache, reported as the ratio —
    rides the standard row/known/all contract."""

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        fake = {"metric": "compile_cold_start", "value": 12.5,
                "unit": "x (cold / warm start-to-first-step)",
                "cold_first_step_s": 10.0, "warm_first_step_s": 0.8,
                "warm_cache_hits": 1, "loss_bit_identical": True}
        monkeypatch.setattr(bench, "bench_compile_cold_start",
                            lambda **kw: dict(fake))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "compile_cold_start",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "compile_cold_start"
        assert lines[-1]["rows"][0]["value"] == 12.5
        with open(out) as f:
            assert "bench_compile_cold_start 12.5" in f.read()

    def test_row_in_all(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "compile_cold_start" in [r["metric"] for r in agg["rows"]]

    def test_real_probe_fast_geometry(self, tmp_path):
        """A REAL two-subprocess cold/warm run on the fast lenet5
        geometry: the warm worker must load (1 hit, 0 misses), be
        faster, and replay the cold loss bit-identically."""
        row = bench.bench_compile_cold_start(
            model="lenet5", batch=32, cache_dir=str(tmp_path))
        assert row["metric"] == "compile_cold_start"
        assert row["warm_cache_hits"] == 1
        assert row["warm_cache_misses"] == 0
        assert row["loss_bit_identical"] is True
        assert row["value"] > 1.0, row   # warm strictly faster
        assert row["cold_first_step_s"] > row["warm_first_step_s"]


class TestBenchGate:
    """ISSUE 9 satellite (ROADMAP item 5): ``--gate BASELINE.json``
    compares selected rows against a recorded baseline with per-row
    thresholds, exits non-zero (4) on a real slowdown, and
    ``--baseline-out`` records the run as the next baseline."""

    ROW = {"metric": "transformer_lm_train_tokens_per_sec_per_chip",
           "value": 100.0, "unit": "tokens/sec/chip"}

    def _arm(self, monkeypatch, value=100.0):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        row = dict(self.ROW, value=value)
        monkeypatch.setattr(bench, "bench_transformer_lm",
                            lambda: dict(row))

    def _baseline(self, tmp_path, value=100.0, **spec):
        path = tmp_path / "BASELINE.json"
        entry = {"value": value, **spec}
        path.write_text(json.dumps(
            {"version": 1, "rows": {self.ROW["metric"]: entry}}))
        return str(path)

    def test_gate_passes_recorded_baseline(self, monkeypatch, capsys,
                                           tmp_path):
        self._arm(monkeypatch)
        path = self._baseline(tmp_path)
        bench.main(["--rows", "transformer", "--gate", path])  # no exit
        lines = _parse_lines(capsys.readouterr().out)
        gate = next(line for line in lines
                    if line.get("metric") == "bench_gate")
        assert gate["value"] == 1.0 and gate["failures"] == []
        assert gate["checked"] == [self.ROW["metric"]]
        # the gate verdict also rides the aggregate (last line)
        assert any(r["metric"] == "bench_gate"
                   for r in lines[-1]["rows"])

    def test_gate_fails_injected_slowdown(self, monkeypatch, capsys,
                                          tmp_path):
        self._arm(monkeypatch, value=50.0)       # 2x slowdown
        path = self._baseline(tmp_path)
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "transformer", "--gate", path])
        assert ei.value.code == 4
        gate = next(line for line in
                    _parse_lines(capsys.readouterr().out)
                    if line.get("metric") == "bench_gate")
        assert gate["value"] == 0.0
        assert gate["failures"][0]["metric"] == self.ROW["metric"]
        assert "min_ratio" in gate["failures"][0]["reason"]

    def test_gate_threshold_tolerates_noise(self, monkeypatch, tmp_path):
        """A value inside the per-row min_ratio band passes; tightening
        the ratio in the baseline file flips it."""
        self._arm(monkeypatch, value=90.0)
        bench.main(["--rows", "transformer", "--gate",
                    self._baseline(tmp_path)])   # default 0.8 passes
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "transformer", "--gate",
                        self._baseline(tmp_path, min_ratio=0.95)])
        assert ei.value.code == 4

    def test_gate_lower_is_better_direction(self, monkeypatch, capsys,
                                            tmp_path):
        """serving_ttft-style rows gate in the other direction: a
        LARGER value is the regression."""
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        row = {"metric": "serving_ttft", "value": 0.30,
               "unit": "seconds"}
        monkeypatch.setattr(bench, "bench_serving_ttft",
                            lambda **kw: dict(row))
        path = tmp_path / "b.json"
        path.write_text(json.dumps({"version": 1, "rows": {
            "serving_ttft": {"value": 0.10}}}))
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "serving_ttft", "--gate", str(path)])
        assert ei.value.code == 4
        row["value"] = 0.11                      # inside 0.1/0.8
        bench.main(["--rows", "serving_ttft", "--gate", str(path)])

    def test_gate_fails_on_errored_baselined_row(self, monkeypatch,
                                                 capsys, tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")

        def boom():
            raise RuntimeError("no tokens today")
        monkeypatch.setattr(bench, "bench_transformer_lm", boom)
        path = self._baseline(tmp_path)
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "transformer", "--gate", path])
        assert ei.value.code == 4
        gate = next(line for line in
                    _parse_lines(capsys.readouterr().out)
                    if line.get("metric") == "bench_gate")
        assert "row errored" in gate["failures"][0]["reason"]

    def test_gate_skips_unrequested_rows_loudly(self, monkeypatch,
                                                capsys, tmp_path):
        """Baseline rows this invocation did not run are reported as
        skipped, not judged and not silently dropped."""
        self._arm(monkeypatch)
        path = tmp_path / "b.json"
        path.write_text(json.dumps({"version": 1, "rows": {
            self.ROW["metric"]: {"value": 100.0},
            "serving_tokens_per_sec": {"value": 512.0}}}))
        bench.main(["--rows", "transformer", "--gate", str(path)])
        gate = next(line for line in
                    _parse_lines(capsys.readouterr().out)
                    if line.get("metric") == "bench_gate")
        assert gate["skipped"] == ["serving_tokens_per_sec"]
        assert gate["value"] == 1.0

    def test_unreadable_baseline_fails_gate(self, monkeypatch, tmp_path):
        self._arm(monkeypatch)
        path = tmp_path / "b.json"
        path.write_text("{not json")
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "transformer", "--gate", str(path)])
        assert ei.value.code == 4

    def test_baseline_out_round_trip(self, monkeypatch, capsys,
                                     tmp_path):
        """--baseline-out records the run; gating the same run against
        it passes (the update-the-baseline workflow)."""
        self._arm(monkeypatch)
        out = tmp_path / "new_baseline.json"
        metrics = tmp_path / "metrics.txt"
        bench.main(["--rows", "transformer", "--baseline-out", str(out),
                    "--metrics-out", str(metrics)])
        doc = json.loads(out.read_text())
        entry = doc["rows"][self.ROW["metric"]]
        assert entry["value"] == 100.0
        assert entry["min_ratio"] == bench.GATE_DEFAULT_MIN_RATIO
        assert entry["direction"] == "higher"
        assert metrics.exists()                 # emitted alongside
        capsys.readouterr()
        bench.main(["--rows", "transformer", "--gate", str(out)])
        gate = next(line for line in
                    _parse_lines(capsys.readouterr().out)
                    if line.get("metric") == "bench_gate")
        assert gate["value"] == 1.0

    def test_baseline_out_skips_error_rows(self, monkeypatch, tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")

        def boom():
            raise RuntimeError("nope")
        monkeypatch.setattr(bench, "bench_transformer_lm", boom)
        monkeypatch.setattr(bench, "bench_decode",
                            lambda: {"metric": "decode_row",
                                     "value": 5.0, "unit": "t/s"})
        out = tmp_path / "b.json"
        with pytest.raises(SystemExit) as ei:
            bench.main(["--rows", "transformer,decode",
                        "--baseline-out", str(out)])
        assert ei.value.code == 2       # the errored row fails the run
        doc = json.loads(out.read_text())
        assert list(doc["rows"]) == ["decode_row"]


class TestServingDecodeHBMRow:
    """ISSUE 9 satellite: serving_decode_hbm_bytes — static accounting
    of the decode step's HBM traffic, dense view vs paged kernel (the
    tentpole's measured receipt) — rides the standard
    row/known/all contract."""

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        fake = {"metric": "serving_decode_hbm_bytes", "value": 4.5,
                "unit": "x (dense-view / paged attention HBM bytes "
                        "per decode step)",
                "materialized_gather_ops_dense": 4,
                "materialized_gather_ops_paged": 0}
        monkeypatch.setattr(bench, "bench_serving_decode_hbm",
                            lambda: dict(fake))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "serving_decode_hbm_bytes",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "serving_decode_hbm_bytes"
        assert lines[-1]["rows"][0]["value"] == 4.5
        with open(out) as f:
            assert "bench_serving_decode_hbm_bytes 4.5" in f.read()

    def test_row_in_all(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "serving_decode_hbm_bytes" in [r["metric"]
                                              for r in agg["rows"]]

    def test_real_subprocess_probe(self):
        """The REAL CPU-subprocess probe (tiny geometry): the dense
        step carries the view-sized gather materializations, the paged
        step carries none, and the static traffic model reports a
        reduction."""
        row = bench.bench_serving_decode_hbm(
            b=3, pages_per_seq=8, page_size=4, d_model=64,
            num_heads=4, num_kv_heads=2, num_layers=2, vocab=128)
        assert row["metric"] == "serving_decode_hbm_bytes"
        assert row["value"] > 1.0
        assert row["materialized_gather_ops_dense"] > 0
        assert row["materialized_gather_ops_paged"] == 0
        assert row["materialized_gather_bytes_paged"] == 0
        assert row["attn_hbm_bytes_paged"] < row["attn_hbm_bytes_dense"]
        assert row["bytes_accessed_dense_exec"] > 0
        # ISSUE 15: the int8 extension rides the same row — static
        # weight+KV byte accounting at fp32 vs int8. The >= 3x
        # acceptance bar is pinned at the row's DEFAULT probe geometry
        # (head_dim 64) in test_quantized_serving.py; this tiny
        # geometry (head_dim 16) carries more per-row scale overhead.
        assert row["int8_weight_kv_bytes_fp32"] > \
            row["int8_weight_kv_bytes_int8"] > 0
        assert row["int8_kv_pool_bytes_fp32"] > \
            row["int8_kv_pool_bytes_int8"] > 0
        assert row["int8_reduction"] > 2.5


class TestTrainPeakHbmRow:
    """ISSUE 10: train_peak_hbm_bytes — static peak-HBM accounting of
    the transformer train step across remat policies at fixed effective
    batch, plus the accumulation scan's executable temp shrink — rides
    the standard row/known/all contract."""

    FAKE = {"metric": "train_peak_hbm_bytes", "value": 2.5,
            "unit": "x (peak HBM none / nothing_saveable, fixed "
                    "effective batch)",
            "peak_hbm_bytes_none": 100.0,
            "peak_hbm_bytes_nothing_saveable": 40.0,
            "accum_temp_reduction": 3.0}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_train_peak_hbm",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "train_peak_hbm_bytes",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "train_peak_hbm_bytes"
        assert lines[-1]["rows"][0]["value"] == 2.5
        with open(out) as f:
            assert "bench_train_peak_hbm_bytes 2.5" in f.read()

    def test_row_in_all(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        metrics = [r["metric"] for r in agg["rows"]]
        assert "train_peak_hbm_bytes" in metrics
        assert "multichip_scaling" in metrics

    def test_real_probe_tiny_geometry_in_process(self):
        """The underlying probe at tiny geometry, in-process (no
        subprocess): the acceptance bar — nothing_saveable frees
        >= 1.5x peak HBM vs none at fixed effective batch — holds even
        here, and the k-microbatch scan shrinks the compiled
        executable's temp buffers."""
        from bigdl_tpu.optim.remat import train_memory_probe
        out = train_memory_probe(d_model=32, num_layers=2, seq=64,
                                 batch=8, vocab=64, accum_k=2)
        peak = out["peak_hbm_bytes"]
        assert peak["none"] > peak["per_block"] > \
            peak["nothing_saveable"]
        assert out["reduction"] >= 1.5
        assert out["accum_temp_reduction"] is not None
        assert out["accum_temp_reduction"] > 1.0

    @pytest.mark.slow
    def test_real_subprocess_probe(self):
        row = bench.bench_train_peak_hbm(d_model=32, num_layers=2,
                                         seq=64, batch=8, vocab=64,
                                         accum_k=2)
        assert row["metric"] == "train_peak_hbm_bytes"
        assert row["value"] >= 1.5
        assert row["peak_hbm_bytes_none"] > \
            row["peak_hbm_bytes_nothing_saveable"]


class TestMultichipScalingRow:
    """ROADMAP item 5 satellite: multichip_scaling — per-chip
    throughput ratio vs ideal across 1/2/4/8-device CPU meshes, one
    subprocess per mesh size."""

    FAKE = {"metric": "multichip_scaling", "value": 0.5,
            "unit": "per-chip throughput ratio vs ideal at 8 devices",
            "device_counts": [1, 2, 4, 8],
            "per_chip_img_per_sec": {"1": 100.0, "8": 50.0},
            "ratio_vs_ideal": {"1": 1.0, "8": 0.5},
            "cpu_mesh_emulated": True}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_multichip_scaling",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "multichip_scaling",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "multichip_scaling"
        assert lines[-1]["rows"][0]["value"] == 0.5
        with open(out) as f:
            assert "bench_multichip_scaling 0.5" in f.read()

    def test_xla_flags_device_count_override(self, monkeypatch):
        monkeypatch.setenv(
            "XLA_FLAGS",
            "--xla_foo=1 --xla_force_host_platform_device_count=8")
        flags = bench._xla_flags_with_device_count(2)
        assert "--xla_force_host_platform_device_count=2" in flags
        assert "count=8" not in flags
        assert "--xla_foo=1" in flags

    @pytest.mark.slow
    def test_real_probe_two_mesh_sizes(self):
        """A REAL pair of subprocess probes: wiring + the ratio math
        (per-chip at N=2 relative to N=1; the shared-core CPU mesh
        makes the ideal unreachable — the row documents that)."""
        row = bench.bench_multichip_scaling(device_counts=(1, 2),
                                            batch_per_chip=16, iters=3)
        assert row["metric"] == "multichip_scaling"
        assert row["device_counts"] == [1, 2]
        assert row["ratio_vs_ideal"]["1"] == 1.0
        assert 0 < row["value"] <= 1.5
        assert row["cpu_mesh_emulated"] is True


class TestDefaultGate:
    """ISSUE 10 satellite (ROADMAP item 5): a CLI invocation gates
    against the committed BASELINE.json by default — --no-gate opts
    out, and a legacy/non-gate-format file skips with a note instead
    of failing every run."""

    ROW = {"metric": "transformer_lm_train_tokens_per_sec_per_chip",
           "value": 100.0, "unit": "tokens/sec/chip"}

    def _arm(self, monkeypatch, argv):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_transformer_lm",
                            lambda: dict(self.ROW))
        import sys as _sys
        monkeypatch.setattr(_sys, "argv", ["bench.py"] + argv)

    def _gate_rows(self, capsys):
        return [line for line in _parse_lines(capsys.readouterr().out)
                if line.get("metric") == "bench_gate"]

    def test_cli_run_gates_against_recorded_baseline(self, monkeypatch,
                                                     capsys, tmp_path):
        path = tmp_path / "BASELINE.json"
        path.write_text(json.dumps({"version": 1, "rows": {
            self.ROW["metric"]: {"value": 100.0}}}))
        monkeypatch.setattr(bench, "DEFAULT_BASELINE", str(path))
        self._arm(monkeypatch, ["--rows", "transformer"])
        bench.main(None)                      # argv=None: the CLI path
        gates = self._gate_rows(capsys)
        assert gates and gates[0]["value"] == 1.0
        assert gates[0]["baseline"] == str(path)

    def test_cli_slowdown_fails_default_gate(self, monkeypatch, capsys,
                                             tmp_path):
        path = tmp_path / "BASELINE.json"
        path.write_text(json.dumps({"version": 1, "rows": {
            self.ROW["metric"]: {"value": 1000.0}}}))
        monkeypatch.setattr(bench, "DEFAULT_BASELINE", str(path))
        self._arm(monkeypatch, ["--rows", "transformer"])
        with pytest.raises(SystemExit) as ei:
            bench.main(None)
        assert ei.value.code == bench.GATE_EXIT_CODE

    def test_no_gate_flag_opts_out(self, monkeypatch, capsys, tmp_path):
        path = tmp_path / "BASELINE.json"
        path.write_text(json.dumps({"version": 1, "rows": {
            self.ROW["metric"]: {"value": 1000.0}}}))
        monkeypatch.setattr(bench, "DEFAULT_BASELINE", str(path))
        self._arm(monkeypatch, ["--rows", "transformer", "--no-gate"])
        bench.main(None)                      # would exit 4 if gated
        assert self._gate_rows(capsys) == []

    def test_legacy_metadata_baseline_skips_with_note(self, monkeypatch,
                                                      capsys, tmp_path):
        """The repo's seed-era BASELINE.json (reference metadata, no
        'rows') must not arm the gate — skipped loudly on stderr."""
        path = tmp_path / "BASELINE.json"
        path.write_text(json.dumps({"metric": "legacy", "published": {}}))
        monkeypatch.setattr(bench, "DEFAULT_BASELINE", str(path))
        self._arm(monkeypatch, ["--rows", "transformer"])
        bench.main(None)
        captured = capsys.readouterr()
        assert self._gate_rows_from(captured.out) == []
        assert "not a recorded gate baseline" in captured.err

    @staticmethod
    def _gate_rows_from(out):
        return [line for line in _parse_lines(out)
                if line.get("metric") == "bench_gate"]

    def test_explicit_argv_runs_never_auto_gate(self, monkeypatch,
                                                capsys, tmp_path):
        """Embedding callers (and this test suite) pass explicit argv —
        the default gate must not surprise them."""
        path = tmp_path / "BASELINE.json"
        path.write_text(json.dumps({"version": 1, "rows": {
            self.ROW["metric"]: {"value": 1000.0}}}))
        monkeypatch.setattr(bench, "DEFAULT_BASELINE", str(path))
        self._arm(monkeypatch, [])
        bench.main(["--rows", "transformer"])   # no SystemExit(4)
        assert self._gate_rows(capsys) == []

    def test_is_gate_baseline_format_check(self, tmp_path):
        good = tmp_path / "good.json"
        good.write_text(json.dumps({"rows": {"m": {"value": 1.0}}}))
        assert bench._is_gate_baseline(str(good))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"published": {}}))
        assert not bench._is_gate_baseline(str(bad))
        assert not bench._is_gate_baseline(str(tmp_path / "absent.json"))
        notjson = tmp_path / "nj.json"
        notjson.write_text("{oops")
        assert not bench._is_gate_baseline(str(notjson))


def _get(url):
    from urllib.request import urlopen
    with urlopen(url, timeout=10) as r:
        return r.status, r.read().decode("utf-8")


def test_serve_metrics_exposes_live_registry(monkeypatch, capsys):
    """--serve-metrics PORT serves the registry DURING the run (rows
    scrape their own process here) and tears the server down after."""
    monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
    seen = {}

    def fake_row(name, headline=False):
        srv = bench._metrics_server
        assert srv is not None and srv.port > 0
        status, text = _get(f"{srv.url}/metrics")
        seen["status"], seen["text"] = status, text
        _, seen["health"] = _get(f"{srv.url}/healthz")
        return {"metric": "inception_v1_train_images_per_sec_per_chip",
                "value": 42.0, "unit": "images/sec/chip",
                "vs_baseline": 0.28}
    monkeypatch.setattr(bench, "bench_convnet_synthetic", fake_row)
    bench.main(["--rows", "headline", "--serve-metrics", "0"])
    assert seen["status"] == 200
    assert json.loads(seen["health"])["status"] == "ok"
    # the scrape happened before this row's gauge was published, but
    # the endpoint IS the live process registry
    assert "# TYPE" in seen["text"] or seen["text"] == ""
    # and the registry now carries the row that ran
    from bigdl_tpu.observability.registry import default_registry
    g = default_registry().get(
        "bench_inception_v1_train_images_per_sec_per_chip")
    assert g is not None and g.value() == 42.0
    # server is gone after main returns
    assert bench._metrics_server is None


def test_serve_metrics_closes_on_probe_failure(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
    with pytest.raises(SystemExit) as ei:
        bench.main(["--serve-metrics", "0"])
    assert ei.value.code == 3
    assert bench._metrics_server is None


class TestPipelineBubbleRow:
    """ISSUE 11: pipeline_bubble_fraction — measured schedule bubbles
    from per-stage span timings vs the extended
    pipeline_schedule_stats model, on the standard row/known/all
    contract. Lower is better and the gate knows."""

    FAKE = {"metric": "pipeline_bubble_fraction", "value": 0.158,
            "unit": "measured interleaved-1F1B bubble fraction "
                    "(fill-drain idle share; lower is better)",
            "measured_gpipe": 0.273, "modeled_gpipe": 0.273,
            "measured_1f1b": 0.273, "modeled_1f1b": 0.273,
            "measured_interleaved_1f1b": 0.158,
            "modeled_interleaved_1f1b": 0.158,
            "n_stages": 4, "num_microbatches": 8, "virtual_stages": 2}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_pipeline_bubble",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "pipeline_bubble_fraction",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "pipeline_bubble_fraction"
        assert lines[-1]["rows"][0]["value"] == 0.158
        with open(out) as f:
            assert "bench_pipeline_bubble_fraction 0.158" in f.read()

    def test_row_in_all_and_gate_direction(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "pipeline_bubble_fraction" in \
            [r["metric"] for r in agg["rows"]]
        # a bubble REGRESSION (larger fraction) must fail the gate
        assert "pipeline_bubble_fraction" in bench._GATE_LOWER_IS_BETTER

    def test_gate_lower_is_better_semantics(self, tmp_path):
        base = tmp_path / "b.json"
        base.write_text(json.dumps({"rows": {
            "pipeline_bubble_fraction": {
                "value": 0.158, "min_ratio": 0.8,
                "direction": "lower"}}}))
        ok_row = [{"metric": "pipeline_bubble_fraction",
                   "value": 0.16}]
        bad_row = [{"metric": "pipeline_bubble_fraction",
                    "value": 0.5}]
        _, ok = bench._gate_check(str(base), ok_row)
        assert ok
        _, ok = bench._gate_check(str(base), bad_row)
        assert not ok

    def test_real_measure_in_process_tiny_geometry(self):
        """The acceptance bar, in-process at tiny geometry: measured
        1F1B-family (interleaved) bubble STRICTLY below measured
        GPipe's at the same (S, M), and each measurement within
        tolerance of the extended model."""
        from bigdl_tpu.parallel.pipeline import measure_pipeline_bubble
        out = measure_pipeline_bubble(
            n_stages=2, num_microbatches=4, virtual_stages=2,
            d_model=16, mb_rows=4, layers_per_stage=2, reps=3)
        sch = out["schedules"]
        assert sch["interleaved_1f1b"]["measured_bubble_fraction"] < \
            sch["gpipe"]["measured_bubble_fraction"]
        for name, r in sch.items():
            assert r["measured_bubble_fraction"] == pytest.approx(
                r["modeled_bubble_fraction"], abs=0.1), name

    @pytest.mark.slow
    def test_real_row_subprocess(self):
        """The REAL subprocess row at a reduced geometry: the emitted
        row carries measured + modeled numbers for every schedule and
        the acceptance inequality holds."""
        row = bench.bench_pipeline_bubble(
            n_stages=2, num_microbatches=4, virtual_stages=2, reps=3)
        assert row["metric"] == "pipeline_bubble_fraction"
        assert row["value"] == row["measured_interleaved_1f1b"]
        assert row["measured_interleaved_1f1b"] < row["measured_gpipe"]
        for name in ("gpipe", "1f1b", "interleaved_1f1b"):
            assert row[f"measured_{name}"] == pytest.approx(
                row[f"modeled_{name}"], abs=0.1)


class TestElasticResumeRow:
    """ISSUE 14 satellite: elastic_resume_secs — SIGKILL a checkpointing
    trainer, resume on a resized mesh from the latest manifest, warm AOT
    cache — rides the standard row/known/all contract."""

    FAKE = {"metric": "elastic_resume_secs", "value": 1.75,
            "unit": "s (kill -> first resumed step, warm AOT cache, "
                    "8->4 mesh)",
            "cold_resume_s": 4.2, "warm_resume_s": 1.75,
            "load_s": 0.3, "resumed_neval": 8, "warm_cache_hits": 1,
            "warm_cache_misses": 0, "loss_bit_identical": True}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_elastic_resume_secs",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "elastic_resume_secs",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "elastic_resume_secs"
        assert lines[-1]["rows"][0]["value"] == 1.75
        with open(out) as f:
            assert "bench_elastic_resume_secs 1.75" in f.read()

    def test_row_in_all(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "elastic_resume_secs" in [r["metric"]
                                         for r in agg["rows"]]

    @pytest.mark.slow
    def test_real_probe_kill_and_resume(self, tmp_path):
        """A REAL kill-and-resume: the trainer is SIGKILLed mid-run
        after its first manifest commits, both resume subprocesses land
        on the 4-device mesh from the same snapshot (bit-identical first
        loss), and the warm one loads its executable from the cache."""
        row = bench.bench_elastic_resume_secs(
            train_devices=8, resume_devices=4,
            ckpt_dir=str(tmp_path / "ck"))
        assert row["metric"] == "elastic_resume_secs"
        assert row["value"] > 0
        assert row["resumed_neval"] >= 8
        assert row["warm_cache_hits"] >= 1
        assert row["warm_cache_misses"] == 0
        assert row["loss_bit_identical"] is True


class TestAutoscaleRow:
    """ISSUE 15: autoscale_time_to_capacity — spike -> fleet at target
    size, cold AOT cache vs warm (the Nth spin-up compiles nothing) —
    rides the standard row/known/all contract. Lower is better and the
    gate knows."""

    FAKE = {"metric": "autoscale_time_to_capacity", "value": 0.06,
            "unit": "s (spike -> fleet at target size, warm AOT cache)",
            "cold_time_to_capacity_s": 0.9, "warm_time_to_capacity_s": 0.06,
            "cold_aot_misses": 3, "warm_aot_misses": 0,
            "warm_aot_hits": 3, "warm_zero_misses": True,
            "scale_downs_warm": 2, "conserved": True}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_autoscale_time_to_capacity",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "autoscale_time_to_capacity",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "autoscale_time_to_capacity"
        assert lines[-1]["rows"][0]["value"] == 0.06
        with open(out) as f:
            assert "bench_autoscale_time_to_capacity 0.06" in f.read()

    def test_row_in_all_and_gate_direction(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "autoscale_time_to_capacity" in \
            [r["metric"] for r in agg["rows"]]
        # slower time-to-capacity is the regression
        assert "autoscale_time_to_capacity" in bench._GATE_LOWER_IS_BETTER

    @pytest.mark.slow
    def test_real_probe_warm_spinup_zero_misses(self):
        """The REAL cold/warm drill (tiny geometry): the warm pass must
        replay every spin-up executable from the AOT cache (zero
        misses), beat the cold pass to capacity, and conserve every
        spike request."""
        row = bench.bench_autoscale_time_to_capacity(n_requests=12,
                                                     target_replicas=2)
        assert row["metric"] == "autoscale_time_to_capacity"
        assert row["warm_aot_misses"] == 0
        assert row["warm_aot_hits"] >= 1
        assert row["warm_zero_misses"] is True
        assert row["cold_aot_misses"] >= 1
        assert row["conserved"] is True
        assert 0 < row["value"] <= row["cold_time_to_capacity_s"] * 5


class TestPublishRow:
    """ISSUE 16: publish_to_fleet_secs — committed checkpoint -> 100%
    of the fleet serving it (warm canary, zero compiles, zero
    dropped/duplicated requests) — rides the standard row/known/all
    contract. Lower is better and the gate knows."""

    FAKE = {"metric": "publish_to_fleet_secs", "value": 0.42,
            "unit": "seconds committed checkpoint -> 100% of fleet "
                    "(2 replicas, warm canary)",
            "canary_compiles": 0, "replicas_rolled": 2,
            "rollback_drill_outcome": "canary_failed",
            "rollback_kept_fleet": True, "fleet_version": "v2",
            "n_requests": 12, "conserved": True}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_publish_to_fleet",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "publish_to_fleet_secs",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "publish_to_fleet_secs"
        assert lines[-1]["rows"][0]["value"] == 0.42
        with open(out) as f:
            assert "bench_publish_to_fleet_secs 0.42" in f.read()

    def test_row_in_all_and_gate_direction(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "publish_to_fleet_secs" in \
            [r["metric"] for r in agg["rows"]]
        # a slower commit-to-fleet rollout is the regression
        assert "publish_to_fleet_secs" in bench._GATE_LOWER_IS_BETTER

    @pytest.mark.slow
    def test_real_probe_rolls_and_rolls_back(self):
        """The REAL drill (tiny geometry): the publish must roll both
        replicas with a zero-compile warm canary and conserve every
        request; the parity-failing follow-up commit must leave the
        fleet on the published version."""
        row = bench.bench_publish_to_fleet(n_requests=9)
        assert row["metric"] == "publish_to_fleet_secs"
        assert row["value"] > 0
        assert row["canary_compiles"] == 0
        assert row["replicas_rolled"] == 2
        assert row["conserved"] is True
        assert row["fleet_version"] == "v2"
        assert row["rollback_drill_outcome"] == "canary_failed"
        assert row["rollback_kept_fleet"] is True


class TestPrefixReuseRow:
    """ISSUE 18: prefix_reuse_ttft — shared-system-prompt TTFT with
    longest-prefix KV reuse ON vs exact-only — rides the standard
    row/known/all contract. Lower is better and the gate knows."""

    FAKE = {"metric": "prefix_reuse_ttft", "value": 0.019,
            "unit": "seconds", "ttft_p50_s": 0.019,
            "ttft_p99_s": 0.027, "exact_ttft_p50_s": 0.027,
            "exact_ttft_p99_s": 0.031, "speedup_p50": 1.37,
            "partial_hits": 10, "tokens_reused_fraction": 0.75,
            "first_tokens_match": True, "n_requests": 10}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_prefix_reuse_ttft",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "prefix_reuse_ttft",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "prefix_reuse_ttft"
        assert lines[-1]["rows"][0]["value"] == 0.019
        with open(out) as f:
            assert "bench_prefix_reuse_ttft 0.019" in f.read()

    def test_row_in_all_and_gate_direction(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "prefix_reuse_ttft" in \
            [r["metric"] for r in agg["rows"]]
        # a slower reuse-ON TTFT is the regression
        assert "prefix_reuse_ttft" in bench._GATE_LOWER_IS_BETTER

    @pytest.mark.slow
    def test_real_probe_reuses_and_matches(self):
        """The REAL drill (tiny geometry): every wave request must be
        a partial hit, the reused-token fraction must clear the 0.5
        acceptance bar, and the reuse run's first tokens must equal
        the exact-only run's."""
        row = bench.bench_prefix_reuse_ttft(n_requests=6, max_new=4,
                                            d_model=32, num_layers=2)
        assert row["metric"] == "prefix_reuse_ttft"
        assert row["value"] > 0
        assert row["partial_hits"] > 0
        assert row["tokens_reused_fraction"] >= 0.5
        assert row["first_tokens_match"] is True


class TestRequestTraceRow:
    """ISSUE 19: request_trace_overhead — tracker-ON vs tracker-OFF
    p50 TTFT ratio plus the induced queue-delay attribution drill —
    rides the standard row/known/all contract. Lower is better and
    the gate knows."""

    FAKE = {"metric": "request_trace_overhead", "value": 1.01,
            "unit": "x (tracker-ON p50 TTFT / tracker-OFF)",
            "ttft_p50_on_s": 0.0202, "ttft_p50_off_s": 0.02,
            "ttft_p99_on_s": 0.031, "ttft_p99_off_s": 0.03,
            "within_overhead_budget": True, "timelines": 11,
            "retained": 11, "drill_queue_fraction": 0.91,
            "drill_queue_attributed": True, "drill_delay_s": 0.3,
            "n_requests": 10}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_request_trace_overhead",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "request_trace_overhead",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "request_trace_overhead"
        assert lines[-1]["rows"][0]["value"] == 1.01
        with open(out) as f:
            assert "bench_request_trace_overhead 1.01" in f.read()

    def test_row_in_all_and_gate_direction(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "request_trace_overhead" in \
            [r["metric"] for r in agg["rows"]]
        # timelines making TTFT slower is the regression
        assert "request_trace_overhead" in bench._GATE_LOWER_IS_BETTER

    @pytest.mark.slow
    def test_real_probe_attributes_queue_wait(self):
        """The REAL drill (tiny geometry): with the replica driver
        held for an induced delay, the tracker's tail attribution must
        put >= 80% of the time on queue wait, and tracking every
        timeline must stay within the 5% TTFT overhead budget."""
        row = bench.bench_request_trace_overhead(
            n_requests=6, max_new=4, d_model=32, num_layers=2)
        assert row["metric"] == "request_trace_overhead"
        assert row["value"] > 0
        assert row["drill_queue_fraction"] >= 0.8
        assert row["timelines"] == row["retained"] == 7


class TestInputPipelineNHostRow:
    """ISSUE 20: input_pipeline_nhost — the overlap receipt at mesh
    scale (1/2/4 emulated hosts over one chunked record store) — rides
    the standard row/known/all contract. Wait fraction is lower-is-
    better and the gate knows."""

    FAKE = {"metric": "input_pipeline_nhost_wait_frac", "value": 0.03,
            "unit": "mean input-wait fraction at 4 hosts",
            "wait_frac_by_hosts": {"1": 0.02, "2": 0.03, "4": 0.03},
            "wait_frac_spread": 0.01, "chunks": 24,
            "shard_local_reads_verified": True,
            "resize_resume_bit_identical": True, "iters": 6}

    def test_row_wiring_and_registry_export(self, monkeypatch, capsys,
                                            tmp_path):
        monkeypatch.setattr(bench, "_backend_info",
                            lambda: "cpu|test|1")
        monkeypatch.setattr(bench, "bench_input_pipeline_nhost",
                            lambda **kw: dict(self.FAKE))
        out = str(tmp_path / "metrics.txt")
        bench.main(["--rows", "input_pipeline_nhost",
                    "--metrics-out", out])
        lines = _parse_lines(capsys.readouterr().out)
        assert lines[0]["metric"] == "input_pipeline_nhost_wait_frac"
        assert lines[-1]["rows"][0]["value"] == 0.03
        with open(out) as f:
            assert "bench_input_pipeline_nhost_wait_frac 0.03" in f.read()

    def test_row_in_all_and_gate_direction(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "_backend_info", _no_backend("wedged"))
        with pytest.raises(SystemExit):
            bench.main(["--rows", "all"])
        agg = _parse_lines(capsys.readouterr().out)[-1]
        assert "input_pipeline_nhost" in \
            [r["metric"] for r in agg["rows"]]
        # a host waiting LONGER on input as the fleet grows is the
        # regression
        assert "input_pipeline_nhost_wait_frac" in \
            bench._GATE_LOWER_IS_BETTER
        assert bench._ROW_METRICS["input_pipeline_nhost"] == \
            "input_pipeline_nhost_wait_frac"

    @pytest.mark.slow
    def test_real_nhost_drill_tiny_geometry(self):
        """The REAL drill (tiny geometry, 1/2 hosts): subprocess hosts
        train over disjoint shard-local chunk sets, and the 4->2
        resize sub-drill reconstructs the remaining stream
        bit-identically — both receipts are hard failures inside the
        row, so a returned row IS the proof."""
        row = bench.bench_input_pipeline_nhost(
            host_counts=(1, 2), iters=2, batch=8, chunk_records=8)
        assert row["metric"] == "input_pipeline_nhost_wait_frac"
        assert 0.0 <= row["value"] <= 1.0
        assert set(row["wait_frac_by_hosts"]) == {"1", "2"}
        assert row["shard_local_reads_verified"] is True
        assert row["resize_resume_bit_identical"] is True
        assert row["chunks"] >= 4    # the resize sub-drill needs 4 hosts

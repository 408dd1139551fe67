"""The readers of the program's spans and scopes (ISSUE 25) on
hand-written events with known answers, and on the recorded v5e slices
under tests/benchmark/data/ (which pin how the chip's trace names the
program's annotations and the step program's operations)."""
import gzip
import json
import os

import pytest

from benchmarks import manifest, tracing
from benchmarks.readers import (scope_device_ms, train_loop_spans,
                                unscoped_share)

DATA = os.path.join(os.path.dirname(__file__), "data")
D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS, HOST = "XLA Ops", "XLA Modules", "/host:CPU"
MS = 1e6
NEW = ["train_loop.host_ms_per_step", "train_loop.gap_attributed_share",
       "step.forward_ms", "step.backward_ms", "step.optimizer_update_ms",
       "step.head_loss_ms", "step.unscoped_share", "step.update_fused_ms"]
CELLS = ["opt-1.3b.train.seq2048", "opt-1.3b.train.dp4"]


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def params_of(metric):
    return manifest.data_file("layer_metrics", metric)["params"]


def record(events, steps=2, chips=1):
    rec = {"trace_events": events, "traced_steps": steps, "chips": chips}
    rec["trace_window"] = tracing.reduce_window(events)
    return rec


# ---- the manifest with the new entries ------------------------------------
# test_benchmark_manifest.py::test_manifest_shape and
# ::test_the_candidate_merges_into_a_sound_manifest pin the number of
# per-layer entries at 6 (11 with the candidate) and FAIL since ISSUE 25
# appended its entries: that file may be edited by a `benchmark` PR only
# (ROADMAP C13). Every other assertion of the two is carried here
# verbatim, with the counts as they now are.

SERVE = "opt-6.7b.serve.chat"
CANDIDATE = manifest.with_candidate(manifest.load_manifest(), SERVE)
OLD = ["train_loop.input_wait_share", "step.device_ms", "step.device_mfu",
       "collective.exposed_share", "flash_attention_roofline",
       "device.idle_share.train"]


def test_manifest_shape_with_the_new_metrics():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    assert man["command"] == ["python3", "benchmarks/run.py"]
    assert man["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= man["run_seconds"] <= 51
    assert len(json.dumps(man)) < 64 * 1024
    assert [m["name"] for m in man["end_to_end"]] == [
        "train.records_per_s_per_chip", "setup_s"]
    assert [m["name"] for m in man["per_layer"]] == OLD + NEW
    assert [(w["name"], w["chips"]) for w in man["workloads"]] == [
        ("opt-1.3b.train.seq2048", 1), ("opt-1.3b.train.dp4", 4)]
    for m in man["per_layer"][len(OLD):]:
        assert m["workloads"] == CELLS
        assert m["moves"] == "train.records_per_s_per_chip"


def test_the_candidate_still_merges_into_a_sound_manifest():
    before = manifest.load_manifest()
    man = CANDIDATE
    assert manifest.check_manifest(man) == []
    assert [m["name"] for m in man["end_to_end"]] == [
        "train.records_per_s_per_chip", "setup_s", "serve.ttft_p95_ms",
        "serve.tpot_p95_ms"]
    assert len(man["per_layer"]) == len(OLD) + len(NEW) + 5
    assert len(man["workloads"]) == 3
    loaded = manifest.load_cell(SERVE, man)
    assert loaded["kind"] == "serve" and loaded["chips"] == 1
    assert not {m["name"] for m in loaded["per_layer"]} & set(NEW)
    dp4 = manifest.load_cell("opt-1.3b.train.dp4", man)
    assert dp4["chips"] == 4
    assert {m["name"] for m in dp4["per_layer"]} >= {
        "collective.exposed_share", "step.device_ms"}
    assert manifest.with_candidate(man, SERVE) == man     # idempotent
    assert before == manifest.load_manifest()             # not mutated


@pytest.mark.parametrize("cell", CELLS)
def test_both_train_cells_report_the_new_metrics(cell):
    loaded = manifest.load_cell(cell)
    specs = {s["name"]: s for s in loaded["per_layer"]}
    assert set(NEW) <= set(specs)
    for name in NEW:
        assert callable(manifest.plugin("readers",
                                        specs[name]["reader"]).read)
    layers = {specs[n]["layer"] for n in NEW}
    assert layers == {"training loop", "step program"}


# ---- hand-written events -------------------------------------------------

TABLE = {"program": "jit_train_step", "scopes": {
    "jit(train_step)/jvp(model)/block_0/dot_general": ["fusion.1"],
    "jit(train_step)/jvp(model)/lm_head/dot_general": ["fusion.2"],
    "jit(train_step)/jvp(criterion)/reduce_sum": ["fusion.3"],
    "jit(train_step)/transpose(jvp(criterion))/mul": ["fusion.4"],
    "jit(train_step)/transpose(jvp(model))/lm_head/dot_general":
        ["fusion.5"],
    "jit(train_step)/transpose(jvp(model))/block_0/dot_general":
        ["fusion.6", "all-reduce.1"],
    "jit(train_step)/grad_clip/mul": ["fusion.7"],
    "jit(train_step)/optimizer_update/sub": ["add_subtract_fusion"],
    "jit(train_step)/convert_element_type": ["convert.9"]},
    # fusion.6: a weight-gradient matmul with that weight's update as its
    # epilogue; the all-reduce holds a reduction of the clip's norm
    "inside": {"jit(train_step)/optimizer_update": ["fusion.6"],
               "jit(train_step)/grad_clip": ["fusion.6", "all-reduce.1"],
               "jit(train_step)/transpose(jvp(model))/block_0":
                   ["add_subtract_fusion"]}}


def step_program(t0, plane=D0):
    """One 100 ms run of the step program from ``t0``: forward 10 + 6 +
    4 = 20 ms, backward 2 + 8 + 20 = 30 ms, a 5 ms all-reduce under a
    ``transpose(`` name, clip 1 + update 24 = 25 ms, 3 ms with a scope
    that is none of the program's and 2 ms with no entry at all."""
    def op(name, at, dur):
        return ev(plane, OPS, f"%{name} = f32[8]{{0}} fusion(...)",
                  t0 + at, dur)
    return [
        ev(plane, MODS, "jit_train_step(17)", t0, 100),
        op("fusion.1", 0, 10), op("fusion.2", 10, 6), op("fusion.3", 16, 4),
        op("fusion.4", 20, 2), op("fusion.5", 22, 8), op("fusion.6", 30, 20),
        op("all-reduce.1", 50, 5),
        op("fusion.7", 55, 1), op("add_subtract_fusion", 56, 24),
        op("convert.9", 80, 3), op("copy-done.4", 83, 2)]


def scoped_trace(planes=(D0,)):
    """Window 0..260 ms, two steps at 10 and 130 ms on each plane, and
    another program's run in between whose fusion.1 is NOT the step's."""
    events = [ev(HOST, "python3", "bench:window_start", -1.0, 1.0),
              ev(HOST, "python3", "bench:window_end", 260.0, 0.5),
              ev(HOST, "python3", "bigdl:compile:step_scopes "
                 + json.dumps(TABLE, separators=(",", ":")), 1.0, 0.01)]
    for plane in planes:
        events += step_program(10, plane) + step_program(130, plane)
        events += [ev(plane, MODS, "jit__threefry_split(3)", 115, 5),
                   ev(plane, OPS, "%fusion.1 = u32[2]{0} fusion(...)",
                      115, 5)]
    return events


@pytest.mark.parametrize("metric, want", [
    ("step.forward_ms", 20.0), ("step.backward_ms", 30.0),
    ("step.optimizer_update_ms", 25.0),
    ("step.head_loss_ms", 6.0 + 4.0 + 2.0 + 8.0),
    # rooted in backward, the update inside: fusion.6 alone (the
    # all-reduce is a collective, the update's own fusions are not fused)
    ("step.update_fused_ms", 20.0)])
def test_device_time_by_scope(metric, want):
    got = scope_device_ms.read(record(scoped_trace()), params_of(metric))
    assert got["value"] == pytest.approx(want)
    # the same on two chips: a per-chip average, not a sum
    two = scope_device_ms.read(record(scoped_trace((D0, D1)), chips=2),
                               params_of(metric))
    assert two["value"] == pytest.approx(want)


def test_collective_under_a_backward_name_is_left_out():
    p = params_of("step.backward_ms")
    got = scope_device_ms.read(record(scoped_trace()), p)
    assert got["value"] == pytest.approx(30.0)       # not 35
    assert got["ops_per_step"] == pytest.approx(3.0)


def test_unscoped_share_and_the_sum_of_the_parts():
    rec = record(scoped_trace())
    got = unscoped_share.read(rec, params_of("step.unscoped_share"))
    # 3 + 2 ms of 80 ms of non-collective step operations
    assert got["value"] == pytest.approx(100 * 5 / 80)
    assert got["step_ops_ms"] == pytest.approx(80.0)
    assert got["top_unscoped_ms"] == [["convert", pytest.approx(3.0)],
                                      ["copy-done", pytest.approx(2.0)]]
    parts = sum(scope_device_ms.read(rec, params_of(m))["value"] for m in (
        "step.forward_ms", "step.backward_ms", "step.optimizer_update_ms"))
    assert parts + got["unscoped_ms"] == pytest.approx(got["step_ops_ms"])


def test_operation_clipped_by_the_window():
    events = [e for e in scoped_trace()
              if not e[2].startswith("bench:window_end")]
    events.append(ev(HOST, "python3", "bench:window_end", 200.0, 0.5))
    got = scope_device_ms.read(record(events),
                               params_of("step.optimizer_update_ms"))
    # first step 25 ms; second: clip 185-186 whole, update 186-210 cut
    # at 200 -> 1 + 14
    assert got["value"] == pytest.approx((25 + 15) / 2)


def loop_trace():
    """Device 0 busy 10-100 and 104-200 of the window 0..210: idle 0-10,
    100-104, 200-210 = 24 ms. The loop's thread: iteration A 1..101.5
    (leaves input_wait 1-2, step_lookup 2-4 holding compile_step 2.5-3.5,
    device_step 4-9, loss_drain 9-101), iteration B 101.5..205
    (input_wait 101.5-103, device_step 103-104.5, loss_drain 105-201,
    emit_steps 201-204.5)."""
    def span(name, a, b, line="python3"):
        return ev(HOST, line, f"bigdl:host:{name}", a, b - a)
    return [
        ev(HOST, "python3", "bench:window_start", -1.0, 1.0),
        ev(HOST, "python3", "bench:window_end", 210.0, 0.5),
        ev(D0, OPS, "%fusion.1 = ...", 10, 90),
        ev(D0, OPS, "%fusion.2 = ...", 104, 96),
        ev(D1, OPS, "%fusion.1 = ...", 0, 210),      # not the first device
        span("train_iteration", 1, 101.5), span("input_wait", 1, 2),
        span("step_lookup", 2, 4), span("compile_step", 2.5, 3.5),
        span("device_step", 4, 9), span("loss_drain", 9, 101),
        span("train_iteration", 101.5, 205), span("input_wait", 101.5, 103),
        span("device_step", 103, 104.5), span("loss_drain", 105, 201),
        span("emit_steps", 201, 204.5),
        ev(HOST, "python3", "bench:summary:Loss", 201.5, 1),
        # a worker thread's span over the whole first gap: another line
        span("input_produce", 0, 10, line="prefetch:train"),
        # and one on a line of the same name, as the chip records it
        span("input_produce", 204.5, 210),
    ]


def test_host_ms_per_step_is_the_iterations_less_their_waits():
    got = train_loop_spans.read(
        record(loop_trace(), chips=2),
        params_of("train_loop.host_ms_per_step"))
    # A: 100.5 - (1 + 92) = 7.5; B: 103.5 - (1.5 + 96) = 6.0
    assert got["iterations"] == 2
    assert got["value"] == pytest.approx(6.75)
    assert got["waits_ms"] == pytest.approx(95.25)


def test_an_iteration_cut_by_the_window_is_left_out():
    events = [e for e in loop_trace()
              if not e[2].startswith("bench:window_end")]
    events.append(ev(HOST, "python3", "bench:window_end", 204.0, 0.5))
    got = train_loop_spans.read(
        record(events), params_of("train_loop.host_ms_per_step"))
    assert got["iterations"] == 1
    assert got["value"] == pytest.approx(7.5)


def test_gaps_are_attributed_to_leaf_spans_of_the_loop_thread_only():
    got = train_loop_spans.read(
        record(loop_trace(), chips=2),
        params_of("train_loop.gap_attributed_share"))
    assert got["idle_s"] == pytest.approx(0.024)
    by = got["idle_s_by_span"]
    # gap 0-10: input_wait 1-2, compile_step 2.5-3.5, device_step 4-9,
    # loss_drain 9-10; step_lookup's own 2-2.5 and 3.5-4 is a parent's
    # self time, 0-1 lies before the iteration; the worker's span covers
    # the whole gap and attributes nothing. Gap 100-104: loss_drain to
    # 101, input_wait 101.5-103, device_step 103-104. Gap 200-210:
    # loss_drain to 201, emit_steps to 204.5, then nothing of the loop's
    assert "bigdl:host:input_produce" not in by
    assert "bigdl:host:step_lookup" not in by
    assert "bigdl:host:train_iteration" not in by
    assert by["bigdl:host:compile_step"] == pytest.approx(0.001)
    assert by["bigdl:host:device_step"] == pytest.approx(0.006)
    assert by["bigdl:host:input_wait"] == pytest.approx(0.0025)
    assert by["bigdl:host:loss_drain"] == pytest.approx(0.003)
    assert by["bigdl:host:emit_steps"] == pytest.approx(0.0035)
    assert sum(by.values()) == pytest.approx(0.016)
    assert got["value"] == pytest.approx(100 * 16 / 24)
    gaps = got["longest_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.010, 0.010, 0.004])
    assert {g[0] for g in gaps[:2]} == {"bigdl:host:device_step",
                                        "bigdl:host:emit_steps"}
    assert gaps[2][0] == "bigdl:host:input_wait"


def test_a_gap_under_a_parent_alone_is_unattributed():
    events = [
        ev(HOST, "python3", "bench:window_start", -1.0, 1.0),
        ev(HOST, "python3", "bench:window_end", 100.0, 0.5),
        ev(D0, OPS, "%fusion.1 = ...", 0, 40),
        ev(D0, OPS, "%fusion.2 = ...", 60, 40),
        ev(HOST, "python3", "bigdl:host:train_iteration", 1, 98),
        ev(HOST, "python3", "bigdl:host:device_step", 2, 10),
        ev(HOST, "python3", "bigdl:host:loss_drain", 70, 20)]
    got = train_loop_spans.read(
        record(events), params_of("train_loop.gap_attributed_share"))
    assert got["value"] == pytest.approx(0.0)
    assert got["longest_gaps"] == [["unattributed", pytest.approx(0.020),
                                    0.0]]


@pytest.mark.parametrize("metric", NEW)
def test_nothing_to_read_without_the_programs_spans(metric):
    """The parent commit writes neither spans nor scope tables: every
    new reader returns None there and the line leaves the metric out."""
    events = [e for e in scoped_trace() + loop_trace()
              if "bigdl:" not in e[2]]
    spec = manifest.data_file("layer_metrics", metric)
    reader = manifest.plugin("readers", spec["reader"])
    assert reader.read(record(events), spec["params"]) is None
    # nor without a traced window, nor with no trace at all
    assert reader.read({"trace_events": None, "trace_window": None,
                        "traced_steps": 0, "chips": 1},
                       spec["params"]) is None


# ---- the recorded v5e slices ----------------------------------------------

def _recorded(stem):
    path = os.path.join(DATA, stem + ".jsonl.gz")
    if not os.path.isfile(path):
        pytest.skip(f"no recorded trace {stem}")
    with gzip.open(path, "rt") as f:
        events = [tuple(json.loads(line)) for line in f]
    with open(os.path.join(DATA, stem + ".known.json")) as f:
        return events, json.load(f)


@pytest.mark.parametrize("stem", ["v5e_train_steps", "v5e_dp4_steps"])
def test_recorded_trace_gives_known_program_figures(stem):
    """A slice of this PR's traced chip runs, host plane with the
    program's spans included, and what the new readers gave on it when
    it was cut (tools/trace_summary.py, then the readers)."""
    events, known = _recorded(stem)
    spans = known["program_spans"]
    rec = record(events, steps=spans["traced_steps"],
                 chips=len(known["planes"]))
    assert rec["trace_window"]["marked"]
    assert {e[2].split(" ", 1)[0] for e in events if e[0] == HOST} >= {
        "bigdl:host:train_iteration", "bigdl:host:loss_drain",
        "bigdl:host:device_step", "bigdl:host:input_wait",
        "bigdl:host:step_lookup", "bigdl:compile:step_scopes"}
    assert list(scope_device_ms.scope_tables(events)) == ["jit_train_step"]
    for metric, want in spans["metrics"].items():
        spec = manifest.data_file("layer_metrics", metric)
        got = manifest.plugin("readers", spec["reader"]).read(
            rec, spec["params"])
        assert got["value"] == pytest.approx(want, rel=1e-9), metric

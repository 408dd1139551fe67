"""The trace reduction on hand-written events with known answers, and on
the small recorded v5e traces under tests/benchmark/data/ (which also pin
the plane, line and operation names the chip really emits)."""
import gzip
import json
import os

import pytest

from benchmarks import trace_reduce as tr
from benchmarks import tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, HOST = "XLA Ops", "/host:CPU"
MS = 1e6


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def hand_written():
    """Window 0..100 ms. Chip 0 busy 10-40, 50-70 (=50 ms); chip 1 busy
    10-30 (=20 ms). Average busy 35 ms -> idle share 65%."""
    return [
        ev(HOST, "python3", tr.MARK_START, -1.0, 1.0),
        ev(HOST, "python3", tr.MARK_END, 100.0, 0.5),
        ev(D0, OPS, "fusion.1", 10, 10),
        ev(D0, OPS, "custom-call.7 jit(step)/flash_attention_fwd", 20, 10),
        ev(D0, OPS, "custom-call.9 jit(step)/flash_attention_dq", 30, 10),
        ev(D0, OPS, "fusion.2", 50, 20),
        ev(D0, "XLA Modules", "jit_step(1)", 10, 60),   # not an op
        ev(D1, OPS, "fusion.1", 10, 20),
        ev(HOST, "python3", "bench:summary:Loss", 41, 8),
        ev(HOST, "python3", "$unrelated", 0, 100),
    ]


def test_busy_idle_and_window_from_markers():
    events = hand_written()
    assert tr.device_planes(events) == [D0, D1]
    assert tr.marked_window(events) == (0.0, 100 * MS)
    got = tr.busy(events, tr.marked_window(events))
    assert got["per_chip"] == pytest.approx([0.050, 0.020])
    assert got["busy_s"] == pytest.approx(0.035)
    assert got["window_s"] == pytest.approx(0.100)
    assert got["idle_share"] == pytest.approx(0.65)
    red = tracing.reduce_window(events)
    assert red["marked"] and red["busy_s"] == pytest.approx(0.035)


def test_window_clips_operations_that_straddle_it():
    events = [ev(D0, OPS, "fusion.1", -5, 10), ev(D0, OPS, "f.2", 95, 10)]
    got = tr.busy(events, (0.0, 100 * MS))
    assert got["busy_s"] == pytest.approx(0.010)
    # without markers the window is the extent of the device operations
    red = tracing.reduce_window(events)
    assert not red["marked"]
    assert red["window_s"] == pytest.approx(0.110)


def test_overlapping_operations_count_once():
    events = [ev(D0, OPS, "a", 0, 10), ev(D0, OPS, "b", 5, 10),
              ev(D0, OPS, "c", 5, 2)]
    assert tr.busy(events, (0.0, 20 * MS))["busy_s"] == pytest.approx(0.015)


def test_kernel_time_by_stable_name():
    events = hand_written()
    got = tr.kernel_seconds(events, "flash_attention_(fwd|dq|dkdv)",
                            (0.0, 100 * MS))
    # 20 ms on chip 0, none on chip 1, averaged over both chips
    assert got["seconds"] == pytest.approx(0.010)
    assert got["calls"] == pytest.approx(1.0)


def test_kernel_time_within_whole_runs_of_a_program():
    events = [
        ev(D0, "XLA Modules", "jit__paged_decode_impl(5)", 10, 20),
        ev(D0, "XLA Modules", "jit__paged_prefill_impl(6)", 40, 20),
        ev(D0, "XLA Modules", "jit__paged_decode_impl(5)", 90, 20),
        ev(D0, OPS, "custom-call.1 x/paged_attention", 12, 4),
        ev(D0, OPS, "custom-call.1 x/paged_attention", 20, 4),
        ev(D0, OPS, "custom-call.2 y/paged_attention", 45, 9),
        ev(D0, OPS, "custom-call.1 x/paged_attention", 95, 4),
    ]
    got = tr.kernel_seconds_within(events, "paged_attention", "decode",
                                   (0.0, 100 * MS))
    # the prefill's call and the run that straddles the window's end
    # are left out
    assert got == {"seconds": pytest.approx(0.008), "calls": 2.0,
                   "runs": 1.0}


def test_exposed_and_hidden_collective_time():
    """Chip 0: all-reduce 10-30 with a fusion at 15-20 hiding 5 ms of it
    (exposed 15, hidden 5); an async pair start 40-41 / done 58-60 with a
    fusion 42-50 in between: in flight 40-60 = 20 ms, 8 hidden, 12
    exposed. Totals: exposed 27 ms, hidden 13 ms."""
    events = [
        ev(D0, OPS, "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)", 10,
           20),
        ev(D0, OPS, "fusion.1", 15, 5),
        ev(D0, OPS, "all-gather-start.2", 40, 1),
        ev(D0, OPS, "fusion.2", 42, 8),
        ev(D0, OPS, "all-gather-done.2", 58, 2),
        ev(D0, OPS, "fusion.3", 70, 10),
    ]
    got = tr.collective_split(events, (0.0, 100 * MS))
    assert got["exposed_s"] == pytest.approx(0.027)
    assert got["hidden_s"] == pytest.approx(0.013)


def test_breakdown_top_ops_and_labelled_gaps():
    events = hand_written()
    window = (0.0, 100 * MS)
    top = tr.top_ops(events, window, 3)
    assert top[0] == ["fusion", pytest.approx(0.025)]   # (10+20+20)/2
    assert len(top) == 3
    gaps = tr.idle_gaps(events, window, 3)
    # chip 0's gaps: 70-100 (30 ms), 0-10, 40-50 (10 ms, covered 41-49
    # by the benchmark's summary annotation)
    assert gaps[0] == ["unattributed", pytest.approx(0.030)]
    assert ["bench:summary:Loss", pytest.approx(0.010)] in gaps
    assert ["unattributed", pytest.approx(0.010)] in gaps


def _recorded(name):
    path = os.path.join(DATA, name)
    if not os.path.isfile(path):
        pytest.skip(f"no recorded trace {name}")
    with gzip.open(path, "rt") as f:
        return [tuple(json.loads(line)) for line in f]


def _known(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.mark.parametrize("stem", ["v5e_train_steps", "v5e_serve_slice",
                                  "v5e_dp4_steps"])
def test_recorded_trace_gives_known_figures(stem):
    """A slice of a real trace, cut on the chip (tools/trace_summary.py),
    with the figures the reduction gave when it was recorded."""
    events = _recorded(stem + ".jsonl.gz")
    known = _known(stem + ".known.json")
    planes = tr.device_planes(events)
    assert planes == known["planes"]
    assert {e[1] for e in events if e[0] == planes[0]} >= {tr.OPS_LINE}
    window = tuple(known["window_ns"])
    got = tr.busy(events, window)
    assert got["busy_s"] == pytest.approx(known["busy_s"], rel=1e-9)
    assert got["idle_share"] == pytest.approx(known["idle_share"],
                                              rel=1e-9)
    for pattern, want in known.get("kernels", {}).items():
        k = tr.kernel_seconds(events, pattern, window)
        assert k["calls"] == want["calls"]
        assert k["seconds"] == pytest.approx(want["seconds"], rel=1e-9)
    if "collective" in known:
        c = tr.collective_split(events, window)
        assert c["exposed_s"] == pytest.approx(
            known["collective"]["exposed_s"], rel=1e-9)
        assert c["hidden_s"] == pytest.approx(
            known["collective"]["hidden_s"], rel=1e-9)

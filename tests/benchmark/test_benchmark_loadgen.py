"""The load generator is a pure function of the seed, gives every seed
the same work in another order, and its lateness is reported; percentile
and ``failed`` arithmetic with a request that never finishes."""
import math

import numpy as np
import pytest

from benchmarks import loadgen, manifest, result, stats
from benchmarks.kinds import serve
from benchmarks.readers import (loadgen_lateness_p95, queue_wait_p95,
                                slot_occupancy)

# the mix's shape at a rate of the tests' own (the file's is provisional)
CHAT = dict(manifest.data_file("traffic", "chat-steady"), rate_rps=5.0)
BIG = 3_000_000_001            # the driver's seeds pass 2**31


def test_schedule_is_a_pure_function_of_the_seed():
    a = loadgen.open_loop_schedule(CHAT, 50272, BIG, 30.0)
    b = loadgen.open_loop_schedule(CHAT, 50272, BIG, 30.0)
    c = loadgen.open_loop_schedule(CHAT, 50272, BIG + 1, 30.0)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    assert len(a) == int(CHAT["rate_rps"] * 30)
    assert all(0 <= r["due_s"] < 30.0 for r in a)
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 32 and max(lens) <= 1024
    assert 200 <= float(np.median(lens)) <= 320
    assert all(1 <= t <= 50272 for r in a for t in r["prompt"])


def test_every_seed_gets_the_same_work_in_another_order():
    a = loadgen.open_loop_schedule(CHAT, 50272, 1, 30.0)
    b = loadgen.open_loop_schedule(CHAT, 50272, BIG, 30.0)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    gaps = lambda s: sorted(np.round(np.diff([r["due_s"] for r in s]), 9))
    ga, gb = gaps(a), gaps(b)
    # all gaps but the (different) first one are the same multiset
    assert len(set(ga) ^ set(gb)) <= 4


def test_prompt_buckets_follow_the_programs_rule():
    def bucket(n):
        b = 8
        while b < n:
            b *= 2
        return b
    assert loadgen.prompt_buckets(CHAT, bucket) == [32, 64, 128, 256,
                                                    512, 1024]


def test_train_batches_are_seeded_next_token_pairs():
    a = next(loadgen.train_batches(50272, 4, 64, BIG))
    b = next(loadgen.train_batches(50272, 4, 64, BIG))
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert a[0].shape == (4, 64) and a[0].dtype == np.int32
    assert (a[0][:, 1:] == a[1][:, :-1]).all()
    assert a[0].min() >= 1 and a[0].max() <= 50272


def test_percentiles_rank_failures_slowest():
    assert stats.percentile([], 95) is None
    assert stats.percentile(range(1, 101), 95) == 95
    assert stats.percentile([5.0], 95) == 5.0
    vals = [0.1] * 99 + [None]
    p, finite = stats.latency_percentile(vals, 95)
    assert (p, finite) == (0.1, True)
    vals = [0.1] * 90 + [None] * 10
    p, finite = stats.latency_percentile(vals, 95)
    assert p == math.inf and not finite
    assert stats.iqr_share([1.0, 1.0, 1.0, 1.0, 1.0, 1.0]) == 0.0


def _events_for(schedule, t0, *, never=()):
    """The program's events for a perfectly regular server."""
    ev = []
    for r in schedule:
        rid, due = r["id"], t0 + r["due_s"]
        ev.append((rid, "submit", due + 0.001, {}))
        ev.append((rid, "prefill_start", due + 0.010, {}))
        ev.append((rid, "first_token", due + 0.050, {}))
        if rid in never:
            continue
        for k in range(2):
            ev.append((rid, "decode", due + 0.1 + k * 0.1,
                       {"tokens": 8, "dur_s": 0.1 + rid * 1e-6 + k}))
        ev.append((rid, "retire", due + 0.050 + 0.127, {"tokens": 128}))
    return ev


def test_request_rows_and_a_request_that_never_finishes():
    sched = loadgen.open_loop_schedule(CHAT, 50272, 7, 10.0)
    t0 = 1000.0
    stuck = sched[3]["id"]
    ev = _events_for(sched, t0, never={stuck})
    outputs = {r["id"]: [1] * 128 for r in sched if r["id"] != stuck}
    sent = {r["id"]: t0 + r["due_s"] + 0.002 for r in sched}
    rows = serve._requests(sched, t0, sent, {}, ev, outputs, 128)
    assert len(rows) == len(sched)
    bad = [r for r in rows if not r["ok"]]
    assert [r["id"] for r in bad] == [stuck]
    assert bad[0]["ttft_s"] is None and bad[0]["tpot_s"] is None
    good = [r for r in rows if r["ok"]]
    assert good[0]["ttft_s"] == pytest.approx(0.050)
    assert good[0]["tpot_s"] == pytest.approx(0.001)
    assert good[0]["queue_wait_s"] == pytest.approx(0.010)
    rec = {"requests": rows}
    # 1 failure in 50: the 95th percentile is still a finished request
    assert result.END_TO_END["serve.ttft_p95_ms"](rec) == \
        pytest.approx(50.0)
    assert loadgen_lateness_p95.read(rec, {}) == pytest.approx(2.0)
    assert queue_wait_p95.read(rec, {}) == pytest.approx(10.0)
    # 4 failures in 50 reach the 95th percentile: no finite tail
    for r in rows[:4]:
        r.update(ok=False, ttft_s=None, tpot_s=None)
    assert result.END_TO_END["serve.ttft_p95_ms"](rec) == math.inf


def test_bursts_group_decode_events_and_know_the_live_context():
    by_id = {0: {"prompt_len": 100}, 1: {"prompt_len": 40}}
    ev = [(0, "decode", 10.0, {"tokens": 8, "dur_s": 0.15}),
          (1, "decode", 10.0001, {"tokens": 8, "dur_s": 0.15}),
          (0, "decode", 10.2, {"tokens": 8, "dur_s": 0.151})]
    bursts = serve._bursts(ev, by_id, 9.0, 10.1)
    assert [b["contexts"] for b in bursts] == [[100, 40], [108]]
    assert [b["in_window"] for b in bursts] == [True, False]
    assert bursts[0]["t0"] == pytest.approx(9.85)
    occ = slot_occupancy.read({"bursts": bursts, "max_batch": 4}, {})
    assert occ == pytest.approx(50.0)

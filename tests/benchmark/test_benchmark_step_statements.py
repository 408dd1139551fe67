"""The readers of what the step program states of itself (ISSUE 35) on
hand-written events with known answers: the compiled step's memory
(``"memory"`` of the ``bigdl:compile:step_scopes`` table), recomputation
(``jax.checkpoint``'s mark and the program's own ``recompute`` scope,
read by ``scope_device_ms`` through two data files) and the experts'
routing (``bigdl:optim:expert_state`` at the traced window's loss
drains); and the manifest with the five entries in it."""
import json

import pytest

from benchmarks import manifest, tracing
from benchmarks.readers import expert_state, scope_device_ms, step_memory

D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS, HOST = "XLA Ops", "XLA Modules", "/host:CPU"
MS = 1e6
LONG = ["evabyte-6.5b.train.long", "keye-vl-2.0-30b-a3b.train.seq16384",
        "kimi-vl-a3b-instruct.train.seq8192"]
TRAIN = ["opt-1.3b.train.seq2048", "opt-1.3b.train.dp4"] + LONG
NEW = {"step.peak_hbm_gb": ("GB", "program_counter", TRAIN),
       "step.recompute_ms": ("ms", "device_trace", LONG),
       "step.recompute_fused_ms": ("ms", "device_trace", LONG),
       "moe.product_row_share": ("%", "program_counter", LONG[1:]),
       "moe.held_load_max_over_mean": ("ratio", "program_counter",
                                       LONG[1:])}


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def params_of(metric):
    return manifest.data_file("layer_metrics", metric)["params"]


def record(events, steps=2, chips=1, peaks=None):
    rec = {"trace_events": events, "traced_steps": steps, "chips": chips,
           "peaks": peaks}
    rec["trace_window"] = tracing.reduce_window(events)
    return rec


def stated(label, payload, at=1.0):
    return ev(HOST, "python3", f"{label} "
              + json.dumps(payload, separators=(",", ":")), at, 0.01)


WINDOW = [ev(HOST, "python3", "bench:window_start", -1.0, 1.0),
          ev(HOST, "python3", "bench:window_end", 260.0, 0.5)]


# ---- the manifest with the five entries -----------------------------------

def test_the_manifest_is_sound_with_the_five_entries_at_its_end():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    assert [m["name"] for m in man["per_layer"][-5:]] == list(NEW)
    for m in man["per_layer"][-5:]:
        unit, source, cells = NEW[m["name"]]
        assert (m["unit"], m["source"], m["workloads"]) == (unit, source,
                                                            cells)
        assert m["layer"] == "step program"
        assert m["moves"] == "train.records_per_s_per_chip"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert len(json.dumps(man)) < 64 * 1024


@pytest.mark.parametrize("metric", list(NEW))
def test_each_cell_listed_loads_the_metric_with_its_reader(metric):
    for cell in NEW[metric][2]:
        specs = {s["name"]: s for s in manifest.load_cell(cell)["per_layer"]}
        assert callable(manifest.plugin("readers",
                                        specs[metric]["reader"]).read)
    others = set(TRAIN) - set(NEW[metric][2])
    for cell in others:
        assert metric not in {s["name"] for s in
                              manifest.load_cell(cell)["per_layer"]}


def test_the_recomputation_metrics_are_data_files_only():
    """One pattern, read by the reader that exists: ``include`` of the
    one is ``exclude`` and ``inside`` of the other, as
    ``step.update_fused_ms`` stands to ``step.optimizer_update_ms``."""
    rooted = manifest.data_file("layer_metrics", "step.recompute_ms")
    fused = manifest.data_file("layer_metrics", "step.recompute_fused_ms")
    assert rooted["reader"] == fused["reader"] == "scope_device_ms"
    mark = rooted["params"]["include"]
    assert rooted["params"] == {"include": mark}
    assert fused["params"] == {"include": "/", "exclude": mark,
                               "inside": mark}


# ---- the compiled step's memory -------------------------------------------

MEMORY = {"arg_bytes": 8.03e9, "output_bytes": 8.03e9, "alias_bytes": 8.03e9,
          "temp_bytes": 3.99e9, "code_bytes": 0.4e9,
          "peak_hbm_bytes": 12.02e9}


def memory_trace(memory="absent", planes=(D0,)):
    """The step program runs 10-110 and 130-230 of the window 0..260;
    another program with a table and memory of its own runs 5 ms."""
    step = {"program": "jit_train_step", "scopes": {}, "inside": {}}
    if memory != "absent":
        step["memory"] = memory
    other = {"program": "jit_eval", "scopes": {}, "inside": {},
             "memory": dict(MEMORY, peak_hbm_bytes=1e9)}
    events = WINDOW + [stated("bigdl:compile:step_scopes", step),
                       stated("bigdl:compile:step_scopes", other, 1.1)]
    for plane in planes:
        events += [ev(plane, MODS, "jit_train_step(17)", 10, 100),
                   ev(plane, MODS, "jit_eval(3)", 115, 5),
                   ev(plane, MODS, "jit_train_step(17)", 130, 100),
                   ev(plane, MODS, "jit__threefry_split(3)", 240, 5),
                   ev(plane, OPS, "%fusion.1 = f32[8]{0} fusion(...)",
                      10, 100)]
    return events


def test_peak_hbm_is_the_windows_program_and_the_note_its_parts():
    got = step_memory.read(record(memory_trace(MEMORY),
                                  peaks={"hbm_bytes": 16e9}), {})
    assert got["value"] == pytest.approx(12.02)
    assert got["program"] == "jit_train_step"       # 200 ms, not the 5
    assert got["arg_gb"] == pytest.approx(8.03)
    assert got["temp_gb"] == pytest.approx(3.99)
    assert got["alias_gb"] == pytest.approx(8.03)
    assert got["room_gb"] == pytest.approx(16.0 - 12.02)
    # one device's figure: the same on two chips, and without a peaks
    # table (a rehearsal) there is no room to state
    two = step_memory.read(record(memory_trace(MEMORY, (D0, D1)), chips=2),
                           {})
    assert two["value"] == pytest.approx(12.02) and "room_gb" not in two


@pytest.mark.parametrize("memory", ["absent", None,
                                    {"arg_bytes": 8.03e9}],
                         ids=["a-parents-table", "null", "no-peak"])
def test_a_table_without_memory_reads_nothing_never_zero(memory):
    assert step_memory.read(record(memory_trace(memory)), {}) is None


def test_nothing_to_read_without_a_trace_or_a_table():
    assert step_memory.read({"trace_events": None}, {}) is None
    bare = [e for e in memory_trace(MEMORY)
            if not e[2].startswith("bigdl:")]
    assert step_memory.read(record(bare), {}) is None
    # the older reader takes a table with ``memory`` as it took one without
    assert scope_device_ms.scope_tables(memory_trace(MEMORY)) == {
        "jit_train_step": {}, "jit_eval": {}}


# ---- recomputation --------------------------------------------------------

BWD = "jit(train_step)/transpose(jvp(model))/block_0/jvp(model)/block_0/" \
      "checkpoint"
REMAT = BWD + "/rematted_computation/1__Residual"
EXPERTS = BWD + "/1__Residual/moe_experts"
TABLE = {"program": "jit_train_step", "scopes": {
    "jit(train_step)/jvp(model)/block_0/1__Residual/moe_experts/"
    "ragged_dot_general": ["fusion.1"],
    # jax.checkpoint's: rooted in recomputation
    REMAT + "/moe_shared/dot_general": ["fusion.2"],
    # by hand: the chunk's forward, and the masked index scores
    EXPERTS + "/recompute/jvp()/ragged_dot_general": ["gmm.3"],
    BWD + "/0__Residual/indexer/recompute/sparse_index_scores/pallas_call":
        ["sparse_index_scores.4"],
    # the chunk's backward: a product, and a custom_vjp's rule named
    # after where its forward was called
    EXPERTS + "/pullback/transpose(jvp())/ragged_dot_general": ["gmm.5"],
    EXPERTS + "/pullback/transpose(1__Residual)/moe_experts/recompute/"
    "jvp()/mul": ["fusion.6"],
    # rooted in backward, a recomputed matmul inside
    BWD + "/1__Residual/moe_shared/mul": ["fusion.7"],
    # rooted in backward, free of the mark
    BWD + "/0__Residual/dot_general": ["fusion.8", "all-reduce.1"],
    "jit(train_step)/optimizer_update/sub": ["fusion.9"]},
    "inside": {REMAT + "/moe_shared": ["fusion.7", "all-reduce.1"],
               EXPERTS + "/recompute/jvp()": ["gmm.5"],
               EXPERTS + "/pullback/transpose(1__Residual)/moe_experts/"
               "recompute/jvp()": ["fusion.8"],
               "jit(train_step)/optimizer_update": ["fusion.8"]}}


def recompute_trace(planes=(D0,)):
    """Two 100 ms steps: forward 10; rooted in recomputation 6 + 7 + 5;
    backward products 8 + 2, of which gmm.5 (8) holds a recomputed
    instruction; fusion.7 (9) holds jax.checkpoint's; fusion.8 (20)
    holds only a custom_vjp rule's name and the update; a collective
    (4) that holds the mark; the update 11."""
    events = WINDOW + [stated("bigdl:compile:step_scopes", TABLE)]

    def op(plane, name, at, dur):
        return ev(plane, OPS, f"%{name} = f32[8]{{0}} fusion(...)", at, dur)
    for plane in planes:
        for t0 in (10, 130):
            events.append(ev(plane, MODS, "jit_train_step(17)", t0, 100))
            at = t0
            for name, dur in (("fusion.1", 10), ("fusion.2", 6),
                              ("gmm.3", 7), ("sparse_index_scores.4", 5),
                              ("gmm.5", 8), ("fusion.6", 2), ("fusion.7", 9),
                              ("fusion.8", 20), ("all-reduce.1", 4),
                              ("fusion.9", 11)):
                events.append(op(plane, name, at, dur))
                at += dur
    return events


@pytest.mark.parametrize("planes", [(D0,), (D0, D1)], ids=["1chip", "2chips"])
@pytest.mark.parametrize("metric, want, ops", [
    # rooted in recomputed work: checkpoint's, the chunk's forward, the
    # index scores; not the rule named ``.../pullback/.../recompute/...``
    ("step.recompute_ms", 6.0 + 7.0 + 5.0, 3),
    # rooted elsewhere, holding recomputed instructions: gmm.5 and
    # fusion.7; not fusion.8 (what it holds lies behind ``pullback``),
    # not the collective
    ("step.recompute_fused_ms", 8.0 + 9.0, 2),
    # the older cuts read what they read: everything under
    # ``transpose(jvp(``, the experts under ``moe_experts``
    ("step.backward_ms", 6.0 + 7 + 5 + 8 + 2 + 9 + 20, 7),
    ("step.moe_routed_ms", 10.0 + 7 + 8 + 2, 4)])
def test_recomputation_by_the_one_pattern(metric, want, ops, planes):
    rec = record(recompute_trace(planes), chips=len(planes))
    got = scope_device_ms.read(rec, params_of(metric))
    assert got["value"] == pytest.approx(want)
    assert got["ops_per_step"] == pytest.approx(ops)


def test_the_two_cuts_lie_inside_backward_and_do_not_overlap():
    rec = record(recompute_trace())
    rooted, fused, backward = (
        scope_device_ms.read(rec, params_of(m))["value"]
        for m in ("step.recompute_ms", "step.recompute_fused_ms",
                  "step.backward_ms"))
    assert rooted + fused <= backward
    assert rooted + fused == pytest.approx(35.0)


def test_a_program_that_recomputes_nothing_reads_zero_not_nothing():
    """Where the table is there and no operation carries the mark (the
    OPT cells; they are not listed) the time is 0; without a table
    there is nothing to read."""
    table = {"program": "jit_train_step", "inside": {}, "scopes": {
        k: v for k, v in TABLE["scopes"].items()
        if "recompute" not in k and "rematted" not in k}}
    events = [e for e in recompute_trace()
              if not e[2].startswith("bigdl:compile")]
    assert scope_device_ms.read(record(events),
                                params_of("step.recompute_ms")) is None
    events.append(stated("bigdl:compile:step_scopes", table))
    for metric in ("step.recompute_ms", "step.recompute_fused_ms"):
        assert scope_device_ms.read(record(events),
                                    params_of(metric))["value"] == 0.0


# ---- the experts' routing -------------------------------------------------

def share_stats(row_share, load_max, load_mean, **more):
    return dict({"moe_product_row_share": row_share, "moe_chunks_run": 1.0,
                 "moe_held_load_max": load_max,
                 "moe_held_load_mean": load_mean,
                 "moe_local_assignment_share": row_share / 2,
                 "moe_tokens_without_local": 0.5}, **more)


def routing_trace(samples):
    return WINDOW + [
        ev(D0, OPS, "%fusion.1 = f32[8]{0} fusion(...)", 10, 200)] + [
        stated("bigdl:optim:expert_state", {"step": step, "layers": layers},
               at) for step, at, layers in samples]


BALANCED = routing_trace([
    (14, 120.0, {"1/1": share_stats(0.25, 800.0, 768.0,
                                    moe_load_max_over_mean=1.1,
                                    moe_bias_abs_max=0.01),
                 "2/1": share_stats(0.24, 920.0, 736.0,
                                    moe_load_max_over_mean=1.3,
                                    moe_bias_abs_max=0.03)}),
    (12, 60.0, {"1/1": share_stats(0.26, 768.0, 768.0,
                                   moe_load_max_over_mean=1.2,
                                   moe_bias_abs_max=0.02),
                "2/1": share_stats(0.25, 880.0, 800.0,
                                   moe_load_max_over_mean=1.2,
                                   moe_bias_abs_max=0.02)})])


def test_product_row_share_is_the_mean_over_layers_and_samples():
    got = expert_state.read(record(BALANCED),
                            params_of("moe.product_row_share"))
    assert got["value"] == pytest.approx(25.0)
    assert (got["samples"], got["steps"], got["layers"]) == (2, [12, 14], 2)
    assert got["chunks_run"] == pytest.approx(1.0)
    assert got["local_assignment_share"] == pytest.approx(0.125)


def test_held_load_ratio_is_each_layers_max_over_its_mean():
    got = expert_state.read(record(BALANCED),
                            params_of("moe.held_load_max_over_mean"))
    assert got["value"] == pytest.approx(
        (800 / 768 + 920 / 736 + 1.0 + 880 / 800) / 4)
    assert got["without_load"] == 0
    assert got["load_max_over_mean"] == pytest.approx(1.2)
    assert got["bias_abs_max"] == pytest.approx(0.02)


def test_a_collapsed_router_reads_far_from_one_and_skips_empty_layers():
    """keye's: one held expert of 16 takes every token (the ratio is
    16), or no held expert gets a row (no ratio: counted, left out);
    no selection bias, so the note's two figures are ``None``."""
    events = routing_trace([
        (8, 60.0, {"1/1": share_stats(0.25, 16384.0, 1024.0),
                   "2/1": share_stats(0.0, 0.0, 0.0)}),
        (10, 120.0, {"1/1": share_stats(0.5, 16384.0, 2048.0),
                     "2/1": share_stats(0.25, 16384.0, 1024.0)})])
    ratio = expert_state.read(record(events),
                              params_of("moe.held_load_max_over_mean"))
    assert ratio["value"] == pytest.approx((16.0 + 8.0 + 16.0) / 3)
    assert ratio["without_load"] == 1
    assert ratio["load_max_over_mean"] is None
    share = expert_state.read(record(events),
                              params_of("moe.product_row_share"))
    assert share["value"] == pytest.approx(25.0)     # j = 1, 0, 2, 1 of 8
    empty = routing_trace([(8, 60.0, {"1/1": share_stats(0.0, 0.0, 0.0)})])
    assert expert_state.read(
        record(empty), params_of("moe.held_load_max_over_mean")) is None
    assert expert_state.read(
        record(empty), params_of("moe.product_row_share"))["value"] == 0.0


def test_nothing_to_read_where_the_program_states_no_routing():
    """A parent's trace, a model without experts, an untraced run."""
    for params in map(params_of, ("moe.product_row_share",
                                  "moe.held_load_max_over_mean")):
        assert expert_state.read(record(routing_trace([])), params) is None
        assert expert_state.read({"trace_events": None}, params) is None
        # a state without ``ExpertShare``'s keys (``MoE``'s alone)
        other = routing_trace([(4, 60.0, {"2": {"moe_aux": 0.1}})])
        assert expert_state.read(record(other), params) is None

"""The benchmark's side of SmallThinker-21BA3B-Instruct: the configuration
against the catalog row, the FLOP, byte and parameter functions by hand,
the new cell's manifest entries and rehearsal end to end (with a control
planted through the harness's own check), and the new readers on
hand-written records. tests/test_smallthinker.py holds the leaf-by-leaf
agreement and the window kernels. Membership and ``<=`` on the
manifest's lists, never ``==`` or "the last": later cells join them."""
import contextlib
import io
import json
import re

import jax.numpy as jnp
import pytest

from benchmarks import manifest, run, shapes_smallthinker
from benchmarks.builders import smallthinker as builder
from benchmarks.kinds import train
from benchmarks.reference import smallthinker as ref
from bigdl_tpu.tensor import DTypePolicy, policy_scope

CELL = "smallthinker-21ba3b-instruct.train.seq16384"
FULL = manifest.data_file("configs", "smallthinker-21ba3b-instruct")
TINY = dict(FULL, **FULL["rehearsal"])
NEW_METRICS = ("step.window_attention_ms", "step.global_attention_ms",
               "window_attention_roofline",
               "flash_attention_roofline.smallthinker",
               "step.device_mfu.smallthinker")
PERIOD = [0, 1, 1, 1] * 13
HARNESS_TOL_GRAD_REL = train.TOL_GRAD_REL
CATALOG_ROW = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


@pytest.fixture(autouse=True, scope="module")
def _float32_here_and_the_policy_put_back():
    f32 = jnp.dtype("float32")
    with policy_scope(DTypePolicy(param_dtype=f32, compute_dtype=f32,
                                  activation_dtype=f32)):
        yield


# -- the configuration ---------------------------------------------------

def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row under the same name; only the depth,
    the experts held and the vocabulary differ, and ``reduced`` says
    so."""
    assert set(CATALOG_ROW) <= set(FULL)
    differs = sorted(k for k, v in CATALOG_ROW.items() if FULL[k] != v)
    assert differs == sorted(FULL["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert FULL["published"] == {k: CATALOG_ROW[k] for k in FULL["reduced"]}
    assert set(FULL["reduced_why"]) == set(FULL["reduced"])
    assert (FULL["num_hidden_layers"], FULL["moe_num_primary_experts"],
            FULL["vocab_size"]) == (8, 8, 18992)
    # the floors: whole periods (two), at least 8 experts, an eighth of
    # the vocabulary
    assert builder.layout(FULL) == [(False, False)] + [(True, True)] * 3 \
        + [(False, False)] + [(True, True)] * 3
    assert FULL["vocab_size"] * 8 == CATALOG_ROW["vocab_size"]
    assert 0 <= FULL["experts_offset"] <= 64 - 8
    assert "eight chips" in FULL["stands_for"]
    assert FULL["source"] == ("https://huggingface.co/PowerInfer/"
                              "SmallThinker-21BA3B-Instruct/blob/main/"
                              "config.json")
    said = " ".join(FULL["assumed"])
    for what in ("router reads RMSNorm_in(x)", "softmax over the 6",
                 "no secondary experts", "no q/k norm", "own initialisers",
                 "deviation of 4", "dtype policy", "residual stream"):
        assert what in said, what
    assert "643,852,800" in FULL["reduced_why"]["num_hidden_layers"]


@pytest.mark.parametrize("key,value", [
    ("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("rope_scaling", {"type": "yarn"})])
def test_the_builder_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match="asks for something else"):
        builder.build(dict(TINY, **{key: value}))


# -- shapes_smallthinker, by hand ------------------------------------------

def test_the_parameters_by_hand():
    per = shapes_smallthinker.layer_params(FULL)
    assert per == {"attention": 2560 * 3584 * 2 + 2 * 2560 * 512,
                   "router_norms": 2560 * 64 + 2 * 2560,
                   "experts": 8 * 3 * 2560 * 768,
                   "outside": 2 * 18992 * 2560 + 2560}
    assert per["attention"] == 20_971_520 and per["experts"] == 47_185_920
    assert sum(v for k, v in per.items() if k != "outside") == 68_326_400
    assert shapes_smallthinker.param_count(FULL) == 643_852_800
    assert shapes_smallthinker.layer_counts(FULL) == {"window": 6,
                                                      "global": 2}


def test_the_pairs_and_the_cores_by_hand():
    assert shapes_smallthinker.window_pairs(16384, 4096) == 58_722_304
    assert shapes_smallthinker.causal_pairs(16384) == 134_225_920
    assert shapes_smallthinker.window_pairs(100, 4096) == 5050
    win = shapes_smallthinker.core_cost(FULL, 1, 16384, "window")
    glob = shapes_smallthinker.core_cost(FULL, 1, 16384, "global")
    assert win["flops"] == 2 * 58_722_304 * 28 * 128 * 7 * 6
    assert glob["flops"] == 2 * 134_225_920 * 28 * 128 * 7 * 2
    assert win["bytes"] == (6 * 28 + 6 * 4) * 128 * 16384 * 2 * 6
    assert glob["bytes"] == win["bytes"] / 3
    # the window's pairs over the causal ones
    assert 58_722_304 / 134_225_920 == pytest.approx(0.4375, abs=1e-4)


def test_the_step_flops():
    """~56 TFLOP a step: cores 31.2 (window 17.7, global 13.5),
    projections 16.5, head 4.8, the held experts 3.5, router 0.1."""
    f = shapes_smallthinker.train_step_flops(FULL, 1, 16384)
    assert f["total"] == f["matmul"] + f["window_core"] + f["global_core"]
    tf = {k: v / 1e12 for k, v in f.items()}
    assert tf["total"] == pytest.approx(56.03, abs=0.01)
    assert tf["window_core"] == pytest.approx(17.68, abs=0.01)
    assert tf["global_core"] == pytest.approx(13.47, abs=0.01)
    assert tf["projections"] == pytest.approx(16.49, abs=0.01)
    assert tf["head"] == pytest.approx(4.78, abs=0.01)
    assert tf["experts"] == pytest.approx(3.48, abs=0.01)
    assert (f["window_core"] + f["global_core"]) / f["total"] == \
        pytest.approx(0.56, abs=0.005)
    # a held expert sees 16384 x 6 / 64 assignments a step
    assert shapes_smallthinker.expected_local_assignments(FULL) * 16384 \
        / 8 == 1536


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(ref))
    mods = {n.module or "" for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert not [m for m in mods if m.startswith("bigdl_tpu")]
    assert [m for m in mods if m.startswith("benchmarks")] == [
        "benchmarks.reference.kimi"]
    # every layer's mask written out
    src = inspect.getsource(ref.attention)
    assert "keys <= rows" in src and "< spec.window" in src


# -- the cell ------------------------------------------------------------

def test_the_cell_and_its_files():
    loaded = manifest.load_cell(CELL)
    assert loaded["kind"] == "train" and loaded["chips"] == 1
    assert loaded["traffic"]["name"] == "pretrain-seq16384"
    assert (loaded["traffic"]["batch_per_chip"],
            loaded["traffic"]["seq_len"]) == (1, 16384)
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "train.records_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW_METRICS) | {
        "step.device_ms", "step.forward_ms", "step.backward_ms",
        "step.optimizer_update_ms", "step.head_loss_ms",
        "step.unscoped_share", "step.update_fused_ms", "step.peak_hbm_gb",
        "step.recompute_ms", "step.recompute_fused_ms",
        "step.moe_routed_ms", "moe.product_row_share",
        "moe.held_load_max_over_mean", "device.idle_share.train",
        "train_loop.input_wait_share", "train_loop.host_ms_per_step",
        "train_loop.gap_attributed_share"} <= names
    # counted by another family's formulas, or only across chips; no
    # shared expert here
    assert not names & {"step.device_mfu", "flash_attention_roofline",
                        "collective.exposed_share", "step.moe_shared_ms",
                        "flash_attention_roofline.nemotron",
                        "step.device_mfu.nemotron", "step.moe_ms"}


def test_the_manifest_is_sound_with_the_cell_in_it():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    assert "smallthinker-21ba3b-instruct" in [c["name"]
                                              for c in man["configs"]]
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert "\t" not in entry["why"] and "\n" not in entry["why"]
    n4 = sum(w["chips"] == 4 for w in man["workloads"])
    assert n4 <= max(1, len(man["workloads"]) // 4)


def test_the_entries_this_cell_adds_keep_the_manifests_limits():
    man = manifest.load_manifest()
    added = [e for key in ("configs", "workloads", "per_layer")
             for e in man[key]
             if "smallthinker" in e["name"] or e["name"] in NEW_METRICS]
    assert len(added) == 7
    for entry in added:
        assert len(entry["name"]) <= 64
        if "layer" in entry:
            assert entry["workloads"] == [CELL]
            assert set(entry) == {"name", "unit", "better", "source",
                                  "layer", "moves", "workloads"}
        for value in entry.values():
            if isinstance(value, str):
                assert 1 <= len(value) <= 200 and value.isprintable()
    assert sorted(m["layer"] for m in added if "layer" in m) == [
        "kernels", "kernels", "step program", "step program",
        "step program"]


@pytest.fixture(scope="module")
def rehearsal():
    """ONE run of the cell at rehearsal size through ``run.main`` (the
    CPU's XLA attention with the rehearsal's window of 32 over 128
    tokens, bf16 at toy widths), with ``train.check`` called on the
    finished job as it is and with each control planted: the router fed
    the experts' input h instead of r, and the window dropped from the
    window layers."""
    from bigdl_tpu.parallel.expert import ExpertShare
    seen = {}
    real_check = train.check
    real_apply = ExpertShare.apply

    def both(ctx, bld, model, *rest):
        with pytest.MonkeyPatch.context() as planted:
            planted.setattr(ExpertShare, "apply",
                            lambda self, *a, route_from=None, **k:
                            real_apply(self, *a, **k))
            seen["router_fed_h"] = real_check(ctx, bld, model, *rest)
        with pytest.MonkeyPatch.context() as planted:
            for block in model.modules[1:-2]:
                planted.setattr(block.modules[1], "window", None)
            seen["window_dropped"] = real_check(ctx, bld, model, *rest)
        seen["good"] = real_check(ctx, bld, model, *rest)
        return seen["good"]

    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(train, "check", both)
        # toy widths round coarsely in bf16 and 128 tokens average
        # little: the rehearsal's own tolerances, as the other cells'
        mp.setattr(train, "TOL_GRAD_REL", 0.6)
        mp.setattr(train, "TOL_LOSS_REL", 2e-3)
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", CELL, "--seed", "4000000917",
                           "--seconds", "0.5", "--trace", "0",
                           "--rehearsal"])
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), seen


def test_the_cells_rehearsal_end_to_end(rehearsal):
    rc, line, seen = rehearsal
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"train.records_per_s_per_chip",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert seen["good"]["ok"] and seen["good"]["param_dtype_ok"]


def test_correct_can_fail_through_the_harness_own_check(rehearsal):
    """The window dropped fails even the rehearsal's loose tolerance; the
    router fed h moves the worst leaf past the harness's own
    TOL_GRAD_REL (what the cell is held to), far from the good run's."""
    *_, seen = rehearsal
    bad = seen["window_dropped"]
    assert not bad["ok"] and not (bad["loss_ok"] and bad["grad_ok"])
    fed_h, good = seen["router_fed_h"], seen["good"]
    assert fed_h["grad_rel_err"] > HARNESS_TOL_GRAD_REL
    assert fed_h["grad_rel_err"] > 4 * good["grad_rel_err"]


# -- the new readers -----------------------------------------------------

def _record(**over):
    rec = {"loaded": {"config": FULL}, "global_batch": 1, "chips": 1,
           "seq": 16384, "traced_steps": 4,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace_window": {"busy_s": 4.0, "window_ns": (0, 5_000_000_000),
                            "planes": ["/device:TPU:0"]},
           "trace_events": []}
    rec.update(over)
    return rec


def test_step_device_mfu_smallthinker_on_a_hand_written_record():
    reader = manifest.plugin("readers", "step_device_mfu_smallthinker")
    got = reader.read(_record(), {})
    # 56.03 TFLOP in a second of busy device a step: 28.4% of 197
    assert got["value"] == pytest.approx(100 * 56.028 / 197, rel=1e-3)
    assert reader.read(_record(trace_window=None), {}) is None
    assert reader.read(_record(peaks=None), {}) is None


def _kernel_events():
    """Per step: six window layers' forward twice (the block is
    recomputed) and their streamed backward, two global layers' the
    same under flash's own names, and a grouped product."""
    plane, ms = "/device:TPU:0", 1_000_000
    events, t = [], 0
    for _ in range(4):
        for name, dur, times in (
                ("%jvp_flash_window_fwd.1 = ...", 3 * ms, 6),
                ("%flash_window_fwd.2 = ...", 3 * ms, 6),
                ("%transpose_flash_window_dq.1 = ...", 4 * ms, 6),
                ("%transpose_flash_window_dkdv.1 = ...", 5 * ms, 6),
                ("%flash_attention_fwd.3 = ...", 7 * ms, 4),
                ("%transpose_flash_attention_dq.1 = ...", 9 * ms, 2),
                ("%transpose_flash_attention_dkdv.1 = ...", 11 * ms, 2),
                ("%gmm.3 = ...", 2 * ms, 8)):
            for _ in range(times):
                events.append((plane, "XLA Ops", name, t, dur))
                t += dur
    return events, t, plane


@pytest.mark.parametrize("metric,kernel_ms,calls,kind", [
    ("window_attention_roofline", 6 * (3 + 3 + 4 + 5), 24, "window"),
    ("flash_attention_roofline.smallthinker", 4 * 7 + 2 * (9 + 11), 8,
     "global")])
def test_the_two_rooflines_read_their_own_kernels(metric, kernel_ms, calls,
                                                  kind):
    reader = manifest.plugin("readers", "kernel_roofline_smallthinker")
    spec = manifest.data_file("layer_metrics", metric)
    assert spec["reader"] == "kernel_roofline_smallthinker"
    events, t, plane = _kernel_events()
    got = reader.read(_record(
        trace_events=events,
        trace_window={"busy_s": t / 1e9, "window_ns": (0, t),
                      "planes": [plane]}), spec["params"])
    assert got["calls"] == 4 * calls and got["bound"] == "compute"
    assert got["kernel_s"] == pytest.approx(4 * kernel_ms / 1e3)
    cost = shapes_smallthinker.core_cost(FULL, 1, 16384, kind)
    least = 4 * cost["flops"] / 197e12
    assert got["value"] == pytest.approx(100 * least / got["kernel_s"],
                                         rel=1e-6)
    assert reader.read(_record(trace_window=None), spec["params"]) is None


def test_the_window_kernels_are_named_as_the_roofline_reads_them():
    from bigdl_tpu.ops.pallas import flash_attention as fa
    win = re.compile(manifest.data_file(
        "layer_metrics", "window_attention_roofline")["params"]["kernels"])
    glob = re.compile(manifest.data_file(
        "layer_metrics",
        "flash_attention_roofline.smallthinker")["params"]["kernels"])
    for kernel in ("fwd", "dq", "dkdv", "dqdkdv"):
        assert win.search(fa._name(kernel, 4096))
        assert not glob.search(fa._name(kernel, 4096))
        assert glob.search(fa._name(kernel, None))
        assert not win.search(fa._name(kernel, None))


@pytest.mark.parametrize("metric,hits,misses", [
    ("step.window_attention_ms",
     ["jit(train_step)/jvp(model)/block_1/checkpoint/attention_window/"
      "dot_general",
      "transpose(jvp(model))/block_2/checkpoint/attention_window/"
      "pallas_call"],
     ["jvp(model)/block_0/checkpoint/attention_global/dot_general",
      "jvp(model)/block_1/3_ExpertShare/moe_experts/gmm",
      "jvp(model)/block_1/0_RMSNorm/mul"]),
    ("step.global_attention_ms",
     ["jvp(model)/block_4/checkpoint/attention_global/pallas_call"],
     ["jvp(model)/block_5/attention_window/pallas_call"])])
def test_the_attention_scopes_are_read_apart(metric, hits, misses):
    spec = manifest.data_file("layer_metrics", metric)
    assert spec["reader"] == "scope_device_ms"
    rx = re.compile(spec["params"]["include"])
    assert all(rx.search(s) for s in hits)
    assert not any(rx.search(s) for s in misses)

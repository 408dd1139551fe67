"""The benchmark's side of the EvaByte family (ISSUE 27): the FLOP and
byte functions against hand-worked values, the plain reference against
the program at rehearsal size (logits, loss, every gradient leaf) and
shown able to fail, the new cell's rehearsal end to end, its manifest
entries, and the new readers on a hand-written record."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, model_setup, run, shapes_evabyte
from benchmarks.builders import evabyte as builder
from benchmarks.kinds import train
from benchmarks.reference import evabyte as ref
from bigdl_tpu.tensor import DTypePolicy, policy_scope

CELL = "evabyte-6.5b.train.long"
FULL = manifest.data_file("configs", "evabyte-6.5b")
TINY = dict(FULL, **FULL["rehearsal"])
HEADS = TINY["num_attention_heads"]


@pytest.fixture(autouse=True, scope="module")
def _float32_here_and_the_policy_put_back():
    """The comparisons are float32 on both sides, and a rehearsal sets
    the process's dtype policy: neither may leak to another file."""
    f32 = jnp.dtype("float32")
    with policy_scope(DTypePolicy(param_dtype=f32, compute_dtype=f32,
                                  activation_dtype=f32)):
        yield


# -- shapes_evabyte, by hand (ISSUE 27's arithmetic) ----------------------

def test_matmul_params_by_hand():
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert layer == 202_375_168
    assert shapes_evabyte.matmul_params(FULL) == 4 * layer + 4096 * 2560
    assert shapes_evabyte.matmul_params(FULL) == 819_986_432


def test_attention_pairs_by_hand():
    pairs = shapes_evabyte.attention_pairs(16384, 2048, 16)
    assert pairs == {"local": 8 * 2048 * 2049 // 2,        # 16.8M
                     "remote": 2048 * 128 * 28,            # 7.3M
                     "total": 16_785_408 + 7_340_032}
    # full causal attention would score 134M pairs
    assert 16384 * 16385 // 2 == 134_225_920
    # at 8192 the summaries are 16% of the work, at 16384 30%
    short = shapes_evabyte.attention_pairs(8192, 2048, 16)
    assert short["remote"] / short["total"] == pytest.approx(0.158, abs=2e-3)
    assert pairs["remote"] / pairs["total"] == pytest.approx(0.304, abs=2e-3)
    # one window: no summaries at all
    assert shapes_evabyte.attention_pairs(2048, 2048, 16)["remote"] == 0


def test_train_step_flops_by_hand():
    flops = shapes_evabyte.train_step_flops(FULL, 1, 16384)
    assert flops["matmul"] == 6.0 * 819_986_432 * 16384       # 80.6 TFLOP
    one = 2.0 * 32 * 128 * 24_125_440                         # 0.198 TFLOP
    assert flops["attention"] == 7.0 * one * 4                # 5.5 TFLOP
    assert flops["total"] == pytest.approx(86.14e12, rel=1e-3)
    assert flops["attention"] / flops["total"] == pytest.approx(0.064,
                                                                abs=2e-3)
    two = shapes_evabyte.train_step_flops(FULL, 2, 8192)
    assert two["matmul"] == flops["matmul"] and \
        two["attention"] < flops["attention"]


def test_eva_attention_cost_by_hand():
    cost = shapes_evabyte.eva_attention_train_cost(FULL, 1, 16384)
    tensor = 16384 * 4096 * 2                                  # 134 MB
    assert cost["flops"] == shapes_evabyte.train_step_flops(
        FULL, 1, 16384)["attention"]
    # forward Q K V O + K~ V~; backward Q K V O dO dQ dK dV + 4 summaries
    assert cost["bytes"] == 4 * (12 * tensor + 6 * tensor / 16)
    from benchmarks import peaks, shapes
    least, bound = shapes.roofline_least_seconds(
        cost, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(0.0281, rel=1e-2)


# -- the reference against the program -----------------------------------

@pytest.fixture(scope="module")
def system():
    """The program's model in float32 at rehearsal size, every leaf moved
    off its initial value (norm offsets start at 0) and q, k scaled so
    that scores are sharp."""
    model = builder.build(TINY)
    model_setup.materialize_lean(model, 5)
    flat, tree = jax.tree_util.tree_flatten_with_path(model.params)
    keys = jax.random.split(jax.random.PRNGKey(9), len(flat))
    def moved(path, x, key):
        x = x + 0.05 * jax.random.normal(key, x.shape, x.dtype)
        name = jax.tree_util.keystr(path)
        return x * 4 if "q_weight" in name or "k_weight" in name else x

    leaves = [moved(path, x, key) for (path, x), key in zip(flat, keys)]
    model.sync(jax.tree.unflatten(tree, leaves), model.init_state())
    toks = np.random.default_rng(0).integers(1, 321, size=(2, 129))
    return model, toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def _sys_loss_and_grads(model, data, labels):
    crit = builder.criterion()

    def loss(p):
        out, _ = model.apply(p, model.state, jnp.asarray(data),
                             training=True)
        return crit.apply(out, jnp.asarray(labels))

    value, grads = jax.value_and_grad(loss)(model.params)
    return float(value), builder.reference_weights(grads, TINY)


def test_reference_logits_match_the_system(system):
    model, data, _ = system
    w = builder.reference_weights(model.params, TINY)
    out, _ = model.apply(model.params, model.state, jnp.asarray(data))
    assert out.shape == (2, 128, 8 * 320)
    for row in range(2):
        got = ref.logits(w, jnp.asarray(data[row] - 1), HEADS)
        np.testing.assert_allclose(got.reshape(128, -1), out[row],
                                   atol=2e-4)


def test_reference_loss_and_every_gradient_leaf_match_the_system(system):
    model, data, labels = system
    w = builder.reference_weights(model.params, TINY)
    value, grads = _sys_loss_and_grads(model, data, labels)
    ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
    assert ref.loss(w, ids, tgt, HEADS) == pytest.approx(value, rel=1e-6)
    ref_value, ref_grads = ref.loss_and_grads(w, ids, tgt, HEADS)
    assert ref_value == pytest.approx(value, rel=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    ref_flat = jax.tree.leaves(ref_grads)
    assert len(flat) == len(ref_flat) == 4 + 11 * TINY["num_hidden_layers"] - 1
    for (path, g), r in zip(flat, ref_flat):
        assert isinstance(r, np.ndarray)      # fetched to the host
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < 2e-4, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("fault", ["a_layer_dropped", "mu_zeroed",
                                   "chunk_size_doubled", "seven_heads"])
def test_the_comparison_can_fail(system, fault):
    model, data, labels = system
    w = builder.reference_weights(model.params, TINY)
    if fault == "a_layer_dropped":
        w = ref.Weights(dict(w.arrays, layers=w["layers"][:-1]), w.spec)
    elif fault == "mu_zeroed":
        layers = [dict(lw, mu=lw["mu"] * 0) for lw in w["layers"]]
        w = ref.Weights(dict(w.arrays, layers=layers), w.spec)
    elif fault == "chunk_size_doubled":
        w = ref.Weights(w.arrays, w.spec._replace(chunk=2 * w.spec.chunk))
    else:
        # head 8 left out of the reference's mean
        w = ref.Weights(dict(w.arrays, head_w=w["head_w"][:7 * 320]),
                        w.spec._replace(pred_heads=7))
    value, _ = _sys_loss_and_grads(model, data, labels)
    got = ref.loss(w, jnp.asarray(data - 1), jnp.asarray(labels - 1), HEADS)
    assert abs(got - value) / value > train.TOL_LOSS_REL


def test_weights_is_a_pytree_with_a_static_part(system):
    model, *_ = system
    w = builder.reference_weights(model.params, TINY)
    assert w.spec == ref.Spec(32, 4, 8, 100000.0, 1e-5)
    leaves, tree = jax.tree.flatten(w)
    assert len(leaves) == len(jax.tree.leaves(model.params))
    again = jax.tree.unflatten(tree, leaves)
    assert again.spec == w.spec and again["tok"] is w["tok"]
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(w)[0]]
    assert ".arrays['layers'][0]['phi']" in paths
    # views, no copies
    assert w["layers"][0]["q_w"] is model.params["1"]["0"]["1"]["q_weight"]


def test_the_builder_refuses_another_head_layout():
    with pytest.raises(ValueError, match="scores 8 heads of 320 bytes"):
        builder.build(dict(TINY, num_pred_heads=4))


# -- the cell ------------------------------------------------------------

def test_the_cell_and_its_files():
    loaded = manifest.load_cell(CELL)
    assert loaded["kind"] == "train" and loaded["chips"] == 1
    assert loaded["traffic"]["batch_per_chip"] * \
        loaded["traffic"]["seq_len"] == 16384
    assert loaded["traffic"]["seq_len"] % loaded["config"]["window_size"] == 0
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "train.records_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in loaded["per_layer"]}
    assert {"step.device_mfu.evabyte", "eva_attention_roofline",
            "step.eva_attention_ms", "step.device_ms", "step.forward_ms",
            "step.backward_ms", "step.optimizer_update_ms",
            "step.head_loss_ms", "step.unscoped_share",
            "step.update_fused_ms", "device.idle_share.train",
            "train_loop.input_wait_share", "train_loop.host_ms_per_step",
            "train_loop.gap_attributed_share"} == names
    # counted by OPT's formulas, or only across chips: not this cell's
    assert not names & {"step.device_mfu", "flash_attention_roofline",
                        "collective.exposed_share"}


def test_the_entries_this_cell_adds_keep_the_drivers_limits():
    """The driver refuses a ``why`` (or any string) over 200 characters
    before any run; ``check_manifest`` does not count them."""
    man = manifest.load_manifest()
    added = [e for key in ("configs", "workloads", "per_layer")
             for e in man[key] if "evabyte" in e["name"] or "eva_" in e["name"]]
    assert len(added) == 5
    for entry in added:
        assert len(entry["name"]) <= 64
        for value in entry.values():
            if isinstance(value, str):
                assert 1 <= len(value) <= 200 and value.isprintable() \
                    and value.isascii(), (entry["name"], value)


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` under the same key;
    only the depth differs, and ``reduced`` says so."""
    published = {
        "chunk_size": 16, "hidden_size": 4096, "init_std": 0.01275,
        "intermediate_size": 11008, "max_position_embeddings": 32768,
        "max_seq_length": 32768, "num_attention_heads": 32,
        "num_hidden_layers": 32, "num_key_value_heads": 32,
        "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_theta": 100000,
        "vocab_size": 320, "window_size": 2048}
    differs = [k for k, v in published.items() if FULL[k] != v]
    assert differs == FULL["reduced"] == ["num_hidden_layers"]
    assert FULL["published"] == {"num_hidden_layers": 32}
    assert FULL["num_hidden_layers"] == 4
    for key in ("fp32_skip_add", "fp32_logits", "norm_add_unit_offset"):
        assert FULL[key] is True
    assert FULL["attention_class"] == "eva" and FULL["hidden_act"] == "silu"


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_the_cells_rehearsal_end_to_end_and_correct_can_fail(capsys,
                                                             monkeypatch):
    seen = {}
    real_check = train.check

    def both(ctx, bld, model, *rest):
        class Dropped:
            """The adapter with the reference's LAST attention dropped."""
            build, criterion = bld.build, bld.criterion

            @staticmethod
            def reference_weights(params, cfg):
                w = bld.reference_weights(params, cfg)
                last = dict(w["layers"][-1])
                last["o_w"] = last["o_w"] * 0.0
                return ref.Weights(
                    dict(w.arrays, layers=w["layers"][:-1] + [last]),
                    w.spec)
        seen["bad"] = real_check(ctx, Dropped, model, *rest)
        seen["good"] = real_check(ctx, bld, model, *rest)
        return seen["good"]

    monkeypatch.setattr(train, "check", both)
    # toy widths round coarsely in bf16: the rehearsal's own tolerance
    monkeypatch.setattr(train, "TOL_GRAD_REL", 0.2)
    monkeypatch.setattr(train, "TOL_LOSS_REL", 2e-4)
    rc = run.main(["--workload", CELL, "--seed", "3000000001", "--seconds",
                   "0.5", "--trace", "0", "--rehearsal"])
    line, _ = _last_line(capsys)
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"train.records_per_s_per_chip",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert seen["good"]["ok"] and seen["good"]["param_dtype_ok"]
    assert not seen["bad"]["ok"]
    assert not (seen["bad"]["loss_ok"] and seen["bad"]["grad_ok"])


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


def test_step_device_mfu_evabyte_on_a_hand_written_record():
    reader = manifest.plugin("readers", "step_device_mfu_evabyte")
    got = reader.read(_record(), {})
    # 86.14 TFLOP in a second of busy device a step: 43.7% of 197
    assert got["value"] == pytest.approx(100 * 86.14 / 197, rel=1e-3)
    assert got["attention"] / got["flops_per_step_per_chip"] < 0.07
    assert reader.read(_record(trace_window=None), {}) is None
    assert reader.read(_record(peaks=None), {}) is None


def test_eva_roofline_reads_every_kernel_and_nothing_without_a_trace():
    reader = manifest.plugin("readers", "kernel_roofline_evabyte")
    spec = manifest.data_file("layer_metrics", "eva_attention_roofline")
    assert spec["reader"] == "kernel_roofline_evabyte"
    plane = "/device:TPU:0"
    ms = 1_000_000

    def op(name, start, dur):
        return (plane, "XLA Ops", name, start, dur)

    # per step: two forward calls a layer (recomputation) and a backward
    events, t = [], 0
    for _ in range(4 * 4):
        for name, dur in (("%jvp_eva_attention_fwd_.1 = ...", 5 * ms),
                          ("%eva_attention_fwd.9 = ...", 5 * ms),
                          ("%transpose_jvp_eva_attention_dqdkdv__.1 = ...",
                           7 * ms), ("%fusion.3 = ...", 9 * ms)):
            events.append(op(name, t, dur))
            t += dur
    got = reader.read(_record(
        trace_events=events,
        trace_window={"busy_s": t / 1e9, "window_ns": (0, t),
                      "planes": [plane]}), spec["params"])
    assert got["calls"] == 48 and got["bound"] == "compute"
    assert got["kernel_s"] == pytest.approx(16 * 0.017)
    assert got["value"] == pytest.approx(
        100 * 4 * 0.02809 / (16 * 0.017), rel=1e-2)
    assert got["value"] < 100
    assert reader.read(_record(trace_window=None), spec["params"]) is None
    # the table the OPT cells' reader looks its cost up in kept its own
    from benchmarks.readers import kernel_roofline
    assert {"flash_attention_train", "paged_attention_decode",
            "eva_attention_train"} <= set(kernel_roofline.COSTS)


def test_the_scope_metric_is_a_data_file_only():
    spec = manifest.data_file("layer_metrics", "step.eva_attention_ms")
    assert spec["reader"] == "scope_device_ms"
    import re
    rx = re.compile(spec["params"]["include"])
    assert rx.search("jit(train_step)/jvp(model)/block_0/checkpoint/"
                     "0_Sequential/1_EvaAttention/eva_attention/pallas_call")
    assert rx.search("transpose(jvp(model))/block_2/eva_prep_kv/reduce_sum")
    assert not rx.search("jvp(model)/block_0/1__Residual/1_GatedFFN/dot")

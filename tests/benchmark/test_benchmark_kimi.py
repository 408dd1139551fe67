"""The benchmark's side of the Kimi family (ISSUE 33): the FLOP and byte
functions against hand-worked values, the plain reference shown able to
fail against the program at rehearsal size (tests/test_kimi.py holds
the leaf-by-leaf agreement), the new cell's rehearsal end to end, its
manifest entries and configuration, and the new readers on hand-written
records."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, model_setup, run, shapes_kimi
from benchmarks.builders import kimi as builder
from benchmarks.kinds import train
from benchmarks.reference import kimi as ref
from bigdl_tpu.tensor import DTypePolicy, policy_scope

CELL = "kimi-vl-a3b-instruct.train.seq8192"
FULL = manifest.data_file("configs", "kimi-vl-a3b-instruct")
TINY = dict(FULL, **FULL["rehearsal"])
HEADS = TINY["num_attention_heads"]
NEW_METRICS = ("step.device_mfu.kimi", "mla_attention_roofline",
               "step.mla_attention_ms", "step.moe_shared_ms",
               "step.moe_routed_ms")


@pytest.fixture(autouse=True, scope="module")
def _float32_here_and_the_policy_put_back():
    """The comparisons are float32 on both sides, and a rehearsal sets
    the process's dtype policy: neither may leak to another file."""
    f32 = jnp.dtype("float32")
    with policy_scope(DTypePolicy(param_dtype=f32, compute_dtype=f32,
                                  activation_dtype=f32)):
        yield


# -- shapes_kimi, by hand (ISSUE 33's arithmetic) -------------------------

def test_matmul_params_by_hand():
    params = shapes_kimi.matmul_params(FULL)
    # q 2048 x 3072, kva 2048 x 576, kvb 512 x 4096, out 2048 x 2048:
    # a layer's 13,763,072 less the latent's norm weight
    assert params["attention"] == 6 * (13_763_072 - 512)
    assert params["dense_ffn"] == 3 * 2048 * 11264          # ONE layer
    assert params["router"] == 5 * 2048 * 64                # over ALL 64
    assert params["shared"] == 5 * 17_301_504               # 2 x 1408 wide
    # 6 x 8 / 64 = three quarters of an assignment a token lands here
    assert shapes_kimi.expected_local_assignments(FULL) == 0.75
    assert params["experts"] == 5 * 0.75 * 8_650_752
    assert params["head"] == 2048 * 20480


def test_the_forward_pass_a_token_by_hand():
    """ISSUE 33: 878 MFLOP a token forward at 8192 tokens: the attention
    core 29%, the latent projections 19%, router + shared + held experts
    27%, the dense layer's FFN 16%, the head 10%."""
    parts = shapes_kimi.forward_flops_per_token(FULL, 8192)
    assert parts["total"] == pytest.approx(878e6, rel=2e-3)
    share = {k: v / parts["total"] for k, v in parts.items()}
    assert share["attention_core"] == pytest.approx(0.29, abs=5e-3)
    assert share["attention"] == pytest.approx(0.19, abs=5e-3)
    assert share["router"] + share["shared"] + share["experts"] \
        == pytest.approx(0.27, abs=5e-3)
    assert share["dense_ffn"] == pytest.approx(0.16, abs=5e-3)
    assert share["head"] == pytest.approx(0.10, abs=5e-3)
    # 2 x (192 + 128) x 16 heads x (8193 / 2) pairs a token x 6 layers
    assert parts["attention_core"] == 640 * 16 * 4096.5 * 6


def test_train_step_flops_by_hand():
    flops = shapes_kimi.train_step_flops(FULL, 2, 8192)
    tokens = 2 * 8192
    assert shapes_kimi.causal_pairs(8192) == 33_558_528
    # seven products: 192 + 128 forward, 192 + 128 + 128 + 192 + 192 back
    assert flops["attention"] == 2.0 * 2 * 33_558_528 * 16 * 1152 * 6
    assert flops["attention"] == pytest.approx(14.85e12, rel=1e-3)
    assert flops["matmul"] == pytest.approx(
        6.0 * tokens * sum(shapes_kimi.matmul_params(FULL).values()))
    assert flops["matmul"] == pytest.approx(30.79e12, rel=1e-3)
    assert flops["total"] == pytest.approx(45.64e12, rel=1e-3)
    assert flops["experts"] == pytest.approx(3.19e12, rel=1e-2)
    assert flops["shared"] == pytest.approx(8.50e12, rel=1e-2)
    assert flops["head"] == pytest.approx(4.12e12, rel=1e-2)
    # the attention core and the latent projections: half the step
    mla = flops["attention"] + 6.0 * tokens * 6 * (13_763_072 - 512)
    assert mla / flops["total"] == pytest.approx(0.50, abs=0.01)


def test_mla_attention_cost_by_hand():
    cost = shapes_kimi.mla_attention_train_cost(FULL, 2, 8192)
    assert cost["flops"] == shapes_kimi.train_step_flops(
        FULL, 2, 8192)["attention"]
    per_head, shared_key, out = 16 * (128 + 64 + 128 + 128), 64, 16 * 128
    # forward reads the five operands and writes o; backward reads them,
    # o and dO and writes five gradients: the shared key once a position
    per_position = (per_head + shared_key + out) \
        + (per_head + shared_key + 2 * out) + (per_head + shared_key)
    assert cost["bytes"] == per_position * 2 * 8192 * 2 * 6
    from benchmarks import peaks, shapes
    least, bound = shapes.roofline_least_seconds(
        cost, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(0.0754, rel=1e-2)


# -- the reference against the program -----------------------------------

@pytest.fixture(scope="module")
def system():
    """The program's model in float32 at rehearsal size, every leaf moved
    off its initial value (norm weights start at 1)."""
    model = builder.build(TINY)
    model_setup.materialize_lean(model, 5)
    flat, tree = jax.tree_util.tree_flatten_with_path(model.params)
    keys = jax.random.split(jax.random.PRNGKey(9), len(flat))
    leaves = [x + 0.1 * jax.random.normal(key, x.shape, x.dtype)
              for (_, x), key in zip(flat, keys)]
    model.sync(jax.tree.unflatten(tree, leaves), model.init_state())
    toks = np.random.default_rng(0).integers(
        1, TINY["vocab_size"] + 1, size=(2, 129))
    return model, toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def _sys_loss_and_grads(model, data, labels):
    crit = builder.criterion()

    def loss(p):
        out, _ = model.apply(p, model.state, jnp.asarray(data),
                             training=True)
        return crit.apply(out, jnp.asarray(labels))

    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(model.params)
    return float(value), builder.reference_weights(grads, TINY)


@pytest.fixture(scope="module")
def system_side(system):
    return _sys_loss_and_grads(*system)


def test_reference_agrees_at_rehearsal_size(system, system_side):
    """128 tokens, 4 of 8 experts held. Loss and the worst leaf
    (tests/test_kimi.py names every leaf)."""
    model, data, labels = system
    w = builder.reference_weights(model.params, TINY)
    value, grads = system_side
    ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
    assert ref.loss(w, ids, tgt, HEADS) == pytest.approx(value, rel=1e-5)
    ref_value, ref_grads = ref.loss_and_grads(w, ids, tgt, HEADS)
    assert ref_value == pytest.approx(value, rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    ref_flat = jax.tree.leaves(ref_grads)
    # a dense layer of 10 leaves, two expert layers of 14
    assert len(flat) == len(ref_flat) == 3 + 10 + 14 * 2
    for (path, g), r in zip(flat, ref_flat):
        assert isinstance(r, np.ndarray)      # fetched to the host
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("fault", [
    "a_layer_dropped", "another_chips_experts", "five_experts_a_token",
    "route_scale_one", "latent_norm_weight_dropped",
    "shared_expert_dropped", "rotary_key_not_rotated"])
def test_the_comparison_can_fail(system, system_side, fault):
    """Each departure of the reference from the program moves the loss
    by more than the harness's tolerance, or the worst gradient leaf by
    more than its own."""
    model, data, labels = system
    w = builder.reference_weights(model.params, TINY)

    def layers(fn):
        return ref.Weights(dict(w.arrays, layers=[fn(lw)
                                                  for lw in w["layers"]]),
                           w.spec)

    if fault == "a_layer_dropped":
        bad = ref.Weights(dict(w.arrays, layers=w["layers"][:-1]), w.spec)
    elif fault == "another_chips_experts":
        bad = ref.Weights(w.arrays, w.spec._replace(experts_offset=0))
    elif fault == "five_experts_a_token":
        bad = ref.Weights(w.arrays, w.spec._replace(
            experts_per_token=w.spec.experts_per_token - 1))
    elif fault == "route_scale_one":
        bad = ref.Weights(w.arrays, w.spec._replace(route_scale=1.0))
    elif fault == "latent_norm_weight_dropped":
        bad = layers(lambda lw: dict(lw, kvn_g=jnp.ones_like(lw["kvn_g"])))
    elif fault == "shared_expert_dropped":
        bad = layers(lambda lw: dict(lw, sh_down_w=lw["sh_down_w"] * 0)
                     if "sh_down_w" in lw else lw)
    else:
        bad = ref.Weights(w.arrays, w.spec._replace(rope_theta=1e30))
    value, grads = system_side
    ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
    got = ref.loss(bad, ids, tgt, HEADS)
    if abs(got - value) / value > train.TOL_LOSS_REL:
        return
    _, bad_grads = ref.loss_and_grads(bad, ids, tgt, HEADS)
    worst = max(float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
                for g, r in zip(jax.tree.leaves(grads),
                                jax.tree.leaves(bad_grads)))
    assert worst > train.TOL_GRAD_REL


def test_weights_is_a_pytree_with_a_static_part(system):
    model, *_ = system
    w = builder.reference_weights(model.params, TINY)
    assert w.spec == ref.Spec(qk_nope=8, qk_rope=4, experts_total=8,
                              experts_offset=2, experts_per_token=3,
                              route_scale=2.446, rope_theta=8e5, eps=1e-5)
    leaves, tree = jax.tree.flatten(w)
    assert len(leaves) == len(jax.tree.leaves(model.params))
    again = jax.tree.unflatten(tree, leaves)
    assert again.spec == w.spec and again["tok"] is w["tok"]
    # views, no copies
    assert w["layers"][0]["kva_w"] is \
        model.params["1"]["0"]["1"]["kva_weight"]
    assert w["layers"][1]["sh_up_w"] is \
        model.params["2"]["1"]["1"]["shared"]["up_weight"]
    assert "router_w" not in w["layers"][0]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(ref))
    mods = {n.module or "" for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert not [m for m in mods if m.startswith(("bigdl_tpu", "benchmarks"))]


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("scoring_func", "softmax"), ("n_group", 8),
    ("rope_scaling", {"type": "yarn"})])
def test_the_builder_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match="asks for something else"):
        builder.build(dict(TINY, **{key: value}))


# -- the cell ------------------------------------------------------------

def test_the_cell_and_its_files():
    loaded = manifest.load_cell(CELL)
    assert loaded["kind"] == "train" and loaded["chips"] == 1
    assert (loaded["traffic"]["batch_per_chip"],
            loaded["traffic"]["seq_len"]) == (2, 8192)
    assert loaded["traffic"]["optimizer"] == {
        "name": "AdamW", "learning_rate": 0.0001, "beta1": 0.9,
        "beta2": 0.95, "weight_decay": 0.1}
    assert (loaded["traffic"]["warmup_steps"],
            loaded["traffic"]["trace_after_steps"],
            loaded["traffic"]["trace_steps"]) == (4, 2, 4)
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "train.records_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW_METRICS) | {
        "step.device_ms", "step.forward_ms", "step.backward_ms",
        "step.optimizer_update_ms", "step.head_loss_ms",
        "step.unscoped_share", "step.update_fused_ms",
        "device.idle_share.train", "train_loop.input_wait_share",
        "train_loop.host_ms_per_step",
        "train_loop.gap_attributed_share"} <= names
    # counted by another family's formulas, or only across chips
    assert not names & {"step.device_mfu", "flash_attention_roofline",
                        "collective.exposed_share",
                        "step.device_mfu.evabyte", "eva_attention_roofline",
                        "step.device_mfu.keye", "sparse_attention_roofline"}


def test_the_manifest_is_sound_with_the_cell_in_it():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    assert "kimi-vl-a3b-instruct" in [c["name"] for c in man["configs"]]
    assert CELL in [w["name"] for w in man["workloads"]]
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    # every metric all four earlier cells report, this one reports too
    four = {"opt-1.3b.train.seq2048", "opt-1.3b.train.dp4",
            "evabyte-6.5b.train.long", "keye-vl-2.0-30b-a3b.train.seq16384"}
    for metric in man["end_to_end"] + man["per_layer"]:
        if four <= set(metric.get("workloads", ())):
            assert CELL in metric["workloads"], metric["name"]


def test_the_entries_this_cell_adds_keep_the_drivers_limits():
    """The driver refuses a ``why`` (or any string) over 200 characters
    before any run; ``check_manifest`` does not count them."""
    man = manifest.load_manifest()
    added = [e for key in ("configs", "workloads", "per_layer")
             for e in man[key]
             if "kimi" in e["name"] or e["name"] in NEW_METRICS]
    assert len(added) == 7
    for entry in added:
        assert len(entry["name"]) <= 64
        if "layer" in entry:
            # a later cell with the same scopes or kernels may join
            assert CELL in entry["workloads"]
            assert set(entry) == {"name", "unit", "better", "source",
                                  "layer", "moves", "workloads"}
        for value in entry.values():
            if isinstance(value, str):
                assert 1 <= len(value) <= 200 and value.isprintable() \
                    and value.isascii(), (entry["name"], value)


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` under the same name;
    only the depth, the experts held and the vocabulary differ, and
    ``reduced`` says so."""
    published = {
        "vocab_size": 163840, "max_position_embeddings": 131072,
        "hidden_size": 2048, "intermediate_size": 11264,
        "moe_intermediate_size": 1408, "num_hidden_layers": 27,
        "num_attention_heads": 16, "n_shared_experts": 2,
        "n_routed_experts": 64, "ep_size": 1,
        "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
        "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "num_experts_per_tok": 6, "moe_layer_freq": 1,
        "first_k_dense_replace": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "seq_aux": True,
        "num_key_value_heads": 16, "hidden_act": "silu",
        "rms_norm_eps": 1e-05, "rope_theta": 800000, "rope_scaling": None,
        "attention_bias": False, "tie_word_embeddings": False}
    assert set(published) <= set(FULL)
    differs = sorted(k for k, v in published.items() if FULL[k] != v)
    assert differs == sorted(FULL["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert FULL["published"] == {k: published[k] for k in FULL["reduced"]}
    assert set(FULL["reduced_why"]) == set(FULL["reduced"])
    assert (FULL["num_hidden_layers"], FULL["n_routed_experts"],
            FULL["vocab_size"]) == (6, 8, 20480)
    # the floors a cut keeps to: the dense layer and at least four expert
    # layers, eight experts, an eighth of the vocabulary
    assert FULL["num_hidden_layers"] - FULL["first_k_dense_replace"] >= 4
    assert FULL["vocab_size"] * 8 == published["vocab_size"]
    assert 0 <= FULL["experts_offset"] <= 64 - 8
    assert "eight chips" in FULL["stands_for"]
    assert FULL["source"] == ("https://huggingface.co/moonshotai/"
                              "Kimi-VL-A3B-Instruct/blob/main/config.json")
    said = " ".join(FULL["assumed"])
    for what in ("no vision tower", "half-split", "bias_update_rate 0.001",
                 "no seq_aux loss", "0.28 / sqrt(hidden)", "dtype policy",
                 "unit variance", "1% of the largest leaf"):
        assert what in said, what


FAULTS = ("last_attention_dropped", "rotary_part_dropped",
          "shared_expert_twice")


@pytest.fixture(scope="module")
def rehearsal():
    """ONE run of the cell at rehearsal size through ``run.main``, with
    ``train.check`` itself called four times on the finished job: as it
    is, and with each of ``FAULTS`` planted: the reference's LAST
    attention dropped (its ``o_w`` zeroed in the adapter); the program's
    rotary score part taken out; the program's shared expert counted
    twice."""
    import contextlib
    import io

    from bigdl_tpu.nn import linear
    from bigdl_tpu.ops.pallas import latent_attention
    seen = {}
    real_check = train.check
    real_core = latent_attention.latent_attention_xla
    real_ffn = linear.GatedFFN.apply

    def twice(self, params, state, x, **kw):
        y, state = real_ffn(self, params, state, x, **kw)
        return (2 * y if self.d_ff == 2 * TINY["moe_intermediate_size"]
                else y), state

    def all_of_them(ctx, bld, model, *rest):
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
        seen["last_attention_dropped"] = real_check(ctx, Dropped, model,
                                                    *rest)
        with pytest.MonkeyPatch.context() as planted:
            planted.setattr(
                latent_attention, "latent_attention_xla",
                lambda qn, qr, kn, kr, v: real_core(
                    qn, jnp.zeros_like(qr), kn, kr, v))
            seen["rotary_part_dropped"] = real_check(ctx, bld, model, *rest)
        with pytest.MonkeyPatch.context() as planted:
            planted.setattr(linear.GatedFFN, "apply", twice)
            seen["shared_expert_twice"] = real_check(ctx, bld, model, *rest)
        seen["good"] = real_check(ctx, bld, model, *rest)
        return seen["good"]

    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(train, "check", all_of_them)
        # toy widths round coarsely in bf16, 128 tokens average little,
        # and at 32 wide a rounding flips one of a token's three experts
        # often enough to move a router's gradient by 0.39 (seed
        # 3000000911): the rehearsal's own tolerances, as the keye
        # cell's; every fault moves leaves by more
        mp.setattr(train, "TOL_GRAD_REL", 0.6)
        mp.setattr(train, "TOL_LOSS_REL", 2e-3)
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", CELL, "--seed", "3000000911",
                           "--seconds", "0.5", "--trace", "0",
                           "--rehearsal"])
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), seen


def test_the_cells_rehearsal_end_to_end(rehearsal):
    rc, line, seen = rehearsal
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"train.records_per_s_per_chip",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert seen["good"]["ok"] and seen["good"]["param_dtype_ok"]


@pytest.mark.parametrize("fault", FAULTS)
def test_correct_can_fail_through_the_harness_own_check(rehearsal, fault):
    """``kinds/train.py``'s ``check``, floor and all, refuses each
    planted fault."""
    *_, seen = rehearsal
    bad = seen[fault]
    assert not bad["ok"]
    assert not (bad["loss_ok"] and bad["grad_ok"])


# -- the new readers -----------------------------------------------------

def _record(**over):
    rec = {"loaded": {"config": FULL}, "global_batch": 2, "chips": 1,
           "seq": 8192, "traced_steps": 4,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace_window": {"busy_s": 4.0, "window_ns": (0, 5_000_000_000),
                            "planes": ["/device:TPU:0"]},
           "trace_events": []}
    rec.update(over)
    return rec


def test_step_device_mfu_kimi_on_a_hand_written_record():
    reader = manifest.plugin("readers", "step_device_mfu_kimi")
    got = reader.read(_record(), {})
    # 45.64 TFLOP in a second of busy device a step: 23.2% of 197
    assert got["value"] == pytest.approx(100 * 45.64 / 197, rel=1e-3)
    assert got["attention"] / got["flops_per_step_per_chip"] \
        == pytest.approx(0.325, abs=2e-3)
    assert reader.read(_record(trace_window=None), {}) is None
    assert reader.read(_record(peaks=None), {}) is None


def test_mla_roofline_reads_every_kernel_and_nothing_without_a_trace():
    reader = manifest.plugin("readers", "kernel_roofline_kimi")
    spec = manifest.data_file("layer_metrics", "mla_attention_roofline")
    assert spec["reader"] == "kernel_roofline_kimi"
    plane = "/device:TPU:0"
    ms = 1_000_000

    def op(name, start, dur):
        return (plane, "XLA Ops", name, start, dur)

    # per step and layer: ONE forward call (a recomputed block keeps its
    # output) and a backward; the experts' grouped products are not the
    # attention's
    events, t = [], 0
    for _ in range(4 * 6):
        for name, dur in (
                ("%jvp_latent_attention_fwd_.1 = ...", 10 * ms),
                ("%transpose_jvp_latent_attention_dqdkdv__.1 = ...",
                 20 * ms),
                ("%gmm.3 = ...", 9 * ms)):
            events.append(op(name, t, dur))
            t += dur
    got = reader.read(_record(
        trace_events=events,
        trace_window={"busy_s": t / 1e9, "window_ns": (0, t),
                      "planes": [plane]}), spec["params"])
    assert got["calls"] == 48 and got["bound"] == "compute"
    assert got["kernel_s"] == pytest.approx(24 * 0.030)
    assert got["value"] == pytest.approx(
        100 * 4 * 0.0754 / (24 * 0.030), rel=1e-2)
    assert got["value"] < 100
    assert reader.read(_record(trace_window=None), spec["params"]) is None
    # the table the other cells' readers look their cost up in kept its own
    from benchmarks.readers import kernel_roofline
    assert {"flash_attention_train", "paged_attention_decode",
            "mla_attention_train"} <= set(kernel_roofline.COSTS)


@pytest.mark.parametrize("metric,hits,misses", [
    ("step.mla_attention_ms",
     ["jit(train_step)/jvp(model)/block_0/checkpoint/0__Residual/"
      "1_LatentAttention/mla_project/dot_general",
      "transpose(jvp(model))/block_2/checkpoint/mla_attention/pallas_call",
      "transpose(jvp(model))/block_2/mla_attention/pallas_call"],
     ["jvp(model)/block_1/1__Residual/1_ExpertShare/moe_shared/dot_general",
      "jvp(model)/block_0/0__Residual/1_LatentAttention/dot_general"]),
    ("step.moe_shared_ms",
     ["jvp(model)/block_1/checkpoint/1__Residual/1_ExpertShare/moe_shared/"
      "dot_general",
      "transpose(jvp(model))/block_3/1__Residual/moe_shared/custom_vjp"],
     ["jvp(model)/block_1/1__Residual/1_ExpertShare/moe_experts/gmm",
      "jvp(model)/block_1/1__Residual/1_ExpertShare/moe_router/sort",
      "jvp(model)/block_0/1__Residual/1_GatedFFN/dot_general"]),
    ("step.moe_routed_ms",
     ["jvp(model)/block_1/1__Residual/1_ExpertShare/moe_experts/gmm",
      "transpose(jvp(model))/block_4/checkpoint/rematted_computation/"
      "1__Residual/1_ExpertShare/moe_router/sort",
      "transpose(jvp(model))/block_2/moe_experts"],
     ["jvp(model)/block_1/1__Residual/1_ExpertShare/moe_shared/dot_general",
      "jvp(model)/block_0/1__Residual/1_GatedFFN/dot_general",
      "jvp(model)/block_1/0__Residual/1_LatentAttention/mla_project/"
      "dot_general"]),
])
def test_the_scope_metrics_are_data_files_only(metric, hits, misses):
    spec = manifest.data_file("layer_metrics", metric)
    assert spec["reader"] == "scope_device_ms"
    rx = re.compile(spec["params"]["include"])
    assert all(rx.search(s) for s in hits)
    assert not any(rx.search(s) for s in misses)


def test_the_routed_experts_are_read_as_step_moe_ms_reads_them():
    """``step.moe_routed_ms`` is this cell's reading of the scopes
    ``step.moe_ms`` reads in the keye cell: the same reader and the same
    pattern, under a name whose list of cells no accepted test pins."""
    mine = manifest.data_file("layer_metrics", "step.moe_routed_ms")
    theirs = manifest.data_file("layer_metrics", "step.moe_ms")
    assert (mine["reader"], mine["params"]) == (theirs["reader"],
                                                theirs["params"])
    assert {k: mine[k] for k in ("unit", "better", "source", "layer",
                                 "moves")} == {
        k: theirs[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert "W_o" in manifest.data_file(
        "layer_metrics", "step.mla_attention_ms")["what"]

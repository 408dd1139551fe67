"""The benchmark's side of the Keye family (ISSUE 31): the FLOP and byte
functions against hand-worked values, the plain reference shown able to
fail against the program at rehearsal size (tests/test_keye.py holds
the leaf-by-leaf agreement), the new cell's rehearsal end to end, its
manifest entries and configuration, and the new readers on hand-written
records."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, model_setup, run, shapes_keye
from benchmarks.builders import keye as builder
from benchmarks.kinds import train
from benchmarks.reference import keye as ref
from bigdl_tpu.tensor import DTypePolicy, policy_scope

CELL = "keye-vl-2.0-30b-a3b.train.seq16384"
FULL = manifest.data_file("configs", "keye-vl-2.0-30b-a3b")
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


# -- shapes_keye, by hand (ISSUE 31's arithmetic) -------------------------

def test_matmul_params_by_hand():
    params = shapes_keye.matmul_params(FULL)
    assert params["attention"] == 6 * 18_874_368       # q, k, v, out
    assert params["indexer"] == 6 * 2048 * (1024 + 64 + 16)
    assert params["router"] == 6 * 2048 * 128          # over ALL experts
    # 8 x 16 / 128 = one assignment a token lands on the 16 held
    assert shapes_keye.expected_local_assignments(FULL) == 1.0
    assert params["experts"] == 6 * 3 * 2048 * 768
    assert params["head"] == 2048 * 18992


def test_attention_pairs_by_hand():
    pairs = shapes_keye.attention_pairs(16384, 2048)
    assert pairs == {"causal": 134_225_920,
                     "selected": 2048 * 2049 // 2 + 14336 * 2048}
    assert pairs["selected"] == 31_458_304
    assert pairs["selected"] / pairs["causal"] == pytest.approx(0.234,
                                                                abs=1e-3)
    # below topk tokens the selection is all of the causal pairs
    short = shapes_keye.attention_pairs(1024, 2048)
    assert short["selected"] == short["causal"] == 1024 * 1025 // 2


def test_train_step_flops_by_hand():
    flops = shapes_keye.train_step_flops(FULL, 1, 16384)
    per_layer = 6.0 * 16384 * (18_874_368 + 2_260_992 + 262_144
                               + 4_718_592)            # 2.57 TFLOP
    assert flops["matmul"] == pytest.approx(6 * per_layer + flops["head"])
    assert flops["head"] == pytest.approx(3.82e12, rel=2e-3)
    assert flops["experts"] / 6 == pytest.approx(0.46e12, rel=1e-2)
    assert flops["attention"] / 6 == pytest.approx(1.80e12, rel=3e-3)
    assert flops["indexer"] / 6 == pytest.approx(0.40e12, rel=1e-2)
    assert flops["total"] == pytest.approx(32.47e12, rel=1e-3)
    # if every causal pair were attended: 46 TFLOP for attention alone
    dense = 7.0 * 2.0 * 32 * 128 * 134_225_920 * 6
    assert dense == pytest.approx(46.2e12, rel=1e-2)
    assert flops["experts"] / flops["total"] == pytest.approx(0.086,
                                                              abs=2e-3)


def test_sparse_attention_cost_by_hand():
    cost = shapes_keye.sparse_attention_train_cost(FULL, 1, 16384)
    assert cost["flops"] == shapes_keye.train_step_flops(
        FULL, 1, 16384)["attention"]
    wide, narrow = 16384 * 4096 * 2, 16384 * 512 * 2
    # forward Q O + K V; backward Q O dO dQ + K V dK dV
    assert cost["bytes"] == 6 * (6 * wide + 6 * narrow)
    from benchmarks import peaks, shapes
    least, bound = shapes.roofline_least_seconds(
        cost, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(0.0549, rel=1e-2)


# -- the reference against the program -----------------------------------

@pytest.fixture(scope="module")
def system():
    """The program's model in float32 at rehearsal size, every leaf moved
    off its initial value (norm weights start at 1, the index key's
    bias at 0)."""
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
    """128 tokens, top-48: most queries select. Loss and the worst leaf
    (tests/test_keye.py names every leaf)."""
    model, data, labels = system
    w = builder.reference_weights(model.params, TINY)
    value, grads = system_side
    ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
    assert ref.loss(w, ids, tgt, HEADS) == pytest.approx(value, rel=1e-5)
    ref_value, ref_grads = ref.loss_and_grads(w, ids, tgt, HEADS)
    assert ref_value == pytest.approx(value, rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    ref_flat = jax.tree.leaves(ref_grads)
    assert len(flat) == len(ref_flat) == 3 + 17 * TINY["num_hidden_layers"]
    for (path, g), r in zip(flat, ref_flat):
        assert isinstance(r, np.ndarray)      # fetched to the host
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("fault", [
    "a_layer_dropped", "topk_halved", "another_chips_experts",
    "seven_experts_a_token", "index_key_norm_bias_dropped",
    "qk_norm_dropped"])
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
    elif fault == "topk_halved":
        bad = ref.Weights(w.arrays, w.spec._replace(topk=w.spec.topk // 2))
    elif fault == "another_chips_experts":
        bad = ref.Weights(w.arrays, w.spec._replace(experts_offset=0))
    elif fault == "seven_experts_a_token":
        bad = ref.Weights(w.arrays, w.spec._replace(experts_per_token=1))
    elif fault == "index_key_norm_bias_dropped":
        bad = layers(lambda lw: dict(lw, ik_ln_b=lw["ik_ln_b"] * 0))
    else:
        bad = layers(lambda lw: dict(lw, qn_g=jnp.ones_like(lw["qn_g"]),
                                     kn_g=jnp.ones_like(lw["kn_g"])))
    value, grads = system_side
    ids, tgt = jnp.asarray(data - 1), jnp.asarray(labels - 1)
    got = ref.loss(bad, ids, tgt, HEADS)
    if abs(got - value) / value > train.TOL_LOSS_REL:
        return
    # the selection moves the loss by little at random weights: the
    # gradients see it
    _, bad_grads = ref.loss_and_grads(bad, ids, tgt, HEADS)
    worst = max(float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
                for g, r in zip(jax.tree.leaves(grads),
                                jax.tree.leaves(bad_grads)))
    assert worst > train.TOL_GRAD_REL


def test_weights_is_a_pytree_with_a_static_part(system):
    model, *_ = system
    w = builder.reference_weights(model.params, TINY)
    assert w.spec == ref.Spec(kv_heads=2, index_heads=2, topk=48,
                              experts_total=8, experts_offset=2,
                              experts_per_token=2, rope_theta=1e7, eps=1e-6)
    leaves, tree = jax.tree.flatten(w)
    assert len(leaves) == len(jax.tree.leaves(model.params))
    again = jax.tree.unflatten(tree, leaves)
    assert again.spec == w.spec and again["tok"] is w["tok"]
    # views, no copies
    assert w["layers"][0]["iq_w"] is model.params["1"]["0"]["1"]["iq_weight"]
    assert w["layers"][1]["gate_w"] is \
        model.params["2"]["1"]["1"]["gate_weight"]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(ref))
    mods = {n.module or "" for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert not [m for m in mods if m.startswith(("bigdl_tpu", "benchmarks"))]


def test_the_builder_refuses_two_counts_of_the_experts_held():
    with pytest.raises(ValueError, match="both count the experts held"):
        builder.build(dict(TINY, num_local_experts=8))


# -- the cell ------------------------------------------------------------

def test_the_cell_and_its_files():
    loaded = manifest.load_cell(CELL)
    assert loaded["kind"] == "train" and loaded["chips"] == 1
    assert (loaded["traffic"]["batch_per_chip"],
            loaded["traffic"]["seq_len"]) == (1, 16384)
    assert loaded["traffic"]["optimizer"] == {
        "name": "AdamW", "learning_rate": 0.0001, "beta1": 0.9,
        "beta2": 0.95, "weight_decay": 0.1}
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "train.records_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in loaded["per_layer"]}
    assert {"step.device_mfu.keye", "sparse_attention_roofline",
            "step.sparse_attention_ms", "step.select_topk_ms", "step.moe_ms",
            "step.device_ms", "step.forward_ms", "step.backward_ms",
            "step.optimizer_update_ms", "step.head_loss_ms",
            "step.unscoped_share", "step.update_fused_ms",
            "device.idle_share.train", "train_loop.input_wait_share",
            "train_loop.host_ms_per_step",
            "train_loop.gap_attributed_share"} == names
    # counted by another family's formulas, or only across chips
    assert not names & {"step.device_mfu", "flash_attention_roofline",
                        "collective.exposed_share",
                        "step.device_mfu.evabyte", "eva_attention_roofline",
                        "step.eva_attention_ms"}


def test_the_manifest_is_sound_with_the_cell_in_it():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    assert [c["name"] for c in man["configs"]][-1] == "keye-vl-2.0-30b-a3b"
    assert [w["name"] for w in man["workloads"]][-1] == CELL
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1


def test_the_entries_this_cell_adds_keep_the_drivers_limits():
    """The driver refuses a ``why`` (or any string) over 200 characters
    before any run; ``check_manifest`` does not count them."""
    man = manifest.load_manifest()
    added = [e for key in ("configs", "workloads", "per_layer")
             for e in man[key]
             if "keye" in e["name"] or e["name"] in (
                 "sparse_attention_roofline", "step.sparse_attention_ms",
                 "step.select_topk_ms", "step.moe_ms")]
    assert len(added) == 7
    for entry in added:
        assert len(entry["name"]) <= 64
        if "layer" in entry:
            assert entry["workloads"] == [CELL]
        for value in entry.values():
            if isinstance(value, str):
                assert 1 <= len(value) <= 200 and value.isprintable() \
                    and value.isascii(), (entry["name"], value)


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` under the same name;
    only the depth, the experts held and the vocabulary differ, and
    ``reduced`` says so."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if FULL[k] != v)
    assert differs == sorted(FULL["reduced"]) == [
        "num_experts", "num_hidden_layers", "num_local_experts",
        "vocab_size"]
    assert FULL["published"] == {k: published[k] for k in FULL["reduced"]}
    assert set(FULL["reduced_why"]) == set(FULL["reduced"])
    assert (FULL["num_hidden_layers"], FULL["num_experts"],
            FULL["vocab_size"]) == (6, 16, 18992)
    # the floors a cut keeps to: four layers, eight experts, an eighth
    assert FULL["vocab_size"] * 8 == published["vocab_size"]
    assert 0 <= FULL["experts_offset"] <= 128 - 16
    assert "eight chips" in FULL["stands_for"]


FAULTS = ("last_attention_dropped", "l_i_gradient_dropped",
          "indexer_input_not_detached")


@pytest.fixture(scope="module")
def rehearsal():
    """ONE run of the cell at rehearsal size through ``run.main``, with
    ``train.check`` itself called four times on the finished job: as it
    is, and with each of ``FAULTS`` planted: the reference's LAST
    attention dropped (its ``o_w`` zeroed in the adapter); the program's
    injection of L_I's gradient taken out; the program's indexer reading
    its input NOT detached (``stop_gradient`` the identity while
    ``SparseSelectAttention.indexer`` is traced, and nowhere else)."""
    import contextlib
    import io

    from bigdl_tpu.nn import attention
    seen = {}
    real_check = train.check
    real_indexer = attention.SparseSelectAttention.indexer

    def leaky_indexer(self, params, h):
        with pytest.MonkeyPatch.context() as inner:
            inner.setattr(jax.lax, "stop_gradient", lambda x: x)
            return real_indexer(self, params, h)

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
            planted.setattr(attention, "_with_gradient_of",
                            lambda y, aux: y)
            seen["l_i_gradient_dropped"] = real_check(ctx, bld, model, *rest)
        with pytest.MonkeyPatch.context() as planted:
            planted.setattr(attention.SparseSelectAttention, "indexer",
                            leaky_indexer)
            seen["indexer_input_not_detached"] = real_check(ctx, bld, model,
                                                            *rest)
        seen["good"] = real_check(ctx, bld, model, *rest)
        return seen["good"]

    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(train, "check", all_of_them)
        # toy widths round coarsely in bf16, 128 tokens average little,
        # and at 32 wide a rounding flips a token's second expert of two
        # often enough to move the router's gradient by 0.39 (seed
        # 3000000001): the rehearsal's own tolerances; every fault moves
        # leaves by more
        mp.setattr(train, "TOL_GRAD_REL", 0.6)
        mp.setattr(train, "TOL_LOSS_REL", 2e-3)
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", CELL, "--seed", "3000000001",
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
    planted fault; the two of the indexer's objective by the GRADIENTS
    (the forward pass, and so the loss, is the same program)."""
    *_, seen = rehearsal
    bad = seen[fault]
    assert not bad["ok"]
    assert not (bad["loss_ok"] and bad["grad_ok"])
    if fault != "last_attention_dropped":
        assert bad["loss_ok"] and not bad["grad_ok"]
        leaf = bad["grad_worst_leaves"][0][1]
        wanted = ("iq_w", "ik_w", "ik_ln_g", "ik_ln_b", "iw_w") \
            if fault == "l_i_gradient_dropped" else ()
        assert not wanted or any(name in leaf for name in wanted), leaf


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


def test_step_device_mfu_keye_on_a_hand_written_record():
    reader = manifest.plugin("readers", "step_device_mfu_keye")
    got = reader.read(_record(), {})
    # 32.47 TFLOP in a second of busy device a step: 16.5% of 197
    assert got["value"] == pytest.approx(100 * 32.47 / 197, rel=1e-3)
    assert got["attention"] / got["flops_per_step_per_chip"] \
        == pytest.approx(0.333, abs=2e-3)
    assert reader.read(_record(trace_window=None), {}) is None
    assert reader.read(_record(peaks=None), {}) is None


def test_sparse_roofline_reads_every_kernel_and_nothing_without_a_trace():
    reader = manifest.plugin("readers", "kernel_roofline_keye")
    spec = manifest.data_file("layer_metrics", "sparse_attention_roofline")
    assert spec["reader"] == "kernel_roofline_keye"
    plane = "/device:TPU:0"
    ms = 1_000_000

    def op(name, start, dur):
        return (plane, "XLA Ops", name, start, dur)

    # per step: two forward calls a layer (recomputation) and a backward;
    # the indexer's own kernels are NOT the selected attention
    events, t = [], 0
    for _ in range(4 * 6):
        for name, dur in (
                ("%jvp_sparse_attention_fwd_.1 = ...", 20 * ms),
                ("%sparse_attention_fwd.9 = ...", 20 * ms),
                ("%transpose_jvp_sparse_attention_dqdkdv__.1 = ...",
                 50 * ms),
                ("%sparse_kept_probs.3 = ...", 9 * ms),
                ("%sparse_select_rows.3 = ...", 9 * ms)):
            events.append(op(name, t, dur))
            t += dur
    got = reader.read(_record(
        trace_events=events,
        trace_window={"busy_s": t / 1e9, "window_ns": (0, t),
                      "planes": [plane]}), spec["params"])
    assert got["calls"] == 72 and got["bound"] == "compute"
    assert got["kernel_s"] == pytest.approx(24 * 0.090)
    assert got["value"] == pytest.approx(
        100 * 4 * 0.05494 / (24 * 0.090), rel=1e-2)
    assert got["value"] < 100
    assert reader.read(_record(trace_window=None), spec["params"]) is None
    # the table the other cells' readers look their cost up in kept its own
    from benchmarks.readers import kernel_roofline
    assert {"flash_attention_train", "paged_attention_decode",
            "sparse_attention_train"} <= set(kernel_roofline.COSTS)


@pytest.mark.parametrize("metric,hits,misses", [
    ("step.sparse_attention_ms",
     ["jit(train_step)/jvp(model)/block_0/checkpoint/0__Residual/"
      "1_SparseSelectAttention/indexer/dot_general",
      "transpose(jvp(model))/block_2/checkpoint/select_topk/pallas_call",
      "transpose(jvp(model))/block_2/sparse_attention/pallas_call",
      "transpose(jvp(model))/block_2/indexer_loss/pallas_call"],
     ["jvp(model)/block_0/1__Residual/1_ExpertShare/moe_experts/gmm",
      "jvp(model)/block_0/0__Residual/1_SparseSelectAttention/dot_general"]),
    ("step.select_topk_ms",
     ["jvp(model)/block_1/checkpoint/select_topk/pallas_call"],
     ["jvp(model)/block_1/checkpoint/sparse_attention/pallas_call",
      "jvp(model)/block_1/checkpoint/indexer/pallas_call"]),
    ("step.moe_ms",
     ["jvp(model)/block_0/checkpoint/1__Residual/1_ExpertShare/moe_router/"
      "dot_general",
      "transpose(jvp(model))/block_0/1__Residual/1_ExpertShare/moe_experts/"
      "cond/branch_1_fun/gmm",
      "transpose(jvp(model))/block_0/1__Residual/moe_experts/pallas_call"],
     ["jvp(model)/block_0/0__Residual/1_SparseSelectAttention/indexer/dot"]),
])
def test_the_scope_metrics_are_data_files_only(metric, hits, misses):
    spec = manifest.data_file("layer_metrics", metric)
    assert spec["reader"] == "scope_device_ms"
    rx = re.compile(spec["params"]["include"])
    assert all(rx.search(s) for s in hits)
    assert not any(rx.search(s) for s in misses)


"""The whole command at tiny widths on the CPU: the driver's model
set-up holds the parameters' bytes and little else, training and serving
still run through the program's normal path without ``grad_params``, the
last line keeps to the contract, and ``correct`` can fail."""
import json

import jax
import pytest

from benchmarks import model_setup, run
from benchmarks.builders import opt as builder
from benchmarks.kinds import serve, train

CFG = dict(hidden_size=64, ffn_dim=256, num_attention_heads=2,
           num_hidden_layers=2, vocab_size=512,
           max_position_embeddings=128, dropout=0.0)


def test_lean_setup_holds_the_parameters_and_nothing_else():
    """ISSUE 23: ``materialize`` leaves 5.2 x the parameters' bytes live;
    the driver's set-up must stay within a few percent of 1 x."""
    lean = builder.build(CFG)
    model_setup.materialize_lean(lean, 3_000_000_001)
    own = model_setup.tree_bytes(lean.params)
    assert own > 0 and model_setup.held_bytes(lean) <= 1.03 * own
    assert lean.grad_params is None
    assert all(m.grad_params is None for m in lean.modules)
    # the same values materialize() would have given
    fat = builder.build(CFG).materialize(
        jax.random.PRNGKey(3_000_000_001 & 0x7FFFFFFF))
    for a, b in zip(jax.tree.leaves(lean.params),
                    jax.tree.leaves(fat.params)):
        assert (a == b).all()
    assert model_setup.held_bytes(fat) > 4 * own, \
        "the program stopped allocating the zero grad trees: drop the " \
        "benchmark's workaround (PERF.md section 7)"
    model_setup.unbind(lean)
    assert lean.params is None and lean.modules[0].params is None


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_train_rehearsal_end_to_end_and_correct_can_fail(capsys,
                                                         monkeypatch):
    seen = {}
    real_check = train.check

    def both(ctx, bld, model, *rest):
        class Dropped:
            """The adapter with the reference's LAST layer perturbed."""
            build, criterion = bld.build, bld.criterion

            @staticmethod
            def reference_weights(params, cfg):
                w = bld.reference_weights(params, cfg)
                last = dict(w["layers"][-1])
                last["fc2_w"] = last["fc2_w"] * 0.0
                return dict(w, layers=w["layers"][:-1] + [last])
        seen["bad"] = real_check(ctx, Dropped, model, *rest)
        seen["good"] = real_check(ctx, bld, model, *rest)
        return seen["good"]

    monkeypatch.setattr(train, "check", both)
    # toy widths round coarsely in bf16: the rehearsal's own tolerance
    monkeypatch.setattr(train, "TOL_GRAD_REL", 0.2)
    monkeypatch.setattr(train, "TOL_LOSS_REL", 2e-4)
    rc = run.main(["--workload", "opt-1.3b.train.seq2048", "--seed",
                   "3000000001", "--seconds", "0.5", "--trace", "0",
                   "--rehearsal"])
    line, _ = _last_line(capsys)
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"train.records_per_s_per_chip",
                                    "setup_s"}
    assert line["metrics"]["train.records_per_s_per_chip"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] > 0
    assert seen["good"]["ok"] and seen["good"]["param_dtype_ok"] is not False
    assert not seen["bad"]["ok"]
    assert not (seen["bad"]["loss_ok"] and seen["bad"]["grad_ok"])


def test_serve_rehearsal_end_to_end_and_correct_can_fail(capsys,
                                                         monkeypatch):
    seen = {}
    real_check = serve.check

    def both(ctx, bld, model, record, new_tokens):
        seen["good"] = real_check(ctx, bld, model, record, new_tokens)
        # ONE emitted token of every request replaced by another token
        vocab = ctx.config["vocab_size"]
        tampered = dict(record, outputs={
            rid: toks[:3] + [toks[3] % vocab + 1] + toks[4:]
            for rid, toks in record["outputs"].items()})
        seen["bad"] = real_check(ctx, bld, model, tampered, new_tokens)
        return seen["good"]

    monkeypatch.setattr(serve, "check", both)
    rc = run.main(["--workload", "opt-6.7b.serve.chat", "--candidate",
                   "--seed", "3000000001", "--seconds", "2", "--trace",
                   "1", "--rehearsal"])
    line, out = _last_line(capsys)
    assert rc == 0
    assert line["correct"] is True, out[-6:]
    assert line["attempted"] == 8 and line["failed"] == 0
    # a traced run reports the per-layer metrics it can read here: the
    # device-trace ones find no TPU plane and are left out, never guessed
    assert {"loadgen.lateness_p95_ms", "serve_loop.queue_wait_p95_ms",
            "serve_loop.slot_occupancy"} <= set(line["metrics"])
    assert "device.idle_share.serve" not in line["metrics"]
    assert "busy_s" not in line["device"]
    assert seen["good"]["ok"] and seen["good"]["sampled"] == 4
    assert not seen["bad"]["ok"] and not seen["bad"]["logits_ok"]
    assert seen["bad"]["logit_deficit_max"] > serve.TOL_LOGIT


def test_no_accelerator_is_a_non_zero_exit_and_no_result(capsys):
    rc = run.main(["--workload", "opt-1.3b.train.seq2048", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "does not fall back" in out.err
    assert not any(ln.startswith("{") for ln in out.out.splitlines())
    with pytest.raises(model_setup.NoAccelerator):
        model_setup.pick_devices(1, rehearsal=False)

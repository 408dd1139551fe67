"""EvaByte through the program's ``EvaByteLM``: pre-norm blocks of
``nn.EvaAttention`` and ``nn.GatedFFN`` on a float32 residual stream,
RMS norms with a unit offset, eight byte heads, each block recomputed in
the backward pass (the model's own recipe). This is the only place that
knows the program's parameter tree; the reference sees named arrays."""
from __future__ import annotations

from benchmarks.reference import evabyte as reference

# the driver kind asks for a criterion without a configuration, so the
# family's two constants live here; build() refuses a file that differs
PRED_HEADS, VOCAB = 8, 320


def build(cfg: dict):
    """The program's model object for a configuration file."""
    from bigdl_tpu.models import EvaByteLM
    if (cfg["num_pred_heads"], cfg["vocab_size"]) != (PRED_HEADS, VOCAB):
        raise ValueError(
            f"builders/evabyte.py scores {PRED_HEADS} heads of {VOCAB} "
            f"bytes; the configuration has {cfg['num_pred_heads']} of "
            f"{cfg['vocab_size']}")
    return EvaByteLM(
        cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],
        ffn_dim=cfg["intermediate_size"], window=cfg["window_size"],
        chunk=cfg["chunk_size"], num_pred_heads=cfg["num_pred_heads"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        remat="per_block")


def criterion():
    from bigdl_tpu import nn
    return nn.MultiBytePredictionCriterion(PRED_HEADS, VOCAB)


def reference_weights(params, cfg: dict):
    """The program's parameter tree (or a gradient tree of the same
    shape) as the reference's named weights. Views, no copies."""
    n = cfg["num_hidden_layers"]
    layers = []
    for i in range(n):
        att, ffn = params[str(1 + i)]["0"], params[str(1 + i)]["1"]
        layers.append({
            "ln1_g": att["0"]["weight"],
            "q_w": att["1"]["q_weight"], "k_w": att["1"]["k_weight"],
            "v_w": att["1"]["v_weight"], "o_w": att["1"]["out_weight"],
            "phi": att["1"]["phi"], "mu": att["1"]["mu"],
            "ln2_g": ffn["0"]["weight"],
            "gate_w": ffn["1"]["gate_weight"],
            "up_w": ffn["1"]["up_weight"],
            "down_w": ffn["1"]["down_weight"]})
    arrays = {"tok": params["0"]["tok"], "layers": layers,
              "lnf_g": params[str(n + 1)]["weight"],
              "head_w": params[str(n + 2)]["weight"]}
    return reference.Weights(arrays, reference.Spec(
        window=cfg["window_size"], chunk=cfg["chunk_size"],
        pred_heads=cfg["num_pred_heads"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"]))

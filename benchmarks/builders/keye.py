"""Keye-VL-2.0-30B-A3B's language model through the program's ``KeyeLM``:
pre-norm blocks of ``nn.SparseSelectAttention`` and
``parallel.expert.ExpertShare`` on a float32 residual stream, each block
recomputed in the backward pass (the model's own recipe), ONE chip's
share of each layer: the configuration's ``num_experts`` experts held of
``published.num_experts``, from ``experts_offset``, and ``vocab_size``
rows of the vocabulary. This is the only place that knows the program's
parameter tree; the reference sees named arrays."""
from __future__ import annotations

from benchmarks.reference import keye as reference


def build(cfg: dict):
    """The program's model object for a configuration file."""
    from bigdl_tpu.models import KeyeLM
    sa = cfg["sa_config"]
    if cfg["num_experts"] != cfg["num_local_experts"]:
        raise ValueError("builders/keye.py: num_experts and "
                         "num_local_experts both count the experts held")
    return KeyeLM(
        cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_layers=cfg["num_hidden_layers"],
        expert_dim=cfg["moe_intermediate_size"],
        experts_total=cfg["published"]["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"],
        experts_offset=cfg["experts_offset"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], topk=sa["topk"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        remat="per_block")


def criterion():
    from bigdl_tpu import nn
    return nn.CrossEntropyCriterion()


def reference_weights(params, cfg: dict):
    """The program's parameter tree (or a gradient tree of the same
    shape) as the reference's named weights. Views, no copies."""
    n = cfg["num_hidden_layers"]
    sa = cfg["sa_config"]
    layers = []
    for i in range(n):
        att, moe = params[str(1 + i)]["0"], params[str(1 + i)]["1"]
        layers.append({
            "ln1_g": att["0"]["weight"],
            "q_w": att["1"]["q_weight"], "k_w": att["1"]["k_weight"],
            "v_w": att["1"]["v_weight"], "o_w": att["1"]["out_weight"],
            "qn_g": att["1"]["q_norm"], "kn_g": att["1"]["k_norm"],
            "iq_w": att["1"]["iq_weight"], "ik_w": att["1"]["ik_weight"],
            "ik_ln_g": att["1"]["ik_norm_weight"],
            "ik_ln_b": att["1"]["ik_norm_bias"],
            "iw_w": att["1"]["iw_weight"],
            "ln2_g": moe["0"]["weight"],
            "router_w": moe["1"]["router_weight"],
            "gate_w": moe["1"]["gate_weight"],
            "up_w": moe["1"]["up_weight"],
            "down_w": moe["1"]["down_weight"]})
    arrays = {"tok": params["0"]["tok"], "layers": layers,
              "lnf_g": params[str(n + 1)]["weight"],
              "head_w": params[str(n + 2)]["weight"]}
    return reference.Weights(arrays, reference.Spec(
        kv_heads=cfg["num_key_value_heads"],
        index_heads=sa["indexer_num_heads"], topk=sa["topk"],
        experts_total=cfg["published"]["num_experts"],
        experts_offset=cfg["experts_offset"],
        experts_per_token=cfg["num_experts_per_tok"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"]))

"""Kimi-VL-A3B-Instruct's language model through the program's
``KimiLM``: pre-norm blocks of ``nn.LatentAttention`` and, after the
leading dense ``nn.GatedFFN`` layer, ``parallel.expert.ExpertShare``
with its sigmoid router, selection bias and shared expert, on a float32
residual stream, each block recomputed in the backward pass (the
model's own recipe); ONE chip's share of each layer: the configuration's
``n_routed_experts`` experts held of ``published.n_routed_experts``,
from ``experts_offset``, and ``vocab_size`` rows of the vocabulary. This
is the only place that knows the program's parameter tree; the reference
sees named arrays."""
from __future__ import annotations

from benchmarks.reference import kimi as reference


def build(cfg: dict):
    """The program's model object for a configuration file."""
    from bigdl_tpu.models import KimiLM
    if cfg["q_lora_rank"] is not None or cfg["rope_scaling"] is not None \
            or (cfg["n_group"], cfg["topk_group"]) != (1, 1) \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" \
            or not cfg["norm_topk_prob"] or cfg["moe_layer_freq"] != 1:
        raise ValueError(
            "builders/kimi.py builds direct queries, plain RoPE, an "
            "ungrouped sigmoid noaux_tc router with normalised weights "
            "and an expert layer after every leading dense one; the "
            "configuration asks for something else")
    return KimiLM(
        cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        num_layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        ffn_dim=cfg["intermediate_size"],
        expert_dim=cfg["moe_intermediate_size"],
        experts_total=cfg["published"]["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        shared_experts=cfg["n_shared_experts"],
        route_scale=cfg["routed_scaling_factor"],
        bias_update_rate=cfg["bias_update_rate"],
        experts_held=cfg["n_routed_experts"],
        experts_offset=cfg["experts_offset"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        remat="per_block")


def criterion():
    from bigdl_tpu import nn
    return nn.CrossEntropyCriterion()


def reference_weights(params, cfg: dict):
    """The program's parameter tree (or a gradient tree of the same
    shape) as the reference's named weights. Views, no copies."""
    n = cfg["num_hidden_layers"]
    layers = []
    for i in range(n):
        att, ffn = params[str(1 + i)]["0"], params[str(1 + i)]["1"]
        lw = {"ln1_g": att["0"]["weight"],
              "q_w": att["1"]["q_weight"], "kva_w": att["1"]["kva_weight"],
              "kvn_g": att["1"]["kv_norm"], "kvb_w": att["1"]["kvb_weight"],
              "o_w": att["1"]["out_weight"],
              "ln2_g": ffn["0"]["weight"],
              "gate_w": ffn["1"]["gate_weight"],
              "up_w": ffn["1"]["up_weight"],
              "down_w": ffn["1"]["down_weight"]}
        if i >= cfg["first_k_dense_replace"]:
            shared = ffn["1"]["shared"]
            lw.update({"router_w": ffn["1"]["router_weight"],
                       "sh_gate_w": shared["gate_weight"],
                       "sh_up_w": shared["up_weight"],
                       "sh_down_w": shared["down_weight"]})
        layers.append(lw)
    arrays = {"tok": params["0"]["tok"], "layers": layers,
              "lnf_g": params[str(n + 1)]["weight"],
              "head_w": params[str(n + 2)]["weight"]}
    return reference.Weights(arrays, reference.Spec(
        qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
        experts_total=cfg["published"]["n_routed_experts"],
        experts_offset=cfg["experts_offset"],
        experts_per_token=cfg["num_experts_per_tok"],
        route_scale=cfg["routed_scaling_factor"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"]))

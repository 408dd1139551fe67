"""SmallThinker-21BA3B-Instruct's language model through the program's
``SmallThinkerLM``: blocks whose attention is a window or a global layer
as ``sliding_window_layout`` / ``rope_layout`` lay them out, and whose
``parallel.expert.ExpertShare`` of ReGLU experts is routed from the
attention's normed input, on a float32 residual stream, each block
recomputed in the backward pass; the first ``num_hidden_layers`` layers,
and ONE chip's share of each layer: ``moe_num_primary_experts`` held of
``published.moe_num_primary_experts`` from ``experts_offset``, and
``vocab_size`` rows of the vocabulary. This is the only place that knows
the program's parameter tree; the reference sees named arrays."""
from __future__ import annotations

from benchmarks.reference import smallthinker as reference


def layout(cfg: dict) -> list:
    """Each layer as run: (windowed, rotary), the layouts' first
    ``num_hidden_layers`` entries (the program's own reading)."""
    from bigdl_tpu.models.transformer.model import attention_layout
    return attention_layout(cfg["sliding_window_layout"],
                            cfg["rope_layout"], cfg["num_hidden_layers"])


def build(cfg: dict):
    """The program's model object for a configuration file."""
    from bigdl_tpu.models import SmallThinkerLM
    if not cfg["moe_primary_router_apply_softmax"] \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["rope_scaling"] is not None:
        raise ValueError(
            "builders/smallthinker.py builds a softmax router normalised "
            "over the chosen experts, plain RoPE and an untied head; the "
            "configuration asks for something else")
    n = cfg["num_hidden_layers"]
    return SmallThinkerLM(
        cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_layers=n, window=cfg["sliding_window_size"],
        window_layout=cfg["sliding_window_layout"][:n],
        rope_layout=cfg["rope_layout"][:n],
        rope_theta=float(cfg["rope_theta"]),
        expert_dim=cfg["moe_ffn_hidden_size"],
        experts_total=cfg["published"]["moe_num_primary_experts"],
        experts_per_token=cfg["moe_num_active_primary_experts"],
        experts_held=cfg["moe_num_primary_experts"],
        experts_offset=cfg["experts_offset"], rms_eps=cfg["rms_norm_eps"],
        remat="per_block")


def criterion():
    from bigdl_tpu import nn
    return nn.CrossEntropyCriterion()


def spec(cfg: dict) -> reference.Spec:
    return reference.Spec(
        layout=tuple(layout(cfg)), num_kv_heads=cfg["num_key_value_heads"],
        window=cfg["sliding_window_size"],
        rope_theta=float(cfg["rope_theta"]),
        experts_total=cfg["published"]["moe_num_primary_experts"],
        experts_offset=cfg["experts_offset"],
        experts_per_token=cfg["moe_num_active_primary_experts"],
        eps=cfg["rms_norm_eps"])


def reference_weights(params, cfg: dict):
    """The program's parameter tree (or a gradient tree of the same
    shape) as the reference's named weights. Views, no copies."""
    n = cfg["num_hidden_layers"]
    layers = []
    for i in range(n):
        block = params[str(1 + i)]
        att, moe = block["1"], block["3"]
        layers.append({
            "ln1_g": block["0"]["weight"],
            "q_w": att["q_weight"], "k_w": att["k_weight"],
            "v_w": att["v_weight"], "o_w": att["out_weight"],
            "ln2_g": block["2"]["weight"],
            "router_w": moe["router_weight"], "gate_w": moe["gate_weight"],
            "up_w": moe["up_weight"], "down_w": moe["down_weight"]})
    return reference.Weights(
        {"tok": params["0"]["tok"], "layers": layers,
         "lnf_g": params[str(n + 1)]["weight"],
         "head_w": params[str(n + 2)]["weight"]}, spec(cfg))

"""OPT through the program's ``TransformerLM``: the block it computes as
published (pre-LN, learned positions, biased projections, ReLU FFN,
LayerNorm). This is the only place that knows the program's parameter
tree; the reference sees named arrays."""
from __future__ import annotations


def build(cfg: dict):
    """The program's model object for a configuration file."""
    from bigdl_tpu.models import TransformerLM
    d, f = cfg["hidden_size"], cfg["ffn_dim"]
    if f % d:
        raise ValueError(f"ffn_dim {f} is not a multiple of hidden {d}")
    return TransformerLM(
        cfg["vocab_size"], d_model=d,
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],
        max_len=cfg["max_position_embeddings"], ffn_mult=f // d,
        dropout=cfg["dropout"], with_log_softmax=False)


def criterion():
    from bigdl_tpu import nn
    return nn.CrossEntropyCriterion()


def reference_weights(params, cfg: dict) -> dict:
    """The program's parameter tree (or a gradient tree of the same
    shape) as the reference's named weights. Views, no copies."""
    n = cfg["num_hidden_layers"]
    layers = []
    for i in range(n):
        blk = params[str(1 + i)]
        att, ffn = blk["0"], blk["1"]
        layers.append({
            "ln1_g": att["0"]["weight"], "ln1_b": att["0"]["bias"],
            "q_w": att["1"]["q_weight"], "q_b": att["1"]["q_bias"],
            "k_w": att["1"]["k_weight"], "k_b": att["1"]["k_bias"],
            "v_w": att["1"]["v_weight"], "v_b": att["1"]["v_bias"],
            "o_w": att["1"]["out_weight"], "o_b": att["1"]["out_bias"],
            "ln2_g": ffn["0"]["weight"], "ln2_b": ffn["0"]["bias"],
            "fc1_w": ffn["1"]["0"]["weight"],
            "fc1_b": ffn["1"]["0"]["bias"],
            "fc2_w": ffn["1"]["2"]["weight"],
            "fc2_b": ffn["1"]["2"]["bias"]})
    return {"tok": params["0"]["tok"], "pos": params["0"]["pos"],
            "layers": layers,
            "lnf_g": params[str(n + 1)]["weight"],
            "lnf_b": params[str(n + 1)]["bias"],
            "head_w": params[str(n + 2)]["weight"],
            "head_b": params[str(n + 2)]["bias"]}


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    return 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * dtype_bytes

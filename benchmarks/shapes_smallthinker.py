"""Operations and bytes the SmallThinker-21BA3B-Instruct language
model's training step needs on ONE chip's share, from shapes alone: the
numerators of ``step.device_mfu.smallthinker``,
``window_attention_roofline`` and ``flash_attention_roofline.
smallthinker``. Beside ``shapes_nemotron.py``. The count is the
ALGORITHM's, whatever computes it: each attention core over the pairs
the MODEL needs (a window layer's sum over rows i of min(i + 1, W), a
global layer's causal pairs) with flash's seven products (forward 2,
backward 5), the experts at the EXPECTED number of assignments that
land on the experts held; recomputation is never counted.
"""
from __future__ import annotations


def experts_total(cfg: dict) -> int:
    return cfg["published"]["moe_num_primary_experts"]


def layout(cfg: dict) -> list:
    """(windowed, rotary) a layer, the layouts' first
    ``num_hidden_layers`` (builders/smallthinker.py)."""
    n = cfg["num_hidden_layers"]
    return [(bool(w), bool(r)) for w, r in
            zip(cfg["sliding_window_layout"][:n], cfg["rope_layout"][:n])]


def layer_counts(cfg: dict) -> dict:
    windowed = sum(w for w, _ in layout(cfg))
    return {"window": windowed, "global": len(layout(cfg)) - windowed}


def window_pairs(seq: int, window: int) -> int:
    """Query-key pairs of one head of a window layer: sum over rows i of
    min(i + 1, W)."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def expected_local_assignments(cfg: dict) -> float:
    """Assignments a token sends to the experts held here under a
    uniform router: experts a token x held / all."""
    return cfg["moe_num_active_primary_experts"] \
        * cfg["moe_num_primary_experts"] / experts_total(cfg)


def layer_params(cfg: dict) -> dict:
    """Parameters of one layer as this chip holds it (attention, router
    and norms, the held experts) and of the embedding, head and final
    norm."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"attention": 2 * q * d + 2 * kv * d,
            "router_norms": experts_total(cfg) * d + 2 * d,
            "experts": cfg["moe_num_primary_experts"] * 3 * d * f,
            "outside": 2 * cfg["vocab_size"] * d + d}


def param_count(cfg: dict) -> int:
    per = layer_params(cfg)
    return per["outside"] + cfg["num_hidden_layers"] * (
        per["attention"] + per["router_norms"] + per["experts"])


def matmul_params(cfg: dict) -> dict:
    """Parameters that take part in a matrix multiplication per token:
    every layer's four attention projections and its router over ALL
    experts, one expert's three matrices per expected local assignment,
    and the head over the rows held here. The embedding is a lookup;
    norms are not matmuls."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    per = layer_params(cfg)
    return {"attention": n * per["attention"],
            "router": n * d * experts_total(cfg),
            "experts": n * expected_local_assignments(cfg) * 3 * d
            * cfg["moe_ffn_hidden_size"],
            "head": d * cfg["vocab_size"]}


def _core_flops(cfg: dict, batch: int, pairs: int, layers: int,
                products: int) -> float:
    return 2.0 * batch * pairs * cfg["num_attention_heads"] \
        * cfg["head_dim"] * products * layers


def core_cost(cfg: dict, batch: int, seq: int, kind: str,
              dtype_bytes: int = 2) -> dict:
    """What the attention cores of one kind (``window`` or ``global``)
    need in ONE training step, every layer of the kind: seven products
    over the pairs the model needs at ``head_dim``; bytes, each operand
    once: forward reads Q, K, V and writes O, backward reads Q, K, V, O,
    dO and writes dQ, dK, dV, with K, V and their gradients at the
    ``num_key_value_heads`` GQA holds (row statistics ignored)."""
    pairs = window_pairs(seq, cfg["sliding_window_size"]) \
        if kind == "window" else causal_pairs(seq)
    layers = layer_counts(cfg)[kind]
    tokens = batch * seq
    q_side = 6 * cfg["num_attention_heads"]      # Q, O; Q, O, dO, dQ
    kv_side = 6 * cfg["num_key_value_heads"]     # K, V; K, V, dK, dV
    return {"flops": _core_flops(cfg, batch, pairs, layers, 7),
            "bytes": float((q_side + kv_side) * cfg["head_dim"] * tokens
                           * dtype_bytes * layers)}


def train_step_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Model FLOPs of one forward+backward step over ``batch`` sequences
    of ``seq`` tokens: 6 x matmul parameters x tokens and both kinds of
    attention core's seven products."""
    params = matmul_params(cfg)
    tokens = batch * seq
    window = core_cost(cfg, batch, seq, "window")["flops"]
    glob = core_cost(cfg, batch, seq, "global")["flops"]
    matmul = 6.0 * sum(params.values()) * tokens
    return {"matmul": matmul,
            "projections": 6.0 * params["attention"] * tokens,
            "router": 6.0 * params["router"] * tokens,
            "experts": 6.0 * params["experts"] * tokens,
            "head": 6.0 * params["head"] * tokens,
            "window_core": window, "global_core": glob,
            "total": matmul + window + glob}

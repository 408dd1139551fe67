"""Operations and bytes the Keye-VL-2.0-30B-A3B language model's
training step needs on ONE chip's share, from shapes alone: the
numerators of ``step.device_mfu.keye`` and ``sparse_attention_roofline``.
Beside ``shapes.py`` (OPT's block) and ``shapes_evabyte.py``. The count
is the ALGORITHM's, whatever computes it: attention over the pairs a
query KEEPS (a kernel that computes every causal pair and masks reads
low, never over 100), the experts at the EXPECTED number of assignments
that land on the experts held; recomputation is never counted.
"""
from __future__ import annotations


def experts_total(cfg: dict) -> int:
    return cfg["published"]["num_experts"]


def expected_local_assignments(cfg: dict) -> float:
    """Assignments a token sends to the experts held here under a
    uniform router: experts a token x held / all."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / experts_total(cfg)


def matmul_params(cfg: dict) -> dict:
    """Parameters that take part in a matrix multiplication per token:
    per layer q, k, v, out, the indexer's three projections, the router
    over ALL experts and one expert's three matrices per expected local
    assignment; and the output head over the rows of the vocabulary held
    here. The embedding is a lookup; norms are not matmuls."""
    d, sa = cfg["hidden_size"], cfg["sa_config"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    attention = 2 * d * q + 2 * d * kv
    indexer = d * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                   + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    router = d * experts_total(cfg)
    experts = expected_local_assignments(cfg) \
        * 3 * d * cfg["moe_intermediate_size"]
    layers = cfg["num_hidden_layers"]
    return {"attention": layers * attention, "indexer": layers * indexer,
            "router": layers * router, "experts": layers * experts,
            "head": d * cfg["vocab_size"]}


def attention_pairs(seq: int, topk: int) -> dict:
    """(query, key) pairs of one sequence: ``causal`` — every s <= t,
    what the indexer scores; ``selected`` — what a query keeps: all of
    its t + 1 while t < topk, topk after."""
    kept = min(topk, seq)
    return {"causal": seq * (seq + 1) // 2,
            "selected": kept * (kept + 1) // 2 + (seq - kept) * kept}


def train_step_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Model FLOPs of one forward+backward step over ``batch`` sequences
    of ``seq`` tokens: 6 x matmul parameters x tokens; the selected
    attention's 7 matmuls (2 forward, 5 backward) over the selected
    pairs, all query heads; the indexer's 1 + 2 (its scores over every
    causal pair forward, and the two gradient matmuls over the selected
    pairs, where alone L_I's gradient is not zero), all indexer heads."""
    sa, layers = cfg["sa_config"], cfg["num_hidden_layers"]
    pairs = attention_pairs(seq, sa["topk"])
    params = matmul_params(cfg)
    matmul = 6.0 * sum(params.values()) * batch * seq
    attention = 7.0 * 2.0 * batch * cfg["num_attention_heads"] \
        * cfg["head_dim"] * pairs["selected"] * layers
    per_pair = 2.0 * batch * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    indexer = per_pair * (pairs["causal"] + 2 * pairs["selected"]) * layers
    return {"matmul": matmul, "experts": 6.0 * params["experts"] * batch
            * seq, "head": 6.0 * params["head"] * batch * seq,
            "attention": attention, "indexer": indexer,
            "total": matmul + attention + indexer}


def sparse_attention_train_cost(cfg: dict, batch: int, seq: int,
                                dtype_bytes: int = 2) -> dict:
    """What the selected attention (step 4 of the layer,
    docs/sparse_attention.md) needs in ONE training step, every layer,
    forward and backward, whatever kernels compute it. FLOPs: 7 matmuls
    over the selected pairs. Bytes: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV (K and V are
    num_key_value_heads wide; row statistics and the selection itself
    ignored)."""
    layers = cfg["num_hidden_layers"]
    wide = batch * seq * cfg["num_attention_heads"] * cfg["head_dim"]
    narrow = batch * seq * cfg["num_key_value_heads"] * cfg["head_dim"]
    pairs = attention_pairs(seq, cfg["sa_config"]["topk"])
    flops = 7.0 * 2.0 * batch * cfg["num_attention_heads"] \
        * cfg["head_dim"] * pairs["selected"] * layers
    return {"flops": flops,
            "bytes": float((6 * wide + 6 * narrow) * dtype_bytes * layers)}

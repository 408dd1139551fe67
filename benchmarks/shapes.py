"""Operations and bytes the algorithms need, from shapes alone.

These are the yardstick's numerators: model FLOPs of a training step
(recomputation never counted), and the FLOPs and HBM bytes of the two
attention kernels. Copied in spirit from bench.py ``bench_transformer_lm``
(6 x matmul parameters x tokens + attention), with the attention term
counted causally — the half of the score matrix the algorithm needs.
"""
from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    q, k, v, out and the two FFN matrices per layer, and the output
    head. Embedding and position tables are lookups, biases and norms
    are not matmuls."""
    d, f = cfg["hidden_size"], cfg["ffn_dim"]
    per_layer = 4 * d * d + 2 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def lm_param_count(cfg: dict, *, tied_head: bool = False) -> int:
    """Every parameter of the TransformerLM the benchmark builds."""
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    per_layer = (4 * d * d + 4 * d) + (d * f + f) + (f * d + d) + 4 * d
    head = 0 if tied_head else d * v + v
    return (v * d + cfg["max_position_embeddings"] * d
            + cfg["num_hidden_layers"] * per_layer + 2 * d + head)


def attention_matmul_flops(batch: int, heads: int, seq: int,
                           head_dim: int, *, causal: bool = True) -> float:
    """FLOPs of ONE attention matmul (QK^T or PV) over a batch: 2 x B x H
    x S x S x D, halved when causal."""
    full = 2.0 * batch * heads * seq * seq * head_dim
    return full / 2 if causal else full


def lm_train_step_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Model FLOPs of one forward+backward step over ``batch`` sequences
    of ``seq`` tokens: 6 x matmul parameters x tokens, plus causal
    attention (2 matmuls forward, 4 backward) per layer."""
    tokens = batch * seq
    matmul = 6.0 * lm_matmul_params(cfg) * tokens
    heads = cfg["num_attention_heads"]
    one = attention_matmul_flops(batch, heads, seq,
                                 cfg["hidden_size"] // heads)
    attention = 6.0 * one * cfg["num_hidden_layers"]
    return {"matmul": matmul, "attention": attention,
            "total": matmul + attention}


def flash_attention_train_cost(cfg: dict, batch: int, seq: int,
                               dtype_bytes: int = 2) -> dict:
    """What the flash-attention kernels of ONE training step
    (``flash_attention_fwd`` + ``_dq`` + ``_dkdv``, every layer) need.

    FLOPs: forward 2 causal matmuls; backward 5 (recompute S once, dV,
    dP, dQ, dK) — the flash algorithm's own count. The split dq/dkdv
    kernels recompute S and dP a second time; that is the
    implementation's cost, not the algorithm's, and is NOT counted, so
    the share stays under 100%.
    Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O,
    dO and writes dQ, dK, dV (row statistics are S-sized and ignored).
    """
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    layers = cfg["num_hidden_layers"]
    one = attention_matmul_flops(batch, heads, seq, hd)
    tensor = batch * heads * seq * hd * dtype_bytes
    return {"flops": 7.0 * one * layers,
            "bytes": float((4 + 8) * tensor * layers)}


def paged_attention_decode_cost(cfg: dict, live_tokens: int, rows: int,
                                dtype_bytes: int = 2) -> dict:
    """What ``paged_attention`` needs for ONE decode step of one layer
    set (all layers): every live K and V entry read once, and 2 matmuls
    of one query row against the live context.

    ``live_tokens``: sum over the batch rows of their context lengths at
    that step. ``rows``: batch rows (query and output traffic)."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    kv_bytes = 2.0 * live_tokens * d * dtype_bytes * layers
    qo_bytes = 2.0 * rows * d * dtype_bytes * layers
    flops = 2.0 * 2.0 * live_tokens * d * layers
    return {"flops": flops, "bytes": kv_bytes + qo_bytes}


def roofline_least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``cost`` and which of the
    two bounds it: max(FLOPs / peak FLOP/s, bytes / peak bytes/s)."""
    t_c = cost["flops"] / peaks["flops_per_s"]
    t_m = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

"""Operations and bytes EvaByte's training step needs, from shapes
alone: the numerators of ``step.device_mfu.evabyte`` and
``eva_attention_roofline``. Beside ``shapes.py``, which counts OPT's
block (two FFN matrices, S x S causal attention); recomputation is never
counted.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    q, k, v, out and the three FFN matrices per layer, and the output
    head of ``num_pred_heads`` x ``vocab_size`` columns. The embedding is
    a lookup; norms and the per-head EVA vectors are not matmuls."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * d * d + 3 * d * f
    return (cfg["num_hidden_layers"] * per_layer
            + d * cfg["num_pred_heads"] * cfg["vocab_size"])


def attention_pairs(seq: int, window: int, chunk: int) -> dict:
    """(query, key) pairs a head's EVA attention scores over ``seq``
    tokens: inside each window the causal half with its diagonal, and for
    each query one summary per chunk of every EARLIER window."""
    windows, per_window = seq // window, window // chunk
    local = windows * window * (window + 1) // 2
    remote = window * per_window * windows * (windows - 1) // 2
    return {"local": local, "remote": remote, "total": local + remote}


def attention_matmul_flops(cfg: dict, batch: int, seq: int) -> float:
    """FLOPs of ONE attention matmul (scores, or probabilities x values)
    over the pairs of ``attention_pairs``, all heads, one layer."""
    heads = cfg["num_attention_heads"]
    pairs = attention_pairs(seq, cfg["window_size"], cfg["chunk_size"])
    return 2.0 * batch * heads * (cfg["hidden_size"] // heads) \
        * pairs["total"]


def train_step_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Model FLOPs of one forward+backward step over ``batch`` sequences
    of ``seq`` bytes: 6 x matmul parameters x tokens, plus EVA attention
    per layer at the flash algorithm's own count (2 matmuls forward, 5
    backward: scores again, dV, dP, dQ, dK), as
    ``shapes.flash_attention_train_cost`` counts its kernels."""
    matmul = 6.0 * matmul_params(cfg) * batch * seq
    attention = 7.0 * attention_matmul_flops(cfg, batch, seq) \
        * cfg["num_hidden_layers"]
    return {"matmul": matmul, "attention": attention,
            "total": matmul + attention}


def eva_attention_train_cost(cfg: dict, batch: int, seq: int,
                             dtype_bytes: int = 2) -> dict:
    """What step 3 of the layer (docs/eva_attention.md) needs in ONE
    training step, every layer, forward and backward, whatever kernels
    compute it. FLOPs: 7 matmuls over the scored pairs. Bytes: forward
    reads Q, K, V and the summaries K~, V~ and writes O; backward reads
    Q, K, V, O, dO, K~, V~ and writes dQ, dK, dV, dK~, dV~ (a summary
    tensor is 1 / chunk of a sequence tensor; row statistics ignored)."""
    layers = cfg["num_hidden_layers"]
    tensor = batch * seq * cfg["hidden_size"] * dtype_bytes
    tensors = (4 + 8) + (2 + 4) / cfg["chunk_size"]
    return {"flops": 7.0 * attention_matmul_flops(cfg, batch, seq) * layers,
            "bytes": float(tensors * tensor * layers)}

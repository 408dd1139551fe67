"""Operations and bytes the Kimi-VL-A3B-Instruct language model's
training step needs on ONE chip's share, from shapes alone: the
numerators of ``step.device_mfu.kimi`` and ``mla_attention_roofline``.
Beside ``shapes.py`` (OPT's block), ``shapes_evabyte.py`` and
``shapes_keye.py``. The count is the ALGORITHM's, whatever computes it:
the attention core over the causal pairs at its two score parts and its
own value width, the routed experts at the EXPECTED number of
assignments that land on the experts held, the shared expert whole;
recomputation is never counted.
"""
from __future__ import annotations


def experts_total(cfg: dict) -> int:
    return cfg["published"]["n_routed_experts"]


def expected_local_assignments(cfg: dict) -> float:
    """Assignments a token sends to the experts held here under a
    uniform router: experts a token x held / all."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / experts_total(cfg)


def matmul_params(cfg: dict) -> dict:
    """Parameters that take part in a matrix multiplication per token.
    Every layer: the direct query projection, the down-projection to
    latent + rotary key, the latent's expansion to content keys and
    values, the output projection. A leading dense layer: its SwiGLU's
    three matrices. An expert layer: the router over ALL experts, the
    shared expert's three matrices (n_shared_experts x the expert
    width), one routed expert's three per expected local assignment.
    And the output head over the rows of the vocabulary held here. The
    embedding is a lookup; norms are not matmuls."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, wide = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    attention = d * heads * (dn + dr) + d * (rank + dr) \
        + rank * heads * (dn + dv) + heads * dv * d
    sparse = layers - dense
    return {"attention": layers * attention,
            "dense_ffn": dense * 3 * d * cfg["intermediate_size"],
            "router": sparse * d * experts_total(cfg),
            "shared": sparse * 3 * d * cfg["n_shared_experts"] * wide,
            "experts": sparse * expected_local_assignments(cfg)
            * 3 * d * wide,
            "head": d * cfg["vocab_size"]}


def causal_pairs(seq: int) -> int:
    """(query, key) pairs of one sequence: every j <= t."""
    return seq * (seq + 1) // 2


def _core_flops(cfg: dict, batch: int, seq: int, widths: int) -> float:
    """2 x pairs x heads x ``widths`` (the contraction or output widths
    of the core's matmuls, summed), all layers."""
    return 2.0 * batch * causal_pairs(seq) * cfg["num_attention_heads"] \
        * widths * cfg["num_hidden_layers"]


def _core_widths(cfg: dict) -> tuple:
    """(forward, backward): the score over qk_nope + qk_rope and the
    value sum over v_head_dim forward; backward the score again, dP and
    dV over v_head_dim, dK and dQ over qk_nope + qk_rope — seven
    products."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return qk + dv, qk + dv + dv + qk + qk


def train_step_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Model FLOPs of one forward+backward step over ``batch`` sequences
    of ``seq`` tokens: 6 x matmul parameters x tokens, and the attention
    core's seven products over the causal pairs, all heads."""
    params = matmul_params(cfg)
    tokens = batch * seq
    matmul = 6.0 * sum(params.values()) * tokens
    attention = _core_flops(cfg, batch, seq, sum(_core_widths(cfg)))
    return {"matmul": matmul, "experts": 6.0 * params["experts"] * tokens,
            "shared": 6.0 * params["shared"] * tokens,
            "head": 6.0 * params["head"] * tokens,
            "attention": attention, "total": matmul + attention}


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """The forward pass a token, by part (PERF.md section 4's shares)."""
    params = matmul_params(cfg)
    core = _core_flops(cfg, 1, seq, _core_widths(cfg)[0]) / seq
    parts = {k: 2.0 * v for k, v in params.items()}
    parts["attention_core"] = core
    parts["total"] = sum(parts.values())
    return parts


def mla_attention_train_cost(cfg: dict, batch: int, seq: int,
                             dtype_bytes: int = 2) -> dict:
    """What the latent attention's core (step 2 of the layer,
    docs/latent_attention.md) needs in ONE training step, every layer,
    forward and backward, whatever kernels compute it. FLOPs: the seven
    products over the causal pairs. Bytes, each operand read or written
    once: forward reads qN, qR, kN, v (per head) and kR (ONE row a
    position) and writes o; backward reads those, o and dO and writes
    dqN, dqR, dkN, dv and dkR (row statistics ignored)."""
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    heads = cfg["num_attention_heads"]
    ins = heads * (dn + dr + dn + dv) + dr        # a position's operands
    elements = (ins + heads * dv) + (ins + 2 * heads * dv + ins)
    return {"flops": _core_flops(cfg, batch, seq, sum(_core_widths(cfg))),
            "bytes": float(elements * batch * seq * dtype_bytes
                           * cfg["num_hidden_layers"])}

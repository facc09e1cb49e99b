"""Operation and byte counts of the DeepSeek-V3-style LM cells: the
training step's FLOPs per token, and the grouped expert matmuls' work for
their roofline.

Kept with the benchmark, from the shapes a configuration states (its
published keys), so that a change to the program cannot change the
yardstick.  Work the program repeats to save memory (remat's second
forward pass) is not counted.
"""

from __future__ import annotations


def _dims(cfg: dict) -> tuple:
    return (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"])


def latent_attention_matmul_params(cfg: dict) -> int:
    """One layer's latent attention projections: queries, the latent with
    the shared rotary key, per-head keys and values from the latent, and
    the output."""
    d, h, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
        + h * v * d


def routed_assignments_per_token(cfg: dict) -> float:
    """The expected assignments a token sends to the experts held here:
    top-k times the held share of the router's experts (6 x 8 / 64)."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])


def moe_lm_matmul_params(cfg: dict) -> float:
    """Parameters that take part in a matrix multiplication per token:
    every layer's latent attention, the leading dense layers' SwiGLU, each
    expert layer's router, shared experts and its expected share of routed
    experts, and the output head.  The embedding is a row lookup and the
    norms are elementwise."""
    d, f, _ = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    expert_layer = (d * cfg["n_routed_experts_published"]
                    + 3 * d * cfg["n_shared_experts"] * f
                    + routed_assignments_per_token(cfg) * 3 * d * f)
    return (layers * latent_attention_matmul_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * expert_layer
            + d * cfg["vocab_size"])


def moe_lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    parameter, plus causal attention's scores (query/key dims) and values
    (value dims): over a sequence a query sees T / 2 keys on average, 2
    flops a multiply-add, three times over for forward and backward."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = cfg["num_hidden_layers"] * 3 * seq_len * h * (qk + cfg["v_head_dim"])
    return 6 * moe_lm_matmul_params(cfg) + attn


def expert_matmul_flops(cfg: dict, assignments: float) -> float:
    """Operations of the grouped expert matmuls over ``assignments`` rows
    (token-expert assignments to held experts): three (d x f) products a
    row, 2 flops a multiply-add, forward and the backward's two."""
    d, f, _ = _dims(cfg)
    return 3 * 2 * 3 * d * f * assignments


def expert_matmul_bytes(cfg: dict, assignments: float, calls: int) -> float:
    """HBM traffic the grouped expert matmuls need, in bf16: per row the
    forward's reads and writes (x, the gate and up outputs, the activation,
    the output: 2d + 3f elements) and the backward's (4d + 6f); per call
    (one expert layer of one group) the held experts' three weights read by
    the forward and the backward and their gradients written once."""
    d, f, held = _dims(cfg)
    per_row = 2 * (6 * d + 9 * f)
    per_call = 3 * 2 * 3 * held * d * f
    return per_row * assignments + per_call * calls

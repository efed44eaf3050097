"""Matrix weights a token meets in a DeepSeek-V3-shaped decoder on one
chip's share of its experts, from shapes alone (``benchmark/flops.py``
says why nothing here looks at a run)."""

from __future__ import annotations


def attention_weights(cfg: dict) -> int:
    """Latent attention's five projections."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, pe = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return (h * heads * (nope + pe) + h * (rank + pe)
            + rank * heads * (nope + vd) + heads * vd * h)


def expert_weights(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_experts_per_token(cfg: dict) -> float:
    """The routed experts *held here* a token meets in expectation under
    even routing: ``k * held / n``."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"]
            / cfg["n_routed_experts"])


def matrix_weights_per_token(cfg: dict) -> float:
    """Every matrix weight one token is multiplied by in a forward pass:
    attention, the dense layers, the router, the shared experts, the
    expected share of the routed experts held here, and the head.  The
    embedding lookup is not a product."""
    h = cfg["hidden_size"]
    dense_layers = cfg["first_k_dense_replace"]
    expert_layers = cfg["num_hidden_layers"] - dense_layers
    per_expert_layer = (
        h * cfg["n_routed_experts"]
        + cfg["n_shared_experts"] * expert_weights(cfg)
        + routed_experts_per_token(cfg) * expert_weights(cfg))
    return (cfg["num_hidden_layers"] * attention_weights(cfg)
            + dense_layers * 3 * h * cfg["intermediate_size"]
            + expert_layers * per_expert_layer
            + h * cfg["vocab_size"])

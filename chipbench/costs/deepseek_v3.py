"""Operations the DeepSeek-V3 family needs, from its shapes and the share of
it held here.

Counted are the operations the algorithm requires, multiply-add as 2: the
matrix products of latent attention, of the dense and shared feed-forwards,
of the router and of the head, the held routed experts at the assignments a
token sends them in expectation (``num_experts_per_tok`` times the held
share of the router's experts), and attention's two products over each
token's real context at q/k and v's own head sizes.  Nothing recomputed is
counted, and element-wise work (RMSNorm, SiLU, rotary, softmax, top-k, the
sort and the gathers of routing, the optimizer) is left out, so a share of
peak worked out from these counts is a floor.
"""

from __future__ import annotations


def _sizes(cfg):
    held = int(cfg["n_routed_experts"])
    return {"L": int(cfg["num_hidden_layers"]), "H": int(cfg["hidden_size"]),
            "nh": int(cfg["num_attention_heads"]),
            "dq": int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "dv": int(cfg["v_head_dim"]), "rank": int(cfg["kv_lora_rank"]),
            "I": int(cfg["intermediate_size"]),
            "F": int(cfg["moe_intermediate_size"]), "held": held,
            "E": int(cfg.get("router_experts", held)),
            "k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["n_shared_experts"]),
            "Ld": int(cfg["first_k_dense_replace"]),
            "V": int(cfg["vocab_size"])}


def mla_params(cfg):
    """Weights of one layer's latent attention that take part in a product:
    W_q, W_kva, W_kvb, W_o."""
    z = _sizes(cfg)
    return (z["H"] * z["nh"] * z["dq"] + z["H"] * (z["rank"] + z["rope"])
            + z["rank"] * z["nh"] * (z["nope"] + z["dv"])
            + z["nh"] * z["dv"] * z["H"])


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    z = _sizes(cfg)
    return 3 * z["H"] * z["F"]


def assignments_per_token(cfg):
    """(token, expert) assignments a token sends to the experts held here,
    in expectation under even routing."""
    z = _sizes(cfg)
    return z["k"] * z["held"] / z["E"]


def matmul_params_per_token(cfg):
    """Weights a token meets in matrix products, all layers and the head."""
    z = _sizes(cfg)
    sparse = (z["shared"] * expert_params(cfg) + z["H"] * z["E"]
              + assignments_per_token(cfg) * expert_params(cfg))
    return (z["L"] * mla_params(cfg) + z["Ld"] * 3 * z["H"] * z["I"]
            + (z["L"] - z["Ld"]) * sparse + z["H"] * z["V"])


def causal_attention_flops(cfg, seq):
    """Forward causal attention of a whole sequence, all layers: QK^T at
    q/k's head size and PV at v's, token t over t keys."""
    z = _sizes(cfg)
    return z["L"] * z["nh"] * 2 * (z["dq"] + z["dv"]) * seq * (seq + 1) // 2


def train_flops_per_sample(cfg, seq):
    """Forward + backward of one sequence of ``seq`` tokens: 3 x forward,
    loss over every position's (sliced) vocabulary."""
    fwd = 2 * matmul_params_per_token(cfg) * seq \
        + causal_attention_flops(cfg, seq)
    return 3 * fwd

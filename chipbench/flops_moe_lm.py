"""Closed-form operation counts of the latent-attention mixture-of-experts
decoder, from a configuration's shapes (see ``flops.py`` for why closed
forms, ``flops_lm.py`` for what a language model's count leaves out).

Model operations, without recomputation: the multiply-adds of the matrix
multiplications the architecture needs **on this chip**: the experts
held, at the expectation of their load. Every token chooses exactly
``num_experts_per_tok`` of the router's ``router_experts`` experts, so
``n_routed_experts / router_experts`` of the chosen pairs fall on the
experts held here if the loads are even, which is what the selection
bias drives them towards; a step whose held experts are unpopular does
less than is counted and one whose held experts are popular more. A
causal attention is counted as half of the S x S scores.
"""

from __future__ import annotations

from chipbench.flops import OPS_PER_MAC, TRAIN_PASSES

BYTES = 2  # of a bf16 element, as the kernels move them


def mla_matmul_macs(cfg: dict) -> int:
    """Multiply-adds per token of one layer's five attention matrices."""
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (hidden * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk
            + hidden * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * heads
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + heads * cfg["v_head_dim"] * hidden)


def attention_macs(cfg: dict) -> int:
    """Multiply-adds per token of one layer's causal scores (q, k wide)
    and probabilities times values (v wide), averaged over the sequence:
    S/2 keys a query."""
    per_score = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                 + cfg["v_head_dim"])
    return cfg["num_attention_heads"] * per_score * cfg["seq_len"] // 2


def expert_macs(cfg: dict) -> int:
    """Multiply-adds of one expert on one token: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_pairs_per_token(cfg: dict) -> float:
    """The chosen pairs of one token that fall on experts held here, in
    expectation."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_experts"])


def moe_macs(cfg: dict) -> int:
    """Multiply-adds per token of one mixture: router, shared experts,
    and the held experts at their expected load."""
    return int(cfg["hidden_size"] * cfg["router_experts"]
               + cfg["n_shared_experts"] * expert_macs(cfg)
               + held_pairs_per_token(cfg) * expert_macs(cfg))


def expert_layers(cfg: dict) -> int:
    """Layers with a mixture in a step: the stack's and the prediction
    module's."""
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            + cfg["num_nextn_predict_layers"])


def forward_macs_per_token(cfg: dict) -> int:
    """One token through the dense layers, the expert layers, the
    prediction module (its projection and its layer) and one read of the
    head a prediction depth."""
    attention = mla_matmul_macs(cfg) + attention_macs(cfg)
    dense = attention + 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    reads = 1 + cfg["num_nextn_predict_layers"]
    return (cfg["first_k_dense_replace"] * dense
            + expert_layers(cfg) * (attention + moe_macs(cfg))
            + cfg["num_nextn_predict_layers"] * 2 * cfg["hidden_size"] ** 2
            + reads * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_sequence(cfg: dict) -> int:
    """Training operations of one sequence of ``seq_len`` tokens."""
    return (forward_macs_per_token(cfg) * cfg["seq_len"]
            * OPS_PER_MAC * TRAIN_PASSES)


def flash_forward_counts(cfg: dict, batch: int) -> tuple[int, int]:
    """(operations, bytes) of ONE call of the causal flash-attention
    forward kernel on ``batch`` sequences, whatever tiles it walks:
    S(S+1)/2 scores a head, each a multiply-add over the width of q and
    k and one over the width of v; q and k read and v read and the
    output written once in bf16, the log-sum-exp written in float32. How
    many calls a step makes is counted in the trace
    (``readers/kernel_calls.py``), not here."""
    heads, s = cfg["num_attention_heads"], cfg["seq_len"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    scores = batch * heads * s * (s + 1) // 2
    return (scores * (qk + cfg["v_head_dim"]) * OPS_PER_MAC,
            batch * heads * s * ((2 * qk + 2 * cfg["v_head_dim"]) * BYTES + 4))


def grouped_product_counts(cfg: dict, pairs: list,
                           experts_used: list) -> tuple[int, int]:
    """(operations, bytes) of a training step's grouped products,
    forward and backward, summed over its expert layers: ``pairs[l]`` the
    chosen pairs that arrived on layer l's held experts and
    ``experts_used[l]`` how many of them got any. Three matrices, three
    passes each (the product, its input's gradient, its weight's
    gradient), 2 x hidden x width operations a pair, matrix and pass.
    Bytes: the weights of the experts that got a pair read twice and
    their gradient written once, and in every pass of every matrix the
    pairs' rows moved once (an input row and an output row), all in
    bf16. Recomputation is not counted."""
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    passes = 3
    macs = 3 * passes * sum(pairs) * hidden * width
    weights = 3 * sum(experts_used) * hidden * width
    rows = 3 * passes * sum(pairs) * (hidden + width)
    return int(macs * OPS_PER_MAC), int((3 * weights + rows) * BYTES)

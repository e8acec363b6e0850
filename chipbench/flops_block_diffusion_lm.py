"""Closed-form operation counts of the block-diffusion mixture-of-experts
decoder, from a configuration's shapes (see ``flops.py`` for why closed
forms, ``flops_moe_lm.py`` for the held experts at their expected load).

A sample is one sequence of ``seq_len`` = L tokens; the model reads its
2L positions ``[clean ; noisy]`` in every layer and its head reads the L
noisy ones. Attention is counted at the scores the block mask lets
count, ``L^2 + block_length * L`` a head, whatever tiles a kernel walks.
Recomputation is not counted.
"""

from __future__ import annotations

from chipbench.flops import OPS_PER_MAC, TRAIN_PASSES

BYTES = 2  # of a bf16 element, as the kernels move them
# multiply-adds a live score, in units of the head width: the forward's
# two products, dK/dV's four, dQ's three
KERNEL_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dkv": 4, "flash_bwd_dq": 3}


def gqa_matmul_macs(cfg: dict) -> int:
    """Multiply-adds per position of one layer's four attention
    matrices: q and the output at all heads, k and v at the k/v heads."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return cfg["hidden_size"] * (2 * q + 2 * kv)


def live_scores(cfg: dict) -> int:
    """Scores a head that the block mask lets count, of the (2L)^2: the
    clean half block-causally (L^2 / 2 + B L / 2), the noisy half on the
    clean blocks before its own (L^2 / 2 - B L / 2) and on its own
    block (B L)."""
    return cfg["seq_len"] ** 2 + cfg["block_length"] * cfg["seq_len"]


def attention_macs_per_sequence(cfg: dict) -> int:
    """One layer's scores and probabilities times values."""
    return (cfg["num_attention_heads"] * live_scores(cfg)
            * 2 * cfg["head_dim"])


def expert_macs(cfg: dict) -> int:
    """Multiply-adds of one expert on one position: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_macs(cfg: dict) -> int:
    """Multiply-adds per position of one mixture: the router over all
    its experts, and the experts held at their expected load
    (``num_experts_per_tok * num_experts / router_experts`` pairs)."""
    held_pairs = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                  / cfg["router_experts"])
    return int(cfg["hidden_size"] * cfg["router_experts"]
               + held_pairs * expert_macs(cfg))


def forward_macs_per_sequence(cfg: dict) -> int:
    """Both halves through every layer, the head over the noisy half."""
    positions = 2 * cfg["seq_len"]
    per_layer = (positions * (gqa_matmul_macs(cfg) + moe_macs(cfg))
                 + attention_macs_per_sequence(cfg))
    return (cfg["num_hidden_layers"] * per_layer
            + cfg["seq_len"] * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_sequence(cfg: dict) -> int:
    return forward_macs_per_sequence(cfg) * OPS_PER_MAC * TRAIN_PASSES


def attention_kernel_counts(cfg: dict, batch: int) -> dict:
    """Kernel name -> (operations, bytes) of ONE call on ``batch``
    sequences, for the three kernels of the attention core: the live
    scores a head (``live_scores``) at 2, 4 and 3 products a score
    (forward, dK/dV, dQ), each a multiply-add over the head width; q, dO,
    dq and the output at all heads, k, v, dk and dv at the k/v heads,
    each moved once in bf16; the log-sum-exp and delta at all heads in
    float32. How many calls a step makes is counted in the trace
    (``readers/kernel_family.py``), not here."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    rows = batch * 2 * cfg["seq_len"]
    wide = rows * heads * cfg["head_dim"] * BYTES   # q, o, dO or dq
    narrow = rows * kv * cfg["head_dim"] * BYTES    # k, v, dk or dv
    stat = rows * heads * 4                         # log-sum-exp or delta
    moved = {"flash_fwd": 2 * wide + 2 * narrow + stat,
             "flash_bwd_dkv": 2 * wide + 4 * narrow + 2 * stat,
             "flash_bwd_dq": 3 * wide + 2 * narrow + 2 * stat}
    scores = batch * heads * live_scores(cfg)
    return {name: (scores * products * cfg["head_dim"] * OPS_PER_MAC,
                   moved[name])
            for name, products in KERNEL_PRODUCTS.items()}

"""Closed-form operation counts of the looped decoder language model,
from a configuration's shapes (see ``flops.py`` for why closed forms).

Model operations, without recomputation: the multiply-adds of the
matrix multiplications the architecture needs. A causal attention needs
half of the S x S scores and of the probabilities times values, and half
is what is counted, whether the program computes the masked half or
skips it. Norms, rotary, softmax, SiLU, the gate (one H-wide dot a
position and pass) and the loss are left out.
"""

from __future__ import annotations

from chipbench.flops import OPS_PER_MAC, TRAIN_PASSES


def layer_matmul_macs(cfg: dict) -> int:
    """Multiply-adds per token of one layer's seven weight matrices."""
    hidden = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return (hidden * q + 2 * hidden * kv + q * hidden
            + 3 * hidden * cfg["intermediate_size"])


def attention_macs(cfg: dict) -> int:
    """Multiply-adds per token of one layer's causal scores and
    probabilities-times-values, averaged over the sequence: each is
    heads x d x S for the full square, S/2 for the causal half."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 2 * q * cfg["seq_len"] // 2


def forward_macs_per_token(cfg: dict) -> int:
    """One token through ``total_ut_steps`` passes of the stack and as
    many reads of the head."""
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (applications * (layer_matmul_macs(cfg) + attention_macs(cfg))
            + cfg["total_ut_steps"] * head)


def train_flops_per_sequence(cfg: dict) -> int:
    """Training operations of one sequence of ``seq_len`` tokens."""
    return (forward_macs_per_token(cfg) * cfg["seq_len"]
            * OPS_PER_MAC * TRAIN_PASSES)


FLASH_TILE = 128  # ops/pallas_attention.py's block_q = block_k


def flash_forward_counts(cfg: dict, batch: int) -> tuple[int, int]:
    """(operations, bytes) of one call of the causal flash-attention
    forward kernel on ``batch`` sequences: the numerator of its roofline
    share. Operations as the kernel computes them: it walks the tile
    pairs at or below the diagonal, n(n+1)/2 of the n x n tiles, the
    diagonal ones whole, and multiplies scores and probabilities x
    values in each. Bytes as the algorithm needs them: q, k and v read
    and the output written once in the compute type (2 bytes), the
    log-sum-exp written in float32; what the kernel streams again tile
    by tile is its own cost, not the roofline's."""
    heads, d, s = cfg["num_attention_heads"], cfg["head_dim"], cfg["seq_len"]
    n = -(-s // FLASH_TILE)
    tile_pairs = n * (n + 1) // 2
    macs = batch * heads * tile_pairs * 2 * FLASH_TILE * FLASH_TILE * d
    return macs * OPS_PER_MAC, batch * heads * s * (4 * d * 2 + 4)


def flash_forward_calls_per_step(cfg: dict) -> int:
    """Calls of the forward kernel in one training step: every layer
    application of every pass once in the forward pass and once more
    where ``jax.checkpoint`` runs it again for the backward pass."""
    return 2 * cfg["total_ut_steps"] * cfg["num_hidden_layers"]

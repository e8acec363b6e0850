"""Looped decoder language models: ``models.looped_lm.LoopedDecoderLM``
under its exit-weighted cross-entropy and AdamW. A sample is one
sequence of ``seq_len`` tokens (``per_chip_batch`` counts sequences).

There is no BatchNorm: the state that must move is every parameter
(``moving_state``), the reference returns no ``stem``, and the tolerances
are this family's own.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import flops_lm, reference_lm
from chipbench.families import _shared

# Relative L2 of each error ``reference_lm.looped_lm`` returns, looked up
# before correct.py's table. Beside each limit: what the chip read at the
# timed sizes (bf16 products; my chip runs, PR 30, PERF.md section 6: the
# range over the seeds of those runs) and what the controls of
# ``chipbench/controls_lm.py`` read there, the reference lowered one
# arithmetic at a time, each of which has to fail: products of
# float8_e4m3fn operands (fp8), the attention's scores and softmax in
# bf16, the loss in bf16. The readings are no last-bit flips: XLA on the
# TPU keeps a bf16 value in float32 where it feeds the next float32
# operation of the same fusion (a rounding it may drop, and drops
# differently in the program's scan and the reference's unrolled
# layers), so nearly every element differs by part of a bf16 step after
# one layer, and 32 layer applications carry that to 1.5e-2. So the
# chain cannot tell a bf16 softmax (layer1 8.2e-3, z_4 1.5e-2 with it)
# nor a bf16 loss; the three numbers that no layer has amplified do:
# ``attention`` the softmax, ``cross_entropy`` the loss, ``head`` the
# product, and 8-bit products fail thirteen of these.
_PASSES = (1, 2, 3, 4)
TOLERANCES = {
    # One layer from the embeddings: read 4.8e-3 to 6.2e-3; fp8 0.12.
    "layer1": 2.5e-2,
    # The reference's head on the program's own z, one product of bf16
    # operands accumulated in float32 on both sides: read 0.0 in every
    # run; fp8 3.3e-2.
    "head": 1e-3,
    # The reference's attention core (float32 from stored bf16 q, k, v)
    # on the program's own q, k and v of the first layer: what the kernel
    # alone adds, amplified by nothing. Read 2.591e-3 to 2.636e-3 over
    # twenty-two seeds (a norm over 8.4M elements): three bf16 roundings in
    # quadrature, of the scaled q, of the probabilities and of the
    # output, because the kernel's float32 dots run as bf16 passes on the
    # TPU (on float32 inputs it reads 1.77e-3). Scores and softmax in
    # bf16 4.67e-3 to 4.69e-3 over three seeds (four roundings more), fp8
    # 3.8e-2. The limit is a quarter above the largest reading, where the
    # seeds move it by 1.7%, and 0.70 of the control's; one more bf16
    # rounding a probability (3.05e-3) still passes, two (3.45e-3) do
    # not.
    "attention": 3.3e-3,
    # The whole chain from the tokens after 8, 16, 24 and 32 layer
    # applications, and its logits: read 1.0e-2 (z_1) to 1.5e-2 (z_4);
    # fp8 0.16 to 0.21.
    **{f"z_{t}": 5e-2 for t in _PASSES},
    **{f"logits_{t}": 5e-2 for t in _PASSES},
    # The exit probabilities, three sigmoids of an H-wide dot with z:
    # read 3.3e-3 to 6.8e-3; fp8 5.6e-2 to 6.2e-2.
    "exit_p": 1.5e-2,
    # The reference's cross-entropy of the program's own z at the
    # compared positions, position by position, float32 on both sides:
    # read 4.6e-8 to 2.5e-6 over nine seeds; the logits and the
    # log-softmax in bf16 2.1e-3, fp8 3.7e-3.
    "cross_entropy": 1e-4,
    # A pass's mean cross-entropy over every position, which the number
    # above does not cover: read 0.0 to 3.6e-5. It tells no precision
    # (the loss in bf16 2.7e-5 to 2.4e-4, fp8 2.3e-5 to 3.5e-4: the mean
    # over 4,096 positions averages the roundings away and is close to
    # log(vocabulary) whatever the logits); it guards which positions
    # are counted.
    **{f"pass_loss_{t}": 2e-4 for t in _PASSES},
    # "loss", the mixed loss of the first train_step, is left to
    # correct.py's LOSS_TOL (1e-2), the limit of the accepted cells: it
    # read 6e-7 to 2.3e-5, and 7e-6 to 4.5e-4 under the controls, so it
    # cannot tell a precision and guards the step, not the arithmetic.
}


def model_kwargs(cfg: dict) -> dict:
    """The configuration's keys as ``LoopedDecoderLM`` names them."""
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("LoopedDecoderLM has no grouped-query attention")
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise ValueError("LoopedDecoderLM is SwiGLU with an untied head")
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], loops=cfg["total_ut_steps"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        exit_beta=cfg["exit_beta"], init_std=cfg["init_std"],
        dtype=_shared.dtype_of(cfg["compute_dtype"]),
        attn_impl=cfg["attn_impl"],
    )


def build_model(cfg: dict, key):
    from tpu_syncbn.models.looped_lm import LoopedDecoderLM

    return _shared.build_on_device(
        lambda rngs: LoopedDecoderLM(**model_kwargs(cfg), rngs=rngs),
        key, sync=False)


def optimizer(cfg: dict, global_batch: int):
    """AdamW at the configuration's constant rate (no scaling with the
    batch: the rate is stated for the run as it is)."""
    import optax

    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    return optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                       weight_decay=opt["weight_decay"])


def loss_fn(model, batch):
    return model.loss(*batch)


COMPARED_POSITIONS = 128  # of a sequence, where z_t and logits_t are compared


def compared_positions(seq_len: int) -> np.ndarray:
    """``COMPARED_POSITIONS`` positions spread evenly over the sequence
    (all of a shorter one), first and last among them."""
    count = min(COMPARED_POSITIONS, seq_len)
    return np.linspace(0, seq_len - 1, count).astype(np.int32)


def outputs(model, batch) -> dict:
    """What the reference is compared on, small enough to sit beside the
    trainer's state, the batch on the leading axis of each (the harness
    joins the replicas' outputs there): the first layer applied once to
    the embeddings (B, S, H), with what its attention core read and
    wrote, ``q``, ``k``, ``v`` and ``attention`` (B, S, heads, d);
    ``z_t`` (B, T, P, H) and ``logits_t`` (B, T, P, vocabulary) at the
    compared positions of the sequence; the exit probabilities at every
    position (B, T, S); each pass's cross-entropy at the compared
    positions (B, T, P) and its mean over a sequence (B, T)."""
    import jax.numpy as jnp

    from tpu_syncbn.models.looped_lm import exit_distribution

    tokens, targets = batch
    at = compared_positions(tokens.shape[1])
    hidden = model.hidden_passes(tokens)
    ce, lam = zip(*(model.read_pass(h, targets) for h in hidden))
    ce = jnp.stack(ce, axis=1)
    z = model.read(jnp.swapaxes(hidden[:, :, at], 0, 1))
    first = model.layer_parts(tokens)
    return {
        "layer1": first.pop("out"),
        **first,
        "z": z,
        "logits": model.logits(z),
        "exit_p": jnp.swapaxes(exit_distribution(jnp.stack(lam)), 0, 1),
        "ce": ce[:, :, at],
        "pass_loss": jnp.mean(ce, axis=-1),
    }


def moving_state(dp) -> np.ndarray:
    """The sum of each parameter leaf: AdamW moves every one of them in
    every step."""
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.device_get(
        [jnp.sum(x.astype(jnp.float32))
         for x in jax.tree_util.tree_leaves(dp.params)]))


def make_pool(cfg: dict, n: int, rng: np.random.Generator) -> tuple:
    """``n`` sequences of uniform random token ids over the whole
    vocabulary, and their next-token targets."""
    tokens = rng.integers(0, cfg["vocab_size"], (n, cfg["seq_len"] + 1),
                          dtype=np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def transform(cfg: dict):
    return lambda sample: sample


def reference_fn(cfg: dict):
    return functools.partial(
        reference_lm.looped_lm,
        positions=compared_positions(cfg["seq_len"]),
        num_heads=cfg["num_attention_heads"], loops=cfg["total_ut_steps"],
        theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        beta=cfg["exit_beta"], dtype=_shared.dtype_of(cfg["compute_dtype"]))


def train_flops_per_image(cfg: dict) -> int:
    """Training operations of one sample, which is one sequence."""
    return flops_lm.train_flops_per_sequence(cfg)


def attention_kernel_counts(cfg: dict, wl: dict) -> tuple[int, int, int]:
    """(operations, bytes) of one call of the attention kernel in a step
    of this cell and the calls a step: ``attention_roofline_pct``'s
    numerator."""
    return (*flops_lm.flash_forward_counts(cfg, wl["per_chip_batch"]),
            flops_lm.flash_forward_calls_per_step(cfg))

"""Block-diffusion mixture-of-experts decoders:
``models.block_diffusion_lm.BlockDiffusionMoELM`` under its weighted
cross-entropy, its balance loss and AdamW. A sample is one clean
sequence of ``seq_len`` tokens, its noisy copy and a weight a position
(``per_chip_batch`` counts sequences; the model reads ``2 * seq_len``
positions a sample); the noising is the loader's transform.

There is no BatchNorm: the state that must move is every parameter
(``moving_state``), the reference returns no ``stem``, and the
tolerances are this family's own.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import (flops_block_diffusion_lm, flops_moe_lm,
                       reference_block_diffusion_lm)
from chipbench.families import _shared
# AdamW at the configuration's constant rate, ``model.loss(*batch)`` and
# the 128 compared positions of a sequence: the looped family's
from chipbench.families.looped_lm import (  # noqa: F401
    compared_positions, loss_fn, optimizer)

# Relative L2 of each error ``reference_block_diffusion_lm
# .block_diffusion_lm`` returns (one is a share, said so), looked up
# before correct.py's table. Beside each limit: what the chip read at the
# timed sizes (bf16 products; my chip runs, PR 36, PERF.md section 6: the
# range over the seeds of those runs) and what the controls of
# ``chipbench/controls_block_diffusion_lm.py`` read there, the reference
# lowered or broken one thing at a time, each of which has to fail a
# limit. As in ``moe_lm``: a chain of layers carries the bf16 roundings
# that XLA keeps or drops differently on the two sides, so the chain's
# limits are wide and the pieces that no layer has amplified
# (``attention``, ``router``, ``moe``, ``head``, ``cross_entropy``) tell
# the precisions and the masks apart.
TOLERANCES = {
    # The first layer from the embeddings, over the 2L positions: read
    # 2.8e-3 to 3.6e-3 (the unit-scale embedding is most of the residual
    # stream, so the chain reads lower than in the other two families);
    # fp8 1.6e-2, the leaking mask 1.4e-2, the wrong group 0.24. (With
    # the embedding at 0.02 too, tried in the review round, it read
    # 6.7e-3 to 1.69e-2 over eight seeds: a near-tie of the router that
    # bf16 products flip moves a token's whole mixture and nothing in
    # the stream hides it; the leaking mask 6.5e-2, fp8 9.0e-2. Every
    # other reading of this table came out in the ranges given here.)
    "layer1": 8e-3,
    # The reference's attention core under the dense mask (float32 from
    # stored bf16 q, k, v; k and v at 4 heads, repeated there) on the
    # program's own q, k and v: what the kernels' forward adds. Read
    # 2.13e-3 to 2.29e-3 over sixteen seeds (2.21e-3 to 2.26e-3 on nine
    # more in the review round): four times the latent cell's 5.6e-4,
    # because q and k leave their head norms at unit scale and the
    # softmax is peaked, so the probabilities' rounding to bf16 where
    # they meet v does not average out (the looped cell reads 2.6e-3 for
    # the same reason). Scores and softmax in bf16 3.5e-3, fp8 1.8e-2,
    # the leaking mask 6.1e-2 to 9.9e-2, a plain causal mask 0.72, the
    # wrong group 1.2. The limit is 1.22 times the largest reading and
    # 0.8 of the nearest control's.
    "attention": 2.8e-3,
    # The reference's router on the program's own router input, as dense
    # (T, 128) maps of the weights: float32 at full precision on both
    # sides, so the chosen sets are equal but for near-ties. Read 5.5e-8
    # to 6.1e-8 (no pair differs); the router in bf16 6.1e-2.
    "router": 1e-3,
    # The loads of the 128 experts from that selection: read 0.0; the
    # router in bf16 5.6e-3 to 1.2e-2.
    "loads": 1e-3,
    # The share of the chosen pairs on held experts that were not
    # computed: none may be. Read 0.0.
    "pairs_not_computed": 0.0,
    # The reference's mixture (every held expert on every position,
    # dense weights) on the program's own router input: read 1.66e-3 on
    # every seed; the router in bf16 4.7e-2, fp8 7.2e-2.
    "moe": 6e-3,
    # The reference's head on the program's own z: read 0.0; fp8 3.3e-2.
    "head": 1e-3,
    # The reference's weighted cross-entropy of the program's own z at
    # the compared positions, position by position: read 2.7e-8 to
    # 9.2e-8; the loss in bf16 2.2e-3, fp8 2.9e-3.
    "cross_entropy": 1e-4,
    # The whole chain from the tokens: what the head reads and its
    # logits at the compared positions of the noisy half: read 5.3e-3 to
    # 7.8e-3; the leaking mask 6.9e-2, fp8 7.4e-2 and 8.2e-2, a plain
    # causal mask 0.38, the wrong group 1.1.
    "z": 2e-2,
    "logits": 2e-2,
    # The chain's mean weighted cross-entropy: guards which positions
    # are counted and weighed, tells no precision (read 5.9e-6 to
    # 6.3e-5; the loss in bf16 9.2e-5, fp8 1.5e-4; a plain causal mask
    # 5.3e-3, the wrong group 1.4e-2).
    "diffusion_loss": 2e-4,
    # The chain's mean balance loss: guards which loads are read (read
    # 1.3e-5 to 1.9e-4, a near-tie that flips in a later layer moves a
    # load; the wrong group 5.9e-3, a plain causal mask 1.5e-2, fp8
    # 1.7e-2).
    "aux": 2e-3,
    # "loss", the first train_step's, is left to correct.py's LOSS_TOL
    # (1e-2), the limit of the accepted cells: read 5.9e-6 to 6.3e-5.
}

# what the model does not do: a configuration that asks for it is refused
_FIXED = {"decoder_sparse_step": 1, "mlp_only_layers": [],
          "norm_topk_prob": True, "rope_scaling": None,
          "attention_bias": False, "hidden_act": "silu",
          "use_sliding_window": False, "tie_word_embeddings": False}


def mask_id(cfg: dict) -> int:
    """The last id of the vocabulary held: no clean token is it."""
    return cfg["vocab_size"] - 1


def model_kwargs(cfg: dict) -> dict:
    """The configuration's keys as ``BlockDiffusionMoELM`` names them."""
    for key, value in _FIXED.items():
        if cfg[key] != value:
            raise ValueError(f"BlockDiffusionMoELM has {key} = {value!r} only")
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_layers=cfg["num_hidden_layers"],
        block_length=cfg["block_length"],
        n_experts=cfg["router_experts"], experts_held=cfg["num_experts"],
        first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        moe_intermediate=cfg["moe_intermediate_size"],
        aux_weight=cfg["router_aux_loss_coef"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        init_std=cfg["init_std"], embed_std=cfg["embed_init_std"],
        dtype=_shared.dtype_of(cfg["compute_dtype"]),
        attn_impl=cfg["attn_impl"],
    )


def build_model(cfg: dict, key):
    from tpu_syncbn.models.block_diffusion_lm import BlockDiffusionMoELM

    return _shared.build_on_device(
        lambda rngs: BlockDiffusionMoELM(**model_kwargs(cfg), rngs=rngs),
        key, sync=False)


def outputs(model, batch) -> dict:
    """What the reference is compared on
    (``reference_block_diffusion_lm.block_diffusion_lm`` lists the
    shapes), the batch or the replica on the leading axis of each: the
    first layer applied to the embeddings of ``[x0 ; xt]``, opened up;
    what the head reads, its logits and the weighted cross-entropy at the
    compared positions of the noisy half; a sequence's mean weighted
    cross-entropy; the mean of the layers' balance losses."""
    import jax.numpy as jnp

    x0, xt, w = batch
    at = compared_positions(x0.shape[1])
    embedded = model.embed_tokens(jnp.concatenate([x0, xt], axis=1))
    parts = model.layer_parts(embedded)
    h, (load, mean_probs, _) = model.hidden(x0, xt)
    noisy, norm = h[:, x0.shape[1]:], model.final_norm[...]
    z = model.read(noisy[:, at], norm)
    wce = w * model.cross_entropy(noisy, norm, x0)
    return {
        **{k: parts[k] for k in ("q", "k", "v", "attention", "router_in",
                                 "idx", "gates", "moe")},
        "layer1": parts["out"],
        "load": parts["load"][None],
        "pairs_not_computed": parts["pairs_not_computed"][None],
        "z": z,
        "logits": model.logits(z),
        "wce": wce[:, at],
        "diffusion": jnp.mean(wce, axis=-1),
        "aux": jnp.mean(model.aux_losses(load, mean_probs)[0])[None],
    }


# what ``moving_state`` read last, as ``families/moe_lm.py`` keeps it, a
# (layers, ..) array a block of expert layers, for ``readers/moe.py`` and
# ``grouped_product_counts``: run.py hands a reader no trainer, and calls
# this hook once more after the loop
LAST_LOADS: list = []  # cumulative, (layers, experts)
LAST_RECENT_LOADS: list = []  # of the last steps, (layers, steps, experts)


def moving_state(dp) -> np.ndarray:
    """The sum of each parameter leaf (AdamW moves every one in every
    step). Keeps the loads it read beside them: the cumulative ones for
    ``bd_expert_load_max_over_mean``, those of the last steps for
    ``bd_moe_experts_roofline_pct``."""
    import jax
    import jax.numpy as jnp

    from chipbench import correct

    layers = correct.pure(dp.rest)["layers"]
    sums, load, recent = jax.device_get((
        [jnp.sum(x.astype(jnp.float32))
         for x in jax.tree_util.tree_leaves(dp.params)],
        layers["load"], layers["recent_load"]))
    LAST_LOADS[:] = [np.asarray(load, np.float64)]
    LAST_RECENT_LOADS[:] = [np.asarray(recent, np.float64)]
    return np.asarray(sums)


def make_pool(cfg: dict, n: int, rng: np.random.Generator) -> tuple:
    """``n`` clean sequences of ``seq_len`` token ids drawn from a Zipf
    distribution with exponent ``token_zipf_exponent`` over the ids below
    the mask's (id 0 the most frequent), and a key a sequence: what the
    noising transform seeds its draws with, so that the noise too comes
    from ``--seed``."""
    ranks = np.arange(1, mask_id(cfg) + 1, dtype=np.float64)
    p = ranks ** -float(cfg["token_zipf_exponent"])
    tokens = rng.choice(mask_id(cfg), size=(n, cfg["seq_len"]),
                        p=p / p.sum()).astype(np.int32)
    return tokens, rng.integers(0, 2**62, size=n, dtype=np.int64)


def transform(cfg: dict):
    """``(x0, key) -> (x0, xt, w)``: the program's own noising."""
    from tpu_syncbn.data import transforms as T

    return T.BlockDiffusionNoise(block=cfg["block_length"],
                                 mask_id=mask_id(cfg), seed=0,
                                 t_min=cfg["noise_t_min"])


def reference_fn(cfg: dict):
    return functools.partial(
        reference_block_diffusion_lm.block_diffusion_lm,
        positions=compared_positions(cfg["seq_len"]),
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], block=cfg["block_length"],
        theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        aux_weight=cfg["router_aux_loss_coef"],
        moe=dict(top_k=cfg["num_experts_per_tok"],
                 first_expert=cfg["first_expert"]),
        dtype=_shared.dtype_of(cfg["compute_dtype"]))


def train_flops_per_image(cfg: dict) -> int:
    """Training operations of one sample, which is one sequence."""
    return flops_block_diffusion_lm.train_flops_per_sequence(cfg)


def attention_kernel_family_counts(cfg: dict, wl: dict) -> dict:
    """Kernel name -> (operations, bytes) of ONE call of each of the
    attention core's three kernels in this cell:
    ``bd_attention_roofline_pct``'s numerator a call."""
    return flops_block_diffusion_lm.attention_kernel_counts(
        cfg, wl["per_chip_batch"])


def grouped_product_counts(cfg: dict, wl: dict, steps: int):
    """[(operations, bytes)] of the grouped products of each of the
    ``steps`` whole steps of the traced slice, over all its layers:
    ``bd_moe_experts_roofline_pct``'s numerator, at the pairs that
    arrived on the held experts in those very steps, as
    ``families/moe_lm.py::grouped_product_counts`` counts them (the
    products are the same: 2 x 2048 x 768 operations a pair, matrix and
    pass). None where no run has kept its loads, or fewer steps' than
    the slice holds."""
    held = slice(cfg["first_expert"],
                 cfg["first_expert"] + cfg["num_experts"])
    if not LAST_RECENT_LOADS:
        return None
    recent = np.concatenate(LAST_RECENT_LOADS)[:, :, held]
    if recent.shape[1] < steps + 1:
        return None
    return [flops_moe_lm.grouped_product_counts(
                cfg, step.sum(axis=-1), (step > 0).sum(axis=-1))
            for step in np.moveaxis(recent[:, -(steps + 1):-1], 1, 0)]

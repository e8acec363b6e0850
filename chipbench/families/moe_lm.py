"""Latent-attention mixture-of-experts decoders with a multi-token
prediction module: ``models.moe_lm.LatentMoEDecoderLM`` under its two
cross-entropies and AdamW. A sample is one sequence of ``seq_len`` tokens
with the two that follow it (``per_chip_batch`` counts sequences).

There is no BatchNorm: the state that must move is every parameter and
every expert layer's selection bias (``moving_state``), the reference
returns no ``stem``, and the tolerances are this family's own.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import flops_moe_lm, reference_moe_lm
from chipbench.families import _shared
# AdamW at the configuration's constant rate, ``model.loss(*batch)``, the
# 128 compared positions of a sequence and the identity transform: the
# looped family's, which holds its language model as this one does
from chipbench.families.looped_lm import (  # noqa: F401
    compared_positions, loss_fn, optimizer, transform)

# Relative L2 of each error ``reference_moe_lm.moe_lm`` returns (two are
# shares, said so), looked up before correct.py's table. Beside each
# limit: what the chip read at the timed sizes (bf16 products; my chip
# runs, PR 34, PERF.md section 6: the range over the seeds of those runs)
# and what the controls of ``chipbench/controls_moe_lm.py`` read there,
# the reference lowered one arithmetic at a time, each of which has to
# fail a limit: products of float8_e4m3fn operands (fp8), the
# attention's scores and softmax in bf16, the router's scores in bf16,
# the loss in bf16. As in ``looped_lm``: a chain of layers carries the
# bf16 roundings that XLA keeps or drops differently on the two sides, so
# the chain's limits are wide and the pieces that no layer has amplified
# (``attention``, ``router``, ``moe``, ``head``, ``cross_entropy``) tell
# the precisions apart.
TOLERANCES = {
    # The dense layer from the embeddings: read 5.3e-3 to 5.4e-3; fp8
    # 0.14.
    "layer1": 2.5e-2,
    # The reference's attention core (float32 from stored bf16 q, k, v,
    # q and k 192 wide, v 128) on the program's own q, k and v of the
    # opened expert layer: what the kernel alone adds. Read 5.4e-4 to
    # 5.7e-4: a fifth of the looped cell's 2.6e-3, because at 8,192
    # tokens and random weights the softmax is nearly flat and an output
    # is the mean of thousands of values, in which the roundings of the
    # probabilities average out. Scores and softmax in bf16 2.8e-3, fp8
    # 3.0e-3. The limit is twice the largest reading and under half the
    # control's.
    "attention": 1.2e-3,
    # The reference's router on the program's own router input, as dense
    # (T, 256) maps of the weights: float32 at full precision on both
    # sides, so the chosen sets are equal but for near-ties. Read 5.2e-8
    # to 5.8e-8 (no pair differs); the router in bf16 0.18.
    "router": 1e-3,
    # The loads of the 256 experts from that selection: read 0.0; the
    # router in bf16 1.7e-2.
    "loads": 1e-3,
    # The share of the chosen pairs on held experts that were not
    # computed: none may be. Read 0.0.
    "pairs_not_computed": 0.0,
    # The reference's mixture (every held expert on every token, dense
    # weights, the shared expert) on the program's own router input:
    # read 4.05e-3 to 4.13e-3; the router in bf16 4.8e-2, fp8 7.4e-2.
    "moe": 1e-2,
    # The opened expert layer on the program's own input of it: read
    # 5.5e-3 to 6.3e-3 (4.7e-3 to 7.6e-3 over fifteen seeds on the first
    # expert layer, which earlier runs opened); fp8 3.3e-2.
    "expert_layer": 2.5e-2,
    # The reference's head on the program's own z, both reads: read 0.0;
    # fp8 3.3e-2.
    "head": 1e-3,
    # The reference's cross-entropy of the program's own z at the
    # compared positions, both reads, position by position: read 3.4e-5
    # to 3.9e-5; the loss in bf16 2.3e-3, fp8 3.9e-3.
    "cross_entropy": 1e-4,
    # The whole chain from the tokens: what the head reads and its
    # logits, main model and prediction module: read 9.8e-3 to 2.1e-2
    # over fifteen seeds; fp8 0.14 to 0.17.
    **{f"{n}_{m}": 5e-2 for n in ("z", "logits") for m in ("main", "mtp")},
    # Each read's mean cross-entropy over every position: guards which
    # positions are counted, tells no precision (read 2.9e-6 to 1.7e-5;
    # the loss in bf16 2.9e-5 and 3.6e-5; fp8 6.5e-4 in the main read).
    "main_loss": 2e-4,
    "mtp_loss": 2e-4,
    # "loss", the first train_step's, is left to correct.py's LOSS_TOL
    # (1e-2), the limit of the accepted cells: read 8.0e-7 to 1.0e-5.
}

# what the model does not do: a configuration that asks for it is refused
_FIXED = {"n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "norm_topk_prob": True,
          "rope_scaling": None, "rope_interleave": True, "moe_layer_freq": 1,
          "attention_bias": False, "hidden_act": "silu",
          "tie_word_embeddings": False}


def model_kwargs(cfg: dict) -> dict:
    """The configuration's keys as ``LatentMoEDecoderLM`` names them."""
    for key, value in _FIXED.items():
        if cfg[key] != value:
            raise ValueError(f"LatentMoEDecoderLM has {key} = {value!r} only")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("latent attention has one k and v a head")
    if cfg["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("the prediction module has depth 0 or 1")
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        dense_layers=cfg["first_k_dense_replace"],
        dense_intermediate=cfg["intermediate_size"],
        moe_layers=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        n_experts=cfg["router_experts"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        moe_intermediate=cfg["moe_intermediate_size"],
        shared_intermediate=(cfg["n_shared_experts"]
                             * cfg["moe_intermediate_size"]),
        routed_scale=cfg["routed_scaling_factor"],
        bias_gamma=cfg["bias_update_gamma"],
        mtp=bool(cfg["num_nextn_predict_layers"]),
        mtp_weight=cfg["mtp_loss_weight"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        init_std=cfg["init_std"],
        dtype=_shared.dtype_of(cfg["compute_dtype"]),
        attn_impl=cfg["attn_impl"],
    )


def build_model(cfg: dict, key):
    from tpu_syncbn.models.moe_lm import LatentMoEDecoderLM

    return _shared.build_on_device(
        lambda rngs: LatentMoEDecoderLM(**model_kwargs(cfg), rngs=rngs),
        key, sync=False)


def outputs(model, batch) -> dict:
    """What the reference is compared on (``reference_moe_lm.moe_lm``
    lists the shapes), the batch or the replica on the leading axis of
    each: the dense layers' output; ONE expert layer of the stack
    applied to the program's own input of it, opened up: the layer
    whose held experts the global batch chose most often (at
    initialisation nearly every token chooses the same few experts, so
    the first layer's held experts may get next to no pair, and a
    dispatch that is given nothing is not compared); what the two heads
    read, their logits and cross-entropies at the compared positions;
    each read's mean cross-entropy; the selection biases."""
    import jax.numpy as jnp

    tokens, targets, targets2 = batch
    at = compared_positions(tokens.shape[1])
    layer1, _ = model.run(model.dense, model.embed_tokens(tokens))
    h, (loads, _, inputs) = model.run(model.sparse, layer1, keep_inputs=True)
    held = slice(model.first_expert,
                 model.first_expert + model.sparse.eg[...].shape[1])
    opened = jnp.argmax(jnp.sum(model.global_load(loads)[:, held], axis=-1))
    parts = model.expert_layer_parts(inputs[opened], opened)
    h_mtp, _ = model.mtp_hidden(h, targets)
    norms = model.final_norm[...], model.mtp_norm[...]
    z = jnp.stack([model.read(h[:, at], norms[0]),
                   model.read(h_mtp[:, at], norms[1])], axis=1)
    ce = jnp.stack([model.cross_entropy(h, norms[0], targets),
                    model.cross_entropy(h_mtp, norms[1], targets2)], axis=1)
    return {
        "layer1": layer1,
        **{k: parts[k] for k in ("q", "k", "v", "attention", "router_in",
                                 "idx", "gates", "moe")},
        "opened": opened[None],
        "expert_layer_in": inputs[opened],
        "expert_layer": parts["out"],
        "load": parts["load"][None],
        "pairs_not_computed": parts["pairs_not_computed"][None],
        "z": z,
        "logits": model.logits(z),
        "ce": ce[:, :, at],
        "losses": jnp.mean(ce, axis=-1),
        "bias_sparse": model.sparse.bias[...][None],
        "bias_mtp": model.mtp_block.bias[...][None],
    }


# what ``moving_state`` read last, a (layers, ..) array a block of expert
# layers, for ``readers/moe.py`` and ``grouped_product_counts``: run.py
# hands a reader no trainer, and calls this hook once more after the loop
LAST_LOADS: list = []  # cumulative, (layers, experts)
LAST_RECENT_LOADS: list = []  # of the last steps, (layers, steps, experts)


def moving_state(dp) -> np.ndarray:
    """The sum of each parameter leaf (AdamW moves every one in every
    step) and the summed magnitude of each expert layer's selection bias
    (every step moves it by gamma an expert). Keeps the loads it read
    beside them: the cumulative ones for ``expert_load_max_over_mean``,
    those of the last steps for ``moe_experts_roofline_pct``."""
    import jax
    import jax.numpy as jnp

    from chipbench import correct

    rest = correct.pure(dp.rest)
    blocks = [rest[name] for name in sorted(rest)]
    sums, loads, recent = jax.device_get((
        [jnp.sum(x.astype(jnp.float32))
         for x in jax.tree_util.tree_leaves(dp.params)]
        + [jnp.sum(jnp.abs(layer)) for b in blocks for layer in b["bias"]],
        [b["load"] for b in blocks], [b["recent_load"] for b in blocks]))
    LAST_LOADS[:] = [np.asarray(x, np.float64) for x in loads]
    LAST_RECENT_LOADS[:] = [np.asarray(x, np.float64) for x in recent]
    return np.asarray(sums)


def make_pool(cfg: dict, n: int, rng: np.random.Generator) -> tuple:
    """``n`` sequences of ``seq_len + 2`` token ids drawn from a Zipf
    distribution with exponent ``token_zipf_exponent`` over the ids of
    the vocabulary held (id 0 the most frequent); the tokens, the next
    and the one after. (The ids hardly reach the routing: as initialised
    nearly every token chooses the same few experts whatever its id,
    PERF.md section 6, PR 34.)"""
    ranks = np.arange(1, cfg["vocab_size"] + 1, dtype=np.float64)
    p = ranks ** -float(cfg["token_zipf_exponent"])
    tokens = rng.choice(cfg["vocab_size"], size=(n, cfg["seq_len"] + 2),
                        p=p / p.sum()).astype(np.int32)
    return tokens[:, :-2], tokens[:, 1:-1], tokens[:, 2:]


def reference_fn(cfg: dict):
    return functools.partial(
        reference_moe_lm.moe_lm,
        positions=compared_positions(cfg["seq_len"]),
        heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], mtp_weight=cfg["mtp_loss_weight"],
        moe=dict(top_k=cfg["num_experts_per_tok"],
                 scale=cfg["routed_scaling_factor"],
                 first_expert=cfg["first_expert"]),
        dtype=_shared.dtype_of(cfg["compute_dtype"]))


def train_flops_per_image(cfg: dict) -> int:
    """Training operations of one sample, which is one sequence."""
    return flops_moe_lm.train_flops_per_sequence(cfg)


def attention_kernel_call_counts(cfg: dict, wl: dict) -> tuple[int, int]:
    """(operations, bytes) of ONE call of the attention kernel in this
    cell: ``mla_kernel_roofline_pct``'s numerator a call."""
    return flops_moe_lm.flash_forward_counts(cfg, wl["per_chip_batch"])


def grouped_product_counts(cfg: dict, wl: dict, steps: int):
    """[(operations, bytes)] of the grouped products of each of the
    ``steps`` whole steps of the traced slice, over all its expert
    layers: ``moe_experts_roofline_pct``'s numerator, **at the pairs that
    arrived in those very steps**. The slice ends where the run's last
    step starts (``trace_reduce.step_slice``), so its steps are the
    ``steps`` before the last of the single steps' loads that
    ``moving_state`` kept after the loop. By layer and step: the loads
    of the experts held are the pairs, and an expert that got one had
    its weights read. At initialisation the router sends nearly every
    token to the same few experts (``expert_load_max_over_mean`` reads
    30), so a layer's held experts get 8,000 to 16,000 pairs or next to
    none, by the seed and the step: a count at the expected 4,096 pairs
    a layer read 113.5% on the chip, and a mean over other steps than
    the traced ones divides one set of steps by another. None where no
    run has kept its loads, or fewer steps' than the slice holds."""
    held = slice(cfg["first_expert"],
                 cfg["first_expert"] + cfg["n_routed_experts"])
    if not LAST_RECENT_LOADS:
        return None
    recent = np.concatenate(LAST_RECENT_LOADS)[:, :, held]
    if recent.shape[1] < steps + 1:
        return None
    return [flops_moe_lm.grouped_product_counts(
                cfg, step.sum(axis=-1), (step > 0).sum(axis=-1))
            for step in np.moveaxis(recent[:, -(steps + 1):-1], 1, 0)]

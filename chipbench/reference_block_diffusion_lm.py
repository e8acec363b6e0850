"""Plain reference of the block-diffusion mixture-of-experts decoder: the
equations of its configuration in straight ``jax.numpy``, Python loops
over layers, experts and query blocks, the mask as a dense boolean map,
k and v repeated a q head, no scan, no kernel, no grouped product, no
sort-and-gather dispatch, reading the program's parameters by name and
sharing no code with ``tpu_syncbn/``.

As in ``reference_lm.py``: products at HIGHEST precision accumulated in
float32, operands and stored activations rounded to the configuration's
``compute_dtype`` where the configuration says the program rounds them
(the matmuls' operands and results, the residual stream, q, k and v, the
attention core's output, the experts' gated product), everything else
(norms, rotary angles, the router's probabilities and weights, the
scores of the attention, the softmax and its probabilities where they
meet v, SiLU, the weighted sum over the chosen experts, the logits, the
losses) in float32. With ``compute_dtype`` float32 (the CPU tests) it is
the pure float32 reference.

Equations, x of shape (B, S, H) (the decoder Qwen3-MoE's, the training
BD3-LM's vectorised form, arXiv:2503.09573):

* ``RMSNorm(x) = x * rsqrt(mean(x^2, -1) + eps) * g``
* a layer: ``a = x + Attn(N1(x))``, ``y = a + MoE(N2(a))``
* attention: ``q = n W_q`` as ``heads`` heads, ``k = n W_k``, ``v = n
  W_v`` as ``kv_heads``; q and k through an RMSNorm over each head's
  width; rotary over the whole head, half-split, **position i of each
  half of the 2L positions at rotary position i**; q head h reads k/v
  head ``h // (heads / kv_heads)`` (``kv_head_of``); ``softmax(q k^T /
  sqrt(d) + M) v``; heads joined; ``W_o``
* the mask M (``visibility``) over positions laid out ``[clean ;
  noisy]``, ``blk(p) = (p mod L) // block``: query p sees key r iff
  ``(clean(p) and clean(r) and blk(r) <= blk(p))`` or ``(noisy(p) and
  clean(r) and blk(r) < blk(p))`` or ``(noisy(p) and noisy(r) and blk(r)
  == blk(p))``
* the mixture: ``p = softmax(x W_r)`` over all E experts; the chosen
  set: the k largest, found by a full sort (ties to the lower index);
  ``g = p / sum over the chosen of p`` on the chosen, 0 elsewhere, kept
  as a dense (T, E) map; ``sum_e g_e E_e(x)`` **over the experts held**
  (``first_expert`` .. ``first_expert + E_held - 1``: the chip's share,
  the others' part is left out as in the program), every expert applied
  to every token by a Python loop
* ``aux = E * sum_e f_e P_e`` a layer, ``f`` the experts' shares of the
  chosen pairs, ``P`` their mean probabilities
* ``loss = mean(w * CE(logits(noisy position i), x0_i)) + aux_weight *
  mean over layers(aux)``: no shift

Attention is computed a block of queries at a time and the head a block
of positions at a time, so that on the chip the reference fits beside
the trainer's state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import rel_l2
# RMSNorm, the half-split rotary, the head's logits and the block sizes
# are the looped reference's, the rounded product and the dense map of a
# selection the other mixture's: plain jax.numpy too. What this file's
# own functions call by name here, a control can replace here
from chipbench.reference_lm import (  # noqa: F401
    HEAD_BLOCK, QUERY_BLOCK, head_logits, rms_norm, rotary)
from chipbench.reference_moe_lm import dense_weights, dot  # noqa: F401

HIGHEST = lax.Precision.HIGHEST


def visibility(clean_len: int, block: int):
    """The dense (2L, 2L) boolean map, queries down the rows: the three
    clauses of the module docstring."""
    p = jnp.arange(2 * clean_len)
    clean, blk = p < clean_len, (p % clean_len) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return ((q_clean & k_clean & (k_blk <= q_blk))
            | (~q_clean & k_clean & (k_blk < q_blk))
            | (~q_clean & ~k_clean & (k_blk == q_blk)))


def kv_head_of(heads: int, kv_heads: int):
    """(heads,): the k/v head each q head reads."""
    return jnp.arange(heads) // (heads // kv_heads)


def attention(q, k, v, block):
    """Softmax attention of q (B, 2L, heads, d) over k, v (B, 2L,
    kv_heads, d) under the dense mask, k and v repeated a q head, a
    block of queries at a time: scores, softmax and probabilities x
    values in float32, the result stored in v's type."""
    s, d = q.shape[1], q.shape[-1]
    visible = visibility(s // 2, block)
    reads = kv_head_of(q.shape[2], k.shape[2])
    k, v = jnp.take(k, reads, axis=2), jnp.take(v, reads, axis=2)
    blocks = []
    for start in range(0, s, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST,
                            preferred_element_type=jnp.float32) / d ** 0.5
        scores = jnp.where(visible[start:start + QUERY_BLOCK], scores,
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        blocks.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32))
    return jnp.concatenate(blocks, axis=1).astype(v.dtype)


def rotary_halves(x, theta):
    """``x`` (B, 2L, heads, d): each half rotated as a sequence of its
    own, position i of either at the angle of i."""
    half = x.shape[1] // 2
    return jnp.concatenate([rotary(x[:, :half], theta),
                            rotary(x[:, half:], theta)], axis=1)


def gqa_qkv(p, x, *, heads, kv_heads, theta, eps, dtype):
    """q (B, 2L, heads, d), k and v (B, 2L, kv_heads, d) of one layer's
    parameters ``p`` on its input ``x``."""
    b, s, _ = x.shape
    n = rms_norm(x, p["norm1"], eps)
    q = dot(n, p["wq"], dtype).reshape(b, s, heads, -1)
    k = dot(n, p["wk"], dtype).reshape(b, s, kv_heads, -1)
    v = dot(n, p["wv"], dtype).reshape(b, s, kv_heads, -1)
    return (rotary_halves(rms_norm(q, p["q_norm"], eps), theta),
            rotary_halves(rms_norm(k, p["k_norm"], eps), theta), v)


def attention_block(p, x, *, block, dtype, **kw):
    """``a = x + Attn(N1(x))``."""
    b, s, _ = x.shape
    o = attention(*gqa_qkv(p, x, dtype=dtype, **kw), block)
    return x + dot(o.reshape(b, s, -1), p["wo"], dtype)


def router_probs(x, w):
    """``softmax(x W_r)``, (T, E) float32 at full precision."""
    return jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                                  w.astype(jnp.float32), precision=HIGHEST),
                          axis=-1)


def router(x, w, *, top_k):
    """The dense (T, E) float32 map of the weights, 0 off the chosen
    set, of the (T, H) router input ``x``; and the probabilities."""
    p = router_probs(x, w)
    order = jnp.argsort(-p, axis=-1, stable=True)
    chosen = jnp.argsort(order, axis=-1, stable=True) < top_k  # by rank
    g = jnp.where(chosen, p, 0.0)
    return g / jnp.sum(g, axis=-1, keepdims=True), p


def experts(x, weights, p, *, first_expert, dtype):
    """``sum_e g_e E_e(x)`` over the experts held, (T, H) in ``dtype``:
    every held expert applied to every token."""
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(p["eg"].shape[0]):
        gate = dot(x, p["eg"][e], dtype, jnp.float32)
        up = dot(x, p["eu"][e], dtype, jnp.float32)
        act = (jax.nn.silu(gate) * up).astype(dtype)
        out = dot(act, p["ed"][e], dtype, jnp.float32)
        y = y + out * weights[:, first_expert + e, None]
    return y.astype(dtype)


def balance_loss(weights, probs):
    """``E * sum_e f_e P_e`` of a layer's dense weights and
    probabilities, and the (E,) loads."""
    load = jnp.sum(weights > 0, axis=0, dtype=jnp.float32)
    share = load / jnp.sum(load)
    return probs.shape[-1] * jnp.sum(share * jnp.mean(probs, axis=0)), load


def mixture(n, p, *, top_k, first_expert, dtype):
    """The mixture on the normed input ``n`` (B, S, H), its dense
    (T, E) weights and its probabilities."""
    flat = n.reshape(-1, n.shape[-1])
    weights, probs = router(flat, p["router"], top_k=top_k)
    routed = experts(flat, weights, p, first_expert=first_expert,
                     dtype=dtype)
    return routed.reshape(n.shape), weights, probs


def layer(p, x, *, eps, dtype, moe, **attn):
    """One layer of parameters ``p`` (its slice of the stacked ones) on
    ``x``; and its balance loss."""
    a = attention_block(p, x, eps=eps, dtype=dtype, **attn)
    mixed, weights, probs = mixture(rms_norm(a, p["norm2"], eps), p,
                                    dtype=dtype, **moe)
    return a + mixed, balance_loss(weights, probs)[0]


def cross_entropy(params, z, targets, dtype):
    """Per-position cross-entropy of ``z`` (.., H) against ``targets``
    (..), a block of positions at a time."""
    zf, tf = z.reshape(-1, z.shape[-1]), targets.reshape(-1)
    out = []
    for start in range(0, zf.shape[0], HEAD_BLOCK):
        logits = head_logits(params, zf[start:start + HEAD_BLOCK], dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, tf[start:start + HEAD_BLOCK, None], axis=-1)
        out.append(-picked[:, 0])
    return jnp.concatenate(out).reshape(targets.shape)


def forward(params, x0, xt, w, *, aux_weight, dtype, **kw) -> dict:
    """Everything the comparison reads from the tokens: ``layer1`` (the
    first layer's output over the 2L positions), ``z`` (B, L, H), the
    noisy half after the final norm, the per-position cross-entropy
    ``ce`` (B, L), ``diffusion`` = mean(w * ce), ``aux`` the mean of the
    layers' balance losses, and the scalar ``loss``."""
    with jax.default_matmul_precision("highest"):
        stack = params["layers"]
        h = params["embed"].astype(dtype)[jnp.concatenate([x0, xt], axis=1)]
        aux, layer1 = [], None
        for i in range(stack["wq"].shape[0]):
            p = jax.tree_util.tree_map(lambda a: a[i], stack)
            h, a = layer(p, h, dtype=dtype, **kw)
            aux.append(a)
            layer1 = h if layer1 is None else layer1
        z = rms_norm(h[:, x0.shape[1]:], params["final_norm"], kw["eps"])
        ce = cross_entropy(params, z, x0, dtype)
        diffusion, aux = jnp.mean(w * ce), jnp.mean(jnp.stack(aux))
    return {"layer1": layer1, "z": z, "ce": ce, "diffusion": diffusion,
            "aux": aux, "loss": diffusion + aux_weight * aux}


def block_diffusion_lm(params, batch, got, *, positions, moe, **config):
    """The errors of the program's outputs ``got`` on ``batch`` = (x0,
    xt, w), and the loss. ``got`` holds, the batch leading, of the FIRST
    layer applied to the embeddings of ``[x0 ; xt]``: ``q`` (B, 2L,
    heads, d), ``k``, ``v`` (B, 2L, kv_heads, d), ``attention``,
    ``router_in`` (B, 2L, H), ``idx`` and ``gates`` (B, 2L, k), ``load``
    (replicas, E), ``pairs_not_computed`` (replicas,), ``moe`` and
    ``layer1`` (B, 2L, H); ``z`` (B, P, H), ``logits`` (B, P, vocabulary)
    and ``wce`` (B, P), the weighted cross-entropy, at ``positions`` of
    the noisy half; ``diffusion`` (B,), a sequence's mean weighted
    cross-entropy; ``aux`` (replicas,), the mean of the layers' balance
    losses. ``layer1``, ``z``, ``logits``, ``diffusion`` and ``aux`` are
    of the whole chain from the tokens; the others are the reference's
    piece on the program's own input of that piece, which no layer has
    amplified."""
    x0, xt, w = batch
    dtype = config.get("dtype", jnp.float32)
    attn = {k: config[k] for k in ("heads", "kv_heads", "theta", "block")}
    kw = dict(eps=config["eps"], moe=moe, **attn)
    want = forward(params, x0, xt, w, dtype=dtype,
                   aux_weight=config["aux_weight"], **kw)
    first = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    n_experts = first["router"].shape[-1]
    held = slice(moe["first_expert"],
                 moe["first_expert"] + first["eg"].shape[0])
    errors = {"layer1": rel_l2(got["layer1"], want["layer1"])}
    with jax.default_matmul_precision("highest"):
        errors["attention"] = rel_l2(
            got["attention"],
            attention(got["q"], got["k"], got["v"], config["block"]))
        mixed, weights, probs = mixture(got["router_in"], first, dtype=dtype,
                                        **moe)
        k = got["idx"].shape[-1]
        errors["router"] = rel_l2(
            dense_weights(got["idx"].reshape(-1, k),
                          got["gates"].reshape(-1, k), n_experts), weights)
        load = balance_loss(weights, probs)[1]
        errors["loads"] = rel_l2(jnp.sum(got["load"], axis=0), load)
        # a share of the held pairs; of one pair where none was held
        errors["pairs_not_computed"] = (
            jnp.sum(got["pairs_not_computed"])
            / jnp.maximum(jnp.sum(load[held]), 1.0))
        errors["moe"] = rel_l2(got["moe"], mixed)
        z = want["z"][:, positions]
        errors["z"] = rel_l2(got["z"], z)
        errors["logits"] = rel_l2(got["logits"], head_logits(params, z, dtype))
        errors["head"] = rel_l2(got["logits"],
                                head_logits(params, got["z"], dtype))
        errors["cross_entropy"] = rel_l2(
            got["wce"], w[:, positions] * cross_entropy(
                params, got["z"], x0[:, positions], dtype))
        errors["diffusion_loss"] = rel_l2(jnp.mean(got["diffusion"]),
                                          want["diffusion"])
        errors["aux"] = rel_l2(jnp.mean(got["aux"]), want["aux"])
    return {"errors": errors, "loss": want["loss"]}

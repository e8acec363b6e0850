"""Plain reference of the latent-attention mixture-of-experts decoder:
the equations of its configuration in straight ``jax.numpy``, Python
loops over layers, experts and query blocks, no scan, no kernel, no
grouped product, no sort-and-gather dispatch, reading the program's
parameters by name and sharing no code with ``tpu_syncbn/``.

As in ``reference_lm.py``: products at HIGHEST precision accumulated in
float32, operands and stored activations rounded to the configuration's
``compute_dtype`` where the configuration says the program rounds them
(the matmuls' operands and results, the residual stream, the rotated
q and k, v, the attention core's output, the experts' gated product),
everything else (norms, rotary angles, the router's scores and weights,
the scores of the attention, the softmax and its probabilities where
they meet v, SiLU, the weighted sum over the chosen experts, the logits,
the loss) in float32. With ``compute_dtype`` float32 (the CPU tests) it
is the pure float32 reference.

Equations, x of shape (B, S, H) (DeepSeek-V3's, arXiv:2412.19437):

* ``RMSNorm(x) = x * rsqrt(mean(x^2, -1) + eps) * g``
* a layer: ``a = x + MLA(N1(x))``, ``y = a + F(N2(a))``; F the dense
  MLP ``(silu(x Wg) * (x Wu)) Wd`` or the mixture of experts
* MLA: ``c_q = N(x W_qa)``, ``q = c_q W_qb`` a head ``[q_nope ;
  q_rope]``; ``[c_kv ; k_rope] = x W_kva``, ``c_kv = N(c_kv)``, ``k_rope``
  shared by the heads; ``[k_nope ; v] = c_kv W_kvb`` a head; rotary on
  ``q_rope`` and ``k_rope`` over the pairs (2i, 2i+1) with ``inv_freq_i
  = theta^(-2i/d)``; ``softmax([q_nope ; q_rope] [k_nope ; k_rope]^T /
  sqrt(d_qk) + causal) v``; heads joined; ``W_o``
* the mixture: ``s = sigmoid(x W_r)`` over all E experts; the chosen
  set: the k largest of ``s + b``, found by a full sort (ties to the
  lower index); ``g = scale * s / (sum over the chosen of s + 1e-20)``
  on the chosen, 0 elsewhere, kept as a dense (T, E) map;
  ``sum_e g_e E_e(x)`` **over the experts held** (``first_expert`` ..
  ``first_expert + E_held - 1``: the chip's share, the others' part is
  left out as in the program), every expert applied to every token by a
  Python loop, ``+ E_shared(x)``
* multi-token prediction: ``h' = W_eh [N_e(E[t_{i+1}]) ; N_h(h_i)]``,
  one expert layer, a norm of its own, the shared head;
  ``loss = CE(main, t_{i+1}) + w * CE(mtp, t_{i+2})``

Attention is computed a block of queries at a time and the head a block
of positions at a time, so that on the chip the reference fits beside
the trainer's state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import rel_l2
# RMSNorm and the causal attention core in query blocks (q and k of one
# width, v of any) are the looped reference's, plain jax.numpy too
from chipbench.reference_lm import HEAD_BLOCK, attention, rms_norm
HIGHEST = lax.Precision.HIGHEST


def dot(x, w, dtype, out=None):
    """Operands rounded to ``dtype``, accumulated in float32, stored in
    ``out`` (default ``dtype``)."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), precision=HIGHEST,
                   preferred_element_type=jnp.float32).astype(out or dtype)


def rotary_pairs(x, theta):
    """``x`` (B, S, heads, d): position s rotates the pair (2i, 2i+1) by
    the angle ``s * theta^(-2i/d)``."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.zeros_like(x32)
    out = out.at[..., 0::2].set(even * cos - odd * sin)
    out = out.at[..., 1::2].set(odd * cos + even * sin)
    return out.astype(x.dtype)


def mla_qkv(p, x, *, heads, nope, rope, theta, eps, dtype):
    """q, k (B, S, heads, nope + rope) and v (B, S, heads, dv) of one
    layer's parameters ``p`` on its input ``x``."""
    b, s, _ = x.shape
    n = rms_norm(x, p["norm1"], eps)
    c_q = rms_norm(dot(n, p["wqa"], dtype), p["q_norm"], eps)
    q = dot(c_q, p["wqb"], dtype).reshape(b, s, heads, nope + rope)
    kva = dot(n, p["wkva"], dtype)
    rank = kva.shape[-1] - rope
    c_kv = rms_norm(kva[..., :rank], p["kv_norm"], eps)
    k_rope = rotary_pairs(kva[..., rank:].reshape(b, s, 1, rope), theta)
    kv = dot(c_kv, p["wkvb"], dtype).reshape(b, s, heads, -1)
    q = jnp.concatenate([q[..., :nope], rotary_pairs(q[..., nope:], theta)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.tile(k_rope, (1, 1, heads, 1))], axis=-1)
    return q, k, kv[..., nope:]


def attention_block(p, x, *, dtype, **kw):
    """``a = x + MLA(N1(x))``."""
    b, s, _ = x.shape
    o = attention(*mla_qkv(p, x, dtype=dtype, **kw))
    return x + dot(o.reshape(b, s, -1), p["wo"], dtype)


def swiglu(x, wg, wu, wd, dtype):
    gate = dot(x, wg, dtype).astype(jnp.float32)
    up = dot(x, wu, dtype).astype(jnp.float32)
    return dot((jax.nn.silu(gate) * up).astype(dtype), wd, dtype)


def router_scores(x, w):
    """``sigmoid(x W_r)``, (T, E) float32 at full precision."""
    return jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                  w.astype(jnp.float32), precision=HIGHEST))


def router(x, w, bias, *, top_k, scale):
    """The dense (T, E) float32 map of the weights, 0 off the chosen
    set, of the (T, H) router input ``x``."""
    s = router_scores(x, w)
    order = jnp.argsort(-(s + bias), axis=-1, stable=True)
    chosen = jnp.argsort(order, axis=-1, stable=True) < top_k  # by rank
    g = jnp.where(chosen, s, 0.0)
    return scale * g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)


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


def mixture(n, p, bias, *, top_k, scale, first_expert, dtype):
    """The mixture on the normed input ``n`` (B, S, H) and its dense
    (T, E) weights."""
    flat = n.reshape(-1, n.shape[-1])
    weights = router(flat, p["router"], bias, top_k=top_k, scale=scale)
    routed = experts(flat, weights, p, first_expert=first_expert, dtype=dtype)
    shared = swiglu(n, p["sg"], p["su"], p["sd"], dtype)
    return routed.reshape(n.shape) + shared, weights


def layer(p, bias, x, *, eps, dtype, moe, **attn):
    """One layer of parameters ``p`` (its slice of the block's stacked
    ones) on ``x``; ``bias`` its selection bias, None in a dense layer."""
    a = attention_block(p, x, eps=eps, dtype=dtype, **attn)
    n = rms_norm(a, p["norm2"], eps)
    if bias is None:
        return a + swiglu(n, p["wg"], p["wu"], p["wd"], dtype)
    return a + mixture(n, p, bias, dtype=dtype, **moe)[0]


def block(stack, biases, x, **kw):
    """Every layer of a block of stacked parameters, in order."""
    for i in range(stack["wqa"].shape[0]):
        p = jax.tree_util.tree_map(lambda a: a[i], stack)
        x = layer(p, None if biases is None else biases[i], x, **kw)
    return x


def head_logits(params, z, dtype):
    """Float32 logits of ``z`` (.., H)."""
    return jnp.dot(z.astype(dtype), params["head"].astype(dtype),
                   precision=HIGHEST, preferred_element_type=jnp.float32)


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


def forward(params, biases, tokens, targets, targets2, *, mtp_weight, dtype,
            **kw) -> dict:
    """Everything the comparison reads from the tokens: ``layer1`` (the
    dense layers' output), ``h`` and ``h_mtp`` (B, S, H) before their
    norms, ``z`` and ``z_mtp`` after, the per-position cross-entropies
    ``ce`` and ``ce_mtp`` and the scalar ``loss``. ``biases``: the
    selection biases, ``sparse`` (n, E) and ``mtp`` (1, E)."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(dtype)[tokens]
        layer1 = block(params["dense"], None, x, dtype=dtype, **kw)
        h = block(params["sparse"], biases["sparse"], layer1, dtype=dtype, **kw)
        z = rms_norm(h, params["final_norm"], eps)
        ce = cross_entropy(params, z, targets, dtype)
        e = rms_norm(params["embed"].astype(dtype)[targets],
                     params["mtp_enorm"], eps)
        n = rms_norm(h, params["mtp_hnorm"], eps)
        x = dot(jnp.concatenate([e, n], axis=-1), params["mtp_proj"], dtype)
        h_mtp = block(params["mtp_block"], biases["mtp"], x, dtype=dtype, **kw)
        z_mtp = rms_norm(h_mtp, params["mtp_norm"], eps)
        ce_mtp = cross_entropy(params, z_mtp, targets2, dtype)
        loss = jnp.mean(ce) + mtp_weight * jnp.mean(ce_mtp)
    return {"layer1": layer1, "h": h, "h_mtp": h_mtp, "z": z, "z_mtp": z_mtp,
            "ce": ce, "ce_mtp": ce_mtp, "loss": loss}


def dense_weights(idx, gates, n_experts):
    """The program's ``idx`` and ``gates`` (T, k) as the dense (T, E)
    map the reference's router returns."""
    hit = idx[..., None] == jnp.arange(n_experts)
    return jnp.sum(jnp.where(hit, gates[..., None], 0.0), axis=1)


def moe_lm(params, batch, got, *, positions, moe, **config):
    """The errors of the program's outputs ``got`` on ``batch`` =
    (tokens, targets, targets2), and the loss. ``got`` holds, the batch
    leading: ``layer1`` (B, S, H), the dense layers' output; ``opened``
    (replicas,), which expert layer of the stack the program opened up
    (the one whose held experts were chosen most often), and of that
    layer applied to ``expert_layer_in`` (B, S, H), the program's own
    input of it, ``q``, ``k``, ``v``, ``attention``, ``router_in`` (B,
    S, H), ``idx`` and ``gates`` (B, S, k), ``load`` (replicas, E),
    ``pairs_not_computed`` (replicas,), ``moe`` and ``expert_layer`` (B,
    S, H); the selection biases as the program holds them,
    ``bias_sparse`` (replicas, n, E) and ``bias_mtp`` (replicas, 1, E); ``z`` (B, 2, P, H), ``logits`` (B, 2, P,
    vocabulary) and ``ce`` (B, 2, P) of the main model and the
    prediction module at ``positions``; ``losses`` (B, 2), a sequence's
    two mean cross-entropies. ``h_*``, ``logits_*``, ``*_loss`` and
    ``layer1`` are of the whole chain from the tokens; the others are
    the reference's piece on the program's own input of that piece, which
    no layer has amplified."""
    tokens, targets, targets2 = batch
    dtype = config.get("dtype", jnp.float32)
    attn = {k: config[k] for k in ("heads", "nope", "rope", "theta")}
    kw = dict(eps=config["eps"], moe=moe, **attn)
    biases = {"sparse": got["bias_sparse"][0], "mtp": got["bias_mtp"][0]}
    want = forward(params, biases, tokens, targets, targets2, dtype=dtype,
                   mtp_weight=config["mtp_weight"], **kw)
    opened = got["opened"][0]
    layer_p = jax.tree_util.tree_map(lambda a: a[opened], params["sparse"])
    bias = biases["sparse"][opened]
    n_experts = layer_p["router"].shape[-1]
    held = slice(moe["first_expert"], moe["first_expert"] + layer_p["eg"].shape[0])
    errors = {"layer1": rel_l2(got["layer1"], want["layer1"])}
    with jax.default_matmul_precision("highest"):
        errors["attention"] = rel_l2(
            got["attention"], attention(got["q"], got["k"], got["v"]))
        mixed, weights = mixture(got["router_in"], layer_p, bias, dtype=dtype,
                                 **moe)
        k = got["idx"].shape[-1]
        errors["router"] = rel_l2(
            dense_weights(got["idx"].reshape(-1, k),
                          got["gates"].reshape(-1, k), n_experts), weights)
        load = jnp.sum(weights > 0, axis=0, dtype=jnp.float32)
        errors["loads"] = rel_l2(jnp.sum(got["load"], axis=0), load)
        # a share of the held pairs; of one pair where none was held
        errors["pairs_not_computed"] = (
            jnp.sum(got["pairs_not_computed"])
            / jnp.maximum(jnp.sum(load[held]), 1.0))
        errors["moe"] = rel_l2(got["moe"], mixed)
        errors["expert_layer"] = rel_l2(got["expert_layer"], layer(
            layer_p, bias, got["expert_layer_in"], dtype=dtype, **kw))
        errors["head"] = rel_l2(got["logits"],
                                head_logits(params, got["z"], dtype))
        errors["cross_entropy"] = rel_l2(got["ce"], jnp.stack(
            [cross_entropy(params, got["z"][:, 0], targets[:, positions],
                           dtype),
             cross_entropy(params, got["z"][:, 1], targets2[:, positions],
                           dtype)], axis=1))
        for i, name in enumerate(("main", "mtp")):
            z = want["z" if i == 0 else "z_mtp"][:, positions]
            errors["z_" + name] = rel_l2(got["z"][:, i], z)
            errors["logits_" + name] = rel_l2(
                got["logits"][:, i], head_logits(params, z, dtype))
            errors[name + "_loss"] = rel_l2(
                jnp.mean(got["losses"][:, i]),
                jnp.mean(want["ce" if i == 0 else "ce_mtp"]))
    return {"errors": errors, "loss": want["loss"]}

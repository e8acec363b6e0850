"""Plain reference of the looped decoder language model: the equations
of its configuration in straight ``jax.numpy``, a Python loop over passes
and layers, no scan, no kernel, no recomputation, reading the program's
parameters by name and sharing no code with ``tpu_syncbn/``.

As in ``reference.py``: products at HIGHEST precision accumulated in
float32, operands and stored activations rounded to the configuration's
``compute_dtype`` where the configuration says the program rounds them
(the matmuls' operands and results, the residual stream, the rotated q
and k, v, the attention core's output), everything else (norms, rotary
angles, the scores, the softmax and its probabilities where they meet
v, SiLU and the gated product, the logits, the gate, the loss) in
float32. With ``compute_dtype`` float32 (the CPU tests) it is the pure
float32 reference.

Equations, x of shape (B, S, H):

* ``RMSNorm(x) = x * rsqrt(mean(x^2, -1) + eps) * g``
* ``q, k, v = x Wq, x Wk, x Wv`` in heads of d; rotary over the whole
  head with ``inv_freq_i = theta^(-2i/d)``, dimension i paired with
  i + d/2 (``rotate_half``); ``softmax(q k^T / sqrt(d) + causal) v``;
  heads joined; ``Wo``
* ``MLP(x) = (silu(x Wg) * (x Wu)) Wd``
* a layer: ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``
* ``h_0 = E[tokens]``, ``h_t = Layer_L(..Layer_1(h_{t-1}))`` with the
  same layers for t = 1..T; ``z_t = N_f(h_t)``, ``logits_t = z_t
  W_head``, ``lambda_t = sigmoid(z_t w_g + b_g)``
* ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` for t < T,
  ``p_T = prod_{j<T}(1 - lambda_j)``
* ``loss = mean over positions [sum_t p_t CE(logits_t, target)
  - beta H(p)]``

Attention is computed a block of queries at a time and the head a block
of positions at a time, so that on the chip the reference fits beside
the trainer's state: one block's float32 scores, one block's logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import rel_l2

QUERY_BLOCK = 512
HEAD_BLOCK = 1024  # positions a block of logits


def dot(x, w, dtype):
    """Operands rounded to ``dtype``, accumulated in float32, stored in
    ``dtype``."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(dtype)


def rms_norm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def rotary(x, theta):
    """``x`` (B, S, heads, d): position s rotates the pair (i, i + d/2)
    by the angle ``s * theta^(-2i/d)``."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    lo, hi = x32[..., : d // 2], x32[..., d // 2:]
    out = jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)
    return out.astype(x.dtype)


def attention(q, k, v):
    """Causal softmax attention of (B, S, heads, d) arrays, a block of
    queries at a time: scores, softmax and probabilities x values in
    float32, the result stored in v's type."""
    s, d = q.shape[1], q.shape[-1]
    keys = jnp.arange(s)
    blocks = []
    for start in range(0, s, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                            precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32) / d ** 0.5
        rows = start + jnp.arange(qb.shape[1])
        scores = jnp.where(rows[:, None] >= keys[None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        blocks.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                                 precision=lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32))
    return jnp.concatenate(blocks, axis=1).astype(v.dtype)


def layer(p, i, x, *, num_heads, theta, eps, dtype):
    """Layer ``i`` of the stacked parameters ``p`` applied to ``x``."""
    b, s, _ = x.shape
    n = rms_norm(x, p["norm1"][i], eps)
    heads = (b, s, num_heads, -1)
    q = rotary(dot(n, p["wq"][i], dtype).reshape(heads), theta)
    k = rotary(dot(n, p["wk"][i], dtype).reshape(heads), theta)
    v = dot(n, p["wv"][i], dtype).reshape(heads)
    o = dot(attention(q, k, v).reshape(b, s, -1), p["wo"][i], dtype)
    a = x + rms_norm(o, p["norm2"][i], eps)
    n = rms_norm(a, p["norm3"][i], eps)
    gate = dot(n, p["wg"][i], dtype).astype(jnp.float32)
    up = dot(n, p["wu"][i], dtype).astype(jnp.float32)
    m = dot((jax.nn.silu(gate) * up).astype(dtype), p["wd"][i], dtype)
    return a + rms_norm(m, p["norm4"][i], eps)


def head_logits(params, z, dtype):
    """Float32 logits of ``z`` (.., H)."""
    return jnp.dot(z.astype(dtype), params["head"].astype(dtype),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def cross_entropy(params, z, targets, dtype):
    """Per-position cross-entropy of ``z`` (B, S, H) against ``targets``
    (B, S), a block of positions at a time."""
    zf, tf = z.reshape(-1, z.shape[-1]), targets.reshape(-1)
    out = []
    for start in range(0, zf.shape[0], HEAD_BLOCK):
        logits = head_logits(params, zf[start:start + HEAD_BLOCK], dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, tf[start:start + HEAD_BLOCK, None], axis=-1)
        out.append(-picked[:, 0])
    return jnp.concatenate(out).reshape(targets.shape)


def gate(params, z):
    z32 = z.astype(jnp.float32)
    return jax.nn.sigmoid(
        jnp.einsum("...h,h->...", z32, params["gate_w"],
                   precision=lax.Precision.HIGHEST) + params["gate_b"])


def exit_distribution(lams: list) -> list:
    """``lams`` the T gate arrays; the last pass takes what is left."""
    p, stayed = [], jnp.ones_like(lams[0])
    for lam in lams[:-1]:
        p.append(stayed * lam)
        stayed = stayed * (1.0 - lam)
    return p + [stayed]


def forward(params, tokens, targets, *, num_heads, loops, theta, eps, beta,
            dtype=jnp.float32) -> dict:
    """Everything the comparison reads: ``layer1`` (the first layer
    applied once to the embeddings), per pass ``z`` (B, S, H), the
    per-position cross-entropy ``ce`` and the exit probabilities ``p``
    (each a list of T (B, S) arrays), and the scalar ``loss``."""
    with jax.default_matmul_precision("highest"):
        stack = params["layers"]
        kw = dict(num_heads=num_heads, theta=theta, eps=eps, dtype=dtype)
        h = params["embed"].astype(dtype)[tokens]
        layer1 = layer(stack, 0, h, **kw)
        zs, ces, lams = [], [], []
        for _ in range(loops):
            for i in range(stack["wq"].shape[0]):
                h = layer(stack, i, h, **kw)
            z = rms_norm(h, params["final_norm"], eps)
            zs.append(z)
            ces.append(cross_entropy(params, z, targets, dtype))
            lams.append(gate(params, z))
        p = exit_distribution(lams)
        mixed = sum(pt * ce for pt, ce in zip(p, ces))
        entropy = -sum(pt * jnp.log(jnp.maximum(pt, 1e-30)) for pt in p)
        loss = jnp.mean(mixed - beta * entropy)
    return {"layer1": layer1, "z": zs, "ce": ces, "p": p, "loss": loss}


def looped_lm(params, batch, got, *, positions, **config):
    """The errors of the program's outputs ``got`` on ``batch`` =
    (tokens, targets), and the loss. ``got`` holds, the batch leading,
    ``layer1`` (B, S, H), the first layer's ``q``, ``k``, ``v`` and
    ``attention`` (B, S, heads, d), ``z`` (B, T, P, H) and ``logits``
    (B, T, P, vocabulary) at ``positions`` (P indices into S),
    ``exit_p`` (B, T, S), ``ce`` (B, T, P), the cross-entropy at
    ``positions``, and ``pass_loss`` (B, T), a sequence's mean
    cross-entropy. Every error is of the whole chain from the tokens,
    but three that no layer has amplified: ``head`` and
    ``cross_entropy``, the logits and the cross-entropy that the
    reference's head gives the program's own ``z``, and ``attention``,
    what the reference's attention core gives the program's own q, k
    and v of the first layer."""
    tokens, targets = batch
    dtype = config.get("dtype", jnp.float32)
    want = forward(params, tokens, targets, **config)
    errors = {"layer1": rel_l2(got["layer1"], want["layer1"]),
              "exit_p": rel_l2(got["exit_p"], jnp.stack(want["p"], axis=1))}
    with jax.default_matmul_precision("highest"):
        errors["attention"] = rel_l2(
            got["attention"], attention(got["q"], got["k"], got["v"]))
        errors["head"] = rel_l2(got["logits"],
                                head_logits(params, got["z"], dtype))
        errors["cross_entropy"] = rel_l2(got["ce"], jnp.stack(
            [cross_entropy(params, got["z"][:, t], targets[:, positions],
                           dtype) for t in range(len(want["z"]))], axis=1))
        for t, (z, ce) in enumerate(zip(want["z"], want["ce"])):
            at = z[:, positions]
            errors[f"z_{t + 1}"] = rel_l2(got["z"][:, t], at)
            errors[f"logits_{t + 1}"] = rel_l2(
                got["logits"][:, t], head_logits(params, at, dtype))
            errors[f"pass_loss_{t + 1}"] = rel_l2(
                jnp.mean(got["pass_loss"][:, t]), jnp.mean(ce))
    return {"errors": errors, "loss": want["loss"]}

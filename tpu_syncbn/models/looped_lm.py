"""A decoder language model whose layer stack can run more than once on
its own output with the same weights, as an nnx module that
``parallel.DataParallel`` holds like any other model.

``loops=1`` is a plain pre-trained-style decoder (rotary positions,
SwiGLU, RMSNorm, untied head); ``loops=T`` applies the whole stack T
times, reads the head after every pass and lets a learned exit gate
weigh the T losses (Zhu et al., "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741). The loop is a mechanism of the
repository, not a model under a name: which sizes make an Ouro-2.6B is
the caller's configuration (``chipbench/configs/ouro-2.6b-l8.json``).

Equations, ``x`` of shape (B, S, H), parameters in float32, products in
``dtype`` (bfloat16 on the chip) accumulated in float32, norms, rotary
angles, softmax and the loss in float32:

* ``RMSNorm(x) = x * rsqrt(mean(x^2, -1) + eps) * g``
* attention: ``q, k, v = x Wq, x Wk, x Wv`` split into heads; rotary on
  q and k over the whole head, ``inv_freq_i = theta^(-2i/d)``, the two
  halves rotated against each other (``rotate_half``);
  ``softmax(q k^T / sqrt(d) + causal mask) v``; heads joined; ``Wo``
* MLP: ``(silu(x Wg) * (x Wu)) Wd``
* a layer, with sandwich norms (four scales a layer):
  ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``
* the loop: ``h_0 = E[tokens]``; ``h_t = Layer_L(..Layer_1(h_{t-1}))``
  with the same layers for every t; ``z_t = N_f(h_t)``;
  ``logits_t = z_t W_head``; ``lambda_t = sigmoid(z_t w_g + b_g)``
* exit distribution per position: ``p_t = lambda_t prod_{j<t}(1 -
  lambda_j)`` for ``t < T`` and ``p_T = prod_{j<T}(1 - lambda_j)``
* loss: ``mean over positions of [sum_t p_t CE(logits_t, target)
  - beta H(p)]``

The layers are stacked on a leading axis and applied by ``lax.scan``;
the passes are a second scan around it that closes over the same stacked
parameters, so the backward pass sums each weight's gradient over its T
uses. With ``remat`` one layer application and each pass's head +
cross-entropy are ``jax.checkpoint``ed, so the T sets of (B, S,
vocabulary) logits are never alive together and nothing of the head is
saved. Of a layer application the backward pass keeps its input and the
outputs named in ``_SAVED`` (``save_only_these_names``: the down
projection's output ``mlp_out``, and ``q`` and ``k`` after the rotation),
and recomputes the rest: ``(1 + len(_SAVED)) x T x L x B x S x H x
itemsize`` bytes where a plain ``jax.checkpoint`` keeps ``1 x``. Only
hidden-width outputs are ever named, never the two
``intermediate_size``-wide ones, so what ``remat`` keeps scales with
what it always kept. ``q`` and ``k`` pass one
``lax.optimization_barrier`` before they are named, and the layer's
checkpoint has ``prevent_cse=False``: both are about what the TPU
compiler fuses, neither changes a value (measured below).

Measured on a TPU v5e at 8 layers x 4 passes of hidden 2048, 16 heads of
128, 2 x 2,048 tokens, 612M parameters under AdamW (PERF.md section 6,
PR 30). The passes as a scan or unrolled: the same step (847 and 850 ms)
at 14.4 against 16.4 GB, so a scan. Saving every matmul's output
(``dots_with_no_batch_dims_saveable``) wants 22.6 GiB and without
recomputation the step wants 71 GiB of the chip's 15.75. ``attn_impl``:
``"xla"`` 847 ms a step, ``"flash"`` 569: the kernel's forward and
its two backward kernels, each with its tiles from the call's shape
(PERF.md section 6, PR 35). On the way there: 774 in PR 30 with the
kernel's backward as an XLA scan over key blocks and 128 x 128 forward
tiles, 643 when the forward chose its tiles (PR 31), 606 with what the
layer keeps (PR 33, below), all three with the scan; the two backward
kernels at 128 x 128 tiles and float32 operands read 848 in PR 30,
slower than the scan, and at 512 x 512 in the inputs' type they are
what ``"flash"`` means; the default stays ``"xla"`` because it
runs everywhere (the kernel's interpret mode does not pass
``shard_map``'s ``check_vma`` on the CPU), and a configuration for the
chip names ``"flash"``. The two differ in one rounding: ``"xla"``
rounds the probabilities to ``dtype`` before they meet v, the kernel
keeps them in float32.

What the layer's checkpoint keeps, measured there with ``"flash"`` while
its backward was the scan (one traced run each, PERF.md section 6,
PR 33; ms a step, bytes at the peak; every row is 37 ms shorter now).
A saved (T, L, B, S, H) stack is written and read through the two scans
by four copies, 0.62-0.70 GB and about 7 ms a name where the closed
form says 0.54 GB and 1.3 ms, so a name pays only where its
recomputation costs more: ``mlp_out`` (a 16.7 ms product) and ``q``,
``k`` (a 6 ms product and a 5 ms rotation each) do, ``v`` (a 7 ms
product) comes out even, ``attn_proj`` slows every neighbour.

=========================================  ======  =========  ========
kept beside the layer's input              step    recompute  bytes
=========================================  ======  =========  ========
nothing, plain ``jax.checkpoint`` (PR 31)  642.59  124.02     13.888e9
nothing, ``prevent_cse=False``             632.26  124.15     13.790e9
+ the barrier around q and k               625.77  124.12     13.790e9
+ ``mlp_out``                              619.08  109.33     14.444e9
+ ``q``, ``k``: ``_SAVED``                 605.55  84.20      15.786e9
+ ``v``                                    605.70  76.33      16.457e9
``mlp_out``, ``attn_proj``, no barrier     643.36  117.69     15.139e9
``mlp_out``, ``q``, ``k``, no barrier      629.88  84.20      15.786e9
the same, plain ``jax.checkpoint``         634.85  81.11      15.854e9
=========================================  ======  =========  ========

Without ``prevent_cse=False`` every recomputation reads its operands
through an ``optimization_barrier``, which made the compiler copy each
layer's slice of every stacked weight (268 slices, 11 ms a step); the
layer only runs inside ``stack``'s scan, whose forward and backward
loops the compiler cannot merge anyway. Without the barrier around q
and k the compiler folds the rotation into each of its readers: saving
them then computes it twice more in the forward pass (+20 ms, which is
all that saving them had bought), and even with nothing saved the
backward pass pays 6.5 ms for the same reason.

Scopes (``jax.named_scope``, docs/OBSERVABILITY.md): ``loop_stack``
around the layer scan, ``attention`` around scores-softmax-values (or
the kernel), ``mlp``, ``lm_head`` around final norm + head +
cross-entropy, ``exit_gate`` around gate, distribution and mix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx
from jax import lax
from jax.ad_checkpoint import checkpoint_name

ATTN_IMPLS = ("xla", "flash")
_MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
_NORMS = ("norm1", "norm2", "norm3", "norm4")
# The outputs of a layer application that its ``jax.checkpoint`` keeps
# for the backward pass, of those the layer names (``v`` and
# ``attn_proj`` are named, measured and left out: module docstring).
_SAVED = ("mlp_out", "q", "k")


def rms_norm(x, scale, eps):
    """In float32, stored in x's type."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rotary_angles(seq_len: int, head_dim: int, theta: float):
    """(cos, sin), each (S, head_dim) float32: the angles
    ``pos * theta^(-2i/d)`` for i < d/2, laid out twice (the
    ``rotate_half`` convention pairs dimension i with i + d/2)."""
    inv_freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                         / head_dim)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, cos, sin):
    """``x`` (B, S, heads, d) rotated in float32, stored in x's type."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * cos[:, None, :] + rotated * sin[:, None, :]).astype(x.dtype)


def causal_attention(q, k, v, impl: str, kept: tuple[str, ...] = ()):
    """``softmax(q k^T / sqrt(d) + causal mask) v`` for (B, S, heads, d)
    arrays. ``"xla"`` materialises the float32 scores; ``"flash"`` runs
    ``ops.pallas_attention.flash_attention``, forward and backward in
    the kernel file's own kernels (the backward's dK/dV and dQ; each
    takes its tiles from the call's shape). ``kept``: what the caller's
    checkpoint keeps, for the kernel to know which of its residuals are
    among it."""
    if impl == "flash":
        from tpu_syncbn.ops.pallas_attention import flash_attention

        return flash_attention(q, k, v, causal=True, backward="pallas",
                               kept=kept)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (q.shape[-1] ** -0.5)
    visible = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(visible, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def block_diffusion_attention(q, k, v, clean_len: int, block: int, impl: str,
                              kept: tuple[str, ...] = ()):
    """``softmax(q k^T / sqrt(d) + M) v`` over ``2 * clean_len``
    positions laid out ``[clean ; noisy]``, M the block-diffusion mask
    (``ops.pallas_attention.Visibility``), for q (B, S, heads, d) and k,
    v (B, S, kv_heads, d), kv_heads dividing heads: q head h reads k/v
    head ``h // (heads / kv_heads)``. The sibling of ``causal_attention``
    under the same switch: ``"xla"`` materialises the masked float32
    scores, a group's q heads against their one k/v head; ``"flash"``
    runs the kernels, forward and backward, k and v at their own heads;
    ``kept`` as in ``causal_attention``."""
    from tpu_syncbn.ops import pallas_attention

    if impl == "flash":
        return pallas_attention.flash_attention(
            q, k, v, block_diffusion_mask=(clean_len, block),
            backward="pallas", kept=kept)
    b, s, heads, d = q.shape
    grouped = q.reshape(b, s, k.shape[2], -1, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", grouped, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (d ** -0.5)
    at = jnp.arange(s)
    visible = pallas_attention.block_diffusion(clean_len, block).visible(
        at[:, None], at[None, :])
    scores = jnp.where(visible, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, heads, v.shape[-1]).astype(v.dtype)


def exit_distribution(lam):
    """``lam`` (T, ...) gate values in (0, 1) -> ``p`` (T, ...): exit at
    pass t with ``lam_t`` times the probability of having stayed through
    every earlier pass; the last pass takes what is left, so ``p`` sums
    to one over T whatever ``lam_T`` is."""
    stayed = jnp.cumprod(1.0 - lam[:-1], axis=0)
    reached = jnp.concatenate([jnp.ones_like(lam[:1]), stayed], axis=0)
    exits = jnp.concatenate([lam[:-1], jnp.ones_like(lam[:1])], axis=0)
    return reached * exits


def checkpointed(fn, saved=None):
    """``fn`` under ``jax.checkpoint``. For a head, plainly. For a
    layer, ``saved`` names the outputs the backward pass keeps and does
    not recompute, and ``prevent_cse=False`` drops the barriers that
    guard a recomputation against being merged with the forward pass:
    a layer only ever runs inside a scan, whose forward and backward
    loops are separate programs to the compiler."""
    if saved is None:
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(*saved))


def _normal(rngs: nnx.Rngs, std: float):
    """``normal(*shape)``: a Param drawn from normal(0, std)."""
    return lambda *shape: nnx.Param(
        std * jax.random.normal(rngs.params(), shape))


class _LayerStack(nnx.Module):
    """The L layers' parameters, each stacked on a leading axis of L."""

    def __init__(self, n: int, hidden: int, inner: int, ffn: int,
                 std: float, rngs: nnx.Rngs):
        normal = _normal(rngs, std)
        self.wq = normal(n, hidden, inner)
        self.wk = normal(n, hidden, inner)
        self.wv = normal(n, hidden, inner)
        self.wo = normal(n, inner, hidden)
        self.wg = normal(n, hidden, ffn)
        self.wu = normal(n, hidden, ffn)
        self.wd = normal(n, ffn, hidden)
        for name in _NORMS:
            setattr(self, name, nnx.Param(jnp.ones((n, hidden))))


class LoopedDecoderLM(nnx.Module):
    """See the module docstring. ``tokens`` and ``targets`` are (B, S)
    integers; nothing here knows about replicas, ``DataParallel`` means
    the loss and the metrics over them."""

    def __init__(self, *, vocab_size: int, hidden_size: int, num_heads: int,
                 head_dim: int, intermediate_size: int, num_layers: int,
                 loops: int = 1, rope_theta: float = 1e4,
                 rms_eps: float = 1e-6, exit_beta: float = 0.0,
                 init_std: float = 0.02, dtype=jnp.float32,
                 attn_impl: str = "xla", remat: bool = True,
                 rngs: nnx.Rngs):
        if attn_impl not in ATTN_IMPLS:
            raise ValueError(
                f"attn_impl must be one of {ATTN_IMPLS}, got {attn_impl!r}")
        if loops < 1 or head_dim % 2:
            raise ValueError("loops must be >= 1 and head_dim even")
        self.num_heads, self.head_dim, self.loops = num_heads, head_dim, loops
        self.rope_theta, self.rms_eps = rope_theta, rms_eps
        self.exit_beta, self.dtype = exit_beta, dtype
        self.attn_impl, self.remat = attn_impl, remat
        normal = _normal(rngs, init_std)
        self.embed = normal(vocab_size, hidden_size)
        self.layers = _LayerStack(num_layers, hidden_size,
                                  num_heads * head_dim, intermediate_size,
                                  init_std, rngs)
        self.final_norm = nnx.Param(jnp.ones((hidden_size,)))
        self.head = normal(hidden_size, vocab_size)
        self.gate_w = normal(hidden_size)
        self.gate_b = nnx.Param(jnp.zeros(()))

    # -- one layer ----------------------------------------------------------

    def _dot(self, x, w):
        """Operands in the compute type, products accumulated in float32,
        the result stored in the compute type."""
        return jnp.dot(x, w.astype(self.dtype),
                       preferred_element_type=jnp.float32).astype(self.dtype)

    def _qkv(self, x, p, cos, sin):
        """What the attention core reads: q and k rotated, v, each
        (B, S, heads, d)."""
        b, s, _ = x.shape
        heads = (b, s, self.num_heads, self.head_dim)
        n = rms_norm(x, p["norm1"], self.rms_eps)
        q = apply_rotary(self._dot(n, p["wq"]).reshape(heads), cos, sin)
        k = apply_rotary(self._dot(n, p["wk"]).reshape(heads), cos, sin)
        v = self._dot(n, p["wv"]).reshape(heads)
        # q and k are written once, here, for all their readers (the
        # attention core, its backward pass, the saved stack): left to
        # itself the TPU compiler folds the rotation into each reader
        # and computes it again there (module docstring, measured).
        q, k = lax.optimization_barrier((q, k))
        return (checkpoint_name(q, "q"), checkpoint_name(k, "k"),
                checkpoint_name(v, "v"))

    def _attend(self, q, k, v):
        with jax.named_scope("attention"):
            return causal_attention(q, k, v, self.attn_impl)

    def _after_attention(self, x, o, p):
        """The rest of a layer from its input ``x`` and the attention
        core's output ``o``."""
        b, s, _ = x.shape
        o = checkpoint_name(self._dot(o.reshape(b, s, -1), p["wo"]),
                            "attn_proj")
        a = x + rms_norm(o, p["norm2"], self.rms_eps)
        with jax.named_scope("mlp"):
            n = rms_norm(a, p["norm3"], self.rms_eps)
            gate = self._dot(n, p["wg"]).astype(jnp.float32)
            up = self._dot(n, p["wu"]).astype(jnp.float32)
            m = checkpoint_name(self._dot(
                (jax.nn.silu(gate) * up).astype(self.dtype), p["wd"]),
                "mlp_out")
        return a + rms_norm(m, p["norm4"], self.rms_eps)

    def _layer(self, x, p, cos, sin):
        """One application of one layer: ``p`` holds that layer's slice of
        every stacked parameter."""
        return self._after_attention(
            x, self._attend(*self._qkv(x, p, cos, sin)), p)

    def _stacked(self) -> dict:
        return {name: getattr(self.layers, name)[...]
                for name in _MATRICES + _NORMS}

    def _angles(self, seq_len: int):
        return rotary_angles(seq_len, self.head_dim, self.rope_theta)

    def _checkpointed(self, fn, saved=None):
        """``fn`` under ``jax.checkpoint`` where ``remat`` says so."""
        return checkpointed(fn, saved) if self.remat else fn

    # -- the pieces a caller may read -----------------------------------------

    def embed_tokens(self, tokens):
        return self.embed[...][tokens].astype(self.dtype)

    def layer_parts(self, tokens, index: int = 0) -> dict:
        """Layer ``index`` applied once to the embeddings, opened up for
        a comparison with a reference (the training path never calls
        it): ``q``, ``k``, ``v`` as the attention core reads them and
        ``attention`` as it writes them, each (B, S, heads, d), and the
        layer's output ``out`` (B, S, H)."""
        h = self.embed_tokens(tokens)
        p = jax.tree_util.tree_map(lambda a: a[index], self._stacked())
        q, k, v = self._qkv(h, p, *self._angles(h.shape[1]))
        o = self._attend(q, k, v)
        return {"q": q, "k": k, "v": v, "attention": o,
                "out": self._after_attention(h, o, p)}

    def stack(self, h):
        """One pass: every layer once, in order."""
        cos, sin = self._angles(h.shape[1])
        layer = self._checkpointed(self._layer, _SAVED)
        with jax.named_scope("loop_stack"):
            h, _ = lax.scan(lambda x, p: (layer(x, p, cos, sin), None),
                            h, self._stacked())
        return h

    def read(self, h):
        """``z = N_f(h)``: what the head and the exit gate read."""
        return rms_norm(h, self.final_norm[...], self.rms_eps)

    def logits(self, z):
        """Float32 logits of ``z`` (.., H) over the vocabulary."""
        return jnp.dot(z, self.head[...].astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def gate(self, z):
        """``lambda = sigmoid(z w_g + b_g)`` in float32, (..,)."""
        z32 = z.astype(jnp.float32)
        return jax.nn.sigmoid(jnp.sum(z32 * self.gate_w[...], axis=-1)
                              + self.gate_b[...])

    def _passes(self, tokens, read):
        """``read(h_t)`` for t = 1..T, stacked on a leading axis: the scan
        over the passes, every one through the same ``stack``."""
        def one(h, _):
            h = self.stack(h)
            return h, read(h)

        return lax.scan(one, self.embed_tokens(tokens), None,
                        length=self.loops)[1]

    def hidden_passes(self, tokens):
        """``h_1 .. h_T`` stacked, (T, B, S, H)."""
        return self._passes(tokens, lambda h: h)

    def __call__(self, tokens):
        """The last pass's logits, (B, S, vocabulary) float32."""
        return self.logits(self.read(self.hidden_passes(tokens)[-1]))

    # -- the loss ---------------------------------------------------------------

    def read_pass(self, h, targets):
        """Per position, one pass's cross-entropy and gate value."""
        with jax.named_scope("lm_head"):
            z = self.read(h)
            logits = self.logits(z)
            picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)
            ce = jax.nn.logsumexp(logits, axis=-1) - picked[..., 0]
        with jax.named_scope("exit_gate"):
            return ce, self.gate(z)

    def pass_losses(self, tokens, targets):
        """``(ce, lam)``, each (T, B, S) float32."""
        read = self._checkpointed(self.read_pass)
        return self._passes(tokens, lambda h: read(h, targets))

    def loss(self, tokens, targets):
        """The exit-weighted loss and its step metrics:
        ``pass_loss_t`` (mean cross-entropy of pass t), ``exit_p_t`` (mean
        probability of leaving after pass t) and ``exit_entropy``."""
        ce, lam = self.pass_losses(tokens, targets)
        with jax.named_scope("exit_gate"):
            p = exit_distribution(lam)
            entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
            loss = jnp.mean(jnp.sum(p * ce, axis=0)
                            - self.exit_beta * entropy)
        metrics = {"exit_entropy": jnp.mean(entropy)}
        for t in range(self.loops):
            metrics[f"pass_loss_{t + 1}"] = jnp.mean(ce[t])
            metrics[f"exit_p_{t + 1}"] = jnp.mean(p[t])
        return loss, metrics

"""A decoder language model with latent attention, a routed mixture of
experts of which this chip holds a share, a shared expert and a
multi-token-prediction module, as an nnx module that
``parallel.DataParallel`` holds like any other model.

The mechanisms are DeepSeek-V3's (DeepSeek-AI 2024, arXiv:2412.19437);
which sizes make a JoyAI-LLM-Flash is the caller's configuration
(``chipbench/configs/joyai-llm-flash-l5.json``), as with
``models.looped_lm``, whose ``rms_norm``, attention core, head under
``jax.checkpoint`` and checkpoint idiom this module shares.

Equations, ``x`` of shape (B, S, H), parameters in float32, products in
``dtype`` (bfloat16 on the chip) accumulated in float32, norms, rotary,
router, softmax, SiLU, logits and loss in float32:

* a layer, pre-norm: ``a = x + MLA(N1(x))``, ``y = a + F(N2(a))``; ``F``
  is a dense SwiGLU MLP in the first ``dense_layers`` layers and the
  mixture of experts in the ``moe_layers`` that follow
* latent attention (MLA), decompressed (training has no cache to absorb
  into): ``c_q = N(x W_qa)``, ``q = c_q W_qb`` a head split into
  ``q_nope`` and ``q_rope``; ``[c_kv ; k_rope] = x W_kva``, ``c_kv =
  N(c_kv)``, ``k_rope`` ONE vector a position that all heads share;
  ``[k_nope ; v] = c_kv W_kvb`` a head. Rotary on ``q_rope`` and
  ``k_rope`` over the pairs (2i, 2i+1), ``inv_freq_i = theta^(-2i/d)``.
  ``q = [q_nope ; q_rope]``, ``k = [k_nope ; k_rope]``,
  ``softmax(q k^T / sqrt(d_qk) + causal mask) v``, heads joined, ``W_o``.
  q and k are wider than v (``ops.pallas_attention.flash_attention``
  takes that, in its forward kernel and in its two backward kernels)
* mixture of experts (``parallel.expert``): ``s = sigmoid(x W_r)`` over
  ALL ``n_experts``, in float32 at full precision; chosen: the top k of
  ``s + b``; ``g = scale * s[chosen] / (sum s[chosen] + 1e-20)``;
  ``sum_k g_k E_k(x) + E_shared(x)``, every expert ``(silu(x Wg) * (x
  Wu)) Wd``. **The chip's share:** the layer holds ``experts_held``
  experts from ``first_expert`` on, routes over all, and computes its
  own experts' part: what the absent ones would add is left out (their
  chips would add it). No pair on a held expert is ever dropped
* the selection bias ``b`` is no parameter: after each step ``b <- b +
  gamma * sign(mean(load) - load)``, ``load`` the tokens each expert was
  chosen by over the GLOBAL batch: summed over ``axis_name`` with the
  collective SyncBN's statistics use, where that axis is in scope.
  ``b``, the cumulative ``load`` and ``recent_load`` (the loads of the
  last ``RECENT_STEPS`` steps, newest last: what a monitor that reads
  the state now and then, not every step, sees of single steps) are nnx
  Variables that are no ``Param``: ``DataParallel`` carries them in
  ``rest``, as it carries BN's running statistics
* multi-token prediction, depth 1: ``h' = W_eh [N_e(E[t_{i+1}]) ;
  N_h(h_i)]`` (the embedding's half first), ``h_i`` the stack's output
  before its final norm; one more expert layer; a norm of its own; the
  model's embedding and head, shared. ``loss = CE(main, t_{i+1}) +
  mtp_weight * CE(mtp, t_{i+2})``

Each kind of layer is stacked on a leading axis and applied by
``lax.scan`` (a stack of one is a scan of one: every layer then runs
under the same checkpoint, whose ``prevent_cse=False`` needs the scan).
With ``remat`` a layer application and each head read are
``jax.checkpoint``ed: the backward pass keeps a layer's input and what
``_saved()`` names (``save_only_these_names``), and never a (B, S,
vocabulary) map. What it names are the attention kernel's two
residuals, its output and its log-sum-exp
(``ops.pallas_attention.FLASH_OUT``, ``FLASH_LSE``: the kernel's forward
rule names them): q, k and v the backward pass makes again from the
projections anyway, but to get these two back it ran the whole forward
kernel a second time, 6.06 ms a layer application at 8,192 tokens.
Under ``attn_impl="xla"`` the names never appear and nothing is kept.

What the layer's checkpoint keeps, measured on a TPU v5e at the
benchmark's cell (``joyai-l5-train-b1x8192``: 1 + 4 layers and the
prediction module's, six layer applications through three scans, one
sequence of 8,192 tokens, 680M parameters under AdamW; one traced run
each on one seed, PERF.md section 6, PR 39; ms a step, ``mixture``
the time under ``moe``, bytes at the peak of 16.91e9):

=======================================  ======  =========  =======  ========
kept beside the layer's input            step    recompute  mixture  bytes
=======================================  ======  =========  =======  ========
nothing (until PR 39)                    445.27  85.30      48.15    14.966e9
the kernel's two: ``_saved()``           414.83  42.89      61.64    14.889e9
+ ``attn_proj``                          409.37  39.05      55.57    15.023e9
+ ``q``                                  402.95  31.64      50.36    15.406e9
+ ``q``, ``k``, ``v``                    does not fit: 2.13 GB over the two
=======================================  ======  =========  =======  ========

The mixture's time is the step's routing (it differs between two runs
of one seed once their roundings differ), so read the step less the
mixture: 397.12, 353.19, 353.80, 352.59. The two names take 43.9 ms off the
step: the recomputed kernel calls' 36.95 and the layout changes around
them; the forward kernel's six calls that are left take 37.25 (36.35
before), the two backward kernels 118.3 (117.1), and the copy that
makes the dQ kernel's column from the kept row 1.4. At the step's
peak they cost no memory (the peak is not where the saved stacks
live); the loss-and-gradient program alone grows by 544 MB (compiled
for a described v5e; the closed form is 409). ``attn_proj`` beside
them takes 3.7 ms off the recomputation and other fusions of the
backward pass take 6.1 more: nothing for 134 MB, not kept
(``block_diffusion_lm``, where it pays, keeps it). ``q`` beside them (six
stacks of 101 MB) takes 11.2 ms off the recomputation and gives 5.6
back in the forward pass and the rest in the backward pass: 0.6 ms of
the step for 517 MB, so it is not kept; q, k and v together are 2.13 GB
more than the two by the compiler's count, about 17.0e9 of the chip's
16.91e9. With q and k behind one barrier before they are named, as
``looped_lm`` has it, the two names give the same step to 0.001 ms and
``q`` beside them 403.70 (352.59 + 0.7 less the mixture): nothing
either.

Scopes (``jax.named_scope``, docs/OBSERVABILITY.md): ``mla`` around the
attention block with ``attention`` around its core inside it; ``mlp``
around the dense layers' feed-forward; ``moe`` around the mixture with
``moe_route`` (scores, selection, sort, gather, scatter-add),
``moe_experts`` (the grouped products) and ``moe_shared`` inside it;
``mtp`` around the prediction module's projection and layer;
``lm_head`` around each norm + head + cross-entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tpu_syncbn.models.looped_lm import (
    ATTN_IMPLS, _normal, causal_attention, checkpointed, rms_norm)
from tpu_syncbn.mesh_axes import DATA_AXIS
from tpu_syncbn.nn.normalization import _axis_in_scope
from tpu_syncbn.parallel import collectives, expert

_ROW_TILE = 512  # rows a tile of the grouped product on the TPU
RECENT_STEPS = 16  # single steps' loads an expert layer keeps


def _saved() -> tuple[str, ...]:
    """What a layer application's ``jax.checkpoint`` keeps for the
    backward pass beside its input: the attention kernel's output and
    log-sum-exp, under the names the kernel file gives them in its
    forward rule (imported here, as the kernel is, so that importing the
    model does not import Pallas), so that the backward pass does not
    run the forward kernel again to get them back. Of the names the
    layer gives itself (``q``, ``k``, ``v``, ``attn_proj``,
    ``ffn_out``) none: the module docstring has the table."""
    from tpu_syncbn.ops.pallas_attention import FLASH_LSE, FLASH_OUT

    return (FLASH_OUT, FLASH_LSE)


class SelectionBias(nnx.Variable):
    """The router's selection bias: state that every step moves, no
    parameter."""


class ExpertLoad(nnx.Variable):
    """Counts of the tokens each expert was chosen by: cumulative, or of
    each of the last steps."""


def rotary_pair_angles(seq_len: int, dim: int, theta: float):
    """(cos, sin), each (S, dim / 2) float32: ``pos * theta^(-2i/dim)``
    for the pair (2i, 2i+1)."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary_pairs(x, cos, sin):
    """``x`` (B, S, heads, d): each pair (2i, 2i+1) rotated by its
    position's angle, in float32, stored in x's type."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, None, :], sin[:, None, :]
    out = jnp.stack([a * c - b * s, b * c + a * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class MoEDecoderBase(nnx.Module):
    """What the decoders with a held share of experts have in common
    (this module's and ``models.block_diffusion_lm``'s), whatever their
    attention and router: the products' precision, the embedding, the
    head under its scope, and the expert loads summed over the replicas
    and kept in ``rest``. Reads ``dtype``, ``rms_eps``, ``axis_name``,
    ``embed`` and ``head`` of the model it is a base of."""

    def _dot(self, x, w):
        """Operands in the compute type, products accumulated in float32,
        the result stored in the compute type."""
        return jnp.dot(x, w.astype(self.dtype),
                       preferred_element_type=jnp.float32).astype(self.dtype)

    def embed_tokens(self, tokens):
        return self.embed[...][tokens].astype(self.dtype)

    def read(self, h, scale):
        """``z = N(h)``: what the head reads."""
        return rms_norm(h, scale, self.rms_eps)

    def logits(self, z):
        """Float32 logits of ``z`` (.., H) over the vocabulary held."""
        return jnp.dot(z, self.head[...].astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def cross_entropy(self, h, scale, targets):
        """Per position, (B, S) float32."""
        with jax.named_scope("lm_head"):
            logits = self.logits(self.read(h, scale))
            picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)
            return jax.nn.logsumexp(logits, axis=-1) - picked[..., 0]

    def global_load(self, load):
        """``load`` summed over the replicas: the identity where
        ``axis_name`` is not in scope."""
        load = lax.stop_gradient(load)
        if self.axis_name is not None and _axis_in_scope(self.axis_name):
            load = collectives.psum(load, self.axis_name)
        return load

    @staticmethod
    def _counted(block, load):
        """The global ``load`` (n, E) of this step joins ``block``'s
        cumulative load, and its recent steps' loads shift by one."""
        block.load[...] = block.load[...] + load
        block.recent_load[...] = jnp.concatenate(
            [block.recent_load[...][:, 1:], load[:, None]], axis=1)


def held_chunk(pairs: int, held: int, experts: int) -> int:
    """Rows a chunk of ``expert.held_expert_moe``'s walk: twice what
    arrives on the ``held`` of ``experts`` experts if the loads of the
    ``pairs`` chosen pairs are even, in whole row tiles (at
    initialisation a layer that holds one of the few experts nearly
    every token chooses gets one to two such chunks; four times the even
    share read 0.75% slower and spread wider over seeds, PERF.md section
    6, PR 34)."""
    return -(-int(2 * pairs * held / experts) // _ROW_TILE) * _ROW_TILE


class _Block(nnx.Module):
    """``n`` layers of one kind, each parameter stacked on a leading
    axis of n: latent attention, two norms, and the feed-forward: a
    dense SwiGLU MLP (``experts`` None) or the mixture of experts with
    its router state."""

    def __init__(self, n: int, *, hidden: int, heads: int, q_rank: int,
                 kv_rank: int, nope: int, rope: int, v_dim: int,
                 dense_ffn: int | None, experts: tuple | None,
                 std: float, rngs: nnx.Rngs):
        normal = _normal(rngs, std)
        ones = lambda *shape: nnx.Param(jnp.ones(shape))
        self.wqa = normal(n, hidden, q_rank)
        self.q_norm = ones(n, q_rank)
        self.wqb = normal(n, q_rank, heads * (nope + rope))
        self.wkva = normal(n, hidden, kv_rank + rope)
        self.kv_norm = ones(n, kv_rank)
        self.wkvb = normal(n, kv_rank, heads * (nope + v_dim))
        self.wo = normal(n, heads * v_dim, hidden)
        self.norm1 = ones(n, hidden)
        self.norm2 = ones(n, hidden)
        self.names = ("wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb",
                      "wo", "norm1", "norm2")
        self.moe = experts is not None
        if not self.moe:
            self.wg = normal(n, hidden, dense_ffn)
            self.wu = normal(n, hidden, dense_ffn)
            self.wd = normal(n, dense_ffn, hidden)
            self.names += ("wg", "wu", "wd")
            return
        n_experts, held, width, shared_width = experts
        self.router = normal(n, hidden, n_experts)
        self.eg = normal(n, held, hidden, width)
        self.eu = normal(n, held, hidden, width)
        self.ed = normal(n, held, width, hidden)
        self.sg = normal(n, hidden, shared_width)
        self.su = normal(n, hidden, shared_width)
        self.sd = normal(n, shared_width, hidden)
        self.names += ("router", "eg", "eu", "ed", "sg", "su", "sd")
        self.bias = SelectionBias(jnp.zeros((n, n_experts)))
        self.load = ExpertLoad(jnp.zeros((n, n_experts)))
        self.recent_load = ExpertLoad(
            jnp.zeros((n, RECENT_STEPS, n_experts)))

    def stacked(self) -> dict:
        return {name: getattr(self, name)[...] for name in self.names}


class LatentMoEDecoderLM(MoEDecoderBase):
    """See the module docstring. ``tokens``, ``targets`` (the next
    token) and ``targets2`` (the one after) are (B, S) integers; nothing
    here knows about replicas but the sum of the experts' loads over
    ``axis_name``, ``DataParallel`` means the loss and the metrics."""

    def __init__(self, *, vocab_size: int, hidden_size: int, num_heads: int,
                 q_lora_rank: int, kv_lora_rank: int, qk_nope_dim: int,
                 qk_rope_dim: int, v_dim: int, dense_layers: int,
                 dense_intermediate: int, moe_layers: int, n_experts: int,
                 experts_held: int, first_expert: int = 0,
                 experts_per_token: int, moe_intermediate: int,
                 shared_intermediate: int, routed_scale: float = 1.0,
                 bias_gamma: float = 1e-3, mtp: bool = False,
                 mtp_weight: float = 0.3, rope_theta: float = 1e4,
                 rms_eps: float = 1e-6, init_std: float = 0.02,
                 dtype=jnp.float32, attn_impl: str = "xla",
                 remat: bool = True, axis_name: str = DATA_AXIS,
                 rngs: nnx.Rngs):
        if attn_impl not in ATTN_IMPLS:
            raise ValueError(
                f"attn_impl must be one of {ATTN_IMPLS}, got {attn_impl!r}")
        if qk_rope_dim % 2:
            raise ValueError("qk_rope_dim must be even")
        if not 0 <= first_expert <= n_experts - experts_held:
            raise ValueError(
                f"experts {first_expert}..{first_expert + experts_held - 1} "
                f"are not among the layer's {n_experts}")
        self.num_heads, self.dims = num_heads, (qk_nope_dim, qk_rope_dim, v_dim)
        self.first_expert, self.top_k = first_expert, experts_per_token
        self.routed_scale, self.bias_gamma = routed_scale, bias_gamma
        self.mtp_weight, self.rope_theta = mtp_weight, rope_theta
        self.rms_eps, self.dtype, self.attn_impl = rms_eps, dtype, attn_impl
        self.remat, self.axis_name = remat, axis_name
        normal = _normal(rngs, init_std)
        block = dict(hidden=hidden_size, heads=num_heads, q_rank=q_lora_rank,
                     kv_rank=kv_lora_rank, nope=qk_nope_dim, rope=qk_rope_dim,
                     v_dim=v_dim, std=init_std, rngs=rngs)
        experts = (n_experts, experts_held, moe_intermediate,
                   shared_intermediate)
        self.embed = normal(vocab_size, hidden_size)
        self.dense = _Block(dense_layers, dense_ffn=dense_intermediate,
                            experts=None, **block)
        self.sparse = _Block(moe_layers, dense_ffn=None, experts=experts,
                             **block)
        self.final_norm = nnx.Param(jnp.ones((hidden_size,)))
        self.head = normal(hidden_size, vocab_size)
        self.mtp = mtp
        if mtp:
            self.mtp_enorm = nnx.Param(jnp.ones((hidden_size,)))
            self.mtp_hnorm = nnx.Param(jnp.ones((hidden_size,)))
            self.mtp_proj = normal(2 * hidden_size, hidden_size)
            self.mtp_block = _Block(1, dense_ffn=None, experts=experts,
                                    **block)
            self.mtp_norm = nnx.Param(jnp.ones((hidden_size,)))

    # -- one layer ----------------------------------------------------------

    def _swiglu(self, x, wg, wu, wd):
        gate = self._dot(x, wg).astype(jnp.float32)
        up = self._dot(x, wu).astype(jnp.float32)
        return self._dot((jax.nn.silu(gate) * up).astype(self.dtype), wd)

    def _qkv(self, x, p, cos, sin):
        """What the attention core reads: q and k (B, S, heads, nope +
        rope) with the rotary part rotated, v (B, S, heads, v_dim)."""
        b, s, _ = x.shape
        nope, rope, v_dim = self.dims
        n = rms_norm(x, p["norm1"], self.rms_eps)
        c_q = rms_norm(self._dot(n, p["wqa"]), p["q_norm"], self.rms_eps)
        q = self._dot(c_q, p["wqb"]).reshape(b, s, self.num_heads, -1)
        kva = self._dot(n, p["wkva"])
        c_kv = rms_norm(kva[..., :-rope], p["kv_norm"], self.rms_eps)
        k_rope = apply_rotary_pairs(kva[..., None, -rope:], cos, sin)
        kv = self._dot(c_kv, p["wkvb"]).reshape(b, s, self.num_heads, -1)
        q = jnp.concatenate(
            [q[..., :nope], apply_rotary_pairs(q[..., nope:], cos, sin)], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope, (b, s, self.num_heads, rope))], -1)
        return (checkpoint_name(q, "q"), checkpoint_name(k, "k"),
                checkpoint_name(kv[..., nope:], "v"))

    def _attend(self, q, k, v):
        with jax.named_scope("attention"):
            return causal_attention(q, k, v, self.attn_impl, _saved())

    def _attention_block(self, x, p, cos, sin):
        """``a = x + MLA(N1(x))``."""
        with jax.named_scope("mla"):
            o = self._attend(*self._qkv(x, p, cos, sin))
            o = self._dot(o.reshape(*x.shape[:2], -1), p["wo"])
            return x + checkpoint_name(o, "attn_proj")

    def _route(self, n, p, bias):
        """``idx``, ``gates`` (T, k) of the (T, H) router input ``n``."""
        with jax.named_scope("moe_route"):
            return expert.sigmoid_topk_route(
                n, p["router"], bias, top_k=self.top_k,
                scale=self.routed_scale)

    def _moe(self, n, p, bias):
        """The mixture on the normed input ``n`` (B, S, H): the held
        experts' part, the shared expert, the (E,) loads of ALL experts
        over these tokens and the held pairs not computed."""
        with jax.named_scope("moe"):
            flat = n.reshape(-1, n.shape[-1])
            idx, gates = self._route(flat, p, bias)
            routed, missed = expert.held_expert_moe(
                flat, idx, gates, p["eg"], p["eu"], p["ed"],
                first_expert=self.first_expert,
                chunk=held_chunk(idx.size, p["eg"].shape[0],
                                 p["router"].shape[-1]))
            with jax.named_scope("moe_route"):
                load = expert.expert_loads(idx, p["router"].shape[-1])
            with jax.named_scope("moe_shared"):
                shared = self._swiglu(n, p["sg"], p["su"], p["sd"])
            return routed.reshape(n.shape) + shared, load, missed

    def _layer(self, x, p, bias, cos, sin):
        """One application of one layer: ``p`` that layer's slice of its
        block's stacked parameters, ``bias`` its selection bias (None in
        a dense layer). Returns the output and (load, pairs not
        computed), empty for a dense layer."""
        a = self._attention_block(x, p, cos, sin)
        n = rms_norm(a, p["norm2"], self.rms_eps)
        if bias is None:
            with jax.named_scope("mlp"):
                f, stats = self._swiglu(n, p["wg"], p["wu"], p["wd"]), ()
        else:
            f, *stats = self._moe(n, p, bias)
        return a + checkpoint_name(f, "ffn_out"), tuple(stats)

    def _angles(self, seq_len: int):
        return rotary_pair_angles(seq_len, self.dims[1], self.rope_theta)

    def run(self, block: _Block, h, keep_inputs: bool = False):
        """Every layer of ``block`` once, in order. Returns the output
        and, for a block of expert layers, (loads (n, E), pairs not
        computed (n,)); with ``keep_inputs`` (a comparison's: the
        training path keeps none) each layer's input (n, B, S, H) joins
        them."""
        cos, sin = self._angles(h.shape[1])
        layer = (checkpointed(self._layer, _saved()) if self.remat
                 else self._layer)
        bias = block.bias[...] if block.moe else None

        def body(x, xs):
            y, stats = layer(x, xs[0], xs[1], cos, sin)
            return y, (*stats, x) if keep_inputs else stats

        return lax.scan(body, h, (block.stacked(), bias))

    # -- the pieces a caller may read -----------------------------------------

    def hidden(self, tokens):
        """The stack's output before its final norm, (B, S, H), and the
        expert layers' (loads, pairs not computed)."""
        h, _ = self.run(self.dense, self.embed_tokens(tokens))
        return self.run(self.sparse, h)

    def mtp_hidden(self, h, next_tokens):
        """The prediction module's output before its norm, from the
        stack's output ``h`` and the tokens one position on; and its
        layer's (loads, pairs not computed)."""
        with jax.named_scope("mtp"):
            e = rms_norm(self.embed_tokens(next_tokens), self.mtp_enorm[...],
                         self.rms_eps)
            n = rms_norm(h, self.mtp_hnorm[...], self.rms_eps)
            x = self._dot(jnp.concatenate([e, n], axis=-1), self.mtp_proj[...])
            return self.run(self.mtp_block, x)

    def expert_layer_parts(self, x, index=0) -> dict:
        """Expert layer ``index`` (an integer, or a traced one) applied
        to ``x`` (B, S, H), opened up for a comparison with a reference
        (the training path never calls it): ``q``, ``k``, ``v`` as the
        attention core reads them and ``attention`` as it writes them; ``router_in`` (B, S, H), what
        router and experts read; ``idx`` and ``gates`` (B, S, k);
        ``load`` (E,); ``moe`` (B, S, H), held experts + shared expert;
        ``pairs_not_computed``; and the layer's output ``out``."""
        p = jax.tree_util.tree_map(lambda a: a[index], self.sparse.stacked())
        bias = self.sparse.bias[...][index]
        q, k, v = self._qkv(x, p, *self._angles(x.shape[1]))
        o = self._attend(q, k, v)
        with jax.named_scope("mla"):
            a = x + self._dot(o.reshape(*x.shape[:2], -1), p["wo"])
        n = rms_norm(a, p["norm2"], self.rms_eps)
        idx, gates = self._route(n.reshape(-1, n.shape[-1]), p, bias)
        moe, load, missed = self._moe(n, p, bias)
        shape = (*x.shape[:2], -1)
        return {"q": q, "k": k, "v": v, "attention": o, "router_in": n,
                "idx": idx.reshape(shape), "gates": gates.reshape(shape),
                "load": load, "moe": moe, "pairs_not_computed": missed,
                "out": a + moe}

    def __call__(self, tokens):
        """The next token's logits, (B, S, vocabulary) float32."""
        return self.logits(self.read(self.hidden(tokens)[0],
                                     self.final_norm[...]))

    # -- the loss ---------------------------------------------------------------

    def _moved(self, block: _Block, load):
        """The step's router state: the bias moved against the global
        ``load`` (n, E), the cumulative load grown by it, the recent
        steps' loads shifted by one. Returns the global load."""
        load = self.global_load(load)
        block.bias[...] = expert.update_selection_bias(
            block.bias[...], load, self.bias_gamma)
        self._counted(block, load)
        return load

    def loss(self, tokens, targets, targets2=None):
        """``CE(main, targets) + mtp_weight * CE(mtp, targets2)`` and the
        step metrics ``main_loss``, ``mtp_loss``,
        ``expert_load_max_over_mean`` (the worst layer's, over the global
        batch) and ``pairs_not_computed`` (must be 0). Moves the router
        state of every expert layer."""
        read = (checkpointed(self.cross_entropy) if self.remat
                else self.cross_entropy)
        h, (load, missed) = self.hidden(tokens)
        main = jnp.mean(read(h, self.final_norm[...], targets))
        loads, missed = [self._moved(self.sparse, load)], jnp.sum(missed)
        loss, metrics = main, {"main_loss": main}
        if self.mtp:
            h2, (load2, missed2) = self.mtp_hidden(h, targets)
            extra = jnp.mean(read(h2, self.mtp_norm[...], targets2))
            loads.append(self._moved(self.mtp_block, load2))
            missed = missed + jnp.sum(missed2)
            loss = main + self.mtp_weight * extra
            metrics["mtp_loss"] = extra
        loads = jnp.concatenate(loads)
        metrics["expert_load_max_over_mean"] = jnp.max(
            jnp.max(loads, axis=-1) / jnp.mean(loads, axis=-1))
        metrics["pairs_not_computed"] = missed
        return loss, metrics

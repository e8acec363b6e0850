"""A decoder language model trained by block diffusion: grouped-query
attention with per-head q/k norms, a softmax-routed mixture of experts of
which this chip holds a share in every layer, a forward pass over TWO
copies of a sequence under a block mask and a weighted cross-entropy at
masked positions, as an nnx module that ``parallel.DataParallel`` holds
like any other model.

The decoder is the Qwen3 mixture-of-experts one; the training is BD3-LM's
vectorised form (Arriola et al. 2025, arXiv:2503.09573; SDAR,
arXiv:2510.06303, adapts autoregressive checkpoints to it). Which sizes
make an SDAR-30B-A3B is the caller's configuration
(``chipbench/configs/sdar-30b-a3b-l6.json``). It shares ``rms_norm``,
the half-split rotary, the checkpoint idiom and the attention switch
with ``models.looped_lm``, and the head, the loads in ``rest`` and the
held experts with ``models.moe_lm`` (``MoEDecoderBase``).

Equations, ``x`` of shape (B, S, H), parameters in float32, products in
``dtype`` (bfloat16 on the chip) accumulated in float32, norms, rotary,
router, softmax, SiLU, logits and losses in float32:

* a layer, pre-norm, every layer alike: ``a = x + Attn(N1(x))``,
  ``y = a + MoE(N2(a))``
* grouped-query attention: ``q = n W_q`` as ``num_heads`` heads,
  ``k = n W_k`` and ``v = n W_v`` as ``num_kv_heads``; each head of q
  and of k through an RMSNorm over its width (one scale vector for q,
  one for k, a layer); rotary on q and k over the whole head,
  half-split; q head h reads k/v head ``h // (num_heads /
  num_kv_heads)``; ``softmax(q k^T / sqrt(d) + M) v``; heads joined;
  ``W_o``
* block-diffusion training: a sample is a clean sequence ``x0`` of L
  tokens, a noisy copy ``xt`` (some tokens replaced by the mask id:
  ``data.transforms.BlockDiffusionNoise``) and a weight ``w`` a
  position. The model reads the 2L tokens ``[x0 ; xt]``, position i of
  each half at rotary position i, under the mask M of
  ``ops.pallas_attention.Visibility``: a clean token sees the clean past
  block-causally, a noisy token the clean blocks before its own and its
  own noisy block. ``loss = (1/L) sum_i w_i CE(logits(noisy position i),
  x0_i)``: no shift; the head reads the noisy half only
* mixture of experts (``parallel.expert``): ``p = softmax(n W_r)`` over
  ALL ``n_experts``, float32 at full precision; chosen: the top k of p;
  ``g = p[chosen] / sum p[chosen]``; ``sum_k g_k E_k(n)``, every expert
  ``(silu(n Wg) * (n Wu)) Wd``. The chip's share as in ``moe_lm``: the
  layer holds ``experts_held`` experts from ``first_expert`` on, routes
  over all and computes its own experts' part; no pair on a held expert
  is ever dropped
* auxiliary loss: ``aux_l = n_experts * sum_e f_e P_e`` a layer, ``f_e``
  the share of the chosen pairs on expert e over the GLOBAL batch (the
  loads summed over ``axis_name``, no gradient), ``P_e`` the mean of
  ``p_e`` over this replica's tokens; ``total = loss + aux_weight *
  mean_l aux_l``. Each layer keeps its cumulative ``load`` and
  ``recent_load`` in ``rest``

The layers are stacked on a leading axis and applied by ``lax.scan``;
with ``remat`` a layer application and the head are
``jax.checkpoint``ed. Of a layer application the backward pass keeps
its input and what ``_saved()`` names (``save_only_these_names``): the
attention kernel's output and log-sum-exp
(``ops.pallas_attention.FLASH_OUT``, ``FLASH_LSE``), as in ``moe_lm``
and for the same reason: without them it ran the forward kernel a
second time, 3.46 ms a layer at 8,192 positions; and the output
projection's result ``attn_proj``, which with the kernel's output kept
is one product a layer that need not be made again. Under
``attn_impl="xla"`` the kernel's names never appear.

What the layer's checkpoint keeps, measured on a TPU v5e at the
benchmark's cell (``sdar-l6-train-b1x4096``: six layers in one scan,
4,096 tokens and their noisy copy, 646M parameters under AdamW; one
traced run each on one seed, PERF.md section 6, PR 39; ms a step,
``mixture`` the time under ``moe``, bytes at the peak of 16.91e9):

=======================================  ======  =========  =======  ========
kept beside the layer's input            step    recompute  mixture  bytes
=======================================  ======  =========  =======  ========
nothing (until PR 39)                    276.64  46.96      45.93    14.213e9
the kernel's two                         254.82  25.57      38.45    14.199e9
+ ``attn_proj``: ``_saved()``            251.43  21.08      41.25    14.282e9
the kernel's two + ``q``                 265.79  17.46      49.06    14.600e9
the kernel's two + ``q``, ``k``, ``v``   266.44  16.69      46.42    14.701e9
q, k behind a barrier: ``_saved()``      253.38  21.72      41.17    14.282e9
the same + ``q``, ``k``                  246.07  12.83      40.19    14.734e9
=======================================  ======  =========  =======  ========

The mixture's time is the step's routing (it differs between two
programs on one seed once their roundings differ; one program on one
seed repeats to 0.005 ms), so read the step less the mixture: 230.71,
216.37, 210.18, 216.73, 220.02, 212.21, 205.88. The kernel's two names
take 14.3 ms off the step: the recomputed kernel calls' 20.77 less 2.1
for the copy that makes the dQ kernel's column from the kept row and
some 3 ms of copies of the saved stacks through the scan; the three
kernels' 18 calls that
are left take what they took (20.75 + 29.67 + 24.87). ``attn_proj``
(six stacks of 33.5 MB, 83 MB at the peak) takes the output
projection's recomputed product off, 4.9 ms of ``gqa``'s 20.1
recomputed, 6.2 ms of the step. ``q`` beside the two takes 8.1 ms off
the recomputation and costs 12.2 in the forward pass (no barrier stands
between the rotation and its readers here, so a saved q is computed
once more: what ``looped_lm`` found before it had its barrier) and
400 MB: not kept; ``k`` and ``v`` beside it, 4 heads each, make it
worse. The last two rows have q and k pass one
``lax.optimization_barrier`` before they are named, as ``looped_lm``
has it (less the mixture 212.21 and 205.88): the barrier alone costs
2.0 ms, and with it ``q`` and ``k`` saved take 4.3 ms off the step that
``_saved()`` gives for 452 MB. That pays by the rule and is left for
the next change to this list: it was read after this list's last runs
on the chip. In ``moe_lm``'s cell ``attn_proj`` does not pay (its
table).

Scopes (``jax.named_scope``, docs/OBSERVABILITY.md): ``gqa`` around the
attention block with ``attention`` around its core inside it; ``moe``
around the mixture with ``moe_route`` and ``moe_experts`` inside it;
``lm_head`` around norm + head + cross-entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tpu_syncbn.mesh_axes import DATA_AXIS
from tpu_syncbn.models.looped_lm import (
    ATTN_IMPLS, _normal, apply_rotary, block_diffusion_attention,
    checkpointed, rms_norm, rotary_angles)
from tpu_syncbn.models.moe_lm import (
    RECENT_STEPS, ExpertLoad, MoEDecoderBase, held_chunk)
from tpu_syncbn.parallel import expert

_MATRICES = ("wq", "wk", "wv", "wo", "router", "eg", "eu", "ed")
_NORMS = ("norm1", "norm2", "q_norm", "k_norm")


def _saved() -> tuple[str, ...]:
    """What a layer application's ``jax.checkpoint`` keeps for the
    backward pass beside its input: the attention kernel's output and
    log-sum-exp under the kernel file's names, as in ``moe_lm``, and the
    output projection's result ``attn_proj``. Of the other names the
    layer gives itself (``q``, ``k``, ``v``, ``ffn_out``) none: the
    module docstring has the table."""
    from tpu_syncbn.ops.pallas_attention import FLASH_LSE, FLASH_OUT

    return (FLASH_OUT, FLASH_LSE, "attn_proj")


class _Layers(nnx.Module):
    """``n`` layers, each parameter stacked on a leading axis of n, and
    the experts' loads."""

    def __init__(self, n: int, *, hidden: int, heads: int, kv_heads: int,
                 head_dim: int, n_experts: int, held: int, width: int,
                 std: float, rngs: nnx.Rngs):
        normal = _normal(rngs, std)
        ones = lambda *shape: nnx.Param(jnp.ones(shape))
        self.wq = normal(n, hidden, heads * head_dim)
        self.wk = normal(n, hidden, kv_heads * head_dim)
        self.wv = normal(n, hidden, kv_heads * head_dim)
        self.wo = normal(n, heads * head_dim, hidden)
        self.router = normal(n, hidden, n_experts)
        self.eg = normal(n, held, hidden, width)
        self.eu = normal(n, held, hidden, width)
        self.ed = normal(n, held, width, hidden)
        self.norm1, self.norm2 = ones(n, hidden), ones(n, hidden)
        self.q_norm, self.k_norm = ones(n, head_dim), ones(n, head_dim)
        self.load = ExpertLoad(jnp.zeros((n, n_experts)))
        self.recent_load = ExpertLoad(
            jnp.zeros((n, RECENT_STEPS, n_experts)))

    def stacked(self) -> dict:
        return {name: getattr(self, name)[...]
                for name in _MATRICES + _NORMS}


class BlockDiffusionMoELM(MoEDecoderBase):
    """See the module docstring. ``x0`` and ``xt`` are (B, L) integers,
    ``w`` (B, L) float32; nothing here knows about replicas but the sum
    of the experts' loads over ``axis_name``, ``DataParallel`` means the
    loss and the metrics."""

    def __init__(self, *, vocab_size: int, hidden_size: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, num_layers: int,
                 block_length: int, n_experts: int, experts_held: int,
                 first_expert: int = 0, experts_per_token: int,
                 moe_intermediate: int, aux_weight: float = 1e-3,
                 rope_theta: float = 1e4, rms_eps: float = 1e-6,
                 init_std: float = 0.02, embed_std: float | None = None,
                 dtype=jnp.float32, attn_impl: str = "xla",
                 remat: bool = True, axis_name: str = DATA_AXIS,
                 rngs: nnx.Rngs):
        if attn_impl not in ATTN_IMPLS:
            raise ValueError(
                f"attn_impl must be one of {ATTN_IMPLS}, got {attn_impl!r}")
        if num_heads % num_kv_heads or head_dim % 2:
            raise ValueError("num_kv_heads must divide num_heads and "
                             "head_dim be even")
        if not 0 <= first_expert <= n_experts - experts_held:
            raise ValueError(
                f"experts {first_expert}..{first_expert + experts_held - 1} "
                f"are not among the layer's {n_experts}")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.block_length = head_dim, block_length
        self.first_expert, self.top_k = first_expert, experts_per_token
        self.aux_weight, self.rope_theta = aux_weight, rope_theta
        self.rms_eps, self.dtype, self.attn_impl = rms_eps, dtype, attn_impl
        self.remat, self.axis_name = remat, axis_name
        # the embedding may have a scale of its own. At unit scale under
        # matrices at 0.02 a token's own vector is not drowned by what
        # attention adds to every position alike, so the routing follows
        # the token ids. Measured (PERF.md section 6, PR 36): the loads
        # are as uneven either way, but how many pairs the HELD experts
        # get swings less from seed to seed (the step's length with it)
        self.embed = _normal(rngs, init_std if embed_std is None
                             else embed_std)(vocab_size, hidden_size)
        self.layers = _Layers(
            num_layers, hidden=hidden_size, heads=num_heads,
            kv_heads=num_kv_heads, head_dim=head_dim, n_experts=n_experts,
            held=experts_held, width=moe_intermediate, std=init_std,
            rngs=rngs)
        self.final_norm = nnx.Param(jnp.ones((hidden_size,)))
        self.head = _normal(rngs, init_std)(hidden_size, vocab_size)

    # -- one layer ----------------------------------------------------------

    def _qkv(self, x, p, cos, sin):
        """What the attention core reads: q (B, S, heads, d) and k
        normed a head and rotated, v, k and v at ``num_kv_heads``."""
        b, s, _ = x.shape
        n = rms_norm(x, p["norm1"], self.rms_eps)

        def heads(w, count):
            return self._dot(n, w).reshape(b, s, count, self.head_dim)

        q = apply_rotary(rms_norm(heads(p["wq"], self.num_heads),
                                  p["q_norm"], self.rms_eps), cos, sin)
        k = apply_rotary(rms_norm(heads(p["wk"], self.num_kv_heads),
                                  p["k_norm"], self.rms_eps), cos, sin)
        return (checkpoint_name(q, "q"), checkpoint_name(k, "k"),
                checkpoint_name(heads(p["wv"], self.num_kv_heads), "v"))

    def _attend(self, q, k, v):
        with jax.named_scope("attention"):
            return block_diffusion_attention(
                q, k, v, q.shape[1] // 2, self.block_length, self.attn_impl,
                _saved())

    def _attention_block(self, x, p, cos, sin, parts: dict | None = None):
        """``a = x + Attn(N1(x))``; q, k, v and the core's output join
        ``parts`` where a comparison asks for them."""
        with jax.named_scope("gqa"):
            q, k, v = self._qkv(x, p, cos, sin)
            o = self._attend(q, k, v)
            if parts is not None:
                parts.update(q=q, k=k, v=v, attention=o)
            o = self._dot(o.reshape(*x.shape[:2], -1), p["wo"])
            return x + checkpoint_name(o, "attn_proj")

    def _moe(self, n, p, parts: dict | None = None):
        """The mixture on the normed input ``n`` (B, S, H): the held
        experts' part, the (E,) loads of ALL experts over these tokens,
        the (E,) mean router probabilities and the held pairs not
        computed."""
        with jax.named_scope("moe"):
            flat = n.reshape(-1, n.shape[-1])
            with jax.named_scope("moe_route"):
                idx, gates, probs = expert.softmax_topk_route(
                    flat, p["router"], top_k=self.top_k)
            routed, missed = expert.held_expert_moe(
                flat, idx, gates, p["eg"], p["eu"], p["ed"],
                first_expert=self.first_expert,
                chunk=held_chunk(idx.size, p["eg"].shape[0],
                                 p["router"].shape[-1]))
            with jax.named_scope("moe_route"):
                load = expert.expert_loads(idx, p["router"].shape[-1])
                mean_probs = jnp.mean(probs, axis=0)
            if parts is not None:
                shape = (*n.shape[:2], -1)
                parts.update(idx=idx.reshape(shape),
                             gates=gates.reshape(shape))
            return routed.reshape(n.shape), (load, mean_probs, missed)

    def _layer(self, x, p, cos, sin, parts: dict | None = None):
        """One application of one layer: ``p`` that layer's slice of the
        stacked parameters. Returns the output and (load, mean router
        probabilities, pairs not computed)."""
        a = self._attention_block(x, p, cos, sin, parts)
        n = rms_norm(a, p["norm2"], self.rms_eps)
        f, stats = self._moe(n, p, parts)
        if parts is not None:
            parts.update(router_in=n, moe=f)
        return a + checkpoint_name(f, "ffn_out"), stats

    def _angles(self, positions: int):
        """Position i of each half at rotary position i: the angles of
        ``positions / 2`` positions, laid out twice."""
        cos, sin = rotary_angles(positions // 2, self.head_dim,
                                 self.rope_theta)
        return jnp.tile(cos, (2, 1)), jnp.tile(sin, (2, 1))

    # -- the pieces a caller may read -----------------------------------------

    def hidden(self, x0, xt):
        """The stack's output over ``[x0 ; xt]`` before its final norm,
        (B, 2L, H), and the layers' (loads (n, E), mean router
        probabilities (n, E), pairs not computed (n,))."""
        h = self.embed_tokens(jnp.concatenate([x0, xt], axis=1))
        cos, sin = self._angles(h.shape[1])
        layer = (checkpointed(self._layer, _saved()) if self.remat
                 else self._layer)
        return lax.scan(lambda x, p: layer(x, p, cos, sin),
                        h, self.layers.stacked())

    def layer_parts(self, x, index: int = 0) -> dict:
        """Layer ``index`` applied to ``x`` (B, 2L, H), opened up for a
        comparison with a reference (the training path never calls it):
        ``q``, ``k``, ``v`` as the attention core reads them and
        ``attention`` as it writes them; ``router_in`` (B, 2L, H), what
        router and experts read; ``idx`` and ``gates`` (B, 2L, k);
        ``load`` and ``mean_probs`` (E,); ``moe`` (B, 2L, H), the held
        experts' part; ``pairs_not_computed``; the layer's output
        ``out``."""
        p = jax.tree_util.tree_map(lambda a: a[index], self.layers.stacked())
        parts: dict = {}
        out, (load, mean_probs, missed) = self._layer(
            x, p, *self._angles(x.shape[1]), parts)
        return {**parts, "load": load, "mean_probs": mean_probs,
                "pairs_not_computed": missed, "out": out}

    def __call__(self, x0, xt):
        """The logits of the noisy half, (B, L, vocabulary) float32."""
        h, _ = self.hidden(x0, xt)
        return self.logits(self.read(h[:, x0.shape[1]:],
                                     self.final_norm[...]))

    # -- the loss ---------------------------------------------------------------

    def aux_losses(self, load, mean_probs):
        """A layer's balance loss, (n,), from the layers' loads summed
        over the replicas and this replica's mean probabilities; and the
        global loads."""
        load = self.global_load(load)
        return expert.load_balance_loss(mean_probs, load), load

    def loss(self, x0, xt, w):
        """``mean(w * CE(noisy position, x0)) + aux_weight * mean over
        layers(aux)`` and the step metrics ``diffusion_loss``,
        ``aux_loss``, ``masked_share`` (the positions with a weight),
        ``expert_load_max_over_mean`` (the worst layer's, over the global
        batch) and ``pairs_not_computed`` (must be 0). Counts every
        layer's loads."""
        read = (checkpointed(self.cross_entropy) if self.remat
                else self.cross_entropy)
        h, (load, mean_probs, missed) = self.hidden(x0, xt)
        ce = read(h[:, x0.shape[1]:], self.final_norm[...], x0)
        diffusion = jnp.mean(w * ce)
        aux, load = self.aux_losses(load, mean_probs)
        self._counted(self.layers, load)
        metrics = {
            "diffusion_loss": diffusion, "aux_loss": jnp.mean(aux),
            "masked_share": jnp.mean(w > 0),
            "expert_load_max_over_mean": jnp.max(
                jnp.max(load, axis=-1) / jnp.mean(load, axis=-1)),
            "pairs_not_computed": jnp.sum(missed)}
        return diffusion + self.aux_weight * jnp.mean(aux), metrics

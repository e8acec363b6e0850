"""Expert parallelism: two mixtures of experts and their three routers.

**Which router is which.** (1) *Switch* (``switch_route`` / ``dense_moe``
/ ``expert_parallel_moe``, below): top-1 softmax routing with a capacity
that drops what overflows, dispatch and combine as one-hot einsums over
(T, E, C), two-matrix ReLU experts, and the two ``all_to_all``s over an
``expert`` mesh axis. (2) *Sigmoid top-k* (``sigmoid_topk_route``):
DeepSeek-V3-style routing: sigmoid scores, a selection bias that is no
parameter and is moved after each step (``update_selection_bias``),
top-k of score + bias, weights normalised and scaled; no auxiliary loss.
(3) *Softmax top-k* (``softmax_topk_route``): Qwen3-MoE-style routing: a
softmax over ALL experts, the top k of it, the chosen probabilities
renormalised to sum to one, no bias; balanced by an auxiliary loss
(``load_balance_loss``: the Switch form over the k chosen pairs a token,
the loads of the global batch against this replica's mean probabilities).

(2) and (3) route for the same mixture, *dropless, the chip's share*
(``held_expert_moe``, at the end of the file): over ALL experts of a
layer that is told which of them this chip holds; SwiGLU experts as
grouped products over the pairs that really arrive, no capacity, no
drop, and no exchange: what the absent experts would add is left out.
``models.moe_lm`` runs (2), ``models.block_diffusion_lm`` (3); the
all-to-all of (1) has not met either yet (ROADMAP.md Queue 2).

The first, Switch over an ``expert`` mesh axis:

The reference recipe has no MoE (absent from ``README.md:1-104``, SURVEY
§2's parallelism inventory) — this is the expert-parallel member of the
beyond-reference set (ring/Ulysses sequence parallelism, ZeRO), built on
the same collective layer. The TPU-native shape:

* tokens are sharded across the axis (data-parallel style);
* expert weights are sharded across the SAME axis — device ``i`` owns
  experts ``[i·E_loc, (i+1)·E_loc)`` and only ever materializes those;
* routing is top-1 (Switch) with a per-(expert, source-device) capacity;
  dispatch/combine are one-hot einsums (static shapes, MXU-friendly —
  no gather/scatter, no dynamic shapes under jit);
* two ``all_to_all``s move token slots to their expert's device and
  back — O(capacity) traffic per device, the EP analogue of the
  sequence module's resharding.

Exactness contract: :func:`expert_parallel_moe` over N devices equals
:func:`dense_moe` (full weights, zero collectives) applied per shard —
the all_to_alls relocate compute without changing it. Pinned with
gradients in ``tests/test_expert_parallel.py``.
"""

from __future__ import annotations

import functools

import jax
from tpu_syncbn.compat import axis_size as _compat_axis_size
import jax.numpy as jnp
from jax import lax

# canonical home: tpu_syncbn.mesh_axes (srclint hardcoded_mesh_axis)
from tpu_syncbn.mesh_axes import EXPERT_AXIS  # noqa: E402


def switch_route(
    x: jax.Array, router_w: jax.Array, capacity: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-1 routing with capacity. ``x``: (T, D); ``router_w``: (D, E).

    Returns ``(dispatch, combine, aux)``:
      dispatch (T, E, C) 0/1 — token t occupies slot c of expert e;
      combine  (T, E, C) f32 — dispatch scaled by the router probability
      (the Switch estimator: output is prob-weighted so the router gets
      gradients); aux — the Switch load-balance loss
      ``E * Σ_e fraction_e · mean_prob_e`` over these tokens.

    Tokens beyond an expert's capacity are dropped (their combine row is
    zero → they pass through as zeros; residual connections restore them
    in a transformer block). Slot assignment is by token order — the
    deterministic tie-break the exactness tests rely on.
    """
    t, _ = x.shape
    e = router_w.shape[-1]
    logits = (x.astype(jnp.float32)) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    idx = jnp.argmax(probs, axis=-1)  # (T,)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (T, E)
    # rank of each token within its expert's queue (>= 0 at the chosen
    # expert since the cumsum includes the token itself; -1 elsewhere)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # (T, E)
    rank = pos.max(axis=-1).astype(jnp.int32)  # (T,)
    # one_hot is all-zeros for rank >= capacity: over-capacity tokens
    # drop out of dispatch with no separate mask needed
    slot = jax.nn.one_hot(rank, capacity, dtype=jnp.float32)
    dispatch = onehot[:, :, None] * slot[:, None, :]  # (T, E, C)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)  # (T, 1)
    combine = dispatch * gate[:, :, None]
    fraction = onehot.mean(axis=0)  # tokens routed to each expert
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(fraction * mean_prob)
    return dispatch, combine, aux


def _expert_mlp(inputs: jax.Array, w_in: jax.Array, w_out: jax.Array):
    """Batched per-expert 2-layer ReLU MLP: (E, C, D) @ (E, D, H) @ (E, H, D)."""
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", inputs, w_in))
    return jnp.einsum("ech,ehd->ecd", h, w_out)


def _capacity(t: int, e: int, capacity_factor: float) -> int:
    return max(1, int(-(-t * capacity_factor // e)))  # ceil


def dense_moe(
    x: jax.Array,
    router_w: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    capacity_factor: float = 1.25,
) -> tuple[jax.Array, jax.Array]:
    """Single-device MoE: full expert weights, zero collectives. The n=1
    path and the exactness oracle for the expert-parallel version.
    Returns ``(y, aux)`` with ``y`` shaped like ``x``."""
    t = x.shape[0]
    e = router_w.shape[-1]
    c = _capacity(t, e, capacity_factor)
    dispatch, combine, aux = switch_route(x, router_w, c)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    expert_out = _expert_mlp(expert_in, w_in, w_out)
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y.astype(x.dtype), aux


def expert_parallel_moe(
    x: jax.Array,
    router_w: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    axis_name: str = EXPERT_AXIS,
    *,
    capacity_factor: float = 1.25,
) -> tuple[jax.Array, jax.Array]:
    """Shard-level expert-parallel MoE (call inside ``shard_map``).

    ``x``: this device's tokens (T_local, D); ``router_w``: replicated
    (D, E_total); ``w_in``/``w_out``: this device's expert shard
    (E_local, D, H) / (E_local, H, D) with ``E_total = E_local · world``.

    Flow: route locally against all experts → dispatch into per-expert
    capacity slots → ``all_to_all`` sends each expert's slots to its
    owning device → batched expert MLP over the local experts → inverse
    ``all_to_all`` → combine. Per-source capacity makes the result
    exactly :func:`dense_moe` per shard. Returns ``(y_local, aux)`` with
    aux ``pmean``'d across the axis.
    """
    n = _compat_axis_size(axis_name)
    t, d = x.shape
    e_local = w_in.shape[0]
    e = router_w.shape[-1]
    if e != e_local * n:
        raise ValueError(
            f"router has {e} experts but shard has {e_local} × world {n}"
        )
    c = _capacity(t, e, capacity_factor)
    dispatch, combine, aux = switch_route(x, router_w, c)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))

    if n == 1:
        expert_out = _expert_mlp(expert_in, w_in, w_out)
    else:
        # (E, C, D) -> (world, E_local, C, D): send slots to expert owners;
        # received leading axis = source device
        grouped = expert_in.reshape(n, e_local, c, d)
        inbound = lax.all_to_all(
            grouped, axis_name, split_axis=0, concat_axis=0, tiled=False
        )  # (world_src, E_local, C, D)
        flat_in = jnp.moveaxis(inbound, 0, 1).reshape(e_local, n * c, d)
        flat_out = _expert_mlp(flat_in, w_in, w_out)
        outbound = jnp.moveaxis(
            flat_out.reshape(e_local, n, c, d), 1, 0
        )  # (world_src, E_local, C, D)
        returned = lax.all_to_all(
            outbound, axis_name, split_axis=0, concat_axis=0, tiled=False
        )  # (world_expert_owner, E_local, C, D)
        expert_out = returned.reshape(e, c, d)

    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y.astype(x.dtype), lax.pmean(aux, axis_name)


# -- sigmoid top-k routing over all experts, the experts held computed ------


def sigmoid_topk_route(
    x: jax.Array, router_w: jax.Array, bias: jax.Array, *,
    top_k: int, scale: float,
) -> tuple[jax.Array, jax.Array]:
    """Sigmoid scores, top-k of scores + bias, weights from the scores.

    ``x`` (T, H); ``router_w`` (H, E), E ALL the layer's experts;
    ``bias`` (E,) the selection bias, no parameter (no gradient reaches
    it: it only chooses). Returns ``(idx, gates)``: ``idx`` (T, k) int32,
    the k experts with the largest ``s + bias`` (ties to the lower
    index), and ``gates`` (T, k) float32, ``scale * s[idx] /
    (sum(s[idx]) + 1e-20)``. The scores are float32, their product at
    full precision: a selection is not a thing to round."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + lax.stop_gradient(bias), top_k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)


def softmax_topk_route(
    x: jax.Array, router_w: jax.Array, *, top_k: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Softmax probabilities over all experts, the top k of them, their
    weights renormalised.

    ``x`` (T, H); ``router_w`` (H, E), E ALL the layer's experts.
    Returns ``(idx, gates, probs)``: ``idx`` (T, k) int32, the k experts
    with the largest probability (ties to the lower index); ``gates``
    (T, k) float32, ``p[idx] / sum(p[idx])``; ``probs`` (T, E) float32,
    what an auxiliary balance loss reads. Float32, the product at full
    precision, as in ``sigmoid_topk_route``."""
    p = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    g, idx = lax.top_k(p, top_k)
    return idx, g / jnp.sum(g, axis=-1, keepdims=True), p


def load_balance_loss(probs: jax.Array, load: jax.Array) -> jax.Array:
    """``E * sum_e f_e P_e``: ``f_e`` the share of the chosen pairs that
    fell on expert e, from ``load`` (.., E) (counts over whatever batch
    the caller summed them over: the global one, if the replicas are to
    be balanced together; no gradient passes through them), ``P_e`` the
    mean router probability of expert e, ``probs`` (.., E) already meaned
    over this replica's tokens (the gradient's way into the router). 1
    where routing is even and the probabilities flat; E / k where every
    token chooses the same k experts with all its probability."""
    load = lax.stop_gradient(load)
    share = load / jnp.maximum(jnp.sum(load, axis=-1, keepdims=True), 1.0)
    return probs.shape[-1] * jnp.sum(share * probs, axis=-1)


def expert_loads(idx: jax.Array, n_experts: int) -> jax.Array:
    """(E,) float32: the tokens each expert was chosen by, from ``idx``
    (T, k). A compare-and-sum, no scatter."""
    hit = idx[..., None] == jnp.arange(n_experts, dtype=idx.dtype)
    return jnp.sum(hit, axis=(0, 1), dtype=jnp.float32)


def update_selection_bias(bias: jax.Array, load: jax.Array,
                          gamma: float) -> jax.Array:
    """``b <- b + gamma * sign(mean(load) - load)``: an expert chosen
    less often than the mean is made easier to choose, one chosen more
    often harder (auxiliary-loss-free balancing). ``bias`` and ``load``
    are (.., E), the mean over the experts. ``load`` is over
    whatever batch the caller summed it over: the global one, if the
    replicas are to keep one bias."""
    return bias + gamma * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)


def _held_chunk(x, gates, w_gate, w_up, w_down, order, sizes, lo, *,
                k, chunk):
    """(T, H) float32: what rows ``lo .. lo + chunk - 1`` of the sorted
    pairs add. ``order`` (P,) the pairs sorted held first and by expert,
    ``sizes`` (E_held,) the held experts' pair counts, ``gates`` (P,)
    the weights by pair."""
    t, h = x.shape
    with jax.named_scope("moe_route"):
        ends = jnp.cumsum(sizes)
        window = lambda a: jnp.clip(a, lo, lo + chunk)
        local = window(ends) - window(ends - sizes)  # group sizes in here
        pair = lax.dynamic_slice(order, (lo,), (chunk,))
        valid = (lo + jnp.arange(chunk) < ends[-1])[:, None]
        token = pair // k
        weight = jnp.where(valid, gates[pair][:, None], 0.0)
        # rows past the held pairs belong to no group: the grouped
        # product leaves them unwritten, so they are masked wherever
        # they could reach a sum, forward or backward
        rows = jnp.where(valid, x[token], 0)
    with jax.named_scope("moe_experts"):
        def grouped(a, w):
            return lax.ragged_dot(a, w.astype(x.dtype), local,
                                  preferred_element_type=jnp.float32)

        act = jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up)
        act = jnp.where(valid, act, 0.0).astype(x.dtype)
        out = jnp.where(valid, grouped(act, w_down), 0.0)
    with jax.named_scope("moe_route"):
        return jnp.zeros((t, h), jnp.float32).at[token].add(out * weight)


def _chunks(sizes, chunk):
    return (jnp.sum(sizes) + chunk - 1) // chunk


def _zeros(like, *others):
    """Zeros of ``like``'s shape and type that vary over the mesh axes
    ``like`` and ``others`` vary over: inside ``shard_map`` a loop's
    carry has to enter with the varying type it leaves with."""
    from tpu_syncbn.parallel.collectives import pcast_varying

    axes = set().union(*(getattr(jax.typeof(a), "vma", ()) or ()
                         for a in (like, *others)))
    zeros = jnp.zeros_like(like)
    return pcast_varying(zeros, tuple(axes)) if axes else zeros


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _held_experts(x, gates, w_gate, w_up, w_down, order, sizes, k, chunk):
    """The held pairs, ``chunk`` rows of the sorted order at a time, for
    as many chunks as hold a held pair: a loop whose trip count is the
    step's own, so neither a buffer nor a product is ever the size of
    the bound (all T*k pairs held). Reverse-mode differentiation cannot
    run such a loop backwards, so the backward pass is written out: the
    same walk, each chunk recomputed and pulled back."""
    part = functools.partial(_held_chunk, k=k, chunk=chunk)
    return lax.fori_loop(
        0, _chunks(sizes, chunk),
        lambda i, y: y + part(x, gates, w_gate, w_up, w_down, order, sizes,
                              i * chunk),
        _zeros(x.astype(jnp.float32), gates, w_gate, w_up, w_down, order,
               sizes))


def _held_experts_fwd(x, gates, w_gate, w_up, w_down, order, sizes,
                      k, chunk):
    y = _held_experts(x, gates, w_gate, w_up, w_down, order, sizes, k, chunk)
    return y, (x, gates, w_gate, w_up, w_down, order, sizes)


def _held_experts_bwd(k, chunk, res, dy):
    *inputs, order, sizes = res
    part = functools.partial(_held_chunk, k=k, chunk=chunk)

    def pull(i, grads):
        _, back = jax.vjp(
            lambda *a: part(*a, order, sizes, i * chunk), *inputs)
        return jax.tree_util.tree_map(jnp.add, grads, back(dy))

    grads = lax.fori_loop(0, _chunks(sizes, chunk), pull,
                          tuple(_zeros(a) for a in inputs))
    return (*grads, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def held_expert_moe(
    x: jax.Array, idx: jax.Array, gates: jax.Array,
    w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, *,
    chunk: int, first_expert: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """The part of ``sum_k g_k E_k(x)`` that the experts held here give.

    ``x`` (T, H); ``idx`` / ``gates`` (T, k) from the router over all E
    experts; ``w_gate`` / ``w_up`` (E_held, H, F) and ``w_down`` (E_held,
    F, H): experts ``first_expert .. first_expert + E_held - 1``, each
    ``(silu(x Wg) * (x Wu)) Wd``. A chosen pair whose expert is not held
    adds nothing (its chip would); **every pair whose expert is held is
    computed**, whatever the imbalance: there is no capacity. Shapes are
    static all the same: the T*k pairs are sorted, held ones first and by
    expert, and walked ``chunk`` rows at a time (the caller's: some small
    multiple of what arrives if the loads are even, in whole row tiles
    of the grouped product) for as many chunks as hold a held pair, so the gather, the
    three grouped products (``lax.ragged_dot``, a Mosaic kernel on the
    TPU that walks only the row tiles its group sizes cover) and the
    weighted scatter-add cost what the pairs that arrived cost, in steps
    of ``chunk``, and all T*k pairs held is only the slowest case, not
    the size of a buffer. Products take operands of x's type and
    accumulate in float32; SiLU, the gated product and the weighted sum
    over k are float32.

    Returns ``(y, pairs_not_computed)``: ``y`` (T, H) in x's type, and
    a float32 scalar, the pairs chosen on held experts less those whose
    row lies in the group of their own expert and in a chunk the walk
    reaches: 0 unless the sort, the group sizes and the trip count
    disagree, and there to be checked."""
    t, _ = x.shape
    k = idx.shape[1]
    e_held = w_gate.shape[0]
    chunk = min(chunk, t * k)
    with jax.named_scope("moe_route"):
        local = idx.reshape(-1) - first_expert
        key = jnp.where((local >= 0) & (local < e_held), local, e_held)
        order = jnp.argsort(key, stable=True)  # held first, by expert
        sizes = jnp.sum(key[:, None] == jnp.arange(e_held, dtype=key.dtype),
                        axis=0, dtype=jnp.int32)
        # the check: a held pair is computed if the row it was sorted to
        # lies in the group of its own expert and in a chunk the walk
        # reaches
        row = jnp.arange(t * k)
        group = jnp.sum(row[:, None] >= jnp.cumsum(sizes), axis=1)
        computed = jnp.sum((key[order] == group) & (group < e_held)
                           & (row < _chunks(sizes, chunk) * chunk))
        missed = jnp.sum(key < e_held) - computed
        order = jnp.pad(order, (0, -(t * k) % chunk))
    y = _held_experts(x, gates.reshape(-1), w_gate, w_up, w_down,
                      lax.stop_gradient(order), sizes, k, chunk)
    return y.astype(x.dtype), missed.astype(jnp.float32)

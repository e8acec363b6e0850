"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference recipe has no attention anywhere (SURVEY §5.7: absent from
``README.md:1-104`` — the recipe is entirely conv-net BatchNorm). These
are the long-context counterparts of the recipe's one idea — keep the
activations local, communicate only what must be shared — promoted to
first-class framework components over the same mesh/collective layer the
SyncBN path uses:

* :func:`ring_attention` — exact blockwise attention for sequences
  sharded across the mesh. KV blocks rotate around the ICI ring
  (``lax.ppermute``, the same neighbor cycle as
  :func:`~tpu_syncbn.parallel.collectives.ring_all_reduce`) while each
  device accumulates its queries' output with an online-softmax running
  (max, denominator, accumulator) — so no device ever materializes the
  full sequence, and per-step traffic is one KV block over a direct ICI
  neighbor link. Compute per step is uniform across devices (SPMD
  lockstep: no load imbalance, no dynamic shapes).

* :func:`ring_attention_zigzag` — the causal ring with the **zigzag
  layout** (device ``i`` holds global chunks ``i`` and ``2n-1-i``):
  fully-masked chunk pairs are skipped *without* unbalancing the ring,
  ~2× the causal throughput of the contiguous ring. Use
  :func:`zigzag_shard`/:func:`zigzag_unshard` (or
  ``sharded_self_attention(impl="ring_zigzag")``) to move between
  position order and the zigzag layout.

* :func:`ulysses_attention` — DeepSpeed-Ulysses-style sequence
  parallelism: two ``all_to_all``s trade the sequence sharding for a
  *head* sharding, run ordinary full attention on the complete sequence
  for this device's head slice, and trade back. Cheaper than the ring
  when heads ≥ devices and the full sequence fits in HBM; the ring wins
  when it does not.

All three are exact (not approximations): output ≡ single-device softmax
attention on the gathered sequence, forward and gradients — pinned by
``tests/test_sequence_parallel.py`` on the 8-virtual-device mesh. All
are shard_map-level functions: arguments are this device's *local*
sequence shard, shaped ``(batch, seq_local, heads, head_dim)``; use
:func:`sharded_self_attention` for the array-level convenience wrapper.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_syncbn.compat import axis_size as _compat_axis_size
from tpu_syncbn.parallel.collectives import pcast_varying

# canonical home: tpu_syncbn.mesh_axes (srclint hardcoded_mesh_axis)
from tpu_syncbn.mesh_axes import SEQ_AXIS  # noqa: E402

# finite stand-in for -inf in masked logits: keeps the online-softmax
# running max finite when an entire KV block is masked out (exp(-inf+inf)
# would poison the rescale with NaN)
_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _qk_scale(head_dim: int, scale: Optional[float]) -> float:
    return float(scale) if scale is not None else head_dim ** -0.5


def _block_attend(q, k, v, bias, o, l, m):
    """One online-softmax accumulation step over a KV block.

    ``q``: (B, Lq, H, D) f32 pre-scaled; ``k``/``v``: (B, Lk, H, D);
    ``bias``: (B, Lq, H, Lk) additive mask (0 or ``_NEG_BIG``);
    carries ``o`` (B, Lq, H, D), ``l`` (B, Lq, H), ``m`` (B, Lq, H).
    """
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k.astype(jnp.float32)) + bias
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bqhk,bkhd->bqhd", p, v.astype(jnp.float32)
    )
    return o_new, l_new, m_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQ_AXIS,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded along ``axis_name``.

    Shard-level function (call inside ``shard_map``): ``q``/``k``/``v``
    are this device's contiguous sequence block, ``(B, L_local, H, D)``;
    device ``i`` holds global positions ``[i·L_local, (i+1)·L_local)``.
    Returns the local block of the attention output, same shape/dtype
    as ``q``.

    Algorithm: N-1 ``ppermute`` hops rotate the (K, V) pair around the
    ring; at hop ``s`` this device combines the KV block that started on
    device ``(me - s) mod N`` into its online-softmax state. Causal
    masking uses the *global* positions reconstructed from the block's
    origin, so the result is identical to masking the full sequence.
    The loop is a ``lax.scan`` — compile size stays O(1) in world size.
    """
    n = _compat_axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, l_q, h, d = q.shape
    qf = q.astype(jnp.float32) * _qk_scale(d, scale)

    if n == 1:
        return _single_device_attention(q, k, v, causal=causal, scale=scale)

    l_k = k.shape[1]
    q_pos = me * l_q + jnp.arange(l_q)  # global query positions
    fwd = [(i, (i + 1) % n) for i in range(n)]

    # scan carries must match the body's device-varying type
    o0, l0, m0 = pcast_varying(
        (
            jnp.zeros((b, l_q, h, d), jnp.float32),
            jnp.zeros((b, l_q, h), jnp.float32),
            jnp.full((b, l_q, h), _NEG_BIG, jnp.float32),
        ),
        axis_name,
    )

    def bias_for(src):
        """Additive mask for the KV block that started on device ``src``."""
        if not causal:
            return jnp.zeros((1, 1, 1, l_k), jnp.float32)
        k_pos = src * l_k + jnp.arange(l_k)
        allowed = q_pos[:, None] >= k_pos[None, :]  # (Lq, Lk)
        return jnp.where(allowed, 0.0, _NEG_BIG)[None, :, None, :]

    # own block first, then exactly N-1 (permute, attend) hops — the last
    # rotation is never wasted (a collective in a uniform scan body cannot
    # be dead-code-eliminated by XLA)
    o, l, m = _block_attend(qf, k, v, bias_for(me), o0, l0, m0)

    def hop(carry, s):
        o, l, m, k_blk, v_blk = carry
        k_blk, v_blk = lax.ppermute((k_blk, v_blk), axis_name, fwd)
        src = (me - s) % n  # ring origin of the block now in hand
        o, l, m = _block_attend(qf, k_blk, v_blk, bias_for(src), o, l, m)
        return (o, l, m, k_blk, v_blk), None

    (o, l, m, _, _), _ = lax.scan(hop, (o, l, m, k, v), jnp.arange(1, n))
    # causal ⇒ every query sees at least itself, so l > 0; keep the
    # guard anyway for degenerate fully-masked rows
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def zigzag_chunk_permutation(n_shards: int) -> list:
    """Chunk order realizing the zigzag layout: the global sequence is cut
    into ``2n`` chunks and device ``i`` holds chunks ``(i, 2n-1-i)`` — one
    early, one late — so causal work is balanced across the ring (the
    contiguous layout gives device 0 almost nothing unmasked and device
    n-1 everything)."""
    return [c for i in range(n_shards) for c in (i, 2 * n_shards - 1 - i)]


def zigzag_shard(x: jax.Array, n_shards: int, axis: int = 1) -> jax.Array:
    """Reorder a *global* sequence axis into the zigzag layout, so that a
    plain contiguous ``P(axis_name)`` sharding lands chunk pair
    ``(i, 2n-1-i)`` on device ``i``. Length must divide by ``2·n_shards``.
    Inverse: :func:`zigzag_unshard`."""
    length = x.shape[axis]
    if length % (2 * n_shards):
        raise ValueError(
            f"sequence length {length} must divide by 2*n_shards "
            f"({2 * n_shards})"
        )
    chunks = jnp.split(x, 2 * n_shards, axis=axis)
    return jnp.concatenate(
        [chunks[c] for c in zigzag_chunk_permutation(n_shards)], axis=axis
    )


def zigzag_unshard(x: jax.Array, n_shards: int, axis: int = 1) -> jax.Array:
    """Inverse of :func:`zigzag_shard`."""
    perm = zigzag_chunk_permutation(n_shards)
    inverse = [0] * len(perm)
    for pos, c in enumerate(perm):
        inverse[c] = pos
    chunks = jnp.split(x, 2 * n_shards, axis=axis)
    return jnp.concatenate([chunks[p] for p in inverse], axis=axis)


def ring_attention_zigzag(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQ_AXIS,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal ring attention over the **zigzag** layout — ~2× the causal
    throughput of :func:`ring_attention` by skipping fully-masked work
    while keeping every device equally busy.

    Shard-level function: this device's block is ``concat(chunk_e,
    chunk_l)`` with ``e = me`` and ``l = 2n-1-me`` of the ``2n`` global
    chunks (produce it with :func:`zigzag_shard` + contiguous sharding).

    Why it's fast AND balanced: under the contiguous layout, causal
    masking makes hop work proportional to the device index (device 0:
    almost all KV masked; device n-1: none) — skipping masked blocks
    would leave the ring gated by the busiest device every hop. In the
    zigzag layout each device owns one early and one late chunk, and at
    every non-self hop exactly TWO chunk-pair attends are live per
    device, both *fully* unmasked:

    * ``q_l × kv_e_incoming`` — a late query chunk against any early
      chunk is always allowed;
    * one of ``q_e × kv_e`` (when the incoming block originated earlier
      on the ring) or ``q_l × kv_l`` (when it originated later) —
      selected with ``jnp.where`` on same-shaped operands, so the
      compiled step stays branch-free and uniform.

    The self block (before the scan) adds the two in-chunk causal
    diagonals. Total: ``2(n-1) + 3`` chunk-attends of the ``4n`` the
    contiguous layout computes. Exact (online softmax, order-free):
    output ≡ the *original-order* causal oracle, presented in the zigzag
    layout — masking follows original positions, not zigzag offsets;
    undo the layout with ``zigzag_unshard`` (as ``sharded_self_attention``
    does).
    """
    n = _compat_axis_size(axis_name)
    me = lax.axis_index(axis_name)
    if n == 1:
        return _single_device_attention(q, k, v, causal=True, scale=scale)
    b, l_local, h, d = q.shape
    if l_local % 2:
        raise ValueError(
            f"zigzag local length must be even (chunk pair), got {l_local}"
        )
    c = l_local // 2
    qf = q.astype(jnp.float32) * _qk_scale(d, scale)
    q_e, q_l = qf[:, :c], qf[:, c:]

    def fresh_state():
        return pcast_varying(
            (
                jnp.zeros((b, c, h, d), jnp.float32),
                jnp.zeros((b, c, h), jnp.float32),
                jnp.full((b, c, h), _NEG_BIG, jnp.float32),
            ),
            axis_name,
        )

    # in-chunk causal diagonal: both chunks attend themselves causally
    # (global positions inside one chunk are consecutive, so the mask is
    # the ordinary lower triangle regardless of which chunk it is)
    tri = jnp.where(
        jnp.arange(c)[:, None] >= jnp.arange(c)[None, :], 0.0, _NEG_BIG
    )[None, :, None, :]
    zero_bias = jnp.zeros((1, 1, 1, c), jnp.float32)

    k_e, k_l = k[:, :c], k[:, c:]
    v_e, v_l = v[:, :c], v[:, c:]

    # self block: e×e diagonal, l×e full (e is always earlier), l×l diagonal
    e_state = _block_attend(q_e, k_e, v_e, tri, *fresh_state())
    l_state = _block_attend(q_l, k_e, v_e, zero_bias, *fresh_state())
    l_state = _block_attend(q_l, k_l, v_l, tri, *l_state)

    fwd = [(i, (i + 1) % n) for i in range(n)]

    def hop(carry, s):
        (o_e, l_e, m_e), (o_l, l_l, m_l), k_blk, v_blk = carry
        k_blk, v_blk = lax.ppermute((k_blk, v_blk), axis_name, fwd)
        src = (me - s) % n
        ke_in, kl_in = k_blk[:, :c], k_blk[:, c:]
        ve_in, vl_in = v_blk[:, :c], v_blk[:, c:]
        # late queries vs the incoming early chunk: always fully allowed
        o_l, l_l, m_l = _block_attend(
            q_l, ke_in, ve_in, zero_bias, o_l, l_l, m_l
        )
        # the other live pair: q_e×kv_e when src rode from earlier on the
        # ring, else q_l×kv_l — same shapes, operand-selected
        pred = src < me
        q_sel = jnp.where(pred, q_e, q_l)
        k_sel = jnp.where(pred, ke_in, kl_in)
        v_sel = jnp.where(pred, ve_in, vl_in)
        o_t = jnp.where(pred, o_e, o_l)
        l_t = jnp.where(pred, l_e, l_l)
        m_t = jnp.where(pred, m_e, m_l)
        o_t, l_t, m_t = _block_attend(q_sel, k_sel, v_sel, zero_bias,
                                      o_t, l_t, m_t)
        o_e = jnp.where(pred, o_t, o_e)
        l_e = jnp.where(pred, l_t, l_e)
        m_e = jnp.where(pred, m_t, m_e)
        o_l = jnp.where(pred, o_l, o_t)
        l_l = jnp.where(pred, l_l, l_t)
        m_l = jnp.where(pred, m_l, m_t)
        return ((o_e, l_e, m_e), (o_l, l_l, m_l), k_blk, v_blk), None

    (e_state, l_state, _, _), _ = lax.scan(
        hop, (e_state, l_state, k, v), jnp.arange(1, n)
    )
    o_e, l_e, _ = e_state
    o_l, l_l, _ = l_state
    out = jnp.concatenate(
        [
            o_e / jnp.maximum(l_e, 1e-30)[..., None],
            o_l / jnp.maximum(l_l, 1e-30)[..., None],
        ],
        axis=1,
    )
    return out.astype(q.dtype)


def _single_device_attention(q, k, v, *, causal, scale):
    """Plain full-softmax attention — the n=1 path and the test oracle."""
    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bqhk",
        q.astype(jnp.float32) * _qk_scale(d, scale),
        k.astype(jnp.float32),
    )
    if causal:
        l_q, l_k = q.shape[1], k.shape[1]
        allowed = jnp.arange(l_q)[:, None] >= jnp.arange(l_k)[None, :]
        s = jnp.where(allowed[None, :, None, :], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQ_AXIS,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    local_impl: Optional[str] = None,
    local_backward: str = "xla",
) -> jax.Array:
    """Sequence parallelism by head redistribution (DeepSpeed-Ulysses).

    Shard-level function: local blocks ``(B, L_local, H, D)`` with the
    sequence sharded along ``axis_name``. An ``all_to_all`` converts the
    layout to (full sequence × ``H/N`` local heads), full attention runs
    locally per head slice, and a second ``all_to_all`` restores the
    sequence sharding. Requires ``H`` divisible by the axis size.

    Exact — the head axis is embarrassingly parallel in attention, so
    resharding it changes nothing numerically. Two all_to_alls move
    2·(N-1)/N of (Q,K,V,O) per device vs the ring's (N-1)/N of (K,V),
    but the attention itself is one big local matmul over the full
    sequence (best MXU shape) instead of N accumulation steps.

    ``local_impl="flash"`` runs the local full-sequence attention
    through the fused Pallas kernel (``ops.flash_attention``) instead of
    the score-matrix oracle: the (L, L) scores — Ulysses' memory ceiling
    for long context — are then never materialized. Default None keeps
    the oracle (the evidence-gating stance: kernels are opt-in until
    timed on hardware). ``local_backward`` forwards to the flash
    kernel's VJP selector ("xla" scan default; "pallas" = the fused
    two-kernel backward — so long-context training can run the whole
    attention fwd+bwd through Pallas). Under the CPU mesh's *interpret*
    lowering the enclosing ``shard_map`` needs ``check_vma=False`` when
    flash is selected (hlo_interpreter dynamic_slice rejects the checker
    around pallas bodies); the TPU lowering keeps the checker on.
    """
    if local_impl not in (None, "flash"):
        raise ValueError(
            f"local_impl must be None or 'flash', got {local_impl!r}"
        )
    if local_impl is None and local_backward != "xla":
        raise ValueError(
            "local_backward applies to local_impl='flash' only"
        )
    n = _compat_axis_size(axis_name)
    h = q.shape[2]
    if local_impl == "flash":
        from tpu_syncbn.ops.pallas_attention import flash_attention

        local_attn = functools.partial(
            flash_attention, causal=causal, scale=scale,
            backward=local_backward,
        )
    else:
        local_attn = functools.partial(
            _single_device_attention, causal=causal, scale=scale
        )
    if n == 1:
        return local_attn(q, k, v)
    if h % n:
        raise ValueError(f"heads ({h}) must be divisible by axis size ({n})")

    def to_heads(x):  # (B, L/n, H, D) -> (B, L, H/n, D)
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def to_seq(x):  # (B, L, H/n, D) -> (B, L/n, H, D)
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    oh = local_attn(qh, kh, vh)
    return to_seq(oh)


def sharded_self_attention(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "ring",
    local_impl: Optional[str] = None,
    local_backward: str = "xla",
) -> jax.Array:
    """Array-level convenience wrapper: shard global ``(B, L, H, D)``
    arrays along ``L`` over ``mesh[axis_name]`` and run ring, zigzag-ring
    or Ulysses attention under ``shard_map`` (select with ``impl``).
    ``"ring_zigzag"`` (causal only) reorders the sequence into the
    zigzag layout on the way in and back on the way out, so callers keep
    ordinary position order end to end. ``local_impl="flash"`` (Ulysses
    only) runs the local attention through the Pallas kernel; off-TPU the
    wrapper builds the shard_map with ``check_vma=False`` (the interpret
    lowering rejects the checker around pallas bodies, DESIGN.md §3) —
    on TPU the checker stays on."""
    if impl == "ring_zigzag":
        if not causal:
            raise ValueError(
                "ring_zigzag is the causal load-balanced layout; use "
                "impl='ring' for non-causal attention (every block is "
                "live there, so zigzag has nothing to skip)"
            )
        n = int(mesh.shape[axis_name])
        fn = functools.partial(
            ring_attention_zigzag, axis_name=axis_name, scale=scale
        )
        q, k, v = (zigzag_shard(x, n) for x in (q, k, v))
    else:
        fns = {"ring": ring_attention, "ulysses": ulysses_attention}
        try:
            base = fns[impl]
        except KeyError:
            raise ValueError(
                f"impl must be one of {sorted(fns) + ['ring_zigzag']}, "
                f"got {impl!r}"
            )
        kw = dict(axis_name=axis_name, causal=causal, scale=scale)
        if impl == "ulysses":
            kw["local_impl"] = local_impl
            kw["local_backward"] = local_backward
        elif local_impl is not None:
            raise ValueError(
                f"local_impl applies to impl='ulysses' only, got "
                f"impl={impl!r}"
            )
        elif local_backward != "xla":
            raise ValueError(
                f"local_backward applies to impl='ulysses' only, got "
                f"impl={impl!r}"
            )
        fn = functools.partial(base, **kw)
    if local_impl is not None and impl == "ring_zigzag":
        raise ValueError("local_impl applies to impl='ulysses' only")
    # checker off ONLY for the interpret lowering of the flash kernel
    # (hlo_interpreter dynamic_slice rejects check_vma=True around pallas
    # bodies on the CPU mesh); on TPU the checker stays on
    from tpu_syncbn import compat

    check_vma = True
    if local_impl == "flash":
        from tpu_syncbn.ops._pallas_common import interpret

        check_vma = not interpret()
    seq_sharded = P(None, axis_name, None, None)
    shard_fn = compat.shard_map(
        fn,
        mesh=mesh,
        in_specs=(seq_sharded, seq_sharded, seq_sharded),
        out_specs=seq_sharded,
        check_vma=check_vma,
    )
    from tpu_syncbn.parallel.layout import SpecLayout

    seq_layout = SpecLayout.from_mesh(mesh, param_shard_axis=None)
    put = lambda x: jax.device_put(x, seq_layout.sharding(seq_sharded))
    out = shard_fn(put(q), put(k), put(v))
    if impl == "ring_zigzag":
        out = zigzag_unshard(out, n)
    return out

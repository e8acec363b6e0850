"""Pallas TPU kernel for the attention hot op (flash-style fused softmax).

The reference recipe has no attention (SURVEY §5.7), but this framework
ships sequence parallelism as first-class (``parallel.sequence``), and
the per-device inner loop of every SP scheme is plain causal attention —
the transformer path's hot op, and the natural second Pallas target
after the BN kernels (``ops/pallas_bn.py``).

:func:`flash_attention` computes exact softmax attention in one fused
kernel: the (L, L) score matrix is never materialized — each grid step
holds one (block_q, D) query tile and streams (block_k, D) KV tiles
through VMEM, carrying the online-softmax running (max, denominator,
accumulator) in f32 scratch — the same algorithm
``parallel.sequence._block_attend`` runs at the ring level, pushed down
to the tile level. Under a mask the grid itself is compressed: only the
(qi, ki) tile pairs that hold a visible score are enumerated (a 1-D
tile walk mapped through scalar-prefetched index arrays), so a tile
without one costs neither MXU work NOR VMEM streaming — the BlockSpec
pipeline never touches its DMA (~2x bandwidth cut under ``causal`` at
long L vs the rectangular grid); only the tiles the mask does not fill
(the diagonal's, and the last key tile of a padded length) build it,
from two 2-D iotas. The forward's tiles come from the call's shape
(:func:`forward_blocks`: 512 x 512 from 512 tokens up, where the length
allows) unless the caller names them: a grid step costs 0.3-0.4 us
whatever it holds, so the walk is made of few, large steps.

**The masks** are one thing, a :class:`Visibility` rule: ``FULL``,
``CAUSAL``, or ``block_diffusion(clean_len, block)`` over positions laid
out ``[clean ; noisy]`` (block-diffusion training, BD3-LM's vectorised
form: a clean position sees the clean past block-causally, a noisy one
the clean blocks strictly before its own and its own noisy block, both
ways; ``L^2 + block L`` live scores of the ``(2L)^2``). The rule says
which pairs count (``visible``, on numpy arrays or a kernel's iotas
alike); every kernel's walk is ONE enumeration of the live tile pairs
of the rule, computed with numpy from the call's shape
(:func:`_live_tiles`: at 512 x 512 tiles and 4,096 clean positions 80
tile pairs a head where a causal walk of the 8,192 visits 136, and a
mask on 24 of them). A window or a segment mask would be a fourth kind
with its ``visible``, its ``hides_in_tile`` and nothing else.

**The shape rule.** q is (B, L, H, D); k (B, L, H_kv, D) and v (B, L,
H_kv, Dv) share ``H_kv`` heads, which divides H (grouped-query
attention): q head h reads k/v head ``h // (H / H_kv)``, through the
block index maps (``b // group``); dK/dV runs one sweep a k/v head over
the query tiles of all its group's q heads into one accumulator, reading
q, dO and the statistics as (k/v head, group x length), which is how
they lie in memory. No copy of k, v, dk or dv a q head ever exists.

q and k share one head width and v (with the output) may have another
(latent attention: 192 for q and k, a rotary part beside the 128 that v
has); neither has to be a multiple of the 128 lanes, a tile holds the
whole width. Where the two are equal the kernel is the one it was before
v had a width: the same tiles, grid and name (PERF.md section 6, PR 34).

Backward is a ``jax.custom_vjp`` with two implementations, both
recomputing P from the saved logsumexp (O(L·block) live memory, never
(L, L)). ``backward="pallas"`` is two fused kernels in the
FlashAttention-2 structure — a dK/dV kernel sweeping query tiles per KV
tile and a dQ kernel sweeping KV tiles per query tile, f32 VMEM
accumulators, compressed walks that never visit a dead tile —
built as the forward is: tiles from the call's shape
(:func:`backward_blocks`, each kernel its own), operands in the inputs'
type with float32 sums, a mask only on the tiles the rule does not fill
or the padding crosses, v and dO of a width of their own, operations named
after their tiles (``flash_bwd_dkv_q512_k512``,
``flash_bwd_dq_q512_k512``). dK/dV is computed transposed (keys down the
rows), so that no tile is ever transposed and the log-sum-exp arrives as
a lane-dense row. ``backward="xla"``, the default of
:func:`flash_attention`, is one ``lax.scan`` over KV blocks: the path of
the callers that name it (``parallel.sequence``'s Ulysses local
attention, ``models.transformer``), which no benchmark cell runs; it
takes equal heads under no mask or the causal one, and refuses grouped
heads and the block-diffusion mask by name.

**What a caller's checkpoint may keep.** The VJP's residuals are q, k,
v, the output ``o`` and the log-sum-exp ``lse``. A caller's
``jax.checkpoint`` recomputes q, k and v anyway (they are its own
projections); to get ``o`` and ``lse`` back it has to run the whole
forward kernel again, unless it keeps them. The forward rule names the
two (:data:`FLASH_OUT`, :data:`FLASH_LSE`, with
``jax.ad_checkpoint.checkpoint_name``), and a caller whose policy is
``save_only_these_names`` lists them (``models.moe_lm``,
``models.block_diffusion_lm``); to any other caller a name is the
identity. What each weighs a call: ``o`` is (B x H, L, Dv)
in the inputs' type, 67 MB at 32 heads of 128 and 8,192 positions in
bf16; ``lse`` is the (B x H, L) float32 row, 1 MB there. Such a caller
says so (``kept``), and the pair then passes one
``lax.optimization_barrier`` before it is named, so that the row exists
as such: the kernel writes the log-sum-exp as (B x H, T, 1), which the
TPU's layout pads to 128 lanes (134 MB a call there), the dQ kernel
reads that column as it is, and left alone the compiler keeps the
column for it. Compiled for a described v5e (PR 39;
``tests/test_tpu_compile.py`` keeps the guard): the latent-attention
decoder cut to three layer applications at 4,096 tokens holds 118 MB
more with the two kept behind the barrier (the closed form is 102) and
316 MB more without it; a barrier around the log-sum-exp alone is no
help (316). The barrier is asked for and not everyone's because it
costs a caller that keeps neither: the dQ kernel's column is then made
from the row by a copy where there was a move of the padded column,
1.62 ms of the looped decoder's 568.77 ms step on the chip (32 calls;
PERF.md section 6, PR 39), and a name alone lowers to nothing, so that
decoder's compiled step is what it was.

Like the BN kernels, everything runs under ``interpret=True`` off-TPU
(the CPU suite exercises the real kernel code path), and the kernel is
an *opt-in* backend (``attn_impl="flash"`` of ``models.transformer``,
``models.looped_lm``, ``models.moe_lm`` and ``models.block_diffusion_lm``). The hardware measurement
(TPU v5e, PERF.md section 6, PR 30, PR 31 and PR 35): inside the looped
decoder at 16 heads of 128, 2 x 2,048 tokens, causal, forward +
recomputed forward + backward of 32 layer applications a step, XLA's
attention takes 332 ms and this kernel 92.5 with the scan as its
backward (the forward's calls 0.38 / 0.34 ms each at 512 x 512 tiles,
49% of the kernel's roofline; the scan 68 of the 92.5). The backward
kernels lost to the scan at 128 x 128 tiles and float32 operands (130.9
ms a step against 67.9, PR 30) for the reason the forward had been slow:
a grid step costs 0.4-0.5 us whatever it holds. At 512 x 512 and in the
inputs' type the two take 1.00 ms a call there against the scan's 2.11,
and 19.9 ms at 32 heads of 192 / 128 and 8,192 tokens against the
scan's 73 (the scan computes the full square and streams q, dO and the
dq accumulator once a key block; the kernels run at the matrix unit's
rate for widths padded to 128: 7.5 and 6.5 ps a score). The products
take their operands in the inputs' type and accumulate in float32; with
bf16 inputs that is what the MXU makes of float32 operands at default
precision anyway (on float32 inputs the TPU multiplies in bf16 passes
too: 1.8e-3 off a float32 reference): dq, dk, dv are as far from a
float32 reference as the scan's (3.6e-3 to 3.8e-3 in relative L2, bf16
results; the scan 3.5e-3 to 4.0e-3). So where a model runs on the chip
its configuration names ``"flash"``, and the models' ``"flash"`` is
forward and backward in these kernels.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_syncbn.ops._pallas_common import NEG_BIG as _NEG_BIG
from tpu_syncbn.ops._pallas_common import interpret as _interpret
from tpu_syncbn.ops._pallas_common import sds as _sds

_LANES = 128
# The names of the two residuals of a call that a caller's
# ``jax.checkpoint`` may keep (``save_only_these_names``): the forward
# kernel's output (B x H, L, Dv) in the inputs' type and its log-sum-exp
# as a lane-dense (B x H, L) float32 row (module docstring).
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
# the forward's tiles: the widest the sweep on the chip found worth
# having (benchmarks/flash_tile_sweep.py, PERF.md section 6, PR 31: at
# 2,048 tokens of 128-wide heads 512 x 512 takes 0.35 ms a call, 512 x
# 1024 and 1024 x 1024 0.41, 128 x 128 2.3), and the share of the 16 MiB
# of VMEM a kernel may scope that one grid step's buffers may take (the
# rest is the compiler's, for what it spills)
_FWD_MAX_BLOCK_Q = 512
_FWD_MAX_BLOCK_K = 512
_VMEM_SCOPED_BYTES = 16 * 2**20
_FWD_VMEM_BUDGET = _VMEM_SCOPED_BYTES // 2


def forward_vmem_bytes(block_q: int, block_k: int, d: int,
                       itemsize: int, dv: int | None = None) -> int:
    """VMEM one grid step of the forward kernel needs: the q, k (``d``
    wide), v and output (``dv`` wide, ``d`` where it is not given) tiles
    (double-buffered by the pipeline) and the log-sum-exp column
    (lane-padded), the scaled q, the float32 scores and probabilities
    and the probabilities in the compute type, the accumulator and the
    two lane-dense statistics."""
    lanes_d = -(-d // _LANES) * _LANES
    lanes_dv = lanes_d if dv is None else -(-dv // _LANES) * _LANES
    streamed = 2 * ((block_q + block_k) * (lanes_d + lanes_dv) * itemsize
                    + block_q * _LANES * 4)
    scores = block_q * block_k * (4 + 4 + itemsize)
    carried = block_q * (lanes_dv * 4 + lanes_d * itemsize + 2 * _LANES * 4)
    return streamed + scores + carried


def _dividing_blocks(padded: int, cap: int) -> list[int]:
    """The multiples of 128 up to ``cap`` that divide ``padded`` (itself
    a multiple of 128), largest first."""
    return [b for b in range(min(cap, padded), 0, -_LANES)
            if padded % b == 0]


def forward_blocks(length: int, d: int, itemsize: int,
                   dv: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) of the forward kernel for a call that names
    none, from the call's shape alone (``d`` the width of q and k,
    ``dv`` of v and the output where it differs). The length is padded to a
    multiple of 128 and no further; each block is the largest multiple
    of 128 that divides the padded length, stays under the widest tile
    the sweep found worth having, and with the other keeps
    ``forward_vmem_bytes`` under the budget (the key block gives way
    first: the query block is what amortises a key tile's DMA)."""
    padded = -(-length // _LANES) * _LANES
    for block_q in _dividing_blocks(padded, _FWD_MAX_BLOCK_Q):
        for block_k in _dividing_blocks(padded, _FWD_MAX_BLOCK_K):
            if forward_vmem_bytes(block_q, block_k, d, itemsize,
                                  dv) <= _FWD_VMEM_BUDGET:
                return block_q, block_k
    return _LANES, _LANES


# -- forward kernel -------------------------------------------------------


def _across(x, n: int):
    """A (rows, 128) statistic, its value replicated across the lanes,
    as (rows, n): whole vector registers repeated where n is a multiple
    of 128, no lane broadcast."""
    if n % _LANES == 0:
        return x if n == _LANES else jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


class Visibility(NamedTuple):
    """Which (query, key) pairs of a call count: the rule every kernel
    here walks and masks by. Hashable and static: the tile walks are
    enumerated from it with numpy when the call is traced, and inside a
    kernel it is asked only on the tiles it does not fill.

    * ``FULL``: every pair.
    * ``CAUSAL``: key r counts for query p iff ``r <= p``.
    * ``block_diffusion(clean_len, block)``: queries and keys are laid
      out ``[clean ; noisy]``, two copies of one sequence of
      ``clean_len`` positions in blocks of ``block`` (the vectorised
      training form of block diffusion, BD3-LM, arXiv:2503.09573). With
      ``blk(p) = (p mod clean_len) // block``: a clean query sees the
      clean keys of its own and earlier blocks; a noisy query sees the
      clean keys of strictly earlier blocks and the noisy keys of its
      own block, both ways; nothing clean sees anything noisy. Every
      query sees itself, so no row of the softmax is empty.
      ``clean_len^2 + block * clean_len`` live scores of the
      ``(2 clean_len)^2``.
    """

    kind: str = "full"
    clean_len: int = 0
    block: int = 0

    def _block_of(self, x):
        """``blk(x)`` of positions ``x >= 0``: ints, numpy or jax."""
        within = x - self.clean_len * (x >= self.clean_len)
        shift = self.block.bit_length() - 1
        return (within >> shift if self.block == 1 << shift
                else within // self.block)

    def visible(self, rows, cols):
        """The boolean map of the pairs that count, from integer
        positions that broadcast against each other: numpy arrays (the
        host's enumeration) or a kernel's iotas. Not asked of ``FULL``."""
        if self.kind == "causal":
            return rows >= cols
        noisy_q, noisy_k = rows >= self.clean_len, cols >= self.clean_len
        blk_q, blk_k = self._block_of(rows), self._block_of(cols)
        return ((~noisy_k & ((~noisy_q & (blk_k <= blk_q))
                             | (noisy_q & (blk_k < blk_q))))
                | (noisy_q & noisy_k & (blk_k == blk_q)))

    def hides_in_tile(self, qi, ki, block_q: int, block_k: int):
        """Whether tile (qi, ki) can hold a pair that does not count
        (``qi`` / ``ki`` traced or plain); None where no tile can. May
        say yes of a tile the rule fills, which then builds a mask that
        hides nothing; never no of one it does not."""
        if self.kind == "full":
            return None
        if self.kind == "causal":
            return ki * block_k + block_k - 1 > qi * block_q
        # filled: clean keys only, all of them in blocks before (for
        # clean queries: up to) the block of the tile's first query
        first_q, last_k = qi * block_q, ki * block_k + block_k - 1
        last_q = first_q + block_q - 1
        blk_q, blk_k = self._block_of(first_q), self._block_of(last_k)
        filled = (last_k < self.clean_len) & (
            ((last_q < self.clean_len) & (blk_k <= blk_q))
            | ((first_q >= self.clean_len) & (blk_k < blk_q)))
        return jnp.logical_not(filled)

    def live_tile(self, qi, ki, block_q: int, block_k: int):
        """On a rectangular grid, whether tile (qi, ki) holds any pair
        that counts; None: visit every tile (what a tile without one
        adds is masked to nothing)."""
        if self.kind == "causal":
            return ki * block_k <= qi * block_q + block_q - 1
        return None


FULL = Visibility()
CAUSAL = Visibility("causal")


def block_diffusion(clean_len: int, block: int) -> Visibility:
    if clean_len < 1 or block < 1:
        raise ValueError(f"clean_len and block must be positive, got "
                         f"{clean_len} and {block}")
    return Visibility("block_diffusion", int(clean_len), int(block))


def _holds_masked_scores(qi, ki, *, rule, block_q, block_k, n_k, pad_k):
    """Whether tile (qi, ki) can hold a score that must not count: only
    a tile the rule does not fill (the diagonal's, under ``CAUSAL``), or
    the last key tile of a padded length. Every other live tile skips
    the iotas, the compares and the select. ``qi`` / ``ki`` traced or
    plain; None where the shape alone says that no tile can (full
    attention, no padding)."""
    edge = rule.hides_in_tile(qi, ki, block_q, block_k)
    if pad_k:
        last = ki == n_k - 1
        edge = last if edge is None else edge | last
    return edge


def _init_carry(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
    l_ref[...] = jnp.zeros_like(l_ref)


def _write_out(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / _across(l, acc_ref.shape[1])).astype(
        o_ref.dtype)
    # lse rides a (BH, T, 1) array: a 2-D (BH, T) output would put
    # the BH axis in the block's last-two-dims window, where the TPU
    # lowering rejects a block size of 1 (must divide 8 / equal the
    # array dim; tests/test_tpu_lowering.py holds the rule)
    lse_ref[0] = (m_ref[...] + jnp.log(l))[:, :1]


def _attend_tile(q_ref, k_ref, v_ref, o_ref, lse_ref,
                 acc_ref, m_ref, l_ref, qs_ref, qi, ki, first_ki, last_ki, *,
                 scale, rule, block_q, block_k, n_k, l_real):
    """One (qi, ki) online-softmax step; ``qi``/``ki`` may be traced
    scalars (a compressed walk) or program ids (rectangular grid).
    The ki sweep for a fixed (bh, qi) is contiguous in the grid walk,
    from ``first_ki`` to ``last_ki``, so
    the VMEM scratch carries the running (max, denom, acc) across it,
    the two statistics lane-dense: (block_q, 128), the value of a row in
    every lane. Each product's operands reach the MXU in the inputs'
    type, rounded once: k and v as stored, ``q * scale`` once a query
    tile into ``qs_ref``, the float32 probabilities where they enter the
    second product (the sum that normalises them takes them unrounded).
    For bf16 inputs this is what the MXU did to float32 operands
    already (PERF.md section 6, PR 30 and PR 31); float32 inputs stay
    float32. A row whose scores so far were all hidden carries the
    stand-in maximum and a sum of ones; its first visible score rescales
    both to exactly zero, and every row has one (itself)."""

    @pl.when(ki == first_ki)
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)
        qs_ref[...] = (q_ref[0].astype(jnp.float32) * scale).astype(
            qs_ref.dtype)

    pad_k = n_k * block_k - l_real  # a fact of the shape, not traced

    def step(masked: bool):
        s = lax.dot_general(
            qs_ref[...], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        if masked:
            s = _mask_scores(s, qi, ki, keys_axis=1, rule=rule,
                             block_q=block_q, block_k=block_k,
                             l_real=l_real, pad_k=pad_k)

        m_prev = m_ref[...]  # (block_q, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _across(m_new, block_k))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (
            acc_ref[...] * _across(corr, acc_ref.shape[1])
            + lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        m_ref[...] = m_new

    edge = _holds_masked_scores(qi, ki, rule=rule, block_q=block_q,
                                block_k=block_k, n_k=n_k, pad_k=pad_k)
    if edge is None:
        step(masked=False)
    else:
        pl.when(edge)(lambda: step(masked=True))
        pl.when(jnp.logical_not(edge))(lambda: step(masked=False))

    @pl.when(ki == last_ki)
    def _finalize():
        _write_out(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _attn_kernel_rect(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, qs_ref, *,
                      scale, rule, block_q, block_k, n_k, l_real):
    """Full rectangular grid (BH, n_q, n_k), ki innermost. Full
    attention always; also the fallback when a compressed walk's index
    arrays would be too large for scalar memory: there, under
    ``CAUSAL``, above-diagonal tiles still stream through VMEM but skip
    their matmuls, and under any other rule every tile is computed and
    masked."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    attend = functools.partial(
        _attend_tile, q_ref, k_ref, v_ref, o_ref, lse_ref,
        acc_ref, m_ref, l_ref, qs_ref, qi, ki, 0, n_k - 1,
        scale=scale, rule=rule, block_q=block_q, block_k=block_k,
        n_k=n_k, l_real=l_real)
    live = rule.live_tile(qi, ki, block_q, block_k)
    if live is None:
        attend()
        return

    @pl.when(ki == 0)
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)

    # a KV tile strictly right of this query tile's last row touches
    # nothing — skip its matmuls (its DMA still streams in this path)
    pl.when(live)(attend)

    @pl.when(ki == n_k - 1)
    def _finalize():
        # _attend_tile's own finalize only fires when the last tile is
        # live, which for a causal row it always is (diagonal end) — but
        # keep the rect path self-sufficient if block ratios change
        _write_out(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _attn_kernel_walk(qids_ref, kids_ref, q_ref, k_ref, v_ref,
                      o_ref, lse_ref, acc_ref, m_ref, l_ref, qs_ref, *,
                      scale, rule, n_tiles, block_q, block_k, n_k, l_real):
    """A compressed 1-D tile walk (BH, T) over ONLY the live (qi, ki)
    pairs of the rule, decoded from the scalar-prefetched index arrays:
    a tile without a visible score is never visited, so its KV DMA never
    happens. Under ``CAUSAL`` a query tile's sweep runs from key tile 0
    to where the diagonal exits its rows (clamped to the KV extent);
    under any other rule its ends are read off the walk itself."""
    t = pl.program_id(1)
    qi = qids_ref[t]
    ki = kids_ref[t]
    if rule.kind == "causal":
        first_ki = 0
        last_ki = jnp.minimum(
            n_k - 1, (qi * block_q + block_q - 1) // block_k
        )
    else:
        is_start, is_end = _walk_group_bounds(qids_ref, t, n_tiles)
        first_ki = jnp.where(is_start, ki, -1)
        last_ki = jnp.where(is_end, ki, -1)
    _attend_tile(q_ref, k_ref, v_ref, o_ref, lse_ref,
                 acc_ref, m_ref, l_ref, qs_ref, qi, ki, first_ki, last_ki,
                 scale=scale, rule=rule, block_q=block_q,
                 block_k=block_k, n_k=n_k, l_real=l_real)


# compressed-walk ceiling: the (qids, kids) int32 pairs live in scalar
# memory (SMEM), which is scarce — past this many tiles fall back to the
# rectangular grid (matmul-skip only). 16384 tiles = 128 KiB of indices
# ~ n_q 180 at equal 128-blocks ~ local L 23k; the SP layer shards
# longer sequences across devices before they reach one kernel.
_MAX_CAUSAL_TILES = 16384


@functools.lru_cache(maxsize=64)
def _live_tiles(rule: Visibility, n_q: int, n_k: int, block_q: int,
                block_k: int, by_key: bool = False, group: int = 1):
    """The walk of every kernel here: the tile pairs that hold a pair
    the rule lets count, enumerated once with numpy from the shape (over
    the padded lengths: a padded row sees what the rule says of its
    position).

    By query tile (the forward's and dQ's): ``(qids, kids)``, qi
    ascending and ki ascending within qi, the scratch-carry contract.
    Under ``CAUSAL`` ~n_q(n_q+1)/2 of the rectangular n_q*n_k when
    blocks match; under ``block_diffusion`` at 512 x 512 and 4,096
    clean positions 80 of 256 (a causal walk of the 8,192: 136).

    ``by_key`` (dK/dV's): ``(kis, qis)`` grouped by ki ascending, the
    scratch carries one KV tile's (dk, dv) across its contiguous sweep.
    With ``group`` q heads a k/v head the sweep runs over the live query
    tiles of each of them in turn, and ``qis`` holds ``g * n_q + qi``:
    the query tile of q laid out (k/v head, group * length)."""
    import numpy as np

    live = np.zeros((n_q, n_k), bool)
    cols = np.arange(n_k * block_k)[None, :]
    for qi in range(n_q):
        rows = qi * block_q + np.arange(block_q)[:, None]
        live[qi] = rule.visible(rows, cols).reshape(
            block_q, n_k, block_k).any(axis=(0, 2))
    if not by_key:
        qids, kids = np.nonzero(live)
        return qids.astype(np.int32), kids.astype(np.int32)
    kis, qis = [], []
    for ki in range(n_k):
        reach = np.nonzero(live[:, ki])[0]
        for g in range(group):
            kis.append(np.full(len(reach), ki))
            qis.append(g * n_q + reach)
    return (np.concatenate(kis).astype(np.int32),
            np.concatenate(qis).astype(np.int32))


def _kv_head(group: int):
    """The k/v batch-head that q's batch-head ``b`` reads: heads are
    laid out (batch, head) and q head h reads k/v head ``h // group``,
    so ``b // group``; ``b`` itself where every q head has its own."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _flash_fwd_2d(q, k, v, *, rule, scale, block_q, block_k):
    """q (BH, L, D), k (BH / group, L, D) and v (BH / group, L, Dv) in →
    ((BH, L, Dv) out, (BH, L) logsumexp). A block the caller did not
    name (None) comes from the shape. Where Dv = D, group = 1 and the
    rule is ``CAUSAL`` or ``FULL`` this is the kernel it was before v had
    a width of its own, k and v heads of their own or the walk a rule:
    the same tiles, blocks, walk and name."""
    bh, l_real, d = q.shape
    dv = v.shape[-1]
    kv = _kv_head(bh // k.shape[0])
    if block_q is None or block_k is None:
        chosen = forward_blocks(l_real, d, q.dtype.itemsize, dv)
        block_q = chosen[0] if block_q is None else block_q
        block_k = chosen[1] if block_k is None else block_k
    n_q = pl.cdiv(l_real, block_q)
    n_k = pl.cdiv(l_real, block_k)
    pad_q = n_q * block_q - l_real
    pad_k = n_k * block_k - l_real
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v

    vmem = pltpu.VMEM
    out_shape = [
        _sds((bh, n_q * block_q, dv), q.dtype, qp),
        # trailing singleton keeps BH out of the block's last-two-dims
        # window (TPU tiling rule); squeezed before returning
        _sds((bh, n_q * block_q, 1), jnp.float32, qp),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, dv), jnp.float32),       # acc
        pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
        pltpu.VMEM((block_q, _LANES), jnp.float32),   # running denom
        pltpu.VMEM((block_q, d), q.dtype),            # q * scale
    ]
    # the operation's name in a trace says which tiles ran
    name = f"flash_fwd_q{block_q}_k{block_k}"
    if rule != FULL:
        # one source of truth for the live-tile set: the gate below must
        # agree exactly with the SMEM index-array size it protects
        qids, kids = _live_tiles(rule, int(n_q), int(n_k), block_q, block_k)
    if rule != FULL and len(qids) <= _MAX_CAUSAL_TILES:
        kernel = functools.partial(
            _attn_kernel_walk, scale=scale, rule=rule, n_tiles=len(qids),
            block_q=block_q, block_k=block_k, n_k=n_k, l_real=l_real,
        )
        # index maps see (b, t, qids_ref, kids_ref): the tile walk is
        # decoded through the prefetched arrays, so the pipeline only
        # ever streams live KV tiles
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, len(qids)),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, t, qids, kids: (b, qids[t], 0),
                             memory_space=vmem),
                pl.BlockSpec((1, block_k, d),
                             lambda b, t, qids, kids: (kv(b), kids[t], 0),
                             memory_space=vmem),
                pl.BlockSpec((1, block_k, dv),
                             lambda b, t, qids, kids: (kv(b), kids[t], 0),
                             memory_space=vmem),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dv),
                             lambda b, t, qids, kids: (b, qids[t], 0),
                             memory_space=vmem),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, t, qids, kids: (b, qids[t], 0),
                             memory_space=vmem),
            ],
            scratch_shapes=scratch_shapes,
        )
        o, lse = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=_interpret(), name=name,
        )(jnp.asarray(qids), jnp.asarray(kids), qp, kp, vp)
        return o[:, :l_real], lse[:, :l_real, 0]

    kernel = functools.partial(
        _attn_kernel_rect, scale=scale, rule=rule,
        block_q=block_q, block_k=block_k, n_k=n_k, l_real=l_real,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=vmem),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0),
                         memory_space=vmem),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (kv(b), j, 0),
                         memory_space=vmem),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0),
                         memory_space=vmem),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=vmem),
        ],
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=_interpret(), name=name,
    )(qp, kp, vp)
    return o[:, :l_real], lse[:, :l_real, 0]


# -- backward (XLA, blockwise scan — O(L·block_k) live memory) ------------


def backward_scan_block(length: int) -> int:
    """The key block of the XLA backward scan for a call that names
    none: a sixteenth of the length, within 128 and 512. Every step of
    the scan streams all the queries once (q, dO and the dq accumulator,
    in float32), so it is the NUMBER of key blocks that the scan's
    traffic grows with: 16 blocks of 128 is what the 2,048-token call
    was measured with (PERF.md section 6, PR 30) and stays; at 8,192
    tokens (32 heads, q and k 192 wide, v 128; PR 34) 64 blocks of 128
    take 111.7 ms a call, 32 of 256 86.9, 16 of 512 76.0 at 0.74 GB more
    scratch, 8 of 1,024 69.8 at 1.55 GB more."""
    return max(128, min(512, length // 16 // _LANES * _LANES))


def _flash_bwd_2d(res, do, *, rule, scale, block_k):
    q, k, v, o, lse = res  # (BH, L, D) x2, (BH, L, Dv) x2, (BH, L)
    if k.shape[0] != q.shape[0] or rule not in (FULL, CAUSAL):
        raise ValueError(
            "the XLA backward scan takes as many k/v heads as q heads and "
            "no mask or the causal one: name backward='pallas' for grouped "
            f"heads or another rule (got {q.shape[0]} over {k.shape[0]} "
            f"heads under {rule})")
    bh, l_real, d = q.shape
    dv = v.shape[-1]
    n_k = -(-l_real // block_k)
    pad = n_k * block_k - l_real
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    kb = k.reshape(bh, n_k, block_k, d)
    vb = v.reshape(bh, n_k, block_k, dv)

    qf = q.astype(jnp.float32) * scale
    dof = do.astype(jnp.float32)
    # D_i = rowsum(dO ∘ O): the softmax-jacobian diagonal correction
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # (BH, L)
    rows = jnp.arange(l_real)

    def kv_block(carry, blk):
        dq_acc = carry
        k_blk, v_blk, ki = blk  # (BH, block_k, D) ×2, scalar
        cols = ki * block_k + jnp.arange(block_k)
        s = jnp.einsum("bqd,bkd->bqk", qf, k_blk.astype(jnp.float32))
        mask = cols[None, :] < l_real
        if rule == CAUSAL:
            mask = mask & (rows[:, None] >= cols[None, :])
        s = jnp.where(mask[None], s, _NEG_BIG)
        p = jnp.exp(s - lse[..., None])  # (BH, L, block_k)
        dv_blk = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, v_blk.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq_acc = dq_acc + jnp.einsum(
            "bqk,bkd->bqd", ds, k_blk.astype(jnp.float32)
        )
        dk_blk = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq_acc, (dk_blk, dv_blk)

    # derive the carry init from a varying operand (qf * 0), not a fresh
    # constant: under check_vma=True a scan carry must keep the same
    # varying type as the body output or lowering fails
    dq0 = qf * 0.0
    dq, (dk_blocks, dv_blocks) = lax.scan(
        kv_block, dq0,
        (kb.transpose(1, 0, 2, 3), vb.transpose(1, 0, 2, 3),
         jnp.arange(n_k)),
    )
    dk = dk_blocks.transpose(1, 0, 2, 3).reshape(bh, n_k * block_k, d)
    dv_ = dv_blocks.transpose(1, 0, 2, 3).reshape(bh, n_k * block_k, dv)
    return (
        (dq * scale).astype(q.dtype),
        dk[:, :l_real].astype(q.dtype),
        dv_[:, :l_real].astype(q.dtype),
    )


# -- backward (Pallas, two fused kernels — FlashAttention-2 structure) ----

# the two backward kernels' tiles: the widest the sweep on the chip found
# worth having (benchmarks/flash_tile_sweep.py --backward, PERF.md
# section 6, PR 35; the numbers are in ``backward_blocks``), and what one
# grid step's buffers may take of the VMEM the kernels scope for
# themselves (four score-sized float32 temporaries where the forward has
# two: at 512 x 512 they alone are 4 MiB)
_BWD_MAX_BLOCK = 512
_BWD_VMEM_SCOPED_BYTES = 32 * 2**20
_BWD_VMEM_BUDGET = _BWD_VMEM_SCOPED_BYTES // 2
_BWD_KERNELS = ("dkv", "dq")


def backward_vmem_bytes(kernel: str, block_q: int, block_k: int, d: int,
                        itemsize: int, dv: int | None = None) -> int:
    """VMEM one grid step of a backward kernel (``"dkv"`` or ``"dq"``)
    needs: the q, k (``d`` wide), v and dO (``dv`` wide, ``d`` where it
    is not given) tiles, double-buffered by the pipeline, with the
    log-sum-exp and delta of the query tile (a lane-padded column each
    for dQ, a sublane-padded row each for dK/dV); the float32 scores,
    probabilities, dp and ds and the two of them that meet the matrix
    unit again in the compute type; and what the kernel carries: for
    dK/dV the two float32 accumulators of ``block_k`` rows, the scaled k
    and the two output tiles, for dQ the accumulator of ``block_q`` rows,
    the scaled q and the output tile."""
    if kernel not in _BWD_KERNELS:
        raise ValueError(f"kernel must be one of {_BWD_KERNELS}, got "
                         f"{kernel!r}")
    lanes_d = -(-d // _LANES) * _LANES
    lanes_dv = lanes_d if dv is None else -(-dv // _LANES) * _LANES
    statistics = 2 * block_q * (8 if kernel == "dkv" else _LANES) * 4
    streamed = 2 * ((block_q + block_k) * (lanes_d + lanes_dv) * itemsize
                    + statistics)
    scores = block_q * block_k * (4 * 4 + 2 * itemsize)
    if kernel == "dkv":
        carried = block_k * ((lanes_d + lanes_dv) * (4 + 2 * itemsize)
                             + lanes_d * itemsize)
    else:
        carried = block_q * lanes_d * (4 + itemsize + 2 * itemsize)
    return streamed + scores + carried


def backward_blocks(length: int, d: int, itemsize: int,
                    dv: int | None = None) -> dict:
    """``{"dkv": (block_q, block_k), "dq": (block_q, block_k)}``: the
    tiles of the two backward kernels for a call that names none, from
    the call's shape alone, as ``forward_blocks`` chooses the forward's:
    each block the largest multiple of 128 that divides the length
    padded to 128, stays under the widest tile the sweep found worth
    having and with the other keeps ``backward_vmem_bytes`` under the
    budget. The block a kernel accumulates over gives way first (the
    query block of dK/dV, the key block of dQ): the other is what
    amortises the tile that stays.

    The sweep on the chip (TPU v5e, bf16, causal, the kernels alone under
    one profiler capture, ``benchmarks/flash_tile_sweep.py --backward``,
    PERF.md section 6, PR 35), ms a call, dK/dV + dQ, by ``block_q`` x
    ``block_k``:

    ===========  ==============================  =========================
    tiles        32 heads of 192 / 128, 8,192    32 batch-heads of 128,
                 tokens (the scan: 73 a call     2,048 tokens (the scan:
                 in the step)                    2.11)
    ===========  ==============================  =========================
    128 x 128    41.61 + 40.92                   2.248 + 2.086
    256 x 256    14.35 + 13.91                   0.801 + 0.762
    512 x 256    11.89 + 11.22                   0.668 + 0.589
    256 x 512    11.90 + 11.06                   0.667 + 0.542
    512 x 512    10.47 + 9.41 = **19.87**        0.563 + 0.434 = **0.997**
    512 x 1024   10.33 + 9.13                    0.609 + 0.464
    1024 x 512   10.33 + 9.14                    0.612 + 0.463
    1024 x 1024  9.96 + 8.73 = 18.69             0.583 + 0.436 = 1.019
    ===========  ==============================  =========================

    A grid step costs 0.42 us in either kernel and a score 5.0 ps in
    dK/dV (four products) and 3.9 ps in dQ (three) at 128-wide heads;
    at 192 / 128 a score costs 7.5 and 6.5 ps, the matrix unit's rate
    for widths padded to 128 lanes (768 and 640 multiply-adds). Past
    512 x 512 a call gains 6% at 8,192 tokens and loses 2% at 2,048, for
    two to four times the VMEM: the cap stays where the forward's is."""
    candidates = _dividing_blocks(-(-length // _LANES) * _LANES,
                                  _BWD_MAX_BLOCK)

    def choose(kernel: str) -> tuple[int, int]:
        for kept in candidates:
            for swept in candidates:
                blocks = (swept, kept) if kernel == "dkv" else (kept, swept)
                if backward_vmem_bytes(kernel, *blocks, d, itemsize,
                                       dv) <= _BWD_VMEM_BUDGET:
                    return blocks
        return _LANES, _LANES

    return {kernel: choose(kernel) for kernel in _BWD_KERNELS}


_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _mask_scores(s, qi, ki, *, keys_axis, rule, block_q, block_k,
                 l_real, pad_k):
    """``s`` with the scores that must not count at ``_NEG_BIG`` (their
    exp against any log-sum-exp is exactly zero): the keys of a padded
    length's last tile and what the rule hides (under ``CAUSAL``, what
    lies above the diagonal), from two iotas. The keys run along
    ``keys_axis`` of the tile."""
    cols = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, keys_axis)
    mask = cols < l_real if pad_k else None
    if rule != FULL:
        rows = qi * block_q + lax.broadcasted_iota(
            jnp.int32, s.shape, 1 - keys_axis)
        visible = rule.visible(rows, cols)
        mask = visible if mask is None else mask & visible
    return jnp.where(mask, s, _NEG_BIG)


def _on_tile(step, qi, ki, live, *, rule, block_q, block_k, n_k, l_real):
    """``step(masked)`` once, where ``live`` (None: always): masked where
    tile (qi, ki) can hold a score that must not count, plain everywhere
    else (``_holds_masked_scores``). Padded QUERY rows need no mask:
    their q, dO, log-sum-exp and delta are zero-padded, so their p is
    at most exp(0 - 0) = 1 against a dO and a ``dp - delta`` of exactly
    zero."""
    edge = _holds_masked_scores(
        qi, ki, rule=rule, block_q=block_q, block_k=block_k, n_k=n_k,
        pad_k=n_k * block_k - l_real)

    def once():
        if edge is None:
            step(False)
        else:
            pl.when(edge)(lambda: step(True))
            pl.when(jnp.logical_not(edge))(lambda: step(False))

    if live is None:
        once()
    else:
        pl.when(live)(once)


def _dkv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              dk_ref, dv_ref, dk_acc, dv_acc, ks_ref,
              qi, ki, first, last, live, *,
              scale, rule, block_q, block_k, n_k, l_real):
    """One (ki, qi) step of dK/dV. The scratch carries one key tile's
    (dk, dv) in float32 across its sweep over query tiles (``first`` /
    ``last`` of the sweep; ``live`` None, or whether this tile holds any
    visible score). Everything is computed TRANSPOSED, keys down the
    rows and queries along the lanes: ``s^T = (k * scale) q^T`` and
    ``dp^T = v dO^T`` contract the head width of both operands, the
    log-sum-exp and delta of the query tile arrive as lane-dense rows,
    and ``dv += p^T dO``, ``dk += ds^T q`` are plain products: no tile
    is ever transposed. Operands reach the matrix unit in the inputs'
    type (``k * scale`` rounded once a key tile, p and ds where they
    enter their product), sums in float32; dk takes ``scale`` once, at
    the end."""

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        ks_ref[...] = (k_ref[0].astype(jnp.float32) * scale).astype(
            ks_ref.dtype)

    def step(masked: bool):
        q, do = q_ref[0], do_ref[0]
        st = lax.dot_general(ks_ref[...], q, _NT,
                             preferred_element_type=jnp.float32)
        if masked:
            st = _mask_scores(st, qi, ki, keys_axis=0, rule=rule,
                              block_q=block_q, block_k=block_k,
                              l_real=l_real, pad_k=n_k * block_k - l_real)
        pt = jnp.exp(st - lse_ref[0])  # (block_k, block_q) - (1, block_q)
        dpt = lax.dot_general(v_ref[0], do, _NT,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0])
        dv_acc[...] += lax.dot_general(pt.astype(do.dtype), do, _NN,
                                       preferred_element_type=jnp.float32)
        dk_acc[...] += lax.dot_general(dst.astype(q.dtype), q, _NN,
                                       preferred_element_type=jnp.float32)

    _on_tile(step, qi, ki, live, rule=rule, block_q=block_q,
             block_k=block_k, n_k=n_k, l_real=l_real)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
             dq_ref, dq_acc, qs_ref, qi, ki, first, last, live, *,
             scale, rule, block_q, block_k, n_k, l_real):
    """One (qi, ki) step of dQ: the scratch carries one query tile's dq
    in float32 across its sweep over key tiles. ``s = (q * scale) k^T``
    as the forward computes it (``q * scale`` rounded once a query tile),
    ``dp = dO v^T``, ``dq += ds k``; the log-sum-exp and delta are
    columns; dq takes ``scale`` once, at the end."""

    @pl.when(first)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        qs_ref[...] = (q_ref[0].astype(jnp.float32) * scale).astype(
            qs_ref.dtype)

    def step(masked: bool):
        k = k_ref[0]
        s = lax.dot_general(qs_ref[...], k, _NT,
                            preferred_element_type=jnp.float32)
        if masked:
            s = _mask_scores(s, qi, ki, keys_axis=1, rule=rule,
                             block_q=block_q, block_k=block_k,
                             l_real=l_real, pad_k=n_k * block_k - l_real)
        p = jnp.exp(s - lse_ref[0])  # (block_q, block_k) - (block_q, 1)
        dp = lax.dot_general(do_ref[0], v_ref[0], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dq_acc[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                       preferred_element_type=jnp.float32)

    _on_tile(step, qi, ki, live, rule=rule, block_q=block_q,
             block_k=block_k, n_k=n_k, l_real=l_real)

    @pl.when(last)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_kernel_rect(tile, swept_axis, n_swept, fold, *refs, rule, block_q,
                     block_k, **static):
    """Either backward kernel on the full rectangular grid, the swept
    tile innermost: (BH / group, n_k, group * n_q) for dK/dV
    (``swept_axis`` 0: the query tile is program id 2, ``fold`` = n_q
    where the sweep runs over a group's q heads in turn, else None),
    (BH, n_q, n_k) for dQ. Full attention always; the fallback when a
    compressed walk's index arrays would be too large for scalar memory,
    where under ``CAUSAL`` a tile that holds no visible score still
    streams through VMEM but skips its products."""
    kept, swept = pl.program_id(1), pl.program_id(2)
    qi, ki = (swept, kept) if swept_axis == 0 else (kept, swept)
    if fold is not None:
        qi = lax.rem(qi, fold)
    live = rule.live_tile(qi, ki, block_q, block_k)
    tile(*refs, qi, ki, swept == 0, swept == n_swept - 1, live,
         rule=rule, block_q=block_q, block_k=block_k, **static)


def _walk_group_bounds(group_ref, t, n_tiles):
    """Group start/end flags for position ``t`` of a compressed tile
    walk, derived from the walk's OWN grouping array (the scalar-
    prefetched kis/qids) rather than re-deriving the diagonal formula —
    one source of truth with the host-side enumeration. A group starts
    where the grouping value changes (or at t=0, which also covers the
    per-batch restart of program_id) and ends where the next value
    differs (or at the final tile)."""
    g = group_ref[t]
    prev = group_ref[jnp.maximum(t - 1, 0)]
    nxt = group_ref[jnp.minimum(t + 1, n_tiles - 1)]
    is_start = (t == 0) | (g != prev)
    is_end = (t == n_tiles - 1) | (g != nxt)
    return is_start, is_end


def _bwd_kernel_walk(tile, q_slot, n_tiles, fold, *refs, **static):
    """Either backward kernel on a compressed walk: a 1-D grid
    (BH, T) over ONLY the live tile pairs of the rule, decoded from the
    two scalar-prefetched index arrays, the first of which groups the
    walk (the key tile for dK/dV, whose query tile is prefetch array 1:
    ``q_slot``; the query tile for dQ, ``q_slot`` 0). A tile that holds
    no visible score is never visited, so its DMA never happens. Where
    dK/dV sweeps a group's q heads in turn the walk's query entry is
    ``g * n_q + qi`` and ``fold`` = n_q gives the tile back; else None."""
    t = pl.program_id(1)
    qi, ki = refs[q_slot][t], refs[1 - q_slot][t]
    if fold is not None:
        qi = lax.rem(qi, fold)
    first, last = _walk_group_bounds(refs[0], t, n_tiles)
    tile(*refs[2:], qi, ki, first, last, None, **static)


def _flash_bwd_2d_pallas(res, do, *, rule, scale, block_q, block_k):
    """The backward as two ``pallas_call``s (dK/dV, then dQ), each named
    after its tiles (``flash_bwd_dkv_q512_k512``, ``flash_bwd_dq_...``:
    a trace says which ran, with which tiles, how often). P is recomputed
    tile by tile from the saved log-sum-exp, (L, L) is never
    materialised; ``delta = rowsum(dO * O)`` is computed once, in
    float32, and both kernels read it. Under a rule both walk only
    the live tile pairs (the forward's compressed walk for dQ, the
    enumeration by key tile for dK/dV), with the rectangular grid as the
    fallback over the cap. A block the caller did not name (None) comes
    from the shape, for each kernel its own (``backward_blocks``).
    q and k ``d`` wide, v and dO ``dv`` wide, neither a multiple of 128
    by need: a tile holds the whole width.

    With ``group`` q heads a k/v head (k and v (BH / group, L, .)): dQ
    reads k/v head ``b // group``; dK/dV runs one sweep a k/v head over
    the query tiles of all its q heads into ONE accumulator, q, dO and
    the two statistics seen as (BH / group, group * length, .), which is
    how they lie in memory: dk and dv leave at BH / group heads and no
    copy of k, v, dk or dv a q head ever exists."""
    q, k, v, o, lse = res
    bh, l_real, d = q.shape
    dv = v.shape[-1]
    group = bh // k.shape[0]
    chosen = backward_blocks(l_real, d, q.dtype.itemsize, dv)
    # softmax-jacobian diagonal correction
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    vmem = pltpu.VMEM
    params = pltpu.CompilerParams(vmem_limit_bytes=_BWD_VMEM_SCOPED_BYTES)

    def call(kernel: str):
        bq = chosen[kernel][0] if block_q is None else block_q
        bk = chosen[kernel][1] if block_k is None else block_k
        n_q, n_k = pl.cdiv(l_real, bq), pl.cdiv(l_real, bk)
        pad = lambda x, n, axis: jnp.pad(
            x, [(0, n - l_real if a == axis else 0) for a in range(x.ndim)]
        ) if n > l_real else x
        qp, dop = pad(q, n_q * bq, 1), pad(do, n_q * bq, 1)
        kp, vp = pad(k, n_k * bk, 1), pad(v, n_k * bk, 1)
        dkv = kernel == "dkv"
        if dkv:
            # lane-dense rows (BH, 1, T): the transposed tile subtracts
            # them along its lanes
            stats = [pad(x[:, None, :], n_q * bq, 2) for x in (lse, delta)]
            stat_block, stat_index = (1, 1, bq), lambda b, i: (b, 0, i)
        else:
            # columns (BH, T, 1): BH stays out of the block's last-two-
            # dims window (the TPU lowering rejects a 2-D (1, block_q)
            # row block — see forward)
            stats = [pad(x[..., None], n_q * bq, 1) for x in (lse, delta)]
            stat_block, stat_index = (1, bq, 1), lambda b, i: (b, i, 0)
        # dK/dV of grouped heads: the query side by k/v head, the q
        # heads of a group one after the other along the length
        fold = int(n_q) if dkv and group > 1 else None
        if fold:
            qp, dop = (x.reshape(bh // group, -1, x.shape[-1])
                       for x in (qp, dop))
            stats = [x.reshape(bh // group, 1, -1) for x in stats]
        kv = _kv_head(1 if dkv else group)
        operands = (qp, kp, vp, dop, *stats)
        static = dict(scale=scale, block_q=bq, block_k=bk, n_k=n_k,
                      l_real=l_real)
        if dkv:
            tile = _dkv_tile
            out_shape = [_sds((bh // group, n_k * bk, d), q.dtype, qp),
                         _sds((bh // group, n_k * bk, dv), q.dtype, qp)]
            out_blocks = [(1, bk, d), (1, bk, dv)]
            scratch = [pltpu.VMEM((bk, d), jnp.float32),
                       pltpu.VMEM((bk, dv), jnp.float32),
                       pltpu.VMEM((bk, d), q.dtype)]
        else:
            tile = _dq_tile
            out_shape = [_sds((bh, n_q * bq, d), q.dtype, qp)]
            out_blocks = [(1, bq, d)]
            scratch = [pltpu.VMEM((bq, d), jnp.float32),
                       pltpu.VMEM((bq, d), q.dtype)]
        name = f"flash_bwd_{kernel}_q{bq}_k{bk}"

        def specs(q_index, k_index):
            """Block specs from the two maps (grid indices and prefetch
            refs -> tile index) of the query side and the key side: ONE
            builder for both kernels and both grids — they differ only in
            which index means what, and a drifted copy would compile but
            misindex."""
            q3 = lambda *a: (a[0], q_index(*a), 0)
            k3 = lambda *a: (kv(a[0]), k_index(*a), 0)
            stat = lambda *a: stat_index(a[0], q_index(*a))
            in_specs = [
                pl.BlockSpec((1, bq, d), q3, memory_space=vmem),    # q
                pl.BlockSpec((1, bk, d), k3, memory_space=vmem),    # k
                pl.BlockSpec((1, bk, dv), k3, memory_space=vmem),   # v
                pl.BlockSpec((1, bq, dv), q3, memory_space=vmem),   # do
                pl.BlockSpec(stat_block, stat, memory_space=vmem),  # lse
                pl.BlockSpec(stat_block, stat, memory_space=vmem),  # delta
            ]
            out_specs = [pl.BlockSpec(block, k3 if dkv else q3,
                                      memory_space=vmem)
                         for block in out_blocks]
            return in_specs, out_specs

        heads = bh // group if dkv else bh  # the grid's leading axis
        sweeps = group if dkv else 1
        walk = None
        if rule != FULL:
            # (kis, qis) grouped by key tile for dK/dV, (qids, kids)
            # grouped by query tile for dQ
            walk = _live_tiles(rule, int(n_q), int(n_k), bq, bk,
                               by_key=dkv, group=sweeps)
            if len(walk[0]) > _MAX_CAUSAL_TILES:
                walk = None
        if walk is not None:
            q_slot = 1 if dkv else 0
            in_specs, out_specs = specs(
                lambda b, t, *refs: refs[q_slot][t],
                lambda b, t, *refs: refs[1 - q_slot][t])
            outs = pl.pallas_call(
                functools.partial(_bwd_kernel_walk, tile, q_slot,
                                  len(walk[0]), fold, rule=rule, **static),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2, grid=(heads, len(walk[0])),
                    in_specs=in_specs, out_specs=out_specs,
                    scratch_shapes=scratch),
                out_shape=out_shape, compiler_params=params,
                interpret=_interpret(), name=name,
            )(jnp.asarray(walk[0]), jnp.asarray(walk[1]), *operands)
        else:
            # the swept tile is program id 2, the kept one program id 1
            q_at, k_at = (2, 1) if dkv else (1, 2)
            in_specs, out_specs = specs(lambda *g: g[q_at],
                                        lambda *g: g[k_at])
            outs = pl.pallas_call(
                functools.partial(
                    _bwd_kernel_rect, tile, 0 if dkv else 1,
                    sweeps * n_q if dkv else n_k, fold, rule=rule, **static),
                grid=((heads, n_k, sweeps * n_q) if dkv
                      else (heads, n_q, n_k)),
                in_specs=in_specs, out_specs=out_specs,
                out_shape=out_shape, scratch_shapes=scratch,
                compiler_params=params, interpret=_interpret(), name=name,
            )(*operands)
        return [x[:, :l_real] for x in outs]

    dk, dv_ = call("dkv")
    (dq,) = call("dq")
    return dq, dk, dv_


# -- public API -----------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_2d(q, k, v, rule, scale, block_q, block_k, backward, dense_lse):
    o, _ = _flash_fwd_2d(q, k, v, rule=rule, scale=scale,
                         block_q=block_q, block_k=block_k)
    return o


def _named_residuals(o, lse, dense_lse: bool):
    """The two residuals a caller's checkpoint may keep, under their
    names. Where the caller keeps the log-sum-exp (``dense_lse``) the
    pair passes one barrier first, so that it exists as the (BH, L) row:
    left alone, the compiler keeps the kernel's (BH, T, 1) output, which
    the TPU's layout pads to 128 lanes, for the dQ kernel that reads a
    column (module docstring)."""
    if dense_lse:
        o, lse = lax.optimization_barrier((o, lse))
    return checkpoint_name(o, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)


def _flash_2d_fwd(q, k, v, rule, scale, block_q, block_k, backward,
                  dense_lse):
    o, lse = _named_residuals(*_flash_fwd_2d(
        q, k, v, rule=rule, scale=scale, block_q=block_q, block_k=block_k),
        dense_lse)
    return o, (q, k, v, o, lse)


def _flash_2d_bwd(rule, scale, block_q, block_k, backward, dense_lse, res,
                  do):
    # a block the caller did not name comes from the shape: each
    # backward kernel's own tiles, or the scan's key block
    if backward == "pallas":
        return _flash_bwd_2d_pallas(res, do, rule=rule, scale=scale,
                                    block_q=block_q, block_k=block_k)
    return _flash_bwd_2d(
        res, do, rule=rule, scale=scale,
        block_k=(backward_scan_block(res[0].shape[1]) if block_k is None
                 else block_k))


_flash_2d.defvjp(_flash_2d_fwd, _flash_2d_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_diffusion_mask: Optional[tuple[int, int]] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    backward: str = "xla",
    kept: tuple[str, ...] = (),
) -> jax.Array:
    """Exact fused softmax attention: q ``(B, L, H, D)``, k ``(B, L,
    H_kv, D)``, v ``(B, L, H_kv, Dv)`` → ``(B, L, H, Dv)``.

    The shape rule: q, k and v share batch and length; q and k share one
    head width and v may have another (latent attention: q and k carry a
    rotary part that v has not); k and v share ``H_kv`` heads, which
    divides q's ``H`` (grouped-query attention: q head h reads k/v head
    ``h // (H / H_kv)``; the kernels index it so, dK/dV sums a group's q
    heads in its accumulator, and neither k, v nor their gradients are
    ever repeated a q head). Neither width has to be a multiple of the
    128 lanes: a tile holds the whole width. Where ``Dv = D`` and
    ``H_kv = H`` the kernel, its tiles, its walk and its name are what
    they were before v had a width and k heads of their own.

    The masks (``Visibility``): none; ``causal``; or
    ``block_diffusion_mask=(clean_len, block)`` over ``L = 2 *
    clean_len`` positions laid out ``[clean ; noisy]`` (block-diffusion
    training: a clean position sees the clean past block-causally, a
    noisy one the clean blocks before its own and its own noisy block).
    Each kernel walks only the tile pairs that hold a visible score and
    builds a mask only on those the rule does not fill.

    Drop-in for ``parallel.sequence._single_device_attention`` (same
    semantics, tolerances at f32 rounding); differentiable via a
    blockwise custom VJP (the kernels' takes the same shapes and masks).
    ``scale`` defaults to ``D**-0.5``, D the width of q and k.
    ``block_q`` / ``block_k``: a caller that names them gets them, in
    the forward and the backward; left out, the forward's come from the
    shape (``forward_blocks``), as do each backward kernel's
    (``backward_blocks``) and the backward scan's key block
    (``backward_scan_block``: 128 up to 4,095 tokens).
    ``backward`` selects the VJP implementation: ``"xla"`` (default —
    blockwise lax.scan over equal heads under no mask or the causal
    one; it refuses grouped heads and the block-diffusion mask by name
    when it is differentiated) or ``"pallas"`` (two fused kernels, dK/dV then
    dQ: what ``models.looped_lm.causal_attention`` names, 2.1 to 3.7
    times as fast as the scan at the benchmark cells' shapes; the
    module's docstring has the numbers).
    ``kept``: what the caller's ``jax.checkpoint`` keeps for the
    backward pass (its ``save_only_these_names`` list; names that are
    not this kernel's count for nothing): where :data:`FLASH_LSE` is
    among them the log-sum-exp is made the lane-dense row before it is
    named (the module's docstring has why, and what it costs a caller
    that keeps neither, which is why it is asked for).
    """
    if q.ndim != 4:
        raise ValueError(f"expected (B, L, H, D), got {q.shape}")
    if backward not in ("xla", "pallas"):
        raise ValueError(f"backward must be 'xla' or 'pallas', got "
                         f"{backward!r}")
    # the 2d lowering takes lengths/padding from q and reuses them for
    # k/v (no cross-attention support) — mismatches must fail here with
    # a clear message, not deep in a pallas lowering error
    if (k.ndim != 4 or v.ndim != 4 or k.shape[:2] != q.shape[:2]
            or k.shape[3] != q.shape[3] or v.shape[:3] != k.shape[:3]
            or q.shape[2] % k.shape[2]):
        raise ValueError(
            "flash_attention requires q (B, L, H, D), k of identical "
            "batch, length and width with H_kv heads that divide H, and "
            f"v of shape (B, L, H_kv, Dv), got q={q.shape}, k={k.shape}, "
            f"v={v.shape}"
        )
    b, l, h, d = q.shape
    rule = CAUSAL if causal else FULL
    if block_diffusion_mask is not None:
        if causal:
            raise ValueError("name causal or block_diffusion_mask, not both")
        rule = block_diffusion(*block_diffusion_mask)
        if l != 2 * rule.clean_len:
            raise ValueError(
                f"block_diffusion_mask: [clean ; noisy] of clean_len "
                f"{rule.clean_len} is {2 * rule.clean_len} positions, got {l}")
    s = float(scale) if scale is not None else d ** -0.5
    to2d = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, l, x.shape[-1])
    o = _flash_2d(to2d(q), to2d(k), to2d(v), rule, s, block_q, block_k,
                  backward, FLASH_LSE in kept)
    return o.reshape(b, h, l, v.shape[-1]).transpose(0, 2, 1, 3)

"""Cross-platform TPU (Mosaic) lowering of every Pallas kernel, on CPU.

The first hardware run exposed a bug class the interpret-mode
suite structurally cannot see: the TPU lowering's block-shape tiling
rule (last two block dims divisible by (8, 128) or equal to the array
dims) fired on the flash kernels' 2-D lse/delta specs at *compile*
time, spending scarce chip time on a failure CPU CI should have
caught. The rule is enforced during lowering, not execution — so
``jax.jit(f).trace(args).lower(lowering_platforms=("tpu",))`` runs the
full Mosaic pipeline on any host, no chip required.

These tests force ``interpret()`` off via monkeypatch (no edit to the
kernel sources) and TPU-lower every kernel entry point. Lowering proves the block specs
legal; ``test_tpu_compile.py`` asks the TPU compiler itself (VMEM,
tiling); ``chip_smoke.py``'s kernel phase proves numerics on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_syncbn.ops import pallas_attention as pa
from tpu_syncbn.ops import pallas_bn


def _tpu_lower(fn, *args):
    """Full Mosaic TPU lowering on the host backend; raises on any
    lowering-rule violation (the negative control below proves the
    mechanism is live, so a pass here is not vacuous)."""
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


@pytest.fixture
def mosaic(monkeypatch):
    """Route pallas_calls through the real TPU lowering, not interpret."""
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_bn, "_interpret", lambda: False)


def test_mechanism_catches_illegal_block_specs():
    """Negative control: the exact shape of the round-5 bug — a 2-D
    output blocked (1, 128) with the leading axis in the last-two-dims
    window — must be rejected by the cross-platform lowering. If this
    starts passing, the guard is vacuous and every other test here
    proves nothing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def k(x_ref, o_ref):
        o_ref[0] = x_ref[0, :, 0]

    def f(x):
        return pl.pallas_call(
            k,
            grid=(8, 2),
            in_specs=[pl.BlockSpec((1, 128, 128), lambda b, i: (b, i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, 128), lambda b, i: (b, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((8, 256), jnp.float32),
        )(x)

    x = jnp.zeros((8, 256, 128), jnp.float32)
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _tpu_lower(f, x)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_lowers_for_tpu(mosaic, causal):
    q = jnp.zeros((1, 256, 8, 64), jnp.float32)
    _tpu_lower(lambda q: pa.flash_attention(q, q, q, causal=causal), q)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("backward", ["xla", "pallas"])
def test_flash_grad_lowers_for_tpu(mosaic, causal, backward):
    q = jnp.zeros((1, 256, 8, 64), jnp.float32)
    _tpu_lower(
        jax.grad(lambda q: pa.flash_attention(
            q, q, q, causal=causal, backward=backward).sum()),
        q,
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("backward", ["xla", "pallas"])
def test_flash_grad_latent_widths_lower_for_tpu(mosaic, causal, backward):
    # q and k 192 wide, no multiple of the 128 lanes, v and dO 128: the
    # backward kernels' dk is as wide as k, their dv as wide as v
    qk = jnp.zeros((1, 512, 4, 192), jnp.bfloat16)
    v = jnp.zeros((1, 512, 4, 128), jnp.bfloat16)
    _tpu_lower(
        jax.grad(lambda q, k, v: pa.flash_attention(
            q, k, v, causal=causal, backward=backward
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        qk, qk, v,
    )


@pytest.mark.parametrize("mask", [dict(causal=True), dict(),
                                  dict(block_diffusion_mask=(128, 4)),
                                  dict(block_diffusion_mask=(128, 12))],
                         ids=["causal", "full", "block4", "block12"])
def test_flash_grad_grouped_heads_and_block_mask_lower_for_tpu(mosaic, mask):
    """8 q heads over 2 k/v heads (dK/dV sweeping a group's q heads, k
    and v indexed ``b // group``), under each mask: the block-diffusion
    mask's iotas, shifts (a block of 4) and integer divisions (12)."""
    q = jnp.zeros((1, 256, 8, 128), jnp.float32)
    kv = jnp.zeros((1, 256, 2, 128), jnp.float32)
    _tpu_lower(
        jax.grad(lambda q, k, v: pa.flash_attention(
            q, k, v, backward="pallas", **mask).sum(), argnums=(0, 1, 2)),
        q, kv, kv,
    )


def test_flash_block_mask_ragged_lowers_for_tpu(mosaic):
    """A clean length that is no multiple of the tiles: the halves'
    border falls inside a tile and the last tile is padded."""
    q = jnp.zeros((1, 600, 4, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 600, 2, 128), jnp.bfloat16)
    _tpu_lower(
        jax.grad(lambda q, k, v: pa.flash_attention(
            q, k, v, block_diffusion_mask=(300, 4), backward="pallas"
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        q, kv, kv,
    )


def test_flash_ragged_lowers_for_tpu(mosaic):
    # non-multiple length exercises the padded final blocks and, under
    # causal, the compressed scalar-prefetch tile walk with a partial row
    q = jnp.zeros((1, 1000, 4, 128), jnp.float32)
    _tpu_lower(lambda q: pa.flash_attention(q, q, q, causal=True), q)


def test_flash_bf16_lowers_for_tpu(mosaic):
    q = jnp.zeros((2, 512, 4, 64), jnp.bfloat16)
    _tpu_lower(
        lambda q: pa.flash_attention(q, q, q, causal=True),
        q,
    )


def test_bn_kernels_lower_for_tpu(mosaic):
    x = jnp.zeros((64, 32, 32, 16), jnp.float32)
    w = jnp.ones((16,), jnp.float32)
    b = jnp.zeros((16,), jnp.float32)

    def fwd(x, w, b):
        y, mean, var, count = pallas_bn.fused_batch_norm(
            x, w, b, eps=1e-5, axis_name=None
        )
        # stats feed the no-grad running-buffer update only; the VJP
        # rejects differentiation through them by design
        return y.sum() + sum(
            jax.lax.stop_gradient(s).sum() for s in (mean, var, count)
        )

    _tpu_lower(fwd, x, w, b)
    # the hand-derived VJP is its own pair of Pallas kernels
    _tpu_lower(jax.grad(fwd), x, w, b)


def test_bn_ragged_rows_lower_for_tpu(mosaic):
    # M=37 exercises _pad_rows' partial final block (the smallest
    # on-chip parity case)
    x = jnp.zeros((37, 8), jnp.float32)
    w = jnp.ones((8,), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)
    _tpu_lower(
        lambda x: pallas_bn.fused_batch_norm(
            x, w, b, eps=1e-5, axis_name=None)[0].sum(),
        x,
    )

"""``ops.pallas_attention.flash_attention`` with a v (and output) head
width of its own and a q/k width that is no multiple of the 128 lanes
(latent attention: 192 for q and k, 128 for v), against the XLA path of
``models.looped_lm.causal_attention``: values and gradients (through
the XLA scan and through the two backward kernels), interpret mode on
the CPU; and that equal widths still walk the kernel they walked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_syncbn.models.looped_lm import causal_attention
from tpu_syncbn.ops import pallas_attention as pa
from tpu_syncbn.parallel import sequence


def make(l, d, dv, seed=0, dtype=jnp.float32, b=2, h=3):
    rng = np.random.default_rng(seed)
    mk = lambda w: jnp.asarray(
        rng.standard_normal((b, l, h, w)).astype(np.float32), dtype)
    return mk(d), mk(d), mk(dv)


# (length, q/k width, v width): latent attention's ratio, a ragged
# length, v wider than q/k, and the published 192 / 128
WIDTHS = [(64, 24, 16), (100, 24, 16), (64, 16, 40), (160, 192, 128)]


@pytest.mark.parametrize("l,d,dv", WIDTHS)
def test_forward_matches_xla_attention(l, d, dv):
    q, k, v = make(l, d, dv)
    got = pa.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert got.shape == v.shape
    np.testing.assert_allclose(got, causal_attention(q, k, v, "xla"),
                               atol=2e-5)
    # the tiles the shape chooses, as the model calls it
    np.testing.assert_allclose(causal_attention(q, k, v, "flash"),
                               causal_attention(q, k, v, "xla"), atol=2e-5)


def dense(q, k, v, causal):
    """The oracle the kernel's own tests use, on the stored values held
    in float32 (it returns its inputs' type)."""
    return sequence._single_device_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal=causal,
        scale=None)


def gradients(attend, q, k, v, seed=2):
    w = jnp.asarray(np.random.default_rng(seed).standard_normal(v.shape),
                    jnp.float32)
    return jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("l,d,dv", WIDTHS)
def test_gradients_match_xla_attention(l, d, dv):
    # as the model calls it: the tiles the shape chooses, the backward
    # the kernel file's two kernels
    q, k, v = make(l, d, dv, seed=1)
    got = gradients(lambda *a: causal_attention(*a, "flash"), q, k, v)
    want = gradients(lambda *a: causal_attention(*a, "xla"), q, k, v)
    for g, r, x in zip(got, want, (q, k, v)):
        assert g.shape == x.shape
        np.testing.assert_allclose(g, r, atol=5e-5)


# tiles the caller names (the two unequal, so that the padded lengths of
# the query and the key side differ at the ragged length) and tiles each
# kernel takes from the shape
BLOCKS = [dict(block_q=64, block_k=32), {}]


@pytest.mark.parametrize("blocks", BLOCKS, ids=["q64_k32", "chosen"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("backward", ["xla", "pallas"])
@pytest.mark.parametrize("l,d,dv", WIDTHS)
def test_both_backwards_match_dense_gradients(l, d, dv, backward, causal,
                                              blocks):
    q, k, v = make(l, d, dv, seed=1)
    got = gradients(lambda *a: pa.flash_attention(
        *a, causal=causal, backward=backward, **blocks), q, k, v)
    want = gradients(lambda *a: dense(*a, causal), q, k, v)
    for g, r, x in zip(got, want, (q, k, v)):
        assert g.shape == x.shape and g.dtype == x.dtype
        np.testing.assert_allclose(g, r, atol=5e-5)


@pytest.mark.parametrize("backward", ["xla", "pallas"])
def test_bfloat16_gradients_stay_near_a_float32_reference(backward):
    """The published widths in the configurations' compute type: q, k,
    v and dO meet the products as bfloat16, p and ds are rounded to it
    where they enter theirs (the kernels; on the CPU the scan's float32
    products round nothing), the sums are float32. Against float32
    gradients of the same stored values each of dq, dk, dv is within
    1e-2 in relative L2: the inputs' and outputs' own rounding is 3e-3,
    the kernels read 3.6e-3 to 4.0e-3 here."""
    q, k, v = make(160, 192, 128, seed=4, dtype=jnp.bfloat16)
    got = gradients(lambda *a: pa.flash_attention(
        *a, causal=True, backward=backward), q, k, v)
    want = gradients(lambda *a: dense(*a, True), q, k, v)
    for g, r in zip(got, want):
        assert g.dtype == jnp.bfloat16
        g = np.asarray(g.astype(jnp.float32))
        assert np.linalg.norm(g - r) / np.linalg.norm(r) < 1e-2


def backward_program(attend, q, k, v) -> str:
    """The jaxpr of the backward pass alone."""
    _, pull = jax.vjp(attend, q, k, v)
    return str(jax.make_jaxpr(pull)(jnp.ones_like(v)))


@pytest.mark.parametrize("l,d,dv,names", [
    (128, 16, 16, ("flash_bwd_dkv_q128_k128", "flash_bwd_dq_q128_k128")),
    (768, 24, 16, ("flash_bwd_dkv_q384_k384", "flash_bwd_dq_q384_k384")),
])
def test_the_backward_kernels_are_named_after_their_tiles(l, d, dv, names):
    q, k, v = make(l, d, dv, b=1, h=1)
    text = backward_program(lambda *a: pa.flash_attention(
        *a, causal=True, backward="pallas"), q, k, v)
    for name in names:
        assert name in text
    named = backward_program(lambda *a: pa.flash_attention(
        *a, causal=True, backward="pallas", block_q=64, block_k=32), q, k, v)
    assert "flash_bwd_dkv_q64_k32" in named
    assert "flash_bwd_dq_q64_k32" in named


def test_the_models_flash_differentiates_through_the_kernels():
    q, k, v = make(160, 192, 128, b=1, h=2)
    text = backward_program(lambda *a: causal_attention(*a, "flash"),
                            q, k, v)
    assert "pallas_call" in text and "flash_bwd_dkv_" in text
    assert "flash_bwd_dq_" in text
    assert "scan" not in text and "while" not in text
    # the scan stays the default of ``flash_attention`` itself
    scan = backward_program(lambda *a: pa.flash_attention(
        *a, causal=True), q, k, v)
    assert "scan" in scan and "flash_bwd_" not in scan


def test_the_backward_kernels_take_their_tiles_from_the_shape():
    # the two cells' calls (PERF.md section 6, PR 35)
    for l, d, dv in ((2048, 128, None), (8192, 192, 128)):
        chosen = pa.backward_blocks(l, d, 2, dv)
        assert chosen == {"dkv": (512, 512), "dq": (512, 512)}
    with pytest.raises(ValueError, match="kernel"):
        pa.backward_vmem_bytes("fwd", 128, 128, 64, 2)


def test_full_attention_and_a_custom_scale_take_the_widths_too():
    q, k, v = make(96, 24, 16, seed=3)
    got = pa.flash_attention(q, k, v, causal=False, scale=0.3,
                             block_q=32, block_k=32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(got, want, atol=2e-5)


# sha256 of the program text (the jaxpr of the forward and the backward
# pass, file paths taken out) of a call with three equal widths, as the
# kernel file of commit 0c2bf51 (before v had a width of its own) wrote
# it under jax 0.9.0: (shape, dtype, causal) -> digest. The first is the
# accepted cell's call (ouro-l8-train-b2x2048). Since PR 39 the forward
# rule names its two residuals (``_named_residuals``); the digest is of
# the program without those two equations, which is what it was.
PROGRAM_AS_IT_WAS = {
    ((2, 2048, 16, 128), "bfloat16", True):
        "64b5078c4478b61dfe9d8b150cf0874c158789654568dc7436ef474982ca3041",
    ((1, 300, 2, 16), "float32", True):
        "88d0bdb11f08e070cb7e48f434b51d41e0ff4b97c5a80ae50786ebef8ca42776",
    ((1, 256, 2, 64), "float32", False):
        "2f6c381a9e779e2a8775ae3573c52ae7cfc581cd4a6a206e55dc74df4e692096",
}


@pytest.mark.parametrize("call", sorted(PROGRAM_AS_IT_WAS, key=str),
                         ids=lambda c: "B{}L{}h{}d{}".format(*c[0]))
def test_equal_widths_write_the_program_they_wrote(call, monkeypatch):
    """Where the widths are equal the call is bit-equal to what it was,
    because it is the same program: kernel, tiles, grid, name, backward
    scan, operation for operation (the text is a golden of jax 0.9.0,
    like tests/contracts; on this CPU the outputs and gradients of the
    two kernel files were also compared bit for bit, CHANGES.md, PR 34)."""
    import hashlib
    import re

    if jax.__version__ != "0.9.0":
        pytest.skip("the program text is pinned under jax 0.9.0")
    shape, dtype, causal = call
    monkeypatch.setattr(pa, "_named_residuals", lambda o, lse, _: (o, lse))
    q = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    text = str(jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda *a: pa.flash_attention(*a, causal=causal), q, k, v)[1](q))(
            q, q, q))
    text = re.sub(r"/[^\s\"']*\.py(:\d+)?", "<file>", text)
    assert hashlib.sha256(text.encode()).hexdigest() == PROGRAM_AS_IT_WAS[call]


def test_equal_widths_choose_the_blocks_and_the_name_they_chose():
    # the timed shapes of the accepted cell: unchanged by a v width
    assert pa.forward_blocks(2048, 128, 2) == (512, 512)
    assert pa.forward_blocks(2048, 128, 2, 128) == (512, 512)
    assert pa.forward_vmem_bytes(512, 512, 128, 2) == \
        pa.forward_vmem_bytes(512, 512, 128, 2, 128)
    # latent attention's widths at 8,192 tokens keep the widest tiles
    assert pa.forward_blocks(8192, 192, 2, 128) == (512, 512)
    q, k, v = make(128, 16, 16, b=1, h=1)
    text = str(jax.make_jaxpr(
        lambda q, k, v: pa.flash_attention(q, k, v, causal=True))(q, k, v))
    assert "flash_fwd_q128_k128" in text


def test_the_backward_scan_walks_sixteen_key_blocks_within_128_and_512():
    # the accepted cell's 2,048 tokens keep the 128 they were measured
    # with; the 8,192 of latent attention's cell take 512
    assert [pa.backward_scan_block(l) for l in
            (100, 2048, 4095, 4096, 6000, 8192, 32768)] == [
                128, 128, 128, 256, 256, 512, 512]
    # a length whose scan block is not 128, against XLA's attention
    q, k, v = make(4096, 24, 16, seed=6, b=1, h=1)
    w = jnp.asarray(np.random.default_rng(7).standard_normal(v.shape),
                    jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(causal_attention(*a, "flash") * w),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(causal_attention(*a, "xla") * w),
                    argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=5e-5)


def test_rejects_shapes_it_cannot_take():
    q, k, v = make(32, 24, 16)
    with pytest.raises(ValueError, match="identical"):
        pa.flash_attention(q, k[..., :16], v)  # q and k differ
    with pytest.raises(ValueError, match="identical"):
        pa.flash_attention(q, k, v[:, :16])  # v of another length

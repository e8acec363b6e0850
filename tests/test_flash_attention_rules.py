"""``ops.pallas_attention`` with fewer k/v heads than q heads and with a
visibility rule that is neither full nor causal (the block-diffusion
mask): the live-tile enumeration against counts by hand and against the
closed forms it replaced, the rule's mask against its three clauses, the
kernels (interpret mode on the CPU) against the XLA path of
``models.looped_lm.block_diffusion_attention``, values and dq, dk, dv,
at lengths that do and do not divide the tiles; and that ``causal=True``
with equal heads is still the program it was.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_syncbn.models.looped_lm import (block_diffusion_attention,
                                         causal_attention)
from tpu_syncbn.ops import pallas_attention as pa


def make(l, heads, kv_heads, d=16, dv=8, seed=0, dtype=jnp.float32, b=2):
    rng = np.random.default_rng(seed)
    mk = lambda h, w: jnp.asarray(
        rng.standard_normal((b, l, h, w)).astype(np.float32), dtype)
    return mk(heads, d), mk(kv_heads, d), mk(kv_heads, dv)


def clauses(clean_len: int, block: int) -> np.ndarray:
    """The block-diffusion mask by its three clauses, pair by pair."""
    n = 2 * clean_len
    out = np.zeros((n, n), bool)
    for p in range(n):
        for r in range(n):
            clean_p, clean_r = p < clean_len, r < clean_len
            blk_p, blk_r = (p % clean_len) // block, (r % clean_len) // block
            out[p, r] = ((clean_p and clean_r and blk_r <= blk_p)
                         or (not clean_p and clean_r and blk_r < blk_p)
                         or (not clean_p and not clean_r and blk_r == blk_p))
    return out


# -- the rule ---------------------------------------------------------------


@pytest.mark.parametrize("clean_len,block", [(12, 4), (10, 3), (8, 8), (7, 1)])
def test_the_rule_is_the_three_clauses(clean_len, block):
    at = np.arange(2 * clean_len)
    rule = pa.block_diffusion(clean_len, block)
    want = clauses(clean_len, block)
    assert np.array_equal(rule.visible(at[:, None], at[None, :]), want)
    # the same arithmetic on a kernel's iotas
    assert np.array_equal(
        rule.visible(jnp.asarray(at)[:, None], jnp.asarray(at)[None, :]), want)
    # every query sees itself; live scores: L^2 + B L where B divides L
    assert want.diagonal().all()
    if clean_len % block == 0:
        assert want.sum() == clean_len ** 2 + block * clean_len
    # nothing clean sees anything noisy
    assert not want[:clean_len, clean_len:].any()


def old_causal_tiles(n_q, n_k, block_q, block_k):
    """The closed-form enumeration the rule-driven one replaced."""
    return [(qi, ki) for qi in range(n_q)
            for ki in range(min(n_k - 1, (qi * block_q + block_q - 1)
                                // block_k) + 1)]


def old_causal_tiles_kv(n_q, n_k, block_q, block_k):
    return [(ki, qi) for ki in range(n_k)
            for qi in range((ki * block_k) // block_q, n_q)]


@pytest.mark.parametrize("shape", [(8, 8, 128, 128), (4, 8, 256, 128),
                                   (8, 4, 128, 256), (5, 5, 64, 64),
                                   (3, 3, 384, 384), (16, 16, 512, 512),
                                   (2, 3, 256, 128), (1, 2, 256, 128)])
def test_the_causal_walks_are_the_closed_forms_they_were(shape):
    by_q = pa._live_tiles(pa.CAUSAL, *shape)
    assert list(zip(*map(np.ndarray.tolist, by_q))) == old_causal_tiles(*shape)
    by_k = pa._live_tiles(pa.CAUSAL, *shape, by_key=True)
    assert list(zip(*map(np.ndarray.tolist, by_k))) == old_causal_tiles_kv(
        *shape)
    assert by_q[0].dtype == by_k[1].dtype == np.int32


def test_live_tiles_of_the_cells_shape_against_a_count_by_hand():
    """4,096 clean positions in blocks of 4, tiles of 512 x 512: 8 tiles
    a half. Clean on clean, block-causal: 8 * 9 / 2 = 36; noisy on clean,
    strictly earlier blocks, the diagonal tile still holds some: 36;
    noisy on noisy, own block only: the 8 diagonal tiles; clean on noisy:
    none. 80 of 256, where a causal walk of the 8,192 visits 136. A mask
    is built on the 3 x 8 diagonal tiles alone."""
    rule = pa.block_diffusion(4096, 4)
    qids, kids = pa._live_tiles(rule, 16, 16, 512, 512)
    assert len(qids) == 36 + 36 + 8 == 80
    assert len(pa._live_tiles(pa.CAUSAL, 16, 16, 512, 512)[0]) == 136
    pairs = set(zip(qids.tolist(), kids.tolist()))
    assert pairs == ({(q, k) for q in range(8) for k in range(q + 1)}
                     | {(8 + q, k) for q in range(8) for k in range(q + 1)}
                     | {(8 + q, 8 + q) for q in range(8)})
    masked = [bool(rule.hides_in_tile(q, k, 512, 512)) for q, k in pairs]
    assert sum(masked) == 24
    # the walk's contract: qi ascending, ki ascending within qi
    assert list(zip(qids, kids)) == sorted(pairs)
    # dK/dV: by key tile, and within it the group's 8 q heads in turn
    kis, qis = pa._live_tiles(rule, 16, 16, 512, 512, by_key=True, group=8)
    assert len(kis) == 8 * 80 and list(kis) == sorted(kis)
    assert {(int(q) % 16, int(k)) for k, q in zip(kis, qis)} == pairs
    first = [int(q) for k, q in zip(kis, qis) if k == 0]
    reach = sorted(q for q, k in pairs if k == 0)
    assert first == [g * 16 + q for g in range(8) for q in reach]


@pytest.mark.parametrize("clean_len,block,bq,bk", [
    (64, 4, 32, 32), (50, 4, 32, 64), (72, 8, 128, 128), (40, 3, 16, 32),
    (96, 64, 32, 32)])
def test_a_tile_the_rule_calls_filled_hides_nothing(clean_len, block, bq, bk):
    """``hides_in_tile`` may be wrong one way only: every tile it says
    holds nothing hidden is all visible, and every live tile that is not
    all visible is said to hide something; the enumeration visits
    exactly the tiles with a visible pair."""
    rule = pa.block_diffusion(clean_len, block)
    n_q, n_k = -(-2 * clean_len // bq), -(-2 * clean_len // bk)
    at_q, at_k = np.arange(n_q * bq), np.arange(n_k * bk)
    full = rule.visible(at_q[:, None], at_k[None, :])
    live = set(zip(*map(np.ndarray.tolist,
                        pa._live_tiles(rule, n_q, n_k, bq, bk))))
    for qi in range(n_q):
        for ki in range(n_k):
            tile = full[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            assert ((qi, ki) in live) == bool(tile.any())
            if not bool(rule.hides_in_tile(qi, ki, bq, bk)):
                assert tile.all(), (qi, ki)


# -- the kernels against the XLA path -----------------------------------------


def gradients(attend, q, k, v, seed=2):
    w = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (*q.shape[:3], v.shape[-1])), jnp.float32)
    return jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(q, k, v)


# (clean length, block, tiles): tiles that divide the length, that do
# not (a ragged last tile, the halves' border inside a tile), tiles from
# the shape, a block wider than a tile
BLOCK_CASES = [(64, 4, 32, 32), (50, 4, 32, 64), (72, 8, None, None),
               (48, 64, 32, 32)]


@pytest.mark.parametrize("clean_len,block,bq,bk", BLOCK_CASES)
def test_grouped_heads_under_the_block_mask_match_the_xla_path(
        clean_len, block, bq, bk):
    """8 q heads over 2 k/v heads."""
    q, k, v = make(2 * clean_len, 8, 2)
    flash = lambda q, k, v: pa.flash_attention(
        q, k, v, block_diffusion_mask=(clean_len, block), block_q=bq,
        block_k=bk, backward="pallas")
    xla = lambda q, k, v: block_diffusion_attention(q, k, v, clean_len,
                                                    block, "xla")
    got = flash(q, k, v)
    assert got.shape == (*q.shape[:3], v.shape[-1])
    np.testing.assert_allclose(got, xla(q, k, v), atol=2e-5)
    grads = gradients(flash, q, k, v)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    for g, want, name in zip(grads, gradients(xla, q, k, v), "qkv"):
        np.testing.assert_allclose(g, want, atol=5e-5, err_msg=f"d{name}")


def test_the_models_flash_switch_runs_the_kernels():
    q, k, v = make(128, 4, 2)
    np.testing.assert_allclose(
        block_diffusion_attention(q, k, v, 64, 4, "flash"),
        block_diffusion_attention(q, k, v, 64, 4, "xla"), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("l", [64, 100])
def test_grouped_heads_causal_and_full_match_repeated_heads(l, causal):
    """Grouped heads equal the same call with k and v repeated a q head
    (which is what no kernel does), and dk, dv the sums over a group."""
    q, k, v = make(l, 6, 2, dv=16)
    rep = lambda x: jnp.repeat(x, 3, axis=2)
    flash = lambda q, k, v: pa.flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32, backward="pallas")
    np.testing.assert_allclose(flash(q, k, v), flash(q, rep(k), rep(v)),
                               atol=2e-6)
    if causal:
        np.testing.assert_allclose(
            flash(q, k, v), causal_attention(q, rep(k), rep(v), "xla"),
            atol=2e-5)
    dq, dk, dv = gradients(flash, q, k, v)
    dq2, dk2, dv2 = gradients(lambda q, k, v: flash(q, rep(k), rep(v)),
                              q, k, v)
    for a, b in ((dq, dq2), (dk, dk2), (dv, dv2)):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("heads,kv_heads,kw", [
    (4, 2, dict(causal=True)), (4, 2, dict()),
    (4, 4, dict(block_diffusion_mask=(16, 4))),
    (4, 2, dict(block_diffusion_mask=(16, 4)))])
def test_the_xla_backward_scan_refuses_them_by_name(heads, kv_heads, kw):
    """The scan (``backward="xla"``, the default) runs the forward
    kernel on any shape and rule, and says what to name instead when it
    is differentiated over grouped heads or the block mask."""
    q, k, v = make(32, heads, kv_heads)
    flash = lambda q, k, v: pa.flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(
        flash(q, k, v), pa.flash_attention(q, k, v, backward="pallas", **kw))
    with pytest.raises(ValueError, match="backward='pallas'"):
        gradients(flash, q, k, v)


def test_the_rectangular_fallback_computes_the_same(monkeypatch):
    q, k, v = make(100, 4, 2)
    run = lambda: (
        pa.flash_attention(q, k, v, block_diffusion_mask=(50, 4),
                           block_q=32, block_k=32, backward="pallas"),
        *gradients(lambda q, k, v: pa.flash_attention(
            q, k, v, block_diffusion_mask=(50, 4), block_q=32, block_k=32,
            backward="pallas"), q, k, v))
    walked = run()
    monkeypatch.setattr(pa, "_MAX_CAUSAL_TILES", 0)  # force rect
    for a, b in zip(walked, run()):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_bf16_operands_stay_within_bf16_of_the_float32_result():
    q, k, v = make(128, 8, 2, d=32, dv=32, dtype=jnp.bfloat16)
    got = pa.flash_attention(q, k, v, block_diffusion_mask=(64, 4),
                             backward="pallas")
    want = block_diffusion_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), 64, 4, "xla")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)


def test_refuses_what_it_cannot_mean():
    q, k, v = make(32, 4, 2)
    with pytest.raises(ValueError, match="not both"):
        pa.flash_attention(q, k, v, causal=True, block_diffusion_mask=(16, 4))
    with pytest.raises(ValueError, match="clean_len"):
        pa.flash_attention(q, k, v, block_diffusion_mask=(12, 4))
    with pytest.raises(ValueError, match="divide"):
        pa.flash_attention(make(32, 4, 3)[0], *make(32, 4, 3)[1:])
    with pytest.raises(ValueError, match="positive"):
        pa.block_diffusion(16, 0)


# -- what the programs are ------------------------------------------------------


def program(q, k, v, **kw) -> str:
    return str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: pa.flash_attention(
            q, k, v, backward="pallas", **kw).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v))


def test_each_kernels_grid_is_the_enumerations_count_at_the_cells_shape():
    """One sequence of 8,192 positions, 32 q heads over 4 k/v heads of
    128, bf16: the forward and dQ take 80 grid steps a q head, dK/dV 8 x
    80 a k/v head, each at 512 x 512 tiles; and k, v, dk, dv are 4 heads
    wherever a kernel reads or writes them."""
    sds = lambda h: jax.ShapeDtypeStruct((1, 8192, h, 128), jnp.bfloat16)
    text = program(sds(32), sds(4), sds(4), block_diffusion_mask=(4096, 4))
    assert text.count("name=flash_fwd_q512_k512") == 1
    assert text.count("name=flash_bwd_dkv_q512_k512") == 1
    assert text.count("name=flash_bwd_dq_q512_k512") == 1
    assert text.count("grid=(32, 80)") == 2   # the forward and dQ
    assert text.count("grid=(4, 640)") == 1   # dK/dV
    # no copy of k or v a q head: nothing of 32 x 8192 x 128 but q, dO,
    # the output and dq
    assert "bf16[4,8192,128]" in text and "repeat" not in text


# sha256 of str(jax.make_jaxpr(grad(flash_attention(causal=True,
# backward="pallas")))) as the PARENT of PR 36 traced it (jax 0.9.0,
# kernels not interpreted; since PR 39 with the forward rule's two
# ``name`` equations taken out, ``_named_residuals``, which is all that
# PR added to such a call), at Ouro's call (2 x 2,048 tokens, 16 heads of
# 128), JoyAI's (8,192 tokens, 32 heads of 192 / 128) and a ragged one:
# the walk, the tiles, the index maps, the kernels' names and bodies. A
# jax that prints jaxprs otherwise needs them taken again from that
# commit; a change to the kernels that moves them has changed the two
# cells' programs.
TODAYS = {
    ("ouro", (2, 2048, 16, 128, 128), jnp.bfloat16): "e25cbd1e50d238f5",
    ("joyai", (1, 8192, 32, 192, 128), jnp.bfloat16): "35f502ccaf95a2eb",
    ("ragged", (1, 300, 2, 64, 64), jnp.bfloat16): "f23e8f5a54bbdd3a",
    ("f32", (1, 1100, 2, 64, 64), jnp.float32): "4401fc6a44fa97f8",
}


@pytest.mark.parametrize("case", list(TODAYS), ids=lambda c: c[0])
def test_causal_with_equal_heads_is_the_program_it_was(case, monkeypatch):
    """Bit-equal by construction: not the outputs of two runs compared,
    the program itself."""
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    monkeypatch.setattr(pa, "_named_residuals", lambda o, lse, _: (o, lse))
    _, (b, l, h, d, dv), dtype = case
    q = jax.ShapeDtypeStruct((b, l, h, d), dtype)
    v = jax.ShapeDtypeStruct((b, l, h, dv), dtype)
    text = program(q, q, v, causal=True)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == TODAYS[case], (
        f"the causal program changed (jax {jax.__version__}; pinned under "
        "0.9.0)")


KEPT = (pa.FLASH_OUT, pa.FLASH_LSE)


@pytest.mark.parametrize("checkpoint,kept,kernel_calls", [
    (None, (), 3), (jax.checkpoint, (), 4),
    (lambda f: jax.checkpoint(f, policy=jax.checkpoint_policies
                              .save_only_these_names(*KEPT)), KEPT, 3)],
    ids=["no-checkpoint", "plain-checkpoint", "the-two-names-kept"])
def test_the_named_residuals_are_what_a_checkpoint_may_keep(
        checkpoint, kept, kernel_calls, monkeypatch):
    """The forward rule names the kernel's output and log-sum-exp. With
    no policy a name is the identity: ``jax.grad`` through
    ``flash_attention`` gives the values it gave without them and runs
    the three kernels. Under a plain ``jax.checkpoint`` the backward
    pass runs the forward kernel again; under one that keeps the two
    names, and says so, it does not: what it keeps beside the arguments
    is the output and a (BH, L) float32 row, and only there does the
    pair pass a barrier. Bit for bit the same gradients in all three."""
    from tests.test_looped_lm import computed_residuals

    q, k, v = make(96, 4, 2, d=16, dv=8, dtype=jnp.bfloat16)
    attend = lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, backward="pallas",
        kept=kept)
    wrapped = checkpoint(attend) if checkpoint else attend
    got = gradients(wrapped, q, k, v)
    traced = str(jax.make_jaxpr(lambda *a: gradients(wrapped, *a))(q, k, v))
    assert traced.count("pallas_call[") == kernel_calls
    if kept:
        assert sorted(computed_residuals(wrapped, q, k, v)) == [
            (2 * 4, 96), (2 * 4, 96, 8)]
    else:
        assert "optimization_barrier" not in str(jax.make_jaxpr(
            lambda *a: gradients(attend, *a))(q, k, v))
    monkeypatch.setattr(pa, "_named_residuals", lambda o, lse, _: (o, lse))
    for g, want in zip(got, gradients(attend, q, k, v)):
        np.testing.assert_array_equal(g, want)

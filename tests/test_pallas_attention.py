"""Pallas flash-attention kernel vs the plain-softmax oracle — forward
and gradients, interpret mode on CPU (the same kernel code path the TPU
compiles; the on-chip battery revalidates compiled)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_syncbn.ops import pallas_attention as pa
from tpu_syncbn.parallel import sequence

B, H, D = 2, 3, 16


def make(l, seed=0, dtype=jnp.float32, b=B, h=H):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((b, l, h, D)).astype(np.float32), dtype
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("l", [32, 64, 100])  # 100: ragged final blocks
@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_oracle(l, causal):
    q, k, v = make(l)
    want = sequence._single_device_attention(q, k, v, causal=causal,
                                             scale=None)
    got = pa.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_oracle(causal):
    l = 96
    q, k, v = make(l, seed=1)
    w = jnp.asarray(
        np.random.default_rng(2).standard_normal((B, l, H, D))
        .astype(np.float32)
    )

    def loss_flash(q, k, v):
        return jnp.sum(w * pa.flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32))

    def loss_oracle(q, k, v):
        return jnp.sum(w * sequence._single_device_attention(
            q, k, v, causal=causal, scale=None))

    g_got = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name}"
        )


def test_custom_scale_and_bf16():
    q, k, v = make(64, seed=3, dtype=jnp.bfloat16)
    want = sequence._single_device_attention(q, k, v, causal=True, scale=0.5)
    got = pa.flash_attention(q, k, v, causal=True, scale=0.5,
                             block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2,  # bf16 rounding
    )


def test_ragged_causal_first_rows():
    """The first rows of a causal attention see almost nothing — the
    masked-row handling (finite _NEG_BIG, denom guard) must hold at the
    block level too."""
    q, k, v = make(40, seed=4)
    got = pa.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    want = sequence._single_device_attention(q, k, v, causal=True, scale=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert bool(jnp.all(jnp.isfinite(got)))


def test_rejects_bad_rank():
    with pytest.raises(ValueError, match="B, L, H, D"):
        pa.flash_attention(jnp.zeros((4, 8, 2)), jnp.zeros((4, 8, 2)),
                           jnp.zeros((4, 8, 2)))


def test_rejects_mismatched_shapes():
    q = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="identical"):
        pa.flash_attention(q, jnp.zeros((1, 32, 2, 8)), q)
    with pytest.raises(ValueError, match="identical"):
        pa.flash_attention(q, q, jnp.zeros((1, 32, 2, 8)))
    # v may have a head width of its own (test_flash_attention_widths.py)
    assert pa.flash_attention(
        q, q, jnp.zeros((1, 16, 2, 4)), block_q=16, block_k=16
    ).shape == (1, 16, 2, 4)


class TestCausalTileWalk:
    """The compressed causal grid must (a) visit ~half the rectangular
    tile count (the DMA win), (b) keep each qi's ki sweep contiguous,
    ascending, starting at 0 (the VMEM scratch-carry contract), and
    (c) cover exactly the at-or-below-diagonal pairs."""

    def test_equal_blocks_triangle(self):
        n = 8
        qids, kids = pa._live_tiles(pa.CAUSAL, n, n, 128, 128)
        assert len(qids) == n * (n + 1) // 2  # vs n*n rectangular
        live = set(zip(qids.tolist(), kids.tolist()))
        expect = {(qi, ki) for qi in range(n) for ki in range(qi + 1)}
        assert live == expect

    def test_walk_order_contract(self):
        for (nq, nk, bq, bk) in [(8, 8, 128, 128), (4, 8, 256, 128),
                                 (8, 4, 128, 256), (5, 5, 64, 64)]:
            qids, kids = pa._live_tiles(pa.CAUSAL, nq, nk, bq, bk)
            # qi non-decreasing; within each qi, ki = 0, 1, 2, ...
            assert list(qids) == sorted(qids)
            for qi in range(nq):
                ks = [k for q, k in zip(qids, kids) if q == qi]
                assert ks == list(range(len(ks))) and ks[0] == 0
                # last ki is where the diagonal leaves this query tile
                assert ks[-1] == min(nk - 1, (qi * bq + bq - 1) // bk)

    def test_mismatched_blocks_parity(self):
        # block_q != block_k exercises the non-trivial diagonal-exit
        # arithmetic in the compressed walk
        q, k, v = make(200, seed=5)
        got = pa.flash_attention(q, k, v, causal=True,
                                 block_q=64, block_k=128)
        want = sequence._single_device_attention(
            q, k, v, causal=True, scale=None
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5
        )

    def test_rect_fallback_over_tile_cap(self, monkeypatch):
        # past _MAX_CAUSAL_TILES the compressed walk's index arrays
        # would strain scalar memory — the rectangular grid (matmul-skip
        # only) must take over with identical numerics
        monkeypatch.setattr(pa, "_MAX_CAUSAL_TILES", 3)
        q, k, v = make(200, seed=6)
        got = pa.flash_attention(q, k, v, causal=True,
                                 block_q=64, block_k=64)
        want = sequence._single_device_attention(
            q, k, v, causal=True, scale=None
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5
        )


class TestForwardBlocks:
    """A call that names no blocks gets the forward's from its shape
    (``forward_blocks``), and each backward kernel its own
    (``backward_blocks``); a call that names them gets them."""

    @pytest.mark.parametrize("itemsize", [2, 4])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("l", [1, 32, 100, 128, 300, 512, 640, 2048,
                                   8192])
    def test_choice_fits_the_shape(self, l, d, itemsize):
        padded = -(-l // 128) * 128
        for block in pa.forward_blocks(l, d, itemsize):
            assert block % 128 == 0 and 0 < block <= padded
            assert padded % block == 0  # no padding beyond 128's
        assert pa.forward_vmem_bytes(
            *pa.forward_blocks(l, d, itemsize), d, itemsize
        ) <= pa._FWD_VMEM_BUDGET < pa._VMEM_SCOPED_BYTES

    @pytest.mark.parametrize("itemsize", [2, 4])
    @pytest.mark.parametrize("d,dv", [(64, None), (128, None), (192, 128)])
    @pytest.mark.parametrize("l", [1, 32, 100, 128, 300, 512, 640, 2048,
                                   8192])
    def test_backward_choice_fits_the_shape(self, l, d, dv, itemsize):
        padded = -(-l // 128) * 128
        chosen = pa.backward_blocks(l, d, itemsize, dv)
        assert sorted(chosen) == ["dkv", "dq"]
        for kernel, blocks in chosen.items():
            for block in blocks:
                assert block % 128 == 0 and 0 < block <= padded
                assert padded % block == 0  # no padding beyond 128's
            assert pa.backward_vmem_bytes(
                kernel, *blocks, d, itemsize, dv
            ) <= pa._BWD_VMEM_BUDGET < pa._BWD_VMEM_SCOPED_BYTES

    def test_choice_at_the_timed_shape(self):
        # the benchmark cell's call: 320 grid steps where 128 x 128
        # tiles took 4,352 (PERF.md section 6, PR 31)
        assert pa.forward_blocks(2048, 128, 2) == (512, 512)
        walk = len(pa._live_tiles(pa.CAUSAL, 4, 4, 512, 512)[0])
        assert 32 * walk == 320

    # (length, causal) -> the chosen tiles, and how many of the walk's
    # tile pairs take the masked and the unmasked branch of the kernel:
    # the diagonal's and the padding's (1100, causal), the padding's
    # alone (1100, full), the diagonal's alone (1280, causal), none
    # (1280, full)
    CASES = {
        (1100, True): ((384, 384), 3, 3),
        (1100, False): ((384, 384), 3, 6),
        (1280, True): ((256, 256), 5, 10),
        (1280, False): ((256, 256), 0, 25),
    }

    @pytest.mark.parametrize("l,causal", list(CASES))
    def test_default_blocks_match_oracle(self, l, causal):
        (bq, bk), masked, unmasked = self.CASES[(l, causal)]
        assert pa.forward_blocks(l, D, 4) == (bq, bk)
        n_q, n_k = -(-l // bq), -(-l // bk)
        pairs = (zip(*pa._live_tiles(pa.CAUSAL, n_q, n_k, bq, bk)) if causal else
                 ((qi, ki) for qi in range(n_q) for ki in range(n_k)))
        took = [bool(pa._holds_masked_scores(
            int(qi), int(ki), rule=pa.CAUSAL if causal else pa.FULL,
            block_q=bq, block_k=bk,
            n_k=n_k, pad_k=n_k * bk - l)) for qi, ki in pairs]
        assert (sum(took), len(took) - sum(took)) == (masked, unmasked)

        q, k, v = make(l, seed=12, b=1, h=1)
        w = make(l, seed=13, b=1, h=1)[0]
        oracle = lambda q, k, v: sequence._single_device_attention(
            q, k, v, causal=causal, scale=None)
        flash = lambda q, k, v: pa.flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)), np.asarray(oracle(q, k, v)),
            atol=2e-5)
        grads = lambda f: jax.grad(
            lambda q, k, v: jnp.sum(w * f(q, k, v)), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b, name in zip(grads(flash), grads(oracle), "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name}")

    @staticmethod
    def _program(l, **blocks):
        q = jnp.zeros((1, l, 1, D))
        return str(jax.make_jaxpr(jax.grad(lambda q: pa.flash_attention(
            q, q, q, causal=True, **blocks).sum()))(q))

    def test_named_blocks_give_todays_walk(self):
        program = self._program(200, block_q=64, block_k=128)
        walk = len(pa._live_tiles(pa.CAUSAL, 4, 2, 64, 128)[0])
        assert "name=flash_fwd_q64_k128" in program
        assert f"grid=(1, {walk})" in program
        # the backward scans the key blocks the caller named
        assert re.search(r"length=2\b", program)

    def test_backward_keeps_128_when_none_is_named(self):
        program = self._program(1280)
        assert "name=flash_fwd_q256_k256" in program
        assert "length=10" in program  # 1280 / 128 key blocks


class TestPallasBackward:
    """backward="pallas": the fused two-kernel VJP must match both the
    oracle's grads and the XLA-scan VJP it can replace."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("l,bq,bk", [(128, 64, 64), (200, 64, 128)])
    def test_grads_match_oracle(self, causal, l, bq, bk):
        q, k, v = make(l, seed=8)
        wgt = jnp.asarray(
            np.random.default_rng(9).standard_normal(q.shape), jnp.float32
        )

        def loss(fn):
            return lambda q, k, v: jnp.sum(wgt * fn(q, k, v))

        g_p = jax.grad(loss(lambda q, k, v: pa.flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk,
            backward="pallas")), argnums=(0, 1, 2))(q, k, v)
        g_o = jax.grad(loss(lambda q, k, v: sequence._single_device_attention(
            q, k, v, causal=causal, scale=None)), argnums=(0, 1, 2))(q, k, v)
        g_x = jax.grad(loss(lambda q, k, v: pa.flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk,
            backward="xla")), argnums=(0, 1, 2))(q, k, v)
        for gp, go, gx, nm in zip(g_p, g_o, g_x, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gp), np.asarray(go), atol=3e-5,
                err_msg=f"d{nm} pallas-vs-oracle (causal={causal})",
            )
            np.testing.assert_allclose(
                np.asarray(gp), np.asarray(gx), atol=3e-5,
                err_msg=f"d{nm} pallas-vs-xla (causal={causal})",
            )

    def test_rejects_bad_backward(self):
        q = jnp.zeros((1, 16, 2, 8))
        with pytest.raises(ValueError, match="backward"):
            pa.flash_attention(q, q, q, backward="cuda")

    def test_kv_tile_walk_contract(self):
        # transposed enumeration for dK/dV: ki groups contiguous, qi
        # ascending from the first query tile reaching the KV columns
        for (nq, nk, bq, bk) in [(8, 8, 128, 128), (4, 8, 256, 128),
                                 (8, 4, 128, 256)]:
            kis, qis = pa._live_tiles(pa.CAUSAL, nq, nk, bq, bk, by_key=True)
            assert list(kis) == sorted(kis)
            for ki in range(nk):
                qs = [q for k2, q in zip(kis, qis) if k2 == ki]
                lo = (ki * bk) // bq
                assert qs == list(range(lo, nq))
            # same live set as the forward walk, transposed
            fwd = set(zip(*pa._live_tiles(pa.CAUSAL, nq, nk, bq, bk)))
            assert {(q2, k2) for k2, q2 in zip(kis, qis)} == fwd

    @pytest.mark.parametrize("l,bq,bk", [(256, 128, 128), (300, 64, 128)])
    def test_compressed_backward_matches_rect(self, l, bq, bk, monkeypatch):
        # compressed causal backward (DMA-skip walks) vs the rectangular
        # fallback: identical numerics
        q, k, v = make(l, seed=10)
        wgt = jnp.asarray(
            np.random.default_rng(11).standard_normal(q.shape), jnp.float32
        )

        def grads():
            return jax.grad(
                lambda q, k, v: jnp.sum(wgt * pa.flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk,
                    backward="pallas")),
                argnums=(0, 1, 2),
            )(q, k, v)

        g_compressed = grads()
        monkeypatch.setattr(pa, "_MAX_CAUSAL_TILES", 0)  # force rect
        g_rect = grads()
        for gc, gr, nm in zip(g_compressed, g_rect, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gc), np.asarray(gr), atol=1e-5,
                err_msg=f"d{nm}",
            )

"""``models.block_diffusion_lm.BlockDiffusionMoELM`` at a small size on
the CPU, seeded random weights, float32: against the plain reference
(``chipbench/reference_block_diffusion_lm.py``: dense mask, repeated k
and v, experts by a Python loop) in outputs, loss and gradients; what the
mask means, as properties of the outputs; the shares of a layer's
experts adding up to the uncut layer; no pair dropped under the worst
routing; the balance loss over the global batch on two devices; k and v
at their own heads in every kernel call of the step; the noising
transform.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import nnx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference_block_diffusion_lm as reference  # noqa: E402
from tpu_syncbn import parallel, runtime  # noqa: E402
from tpu_syncbn.data.transforms import BlockDiffusionNoise  # noqa: E402
from tpu_syncbn.models import block_diffusion_lm  # noqa: E402
from tpu_syncbn.models.block_diffusion_lm import BlockDiffusionMoELM  # noqa: E402
from tpu_syncbn.obs import tracing  # noqa: E402

SIZES = dict(vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
             head_dim=8, num_layers=2, block_length=4, n_experts=8,
             experts_held=4, first_expert=2, experts_per_token=2,
             moe_intermediate=16, aux_weight=0.01, embed_std=1.0)
REF = dict(heads=4, kv_heads=2, theta=1e4, eps=1e-6, block=4,
           moe=dict(top_k=2, first_expert=2))
MASK = SIZES["vocab_size"] - 1
L = 16


def make(seed=0, **sizes):
    return BlockDiffusionMoELM(**{**SIZES, **sizes}, rngs=nnx.Rngs(seed))


def batch(n=2, seed=0, length=L):
    rng = np.random.default_rng(seed)
    noise = BlockDiffusionNoise(block=4, mask_id=MASK, seed=seed)
    samples = [noise((rng.integers(0, MASK, size=length, dtype=np.int32), i))
               for i in range(n)]
    return tuple(jnp.asarray(np.stack(a)) for a in zip(*samples))


def pure(model) -> dict:
    return nnx.to_pure_dict(nnx.state(model, nnx.Param))


# -- against the plain reference --------------------------------------------


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
def test_outputs_loss_and_gradients_match_the_plain_reference(attn_impl):
    model = make(attn_impl=attn_impl)
    x0, xt, w = batch()
    want = reference.forward(pure(model), x0, xt, w, aux_weight=0.01,
                             dtype=jnp.float32, **REF)
    h, _ = model.hidden(x0, xt)
    np.testing.assert_allclose(
        model.read(h[:, L:], model.final_norm[...]), want["z"], atol=2e-5)
    np.testing.assert_allclose(
        model(x0, xt), reference.head_logits(pure(model), want["z"],
                                             jnp.float32), atol=2e-5)
    loss, metrics = model.loss(x0, xt, w)
    np.testing.assert_allclose(loss, want["loss"], rtol=1e-5)
    np.testing.assert_allclose(metrics["diffusion_loss"], want["diffusion"],
                               rtol=1e-5)
    np.testing.assert_allclose(metrics["aux_loss"], want["aux"], rtol=1e-5)
    assert float(metrics["pairs_not_computed"]) == 0.0
    assert float(metrics["masked_share"]) == float(jnp.mean(w > 0))

    graphdef, params, rest = nnx.split(model, nnx.Param, ...)
    got = jax.grad(lambda p: nnx.merge(graphdef, p, rest, copy=True).loss(
        x0, xt, w)[0])(params)
    ref = jax.grad(lambda p: reference.forward(
        p, x0, xt, w, aux_weight=0.01, dtype=jnp.float32, **REF)["loss"])(
            pure(model))
    flat = jax.tree_util.tree_leaves_with_path(nnx.to_pure_dict(got))
    assert len(flat) == 15
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref)):
        assert float(jnp.max(jnp.abs(r))) > 0, path  # every leaf is reached
        np.testing.assert_allclose(g, r, atol=1e-5 + 1e-4 * float(
            jnp.max(jnp.abs(r))), err_msg=jax.tree_util.keystr(path))


def test_the_first_layer_opened_up_matches_the_references_pieces():
    model = make()
    x0, xt, _ = batch()
    x = model.embed_tokens(jnp.concatenate([x0, xt], axis=1))
    parts = model.layer_parts(x)
    first = jax.tree_util.tree_map(lambda a: a[0], pure(model)["layers"])
    kw = {k: REF[k] for k in ("heads", "kv_heads", "theta", "eps")}
    for got, want in zip((parts["q"], parts["k"], parts["v"]),
                         reference.gqa_qkv(first, x, dtype=jnp.float32, **kw)):
        np.testing.assert_allclose(got, want, atol=1e-5)
    assert parts["k"].shape == (2, 2 * L, 2, 8)  # k and v at their 2 heads
    np.testing.assert_allclose(
        parts["attention"],
        reference.attention(parts["q"], parts["k"], parts["v"], 4), atol=1e-5)
    mixed, weights, probs = reference.mixture(
        parts["router_in"], first, dtype=jnp.float32, **REF["moe"])
    np.testing.assert_allclose(parts["moe"], mixed, atol=1e-5)
    np.testing.assert_allclose(
        reference.dense_weights(parts["idx"].reshape(-1, 2),
                                parts["gates"].reshape(-1, 2), 8),
        weights, atol=1e-6)
    np.testing.assert_array_equal(parts["load"],
                                  reference.balance_loss(weights, probs)[1])
    np.testing.assert_allclose(parts["mean_probs"], jnp.mean(probs, axis=0),
                               atol=1e-6)


# -- what the mask means ----------------------------------------------------


def changed(model, x0, xt, x0_b, xt_b) -> tuple:
    """Which positions' outputs differ between two inputs: (clean half,
    noisy half), each (L,) booleans."""
    a, _ = model.hidden(x0, xt)
    b, _ = model.hidden(x0_b, xt_b)
    moved = np.asarray(jnp.any(jnp.abs(a - b) > 1e-6, axis=-1))[0]
    return moved[:L], moved[L:]


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
def test_the_masks_meaning_as_properties_of_the_outputs(attn_impl):
    """All 8 experts held, so that nothing but attention carries a token
    from one position to another and a changed token changes whatever
    sees it."""
    model = make(attn_impl=attn_impl, experts_held=8, first_expert=0)
    x0, xt, _ = batch(n=1)
    blocks = np.arange(L) // 4
    b = 1  # the block whose token changes, at position 5
    other = x0.at[0, 5].set((x0[0, 5] + 1) % MASK)
    # a clean token of block b changes no output of noisy block b (a
    # noisy token never sees its own block's answer), changes those of
    # later noisy blocks, and on the clean side its own and later blocks
    clean, noisy = changed(model, x0, xt, other, xt)
    assert not noisy[blocks <= b].any()
    assert noisy[blocks > b].all()
    assert clean[5] and clean[blocks > b].all() and not clean[blocks < b].any()
    # a noisy token changes only its own block's noisy outputs, and no
    # clean output depends on any noisy token
    other = xt.at[0, 5].set((xt[0, 5] + 1) % MASK)
    clean, noisy = changed(model, x0, xt, x0, other)
    assert not clean.any()
    assert noisy[blocks == b].all() and not noisy[blocks != b].any()


def test_position_i_of_each_half_is_at_rotary_position_i():
    """With the noisy half a copy of the clean one and one block a
    sequence, a noisy position reads what its clean twin reads, rotated
    alike: the two halves' outputs are equal."""
    model = make(block_length=L, experts_held=8, first_expert=0)
    x0, _, _ = batch(n=1)
    h, _ = model.hidden(x0, x0)
    # block 0 of the noisy half sees no clean block and all of its own;
    # the clean half sees its one block whole: the same keys, values and
    # angles
    np.testing.assert_allclose(h[:, :L], h[:, L:], atol=1e-5)


# -- the chip's share ---------------------------------------------------------


def test_four_shares_of_four_sum_to_the_uncut_layer():
    """A 16-expert layer cut four ways: each share routes over all 16
    and computes its own 4; what the four give adds up to the reference's
    mixture with all 16 held."""
    sizes = dict(n_experts=16, experts_per_token=3, num_layers=1)
    whole = make(experts_held=16, first_expert=0, **sizes)
    x0, xt, _ = batch()
    x = whole.embed_tokens(jnp.concatenate([x0, xt], axis=1))
    state = pure(whole)
    total = 0.0
    for first in (0, 4, 8, 12):
        share = make(experts_held=4, first_expert=first, **sizes)
        cut = jax.tree_util.tree_map(lambda a: a, state)
        for name in ("eg", "eu", "ed"):
            cut["layers"][name] = state["layers"][name][:, first:first + 4]
        nnx.update(share, cut)
        parts = share.layer_parts(x)
        assert float(parts["pairs_not_computed"]) == 0.0
        total = total + parts["moe"]
    layer = jax.tree_util.tree_map(lambda a: a[0], state["layers"])
    uncut = reference.mixture(whole.layer_parts(x)["router_in"], layer,
                              top_k=3, first_expert=0, dtype=jnp.float32)[0]
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    np.testing.assert_allclose(whole.layer_parts(x)["moe"], uncut, atol=1e-5)


def test_no_pair_is_dropped_when_every_token_chooses_the_same_experts():
    """A router of zeros: every probability equal, ties to the lower
    index, so every token chooses experts 0 and 1, both held: all 2T
    pairs arrive on two experts, none is dropped, and the mixture is the
    reference's."""
    model = make(first_expert=0, num_layers=1)
    model.layers.router[...] = jnp.zeros_like(model.layers.router[...])
    x0, xt, w = batch()
    parts = model.layer_parts(
        model.embed_tokens(jnp.concatenate([x0, xt], axis=1)))
    assert set(np.asarray(parts["idx"]).ravel()) == {0, 1}
    np.testing.assert_array_equal(
        parts["load"], [4 * L, 4 * L, 0, 0, 0, 0, 0, 0])
    assert float(parts["pairs_not_computed"]) == 0.0
    layer = jax.tree_util.tree_map(lambda a: a[0], pure(model)["layers"])
    want = reference.mixture(parts["router_in"], layer, top_k=2,
                             first_expert=0, dtype=jnp.float32)[0]
    np.testing.assert_allclose(parts["moe"], want, atol=1e-5)
    _, metrics = model.loss(x0, xt, w)
    assert float(metrics["pairs_not_computed"]) == 0.0
    # everything on 2 of 8 experts: max over mean 4; the probabilities
    # are flat, so the balance loss reads 8 x (1/2 x 1/8 + 1/2 x 1/8) = 1
    assert float(metrics["expert_load_max_over_mean"]) == 4.0
    np.testing.assert_allclose(metrics["aux_loss"], 1.0, rtol=1e-6)


def test_the_balance_loss_reads_the_global_batchs_loads_on_two_devices():
    model = make()
    x0, xt, w = batch(n=2, seed=3)
    # each sample alone, outside any mesh: a replica's own loads
    alone = [model.hidden(x0[i:i + 1], xt[i:i + 1])[1] for i in range(2)]
    loads = [a[0] for a in alone]
    probs = [a[1] for a in alone]
    balance = lambda load, p: 8 * jnp.sum(
        load / jnp.sum(load, -1, keepdims=True) * p, axis=-1)
    over_global = np.mean([balance(loads[0] + loads[1], p) for p in probs])
    per_replica = np.mean([balance(l, p) for l, p in zip(loads, probs)])
    assert abs(over_global - per_replica) > 1e-3

    dp = parallel.DataParallel(make(), optax.sgd(0.0),
                               lambda m, b: m.loss(*b),
                               mesh=runtime.data_parallel_mesh(2))
    out = dp.train_step(jax.device_put((x0, xt, w), dp.batch_sharding))
    np.testing.assert_allclose(out.metrics["aux_loss"], over_global,
                               rtol=1e-5)
    # and the loads kept in ``rest`` are the global batch's
    rest = nnx.to_pure_dict(dp.rest)["layers"]
    np.testing.assert_allclose(rest["load"], loads[0] + loads[1])
    np.testing.assert_allclose(rest["recent_load"][:, -1],
                               loads[0] + loads[1])
    assert float(jnp.sum(rest["recent_load"][:, :-1])) == 0.0


# -- the kernels' operands ------------------------------------------------------


def pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation of a jaxpr, through the scans,
    checkpoints and custom derivatives that hold them."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += pallas_calls(inner)
    return found


def test_k_and_v_reach_and_leave_every_kernel_at_their_own_heads():
    """The model's step with the kernels (traced, not run): 4 q heads
    over 2 k/v heads, a batch of 2. In each of the three kernels the k
    and v operands, and dK/dV's results, have B x 2 batch-heads; q, dO,
    the output and dq B x 4 (dK/dV reads them by k/v head, the group's q
    heads along the length)."""
    model = make(attn_impl="flash")
    x0, xt, w = batch()
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: nnx.merge(
        graphdef, p, rest, copy=True).loss(x0, xt, w)[0]))(params)
    calls = pallas_calls(jaxpr.jaxpr)
    names = [c.params["name"] for c in calls]
    # a layer's forward (once: the layer's checkpoint keeps its output
    # and log-sum-exp), dK/dV and dQ, each in one scan body
    assert sorted(names) == ["flash_bwd_dkv_q128_k128",
                             "flash_bwd_dq_q128_k128", "flash_fwd_q128_k128"]
    heads = lambda v: v.aval.shape[0]
    for call, name in zip(calls, names):
        q, k, v = call.invars[2:5]
        assert (heads(k), heads(v)) == (4, 4), name  # B x 2 k/v heads
        if "dkv" in name:
            assert heads(q) == 4 and q.aval.shape[1] == 2 * 128
            assert [heads(o) for o in call.outvars] == [4, 4]
        else:
            assert heads(q) == 8 and heads(call.outvars[0]) == 8


# -- the noising ----------------------------------------------------------------


def test_the_noising_repeats_and_weighs_by_one_over_t():
    rng = np.random.default_rng(0)
    x0 = rng.integers(0, 1000, size=4096, dtype=np.int32)
    noise = BlockDiffusionNoise(block=512, mask_id=1000, seed=7)
    a0, xt, w = noise((x0, 11))
    assert a0 is not None and np.array_equal(a0, x0)
    assert xt.dtype == x0.dtype and w.dtype == np.float32
    again = noise((x0, 11))
    assert np.array_equal(again[1], xt) and np.array_equal(again[2], w)
    other = noise((x0, 12))
    assert not np.array_equal(other[1], xt)
    assert not np.array_equal(
        BlockDiffusionNoise(block=512, mask_id=1000, seed=8)((x0, 11))[1], xt)
    masked = xt == 1000
    # the mask id nowhere in x0, x0 wherever xt is not the mask, a
    # weight exactly where a token was replaced
    assert not (x0 == 1000).any()
    assert np.array_equal(xt[~masked], x0[~masked])
    assert np.array_equal(w > 0, masked)
    # one noise level a block: its weight is 1 / t, and the block's
    # masked share follows its t (512 draws: within 0.1 of it)
    for b in range(8):
        at = slice(512 * b, 512 * (b + 1))
        levels = np.unique(w[at][masked[at]])
        assert len(levels) == 1
        t = 1.0 / levels[0]
        assert 1e-3 <= t <= 1.0
        assert abs(masked[at].mean() - t) < 0.1
    with pytest.raises(ValueError, match="mask id"):
        noise((np.full(8, 1000, np.int32), 0))
    with pytest.raises(ValueError):
        BlockDiffusionNoise(block=0, mask_id=5, seed=0)


def test_the_noising_masks_half_on_average_and_a_short_last_block():
    noise = BlockDiffusionNoise(block=4, mask_id=99, seed=1)
    shares = [np.mean(noise((np.zeros(4094, np.int32), k))[1] == 99)
              for k in range(8)]
    assert 0.47 < np.mean(shares) < 0.53  # E[t] = 0.5005
    _, xt, w = noise((np.zeros(6, np.int32), 3))
    assert xt.shape == w.shape == (6,)


def test_the_noising_opens_a_noise_span_with_the_share_it_masked():
    tracer = tracing.install(tracing.Tracer())
    try:
        with tracing.span("loader.build"):
            _, xt, _ = BlockDiffusionNoise(block=4, mask_id=99, seed=1)(
                (np.zeros(64, np.int32), 5))
    finally:
        tracing.uninstall()
    (span,) = tracer.spans("noise")
    (build,) = [e for e in tracer.events if e["name"] == "loader.build"]
    event = [e for e in tracer.events if e["name"] == "noise"][0]
    assert event["args"]["masked"] == float(np.mean(xt == 99))
    assert event["args"]["parent_id"] == build["args"]["span_id"]
    assert span[0] == "noise" and span[2] >= span[1]


# -- what the layer's checkpoint keeps ------------------------------------------


def loss_and_grads(model, x0, xt, w):
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)
    return jax.value_and_grad(lambda p: nnx.merge(
        graphdef, p, rest, copy=True).loss(x0, xt, w)[0])(params)


def keeps_the_kernels_output_one_row_and_the_projection(monkeypatch):
    """A layer application under the model's own checkpoint: beside its
    arguments the backward pass keeps exactly the kernel's output (BH,
    2L, head width), its log-sum-exp as a (BH, 2L) float32 row and the
    output projection's result (B, 2L, H): no (BH, 2L, 1) column,
    nothing as wide as an expert, the router or the vocabulary. With
    nothing saved the step runs the forward kernel once more a layer
    application."""
    from tests.test_looped_lm import computed_residuals

    model, (x0, xt, w) = make(attn_impl="flash"), batch()
    x = model.embed_tokens(jnp.concatenate([x0, xt], axis=1))
    p = jax.tree_util.tree_map(lambda a: a[0], model.layers.stacked())
    layer = block_diffusion_lm.checkpointed(model._layer,
                                            block_diffusion_lm._saved())
    kept = computed_residuals(layer, x, p, *model._angles(2 * L))
    rows = x0.shape[0] * SIZES["num_heads"]
    assert sorted(kept) == sorted([
        (rows, 2 * L), (rows, 2 * L, SIZES["head_dim"]), x.shape])
    calls = lambda: str(jax.make_jaxpr(lambda m: loss_and_grads(
        m, x0, xt, w))(make(attn_impl="flash"))).count("name=flash_fwd")
    ours = calls()  # the scan's body holds its call once
    monkeypatch.setattr(block_diffusion_lm, "_saved", lambda: ())
    assert calls() == 2 * ours == 2


def equals_the_plain_checkpoint_bit_for_bit(monkeypatch):
    """In bfloat16, the type the chip runs: the saved output and
    log-sum-exp are the values the recomputation would give, so loss and
    every gradient are those of a plain ``jax.checkpoint(fn)`` around the
    same layer."""
    model = lambda: make(attn_impl="flash", dtype=jnp.bfloat16)
    loss, grads = loss_and_grads(model(), *batch())
    monkeypatch.setattr(block_diffusion_lm, "checkpointed",
                        lambda fn, saved=None: jax.checkpoint(fn))
    want_loss, want = loss_and_grads(model(), *batch())
    assert float(loss) == float(want_loss)
    jax.tree_util.tree_map(np.testing.assert_array_equal, grads, want)


@pytest.mark.parametrize(
    "check", [keeps_the_kernels_output_one_row_and_the_projection,
              equals_the_plain_checkpoint_bit_for_bit],
    ids=lambda c: c.__name__)
def test_the_layers_checkpoint_keeps_the_attention_kernels_results(
        check, monkeypatch):
    check(monkeypatch)

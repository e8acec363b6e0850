"""``models.moe_lm.LatentMoEDecoderLM`` and the routed-expert layer of
``parallel.expert`` against the plain reference
(``chipbench/reference_moe_lm.py``, which shares no code with either), at
a small size in float32 on the CPU: outputs, loss and gradients; the
shares of an expert-parallel deployment add up to the uncut layer; no
pair is dropped under the worst imbalance; the selection bias moves
against the global batch's load.
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

from chipbench import reference_moe_lm as ref  # noqa: E402
from tpu_syncbn import parallel, runtime  # noqa: E402
from tpu_syncbn.models import moe_lm  # noqa: E402
from tpu_syncbn.models.moe_lm import LatentMoEDecoderLM  # noqa: E402
from tpu_syncbn.parallel import expert  # noqa: E402

SIZES = dict(
    vocab_size=64, hidden_size=32, num_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_dim=8, dense_layers=1,
    dense_intermediate=48, moe_layers=2, n_experts=16, experts_held=4,
    first_expert=4, experts_per_token=3, moe_intermediate=16,
    shared_intermediate=16, routed_scale=2.5, mtp=True, mtp_weight=0.3,
    rope_theta=1e4)
REF = dict(heads=4, nope=8, rope=4, theta=1e4, eps=1e-6,
           moe=dict(top_k=3, scale=2.5, first_expert=4))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def pure(state) -> dict:
    return nnx.to_pure_dict(state)


@pytest.fixture(scope="module")
def model():
    return LatentMoEDecoderLM(**SIZES, rngs=nnx.Rngs(7))


@pytest.fixture(scope="module")
def batch():
    t = np.random.default_rng(3).integers(0, 64, (2, 26)).astype(np.int32)
    return t[:, :-2], t[:, 1:-1], t[:, 2:]


def reference_loss(params, biases, batch):
    return ref.forward(params, biases, *batch, mtp_weight=0.3,
                       dtype=jnp.float32, **REF)["loss"]


def split(model):
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)
    biases = {"sparse": pure(rest)["sparse"]["bias"],
              "mtp": pure(rest)["mtp_block"]["bias"]}
    return graphdef, params, rest, biases


def test_hidden_states_and_loss_match_the_reference(model, batch):
    _, params, _, biases = split(model)
    want = ref.forward(pure(params), biases, *batch, mtp_weight=0.3,
                       dtype=jnp.float32, **REF)
    h, (load, missed) = model.hidden(batch[0])
    h_mtp, _ = model.mtp_hidden(h, batch[1])
    assert rel(h, want["h"]) < 1e-5 and rel(h_mtp, want["h_mtp"]) < 1e-5
    assert load.shape == (2, 16) and float(jnp.sum(missed)) == 0.0
    # every token chooses exactly three experts in each layer
    assert np.allclose(np.asarray(load).sum(-1), 3 * batch[0].size)
    loss, metrics = nnx.merge(*nnx.split(model)).loss(*batch)
    assert abs(float(loss) - float(want["loss"])) < 1e-5
    assert abs(float(metrics["main_loss"]) - float(jnp.mean(want["ce"]))) < 1e-5
    assert abs(float(metrics["mtp_loss"])
               - float(jnp.mean(want["ce_mtp"]))) < 1e-5
    assert float(metrics["pairs_not_computed"]) == 0.0
    assert float(metrics["expert_load_max_over_mean"]) >= 1.0


def test_gradients_match_the_reference(model, batch):
    graphdef, params, rest, biases = split(model)

    def loss(p):
        return nnx.merge(graphdef, p, rest, copy=True).loss(*batch)[0]

    got = pure(jax.grad(loss)(params))
    want = jax.grad(reference_loss)(pure(params), biases, batch)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert set(flat_got) == set(flat_want) and len(flat_got) > 40
    for path, g in flat_got.items():
        assert np.linalg.norm(flat_want[path]) > 0, path  # every leaf learns
        assert rel(g, flat_want[path]) < 2e-4, (path, rel(g, flat_want[path]))


def test_the_remat_and_the_plain_model_agree(batch):
    plain = LatentMoEDecoderLM(**SIZES, remat=False, rngs=nnx.Rngs(7))
    remat = LatentMoEDecoderLM(**SIZES, remat=True, rngs=nnx.Rngs(7))

    def grads(m):
        graphdef, params, rest = nnx.split(m, nnx.Param, ...)
        return jax.grad(lambda p: nnx.merge(graphdef, p, rest, copy=True)
                        .loss(*batch)[0])(params)

    for a, b in zip(jax.tree_util.tree_leaves(grads(plain)),
                    jax.tree_util.tree_leaves(grads(remat))):
        assert rel(a, b) < 1e-5


# -- the routed-expert layer ---------------------------------------------------


def layer_inputs(t=40, h=16, e=16, f=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(0, 0.5, s), jnp.float32)
    return dict(x=mk(t, h), router=mk(h, e), eg=mk(e, h, f), eu=mk(e, h, f),
                ed=mk(e, f, h), sg=mk(h, f), su=mk(h, f), sd=mk(f, h))


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each: the routed parts of all shares
    plus the shared expert counted once equal the uncut reference's
    whole layer (all sixteen experts held by one)."""
    p = layer_inputs()
    bias = jnp.zeros(16)
    idx, gates = expert.sigmoid_topk_route(p["x"], p["router"], bias,
                                           top_k=3, scale=2.5)
    total = jnp.zeros_like(p["x"])
    for first in (0, 4, 8, 12):
        held = slice(first, first + 4)
        part, missed = expert.held_expert_moe(
            p["x"], idx, gates, p["eg"][held], p["eu"][held], p["ed"][held],
            first_expert=first, chunk=32)
        assert float(missed) == 0.0
        total = total + part
    total = total + ref.swiglu(p["x"], p["sg"], p["su"], p["sd"], jnp.float32)
    uncut, weights = ref.mixture(p["x"][None], p, bias, top_k=3, scale=2.5,
                                 first_expert=0, dtype=jnp.float32)
    assert rel(total, uncut[0]) < 1e-5
    assert np.all(np.sum(np.asarray(weights) > 0, axis=1) == 3)
    # one share alone is not the layer: the cut leaves something out
    assert rel(part, uncut[0]) > 0.1


@pytest.mark.parametrize("chunk", [120, 64, 24])
def test_no_pair_is_dropped_when_every_token_chooses_the_same_experts(chunk):
    """A bias that makes all 40 tokens choose experts 4, 5 and 6, all
    held: 120 of 120 pairs on three of the four experts held, walked in
    one, two and five chunks; every one is computed."""
    p = layer_inputs(seed=1)
    bias = jnp.zeros(16).at[4:7].set(10.0)
    idx, gates = expert.sigmoid_topk_route(p["x"], p["router"], bias,
                                           top_k=3, scale=2.5)
    assert np.all(np.sort(np.asarray(idx), axis=1) == [4, 5, 6])
    held = slice(4, 8)

    def routed(x, eg, eu, ed):
        return expert.held_expert_moe(x, idx, gates, eg, eu, ed,
                                      first_expert=4, chunk=chunk)

    args = (p["x"], p["eg"][held], p["eu"][held], p["ed"][held])
    got, missed = routed(*args)
    weights = ref.router(p["x"], p["router"], bias, top_k=3, scale=2.5)
    want = ref.experts(p["x"], weights, {k: p[k][held] for k in
                                         ("eg", "eu", "ed")},
                       first_expert=4, dtype=jnp.float32)
    assert float(missed) == 0.0 and rel(got, want) < 1e-5
    loads = np.asarray(expert.expert_loads(idx, 16))
    assert loads[4:7].tolist() == [40, 40, 40] and loads.sum() == 120
    # and backwards: the written-out backward pass of the chunked walk
    g_got = jax.grad(lambda *a: jnp.sum(routed(*a)[0] ** 2),
                     argnums=(0, 1, 2, 3))(*args)
    g_want = jax.grad(
        lambda x, eg, eu, ed: jnp.sum(ref.experts(
            x, weights, {"eg": eg, "eu": eu, "ed": ed}, first_expert=4,
            dtype=jnp.float32) ** 2), argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(g_got, g_want):
        assert rel(a, b) < 1e-5
    assert float(jnp.abs(g_got[1][3]).sum()) == 0.0  # expert 7: no pair


def test_a_token_none_of_whose_experts_is_held_gets_nothing():
    p = layer_inputs(seed=2)
    bias = jnp.zeros(16).at[0:3].set(10.0)  # all choose 0, 1, 2: none held
    idx, gates = expert.sigmoid_topk_route(p["x"], p["router"], bias,
                                           top_k=3, scale=2.5)
    got, missed = expert.held_expert_moe(
        p["x"], idx, gates, p["eg"][4:8], p["eu"][4:8], p["ed"][4:8],
        first_expert=4, chunk=16)
    assert float(jnp.abs(got).max()) == 0.0 and float(missed) == 0.0


def test_router_weights_are_normalised_scaled_and_the_bias_only_chooses():
    p = layer_inputs(seed=3)
    bias = jnp.asarray(np.random.default_rng(0).normal(0, 0.3, 16), jnp.float32)
    idx, gates = expert.sigmoid_topk_route(p["x"], p["router"], bias,
                                           top_k=3, scale=2.5)
    assert np.allclose(np.asarray(gates).sum(-1), 2.5, atol=1e-5)
    s = np.asarray(ref.router_scores(p["x"], p["router"]))
    top = np.argsort(-(s + np.asarray(bias)), axis=1, kind="stable")[:, :3]
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(top, 1))
    picked = np.take_along_axis(s, np.asarray(idx), axis=1)
    assert np.allclose(gates, 2.5 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)
    # no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(expert.sigmoid_topk_route(
        p["x"], p["router"], b, top_k=3, scale=2.5)[1] ** 2))(bias)
    assert float(jnp.abs(g).max()) == 0.0


def test_the_selection_bias_moves_against_the_load():
    load = jnp.asarray([[10.0, 2.0, 6.0, 6.0], [0.0, 0.0, 8.0, 0.0]])
    bias = expert.update_selection_bias(jnp.zeros((2, 4)), load, 0.001)
    want = [[-0.001, 0.001, 0.0, 0.0], [0.001, 0.001, -0.001, 0.001]]
    assert np.allclose(bias, want, atol=1e-9)


def test_on_two_devices_the_bias_reads_the_global_batchs_load(batch):
    """Through ``DataParallel`` on a two-replica mesh, one sequence a
    replica: after a step the cumulative load is the load of BOTH
    sequences and the bias has moved against it, the same on both
    devices."""
    model = LatentMoEDecoderLM(**SIZES, rngs=nnx.Rngs(7))
    _, (load, _) = model.hidden(batch[0])  # the global batch, one device
    h, _ = model.hidden(batch[0])
    _, (load_mtp, _) = model.mtp_hidden(h, batch[1])
    half, _ = model.hidden(batch[0][:1])[1]
    assert not np.allclose(half, load)
    runtime.initialize()
    dp = parallel.DataParallel(
        model, optax.adamw(1e-3), lambda m, b: m.loss(*b),
        mesh=runtime.data_parallel_mesh(2))
    out = dp.train_step(jax.device_put(batch, dp.batch_sharding))
    rest = pure(dp.rest)
    assert np.array_equal(rest["sparse"]["load"], load)
    assert np.array_equal(rest["mtp_block"]["load"], load_mtp)
    for name, want in (("sparse", load), ("mtp_block", load_mtp)):
        moved = -0.001 * np.sign(np.asarray(want)
                                 - np.asarray(want).mean(-1, keepdims=True))
        assert np.allclose(rest[name]["bias"], moved, atol=1e-9)
        shards = dp.rest[name]["bias"][...].addressable_shards
        assert len(shards) == 2
        assert np.array_equal(shards[0].data, shards[1].data)
    worst = max(float(np.max(l.max(-1) / l.mean(-1)))
                for l in (np.asarray(load), np.asarray(load_mtp)))
    assert float(out.metrics["expert_load_max_over_mean"]) == pytest.approx(
        worst, rel=1e-6)
    assert float(out.metrics["pairs_not_computed"]) == 0.0


def test_each_expert_layer_keeps_the_loads_of_its_last_steps(batch):
    """Three steps through ``DataParallel`` on one device: the newest of
    ``recent_load`` is the third step's load of all 16 experts, the one
    before it the second's, the steps never run are empty, and the kept
    steps add up to the cumulative load."""
    from tpu_syncbn.models.moe_lm import RECENT_STEPS

    model = LatentMoEDecoderLM(**SIZES, rngs=nnx.Rngs(7))
    runtime.initialize()
    dp = parallel.DataParallel(
        model, optax.adamw(1e-3), lambda m, b: m.loss(*b),
        mesh=runtime.data_parallel_mesh(1))
    seen = []
    for _ in range(3):
        before = np.asarray(pure(dp.rest)["sparse"]["load"])  # donated
        dp.train_step(jax.device_put(batch, dp.batch_sharding))
        seen.append(np.asarray(pure(dp.rest)["sparse"]["load"] - before))
    for name, layers in (("sparse", 2), ("mtp_block", 1)):
        recent = np.asarray(pure(dp.rest)[name]["recent_load"])
        assert recent.shape == (layers, RECENT_STEPS, 16)
        assert not recent[:, :-3].any() and recent[:, -3:].all(axis=1).any()
        assert np.array_equal(recent.sum(axis=1),
                              pure(dp.rest)[name]["load"])
        assert np.all(recent[:, -3:].sum(axis=-1) == 3 * batch[0].size)
    recent = np.asarray(pure(dp.rest)["sparse"]["recent_load"])
    assert [np.array_equal(recent[:, -3 + i], seen[i]) for i in range(3)] == [
        True] * 3


def test_an_opened_layer_may_be_any_of_the_stack(model, batch):
    """``expert_layer_parts`` takes the layer as a traced index (the
    benchmark opens the layer that holds most pairs) and ``run`` hands
    back each layer's input: layer 1 opened on its own input gives the
    stack's output."""
    layer1, _ = model.run(model.dense, model.embed_tokens(batch[0]))
    h, (load, _, inputs) = model.run(model.sparse, layer1, keep_inputs=True)
    assert inputs.shape == (2, *layer1.shape)
    assert np.array_equal(inputs[0], layer1)
    parts = jax.jit(lambda x, i: model.expert_layer_parts(x, i))(
        inputs[1], jnp.asarray(1))
    assert rel(parts["out"], h) < 1e-6
    assert np.array_equal(parts["load"], load[1])
    assert not np.array_equal(load[0], load[1])


def test_the_model_refuses_a_share_outside_the_routers_experts():
    with pytest.raises(ValueError, match="are not among"):
        LatentMoEDecoderLM(**{**SIZES, "first_expert": 14}, rngs=nnx.Rngs(0))
    with pytest.raises(ValueError, match="attn_impl"):
        LatentMoEDecoderLM(**SIZES, attn_impl="paged", rngs=nnx.Rngs(0))


def test_the_check_counts_a_pair_the_walk_does_not_reach(monkeypatch):
    """``pairs_not_computed`` is no constant: a walk that stops a chunk
    early (planted here) leaves the last chunk's held pairs out of the
    sum and the check reads exactly them."""
    p = layer_inputs(seed=1)
    bias = jnp.zeros(16).at[4:7].set(10.0)  # 120 held pairs
    idx, gates = expert.sigmoid_topk_route(p["x"], p["router"], bias,
                                           top_k=3, scale=2.5)
    args = (p["x"], idx, gates, p["eg"][4:8], p["eu"][4:8], p["ed"][4:8])
    whole, none_missed = expert.held_expert_moe(*args, first_expert=4,
                                                chunk=50)
    chunks = expert._chunks
    monkeypatch.setattr(expert, "_chunks", lambda s, c: chunks(s, c) - 1)
    short, missed = expert.held_expert_moe(*args, first_expert=4, chunk=50)
    assert float(none_missed) == 0.0 and float(missed) == 120 - 2 * 50
    assert rel(short, whole) > 1e-2


# -- what the layer's checkpoint keeps ------------------------------------------


def flash_model(**over):
    return LatentMoEDecoderLM(**{**SIZES, "attn_impl": "flash", **over},
                              rngs=nnx.Rngs(7))


def loss_and_grads(model, batch):
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)
    return jax.value_and_grad(lambda p: nnx.merge(
        graphdef, p, rest, copy=True).loss(*batch)[0])(params)


def keeps_the_kernels_output_and_one_row(batch, monkeypatch):
    """A layer application of each kind under the model's own
    checkpoint: beside its arguments the backward pass keeps exactly
    the kernel's output (BH, L, v width) and its log-sum-exp as a
    (BH, L) float32 row: no (BH, L, 1) column, nothing as wide as an
    expert, the router or the vocabulary. With nothing saved the step
    runs the forward kernel once more a layer application."""
    from tests.test_looped_lm import computed_residuals

    model = flash_model()
    x = model.embed_tokens(batch[0])
    b, s = batch[0].shape
    heads, width = SIZES["num_heads"], SIZES["v_dim"]
    for block in (model.dense, model.sparse):
        p = jax.tree_util.tree_map(lambda a: a[0], block.stacked())
        bias = block.bias[...][0] if block.moe else None
        layer = moe_lm.checkpointed(model._layer, moe_lm._saved())
        kept = computed_residuals(layer, x, p, bias, *model._angles(s))
        assert sorted(kept) == [(b * heads, s), (b * heads, s, width)]
    calls = lambda: str(jax.make_jaxpr(lambda m: loss_and_grads(m, batch))(
        flash_model())).count("name=flash_fwd")
    ours = calls()  # each of the three scans' bodies holds its call once
    monkeypatch.setattr(moe_lm, "_saved", lambda: ())
    assert calls() == 2 * ours == 6


def equals_the_plain_checkpoint_bit_for_bit(batch, monkeypatch):
    """In bfloat16, the type the chip runs: the saved output and
    log-sum-exp are the values the recomputation would give, so loss and
    every gradient are those of a plain ``jax.checkpoint(fn)`` around the
    same layer."""
    model = lambda: flash_model(dtype=jnp.bfloat16)
    loss, grads = loss_and_grads(model(), batch)
    monkeypatch.setattr(moe_lm, "checkpointed",
                        lambda fn, saved=None: jax.checkpoint(fn))
    want_loss, want = loss_and_grads(model(), batch)
    assert float(loss) == float(want_loss)
    jax.tree_util.tree_map(np.testing.assert_array_equal, grads, want)


@pytest.mark.parametrize("check", [keeps_the_kernels_output_and_one_row,
                                   equals_the_plain_checkpoint_bit_for_bit],
                         ids=lambda c: c.__name__)
def test_the_layers_checkpoint_keeps_the_attention_kernels_results(
        check, batch, monkeypatch):
    check(batch, monkeypatch)

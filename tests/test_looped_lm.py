"""``models.looped_lm.LoopedDecoderLM`` at small sizes on the CPU, seeded
random weights, float32: against the plain reference
(``chipbench/reference_lm.py``, which shares no code with the model), and
the loop's own properties: the gradient of a shared weight is the sum
over its uses, one loop is a plain decoder, recomputation and the choice
of attention change nothing but rounding.

Tolerances: everything is float32 on both sides, so what is left is the
order of accumulation (XLA's dot against the reference's at HIGHEST, the
flash kernel's tiles against a whole row). Outputs and losses agree to
1e-5 relative; gradients, which sum such differences over 16 layer
applications and 64 positions, to 1e-4 of the largest entry of a leaf.
"""

import contextlib
import io
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import nnx

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import reference_lm  # noqa: E402
from tpu_syncbn import parallel, runtime  # noqa: E402
from tpu_syncbn.models import looped_lm  # noqa: E402

SIZES = dict(vocab_size=96, hidden_size=32, num_heads=4, head_dim=8,
             intermediate_size=48, num_layers=2, rope_theta=1e4,
             exit_beta=0.1)
SEQ = 16


def make(loops=4, seed=0, **over):
    return looped_lm.LoopedDecoderLM(**{**SIZES, "loops": loops, **over},
                                     rngs=nnx.Rngs(seed))


def batch_of(n=2, seed=1):
    tokens = np.random.default_rng(seed).integers(
        0, SIZES["vocab_size"], (n, SEQ + 1), dtype=np.int32)
    return jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])


def reference(model, tokens, targets):
    return reference_lm.forward(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)), tokens, targets,
        num_heads=SIZES["num_heads"], loops=model.loops,
        theta=SIZES["rope_theta"], eps=1e-6, beta=SIZES["exit_beta"])


def loss_and_grads(model, batch):
    """The loss, its metrics and the gradient as nested dicts."""
    graphdef, params = nnx.split(model, nnx.Param)

    def lossed(p):
        return nnx.merge(graphdef, p).loss(*batch)

    (loss, metrics), grads = jax.value_and_grad(lossed, has_aux=True)(params)
    return loss, metrics, nnx.to_pure_dict(grads)


def assert_trees_close(got, want, rel):
    """Every leaf within ``rel`` of the leaf's largest magnitude."""
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a, b, atol=rel * scale, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))


# -- against the plain reference --------------------------------------------


@pytest.mark.parametrize("loops", [1, 4])
def test_outputs_and_loss_match_the_plain_reference(loops):
    model, (tokens, targets) = make(loops), batch_of()
    want = reference(model, tokens, targets)
    first = model.layer_parts(tokens)
    np.testing.assert_allclose(first["out"], want["layer1"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        first["attention"],
        reference_lm.attention(first["q"], first["k"], first["v"]),
        rtol=1e-5, atol=1e-6)
    hidden = model.hidden_passes(tokens)
    ce, lam = model.pass_losses(tokens, targets)
    p = looped_lm.exit_distribution(lam)
    for t in range(loops):
        z = model.read(hidden[t])
        np.testing.assert_allclose(z, want["z"][t], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            model.logits(z), reference_lm.head_logits(
                {"head": model.head[...]}, want["z"][t], jnp.float32),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(ce[t], want["ce"][t], rtol=1e-5)
        np.testing.assert_allclose(p[t], want["p"][t], rtol=1e-5)
    loss, metrics = model.loss(tokens, targets)
    np.testing.assert_allclose(loss, want["loss"], rtol=1e-6)
    np.testing.assert_allclose(model(tokens), model.logits(
        model.read(hidden[-1])), rtol=1e-6)
    assert set(metrics) == {"exit_entropy"} | {
        f"{name}_{t}" for name in ("pass_loss", "exit_p")
        for t in range(1, loops + 1)}
    np.testing.assert_allclose(
        sum(metrics[f"exit_p_{t}"] for t in range(1, loops + 1)), 1.0,
        rtol=1e-6)


def test_gradients_match_the_plain_reference():
    model, batch = make(), batch_of()
    _, _, grads = loss_and_grads(model, batch)
    params = nnx.to_pure_dict(nnx.state(model, nnx.Param))
    want = jax.grad(lambda p: reference_lm.forward(
        p, *batch, num_heads=SIZES["num_heads"], loops=4,
        theta=SIZES["rope_theta"], eps=1e-6,
        beta=SIZES["exit_beta"])["loss"])(params)
    assert_trees_close(grads, want, rel=1e-4)
    # every parameter is reached, the gate through passes 1..3
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(grads))


def test_one_loop_is_a_plain_decoder():
    """``loops=1``: the exit distribution is 1 whatever the gate says and
    the loss is the mean cross-entropy of the only pass (the entropy of a
    certain exit is 0)."""
    model, (tokens, targets) = make(loops=1), batch_of()
    loss, metrics = model.loss(tokens, targets)
    logits = model(tokens)
    plain = optax.softmax_cross_entropy_with_integer_labels(
        logits, targets).mean()
    np.testing.assert_allclose(loss, plain, rtol=1e-6)
    assert float(metrics["exit_p_1"]) == 1.0
    assert float(metrics["exit_entropy"]) == 0.0
    np.testing.assert_allclose(metrics["pass_loss_1"], plain, rtol=1e-6)


# -- the loop -------------------------------------------------------------------


def test_a_shared_weights_gradient_is_the_sum_over_its_uses():
    """The looped model against the same model unrolled with four untied
    copies of the stack, each used by one pass: the gradient of the
    shared stack is the sum of the four copies' gradients."""
    model, (tokens, targets) = make(), batch_of()
    graphdef, params = nnx.split(model, nnx.Param)
    _, _, shared = loss_and_grads(model, (tokens, targets))

    def untied_loss(copies):
        p = jax.tree_util.tree_map(lambda x: x, params)
        h, ces, lams = None, [], []
        for copy in copies:
            p["layers"] = copy
            m = nnx.merge(graphdef, p)
            h = m.stack(m.embed_tokens(tokens) if h is None else h)
            ce, lam = m.read_pass(h, targets)
            ces.append(ce)
            lams.append(lam)
        dist = looped_lm.exit_distribution(jnp.stack(lams))
        entropy = -jnp.sum(dist * jnp.log(dist), axis=0)
        return jnp.mean(jnp.sum(dist * jnp.stack(ces), axis=0)
                        - SIZES["exit_beta"] * entropy)

    copies = [params["layers"]] * 4
    loss = untied_loss(copies)
    np.testing.assert_allclose(loss, model.loss(tokens, targets)[0],
                               rtol=1e-6)
    per_copy = [nnx.to_pure_dict(g) for g in jax.grad(untied_loss)(copies)]
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_copy)
    assert_trees_close(shared["layers"], summed, rel=1e-5)
    # and no single use is the whole of it
    first = float(jnp.max(jnp.abs(per_copy[0]["wq"])))
    assert float(jnp.max(jnp.abs(shared["layers"]["wq"]
                                 - per_copy[0]["wq"]))) > 0.1 * first


@pytest.mark.parametrize("loops", [1, 2, 4])
def test_the_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest(
        loops):
    lam = jax.random.uniform(jax.random.key(loops), (loops, 3, 5),
                             minval=0.05, maxval=0.95)
    p = looped_lm.exit_distribution(lam)
    assert p.shape == lam.shape
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[-1], jnp.prod(1.0 - lam[:-1], axis=0),
                               rtol=1e-6)
    np.testing.assert_allclose(p[0], lam[0] if loops > 1 else 1.0)
    # the last gate value is never read
    again = looped_lm.exit_distribution(lam.at[-1].set(0.5))
    np.testing.assert_array_equal(p, again)


@pytest.mark.parametrize("variant", [
    dict(remat=False),
    dict(attn_impl="flash"),
    dict(attn_impl="flash", remat=False),
], ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items()))
def test_recomputation_and_the_attention_kernel_change_only_rounding(variant):
    """``jax.checkpoint`` around a layer application and a pass's head,
    and ``ops.pallas_attention.flash_attention`` (interpret mode here;
    its backward the kernel file's two backward kernels) in place of
    XLA's attention, inside the model: same loss, same gradients."""
    batch = batch_of()
    want_loss, _, want = loss_and_grads(make(), batch)
    loss, _, grads = loss_and_grads(make(**variant), batch)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert_trees_close(grads, want,
                       rel=1e-4 if "attn_impl" in variant else 1e-5)


# -- what the layer's checkpoint keeps ------------------------------------------

# the name of each output the layer marks, and the shape it has below
_NAMED = {"q": "heads", "k": "heads", "v": "heads", "attn_proj": "hidden",
          "mlp_out": "hidden"}


def computed_residuals(fn, *args):
    """The shapes ``jax.ad_checkpoint.print_saved_residuals`` lists for
    ``fn``'s backward pass, those that ``fn`` computed: arguments and
    constants, which cost nothing more to keep, are left out."""
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        jax.ad_checkpoint.print_saved_residuals(fn, *args)
    shapes = []
    for line in printed.getvalue().splitlines():
        aval, _, origin = line.partition(" ")
        if not origin.startswith(("from the argument", "from a constant",
                                  "from a literal")):
            dims = aval[aval.index("[") + 1:aval.index("]")]
            shapes.append(tuple(int(d) for d in dims.split(",") if d))
    return shapes


def plainly_checkpointed(monkeypatch):
    """Every ``remat`` of the model a plain ``jax.checkpoint(fn)``: what
    the layer had before it named its outputs."""
    monkeypatch.setattr(
        looped_lm.LoopedDecoderLM, "_checkpointed",
        lambda self, fn, saved=None: jax.checkpoint(fn) if self.remat else fn)


def lowered_step(model, batch) -> str:
    graphdef, params = nnx.split(model, nnx.Param)
    return jax.jit(jax.value_and_grad(
        lambda p: nnx.merge(graphdef, p).loss(*batch)[0])).lower(
            params).as_text()


@pytest.mark.parametrize("saved", [
    (), ("mlp_out",), ("q", "k"), ("v",), ("attn_proj",), None,
    tuple(_NAMED)], ids=lambda s: "default" if s is None else
    "+".join(s) or "nothing")
def test_the_layers_checkpoint_saves_the_named_outputs_and_nothing_wider(
        saved, monkeypatch):
    """One layer application under the model's own checkpoint: beside
    its arguments the backward pass keeps one tensor for each name in
    ``_SAVED``, of that output's shape, and none as wide as
    ``intermediate_size``; the lowered step runs one ``dot_general``
    fewer for each than under a plain ``jax.checkpoint(fn)``. (``q``
    and ``k`` pass one barrier together, so they are kept together: to
    recompute one is to recompute both.)"""
    if saved is not None:
        monkeypatch.setattr(looped_lm, "_SAVED", saved)
    saved = looped_lm._SAVED
    model, batch = make(loops=2, num_layers=3), batch_of()
    h = model.embed_tokens(batch[0])
    p = jax.tree_util.tree_map(lambda a: a[0], model._stacked())
    layer = model._checkpointed(model._layer, saved)
    kept = computed_residuals(layer, h, p, *model._angles(SEQ))
    shape = {"heads": (2, SEQ, SIZES["num_heads"], SIZES["head_dim"]),
             "hidden": (2, SEQ, SIZES["hidden_size"])}
    assert sorted(kept) == sorted(shape[_NAMED[name]] for name in saved)
    assert all(s[-1:] != (SIZES["intermediate_size"],) for s in kept)
    with_names = lowered_step(model, batch)
    plainly_checkpointed(monkeypatch)
    without = lowered_step(make(loops=2, num_layers=3), batch)
    assert (with_names.count("dot_general")
            == without.count("dot_general") - len(saved))


def test_the_head_is_checkpointed_plainly_and_the_layer_without_barriers(
        monkeypatch):
    """The lowered step keeps the barriers of the head's plain
    ``jax.checkpoint`` and has none for the layer's recomputation
    (``prevent_cse=False``: the layer runs inside a scan, where they buy
    nothing); the layer's own, around q and k, is in every step, with
    ``remat`` or without."""
    batch = batch_of()

    def barriers(model):
        return lowered_step(model, batch).count("optimization_barrier")

    ours, none = barriers(make()), barriers(make(remat=False))
    assert ours > none > 0
    plainly_checkpointed(monkeypatch)
    assert barriers(make()) > ours


@pytest.mark.parametrize("attn_impl", looped_lm.ATTN_IMPLS)
def test_saved_outputs_are_the_values_recomputation_would_give(
        attn_impl, monkeypatch):
    """In bfloat16, the type the chip runs: loss and every gradient with
    the policy are bit for bit those of a plain ``jax.checkpoint(fn)``
    around the same layer."""
    batch = batch_of()
    kwargs = dict(attn_impl=attn_impl, dtype=jnp.bfloat16)
    loss, _, grads = loss_and_grads(make(**kwargs), batch)
    plainly_checkpointed(monkeypatch)
    want_loss, _, want = loss_and_grads(make(**kwargs), batch)
    assert float(loss) == float(want_loss)
    jax.tree_util.tree_map(np.testing.assert_array_equal, grads, want)


def test_the_heads_checkpoint_keeps_nothing_of_vocabulary_width():
    """``read_pass`` under the model's checkpoint keeps its arguments and
    the parameters it closes over; through the whole loss no computed
    residual is as wide as the vocabulary (the logits of a pass) or as
    ``intermediate_size``."""
    model, (tokens, targets) = make(), batch_of()
    h = model.embed_tokens(tokens)
    assert computed_residuals(
        model._checkpointed(model.read_pass), h, targets) == []
    kept = computed_residuals(lambda m: m.loss(tokens, targets)[0], model)
    stacked = (model.loops, SIZES["num_layers"], 2, SEQ)
    assert sum(s[:4] == stacked for s in kept) == 1 + len(looped_lm._SAVED)
    wide = ((SIZES["vocab_size"],), (SIZES["intermediate_size"],))
    assert not any(s[-1:] in wide for s in kept)
    # and with no recomputation the logits are kept: the check can see them
    free = computed_residuals(lambda m: m.loss(tokens, targets)[0],
                              make(remat=False))
    assert any(s[-1:] == wide[0] for s in free)


def test_rotary_is_a_complex_rotation_of_paired_dimensions():
    """Dimension i and i + d/2 of a head are the real and imaginary part
    of one complex number, multiplied by exp(i * pos * theta^(-2i/d))."""
    d, s, theta = 8, 6, 1e4
    x = jax.random.normal(jax.random.key(0), (2, s, 3, d))
    got = looped_lm.apply_rotary(x, *looped_lm.rotary_angles(s, d, theta))
    xn = np.asarray(x, np.float64)
    z = xn[..., : d // 2] + 1j * xn[..., d // 2:]
    freq = theta ** (-2.0 * np.arange(d // 2) / d)
    turned = z * np.exp(1j * np.arange(s)[None, :, None, None] * freq)
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])  # position 0 stays
    np.testing.assert_allclose(  # the reference's own rotary is the same
        reference_lm.rotary(x, theta), want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["ring", "flash_pallas_bwd"])
def test_an_unknown_attention_is_refused(impl):
    """``flash_pallas_bwd`` among them: since PR 35 ``flash`` itself
    differentiates through the kernel's own two backward kernels
    (PERF.md section 6), so there is no second value to name."""
    with pytest.raises(ValueError, match="attn_impl"):
        make(attn_impl=impl)


# -- through the trainer --------------------------------------------------------


def test_data_parallel_steps_it_and_carries_the_pass_metrics():
    """Eight replicas, one sequence each: the step's loss is the
    single-device loss of the global batch, the metrics come out through
    ``StepOutput.metrics``, and AdamW moves every parameter."""
    mesh = runtime.data_parallel_mesh()
    n = mesh.devices.size
    batch = batch_of(n)
    want_loss, want_metrics, _ = loss_and_grads(make(), batch)
    dp = parallel.DataParallel(
        make(), optax.adamw(1e-3), lambda m, b: m.loss(*b), mesh=mesh)
    before = jax.tree_util.tree_map(np.asarray, dp.params)
    out = dp.train_step(jax.device_put(batch, dp.batch_sharding))
    np.testing.assert_allclose(out.loss, want_loss, rtol=1e-5)
    assert set(want_metrics) <= set(out.metrics)
    for name, want in want_metrics.items():
        np.testing.assert_allclose(out.metrics[name], want, rtol=1e-5,
                                   err_msg=name)
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(np.any(np.asarray(a) != b)), dp.params, before)
    assert all(jax.tree_util.tree_leaves(moved))

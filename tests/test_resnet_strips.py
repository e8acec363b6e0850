"""``ResNet.features`` on row strips folded into the batch (a device batch
under 8, ``models/resnet.py``'s docstring) against the plain composition
of the same modules, written out here: the stem, then every block called
on whole images, which is what ``features`` itself does from 8 images up.
Folding is exact mathematics, so CPU float32 at toy widths holds it to
rounding: the maps, every parameter's gradient, the BN running statistics,
and one SyncBN step across a mesh against the one-device global batch."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import nnx

from tpu_syncbn import compat, nn as tnn, parallel, runtime
from tpu_syncbn.models import resnet
from tpu_syncbn.models.resnet import BasicBlock, Bottleneck, ResNet, strip_count


@pytest.mark.parametrize("n, h, strided, want", [
    # retinanet-train-b2's four stages: 2 images of 800x1344
    (2, 200, False, 4), (2, 200, True, 4), (2, 100, True, 5), (2, 50, True, 5),
    (1, 200, False, 8), (4, 200, True, 2), (3, 9, False, 3), (7, 10, False, 2),
    # N * G >= 8 needs a divisor of H that the stride leaves even strips
    (2, 25, False, 5), (2, 25, True, 1), (1, 7, False, 1), (4, 7, False, 7),
    (4, 7, True, 1), (1, 8, False, 8), (1, 8, True, 1), (2, 6, True, 1),
    # from 8 images up: whole images, whatever the height
    (8, 200, False, 1), (8, 200, True, 1), (128, 56, False, 1),
])
def test_strip_count_is_the_least_that_reaches_eight(n, h, strided, want):
    got = strip_count(n, h, strided)
    assert got == want
    if got > 1:
        assert n * got >= 8 and h % got == 0
        assert not (strided and (h // got) % 2)
        assert not any(
            n * g >= 8 and h % g == 0 and not (strided and (h // g) % 2)
            for g in range(1, got))


@pytest.mark.parametrize("width", [12, 11], ids=["w-even", "w-odd"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("strips", [2, 4, 5])
def test_conv_on_strips_is_the_conv_of_the_image(strips, stride, width):
    """The 3x3 alone, on strips of 4 rows: equal to ``conv(x)`` of the
    unfolded images, the rows at a strip's edge included."""
    conv = resnet._conv(6, 10, 3, stride, nnx.Rngs(0))
    x = jax.random.normal(jax.random.key(1), (3, 4 * strips, width, 6))
    want = conv(x)
    got = resnet._conv_on_strips(
        conv, x.reshape(3 * strips, 4, width, 6), strips)
    assert got.shape == (3 * strips, 4 // stride, *want.shape[2:])
    np.testing.assert_allclose(
        got.reshape(want.shape), want, rtol=1e-5, atol=1e-5)


def _plain_features(model, x):
    x = nnx.relu(model.stem_bn(model.stem_conv(x)))
    if not model.small_input:
        x = nnx.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
    feats = []
    for stage in model.stages:
        for blk in stage:
            x = blk(x)
        feats.append(x)
    return feats


def _value_grads_stats(features, model, x):
    """C2..C5, the gradient of a loss over them by every parameter, and
    the BN running statistics after the call."""
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)

    def loss(p, r):
        m = compat.nnx_merge(graphdef, p, r, copy=True)
        m.train()
        feats = features(m, x)
        head = m.fc(feats[-1].mean(axis=(1, 2)))  # so that fc has a gradient
        value = sum((f ** 2).mean() for f in feats) + (head ** 2).sum()
        _, _, new_r = nnx.split(m, nnx.Param, ...)
        return value, (feats, new_r)

    (_, (feats, new_rest)), grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(params, rest)
    return feats, grads, new_rest


def _rel(got, want):
    got, want = (jnp.asarray(a, jnp.float32).ravel() for a in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _scopes(model, x):
    return jax.make_jaxpr(lambda x: nnx.clone(model).features(x))(
        x).pretty_print(name_stack=True)


# (N, H, W) with the CIFAR stem, so that H is layer1's: the strips each
# stage must take, by the rule, beside it
SHAPES = [
    (1, 32, 20, (8, 8, 8, 1)),      # layer4 enters at 8: strips of 1 are odd
    (2, 32, 23, (4, 4, 4, 4)),      # an odd width: "SAME" pads it (1, 1)
    (4, 32, 20, (2, 2, 2, 2)),
    (2, 40, 12, (4, 4, 5, 5)),      # the strips change between stages
    (1, 48, 8, (8, 8, 12, 1)),      # 24 rows: 8 strips of 3 are odd, so 12
    (4, 12, 12, (2, 2, 3, 1)),      # 6 rows, strided: 2 strips are of 3
    (2, 28, 12, (4, 7, 7, 1)),      # 28 rows, strided: 4 strips are of 7
]


@pytest.mark.parametrize("block, layers", [
    (Bottleneck, (2, 1, 2, 1)), (BasicBlock, (1, 2, 1, 1))],
    ids=["bottleneck", "basic"])
@pytest.mark.parametrize("n, h, w, strips", SHAPES,
                         ids=[f"n{s[0]}-h{s[1]}-w{s[2]}" for s in SHAPES])
def test_features_on_strips_equal_whole_images(block, layers, n, h, w, strips):
    model = ResNet(block, layers, num_classes=3, width=8, small_input=True,
                   rngs=nnx.Rngs(0))
    x = jax.random.normal(jax.random.key(n * h), (n, h, w, 3))
    # the rule picked what the case is here for
    heights = [h, h, h // 2, h // 4]
    assert tuple(strip_count(n, hh, i > 0)
                 for i, hh in enumerate(heights)) == strips
    text = _scopes(model, x)
    for i, g in enumerate(strips):
        assert (f"layer{i + 1}/strips" in text) == (g > 1)
    assert "halo" in text

    feats, grads, stats = _value_grads_stats(
        lambda m, x: m.features(x), model, x)
    want_feats, want_grads, want_stats = _value_grads_stats(
        _plain_features, model, x)
    for got, want in zip(feats, want_feats, strict=True):
        assert got.shape == want.shape
        assert _rel(got, want) <= 1e-5
    for (path, got), want in zip(
            jax.tree.leaves_with_path(grads), jax.tree.leaves(want_grads),
            strict=True):
        assert _rel(got, want) <= 1e-4, jax.tree_util.keystr(path)
    # 1e-6 of the unit-scale activations they are means of (their own
    # scale is 0.01-0.1: the sums run in another order over the fold)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-6, atol=1e-6),
        stats, want_stats)


def test_imagenet_stem_and_bf16_compute_on_strips():
    """The 7x7 stem and the max-pool stay on whole images, and the halo'd
    call promotes to the module's compute dtype as ``nnx.Conv`` does."""
    model = ResNet(Bottleneck, (1, 1, 1, 1), num_classes=3, width=8,
                   dtype=jnp.bfloat16, rngs=nnx.Rngs(0))
    x = jax.random.normal(jax.random.key(0), (2, 64, 48, 3))
    text = _scopes(model, x)
    assert "layer1/strips" in text and "stem/strips" not in text
    feats = model.features(x)
    want = _plain_features(nnx.clone(model), x)
    for got, w in zip(feats, want, strict=True):
        assert got.dtype == w.dtype == jnp.bfloat16 and got.shape == w.shape
        np.testing.assert_allclose(
            got.astype(jnp.float32), w.astype(jnp.float32),
            rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("n, h", [(8, 32), (16, 16), (1, 7), (2, 3)],
                         ids=["n8", "n16", "n1-h7", "n2-h3"])
def test_whole_images_where_the_rule_refuses(n, h):
    """From 8 images up, and where no strip count fits (N * G >= 8 with
    G dividing an odd or a small height): no ``strips``, no ``halo``,
    and the program ``features`` lowers to is the plain composition's."""
    model = ResNet(Bottleneck, (1, 1, 1, 1), num_classes=3, width=8,
                   small_input=True, rngs=nnx.Rngs(0))
    x = jnp.zeros((n, h, 8, 3))
    text = _scopes(model, x)
    assert "strips" not in text and "halo" not in text
    lowered = [
        jax.jit(lambda x, f=f: f(nnx.clone(model), x)).lower(x).as_text()
        for f in (lambda m, x: m.features(x), _plain_features)]
    assert lowered[0] == lowered[1]


def test_syncbn_step_at_two_images_a_device_equals_the_global_batch():
    """``DataParallel`` on a mesh of 4 at 2 images a device (every stage
    on strips, SyncBN's sums over the same elements) against one device
    holding the global batch of 8, which runs on whole images."""
    def build():
        return ResNet(Bottleneck, (1, 1, 1, 1), num_classes=5, width=8,
                      small_input=True, rngs=nnx.Rngs(0))

    def loss_fn(m, batch):
        x, y = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            m(x), y).mean()

    rng = np.random.RandomState(0)
    batch = (jnp.asarray(rng.randn(8, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randint(0, 5, size=8), jnp.int32))
    dp = parallel.DataParallel(
        tnn.convert_sync_batchnorm(build()), optax.sgd(0.1), loss_fn,
        mesh=runtime.data_parallel_mesh(4), donate=False)
    text = dp.lowered_train_step(batch).as_text(debug_info=True)
    assert "strips" in text and "halo" in text
    out = dp.train_step(batch)

    graphdef, params, rest = nnx.split(build(), nnx.Param, ...)

    def loss_ref(p, r, b):
        m = compat.nnx_merge(graphdef, p, r, copy=True)
        m.train()
        loss = loss_fn(m, b)
        _, _, new_r = nnx.split(m, nnx.Param, ...)
        return loss, new_r

    assert "strips" not in jax.make_jaxpr(loss_ref)(
        params, rest, batch).pretty_print(name_stack=True)
    (loss_r, new_rest), grads = jax.value_and_grad(loss_ref, has_aux=True)(
        params, rest, batch)
    opt = optax.sgd(0.1)
    upd, _ = opt.update(grads, opt.init(params), params)
    params_r = optax.apply_updates(params, upd)

    np.testing.assert_allclose(float(out.loss), float(loss_r), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5),
        dp.params, params_r)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        dp.rest, new_rest)

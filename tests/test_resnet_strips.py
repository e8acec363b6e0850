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


@pytest.mark.parametrize("n, h, multiple, want", [
    # retinanet-train-b2's four stages: 2 images of 800x1344
    (2, 200, 1, 4), (2, 200, 2, 4), (2, 100, 2, 5), (2, 50, 2, 5),
    (1, 200, 1, 8), (4, 200, 2, 2), (3, 9, 1, 3), (7, 10, 1, 2),
    # N * G >= 8 needs a divisor of H that the stride leaves even strips
    (2, 25, 1, 5), (2, 25, 2, 1), (1, 7, 1, 1), (4, 7, 1, 7),
    (4, 7, 2, 1), (1, 8, 1, 8), (1, 8, 2, 1), (2, 6, 2, 1),
    # from 8 images up: whole images, whatever the height
    (8, 200, 1, 1), (8, 200, 2, 1), (128, 56, 1, 1),
    # the ImageNet stem, 7x7/2 then 3x3/2: strips of a multiple of 4
    # rows; retinanet-train-b2's 800 rows, a height whose quarters are
    # not (50), one with no such G, and 8 images or more
    (2, 800, 4, 4), (2, 200, 4, 5), (4, 800, 4, 2), (1, 224, 4, 8),
    (1, 12, 4, 1), (8, 800, 4, 1), (128, 224, 4, 1),
])
def test_strip_count_is_the_least_that_reaches_eight(n, h, multiple, want):
    got = strip_count(n, h, multiple)
    assert got == want
    if got > 1:
        assert n * got >= 8 and h % got == 0 and (h // got) % multiple == 0
        assert not any(
            n * g >= 8 and h % g == 0 and (h // g) % multiple == 0
            for g in range(1, got))


def _check_conv_on_strips(kernel, stride, strips, rows, width):
    """The convolution alone on strips of ``rows`` rows: equal to
    ``conv(x)`` of the unfolded images, the rows at a strip's edge
    included."""
    conv = resnet._conv(6, 10, kernel, stride, nnx.Rngs(0))
    x = jax.random.normal(jax.random.key(1), (3, rows * strips, width, 6))
    want = conv(x)
    got = resnet._conv_on_strips(
        conv, x.reshape(3 * strips, rows, width, 6), strips)
    assert got.shape == (3 * strips, rows // stride, *want.shape[2:])
    np.testing.assert_allclose(
        got.reshape(want.shape), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("width", [12, 11], ids=["w-even", "w-odd"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("strips", [2, 4, 5])
def test_conv_on_strips_is_the_conv_of_the_image(strips, stride, width):
    """A 3x3: the row above and the row below at stride 1, the row below
    at stride 2."""
    _check_conv_on_strips(3, stride, strips, 4, width)


@pytest.mark.parametrize("width", [12, 11], ids=["w-even", "w-odd"])
@pytest.mark.parametrize("rows", [4, 6])
@pytest.mark.parametrize("strips", [2, 4, 5])
def test_stem_conv_on_strips_is_the_conv_of_the_image(strips, rows, width):
    """The stem's 7x7/2: two rows from the strip above, three from the
    strip below, at even strip heights down to 4."""
    _check_conv_on_strips(7, 2, strips, rows, width)


def _pool_input(kind, shape):
    """Integer values, so that every sum of the backward is exact:
    ``relu``: the stem's, runs of zeros and of equal values across the
    strips' edges; ``negative``: -3 to -1, which a pad of 0 would beat;
    ``flat``: zeros everywhere, every window a tie."""
    rng = np.random.RandomState(7)
    x = rng.randint(-2, 3, size=shape).astype(np.float32)
    if kind == "flat":
        return jnp.zeros(shape)
    if kind == "negative":
        return jnp.asarray(np.minimum(x, 0) - 1)
    x = jnp.asarray(np.maximum(x, 0))
    return x.astype(jnp.bfloat16) if kind == "relu-bf16" else x


@pytest.mark.parametrize("kind", ["relu", "negative", "flat", "relu-bf16"])
@pytest.mark.parametrize("width", [12, 11], ids=["w-even", "w-odd"])
@pytest.mark.parametrize("strips", [2, 4])
def test_max_pool_on_strips_is_the_pool_of_the_image(strips, width, kind):
    """The stem's 3x3/2 max-pool on strips of 2 rows (the least the rule
    leaves it) against ``nnx.max_pool`` of the whole images: the forward
    and the input's gradient equal to the bit, so the halo's fill at an
    image's last strip is -inf and the backward picks, among equal
    values, the element the whole image's pool picks."""
    x = _pool_input(kind, (3, 2 * strips, width, 5))

    def whole(x):
        return nnx.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

    def folded(x):
        y = resnet._max_pool_on_strips(
            x.reshape(3 * strips, 2, width, 5), strips)
        return y.reshape(3, strips, *y.shape[2:])

    want, want_vjp = jax.vjp(whole, x)
    got, got_vjp = jax.vjp(folded, x)
    cot = jnp.asarray(np.random.RandomState(8).randint(
        -3, 4, size=want.shape), x.dtype)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(want.shape), np.float32),
        np.asarray(want, np.float32))
    np.testing.assert_array_equal(
        np.asarray(got_vjp(cot.reshape(got.shape))[0], np.float32),
        np.asarray(want_vjp(cot)[0], np.float32))


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
    assert tuple(strip_count(n, hh, 2 if i else 1)
                 for i, hh in enumerate(heights)) == strips
    text = _scopes(model, x)
    for i, g in enumerate(strips):
        assert (f"layer{i + 1}/strips" in text) == (g > 1)
    # the CIFAR stem, 3x3/1, is a stage's case: a row from either side
    assert ("stem/strips" in text) == (strip_count(n, h) > 1)
    assert "halo" in text
    _assert_features_equal(model, x)


def _assert_features_equal(model, x):
    """``features`` against the plain composition: maps, gradients and
    BN running statistics, to float32 rounding."""
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


@pytest.mark.parametrize("n, h, w, stem_strips", [
    (2, 64, 20, 4), (2, 16, 23, 4), (4, 40, 12, 2), (2, 24, 12, 6)],
    ids=["n2-h64", "n2-h16-w23", "n4-h40", "n2-h24"])
def test_imagenet_stem_on_strips_equals_whole_images(n, h, w, stem_strips):
    """The 7x7/2 convolution, its BN and ReLU and the 3x3/2 max-pool on
    strips of a multiple of 4 rows (4 the least), the stages after them
    by their own rule."""
    model = ResNet(Bottleneck, (1, 1, 1, 1), num_classes=3, width=8,
                   rngs=nnx.Rngs(0))
    x = jax.random.normal(jax.random.key(n * h), (n, h, w, 3))
    assert strip_count(n, h, 4) == stem_strips
    text = _scopes(model, x)
    assert "stem/strips" in text and "stem/halo" in text
    _assert_features_equal(model, x)


def test_imagenet_stem_and_bf16_compute_on_strips():
    """retinanet-train-b2's case at a toy size: 2 images, the 7x7/2 stem
    and the max-pool on 4 strips an image as ``layer1`` is, in bf16; the
    halo'd calls promote to the module's compute dtype as ``nnx.Conv``
    does."""
    model = ResNet(Bottleneck, (1, 1, 1, 1), num_classes=3, width=8,
                   dtype=jnp.bfloat16, rngs=nnx.Rngs(0))
    x = jax.random.normal(jax.random.key(0), (2, 64, 48, 3))
    assert strip_count(2, 64, 4) == strip_count(2, 16) == 4
    text = _scopes(model, x)
    assert "stem/strips" in text and "stem/halo" in text
    assert "layer1/strips" in text
    feats = model.features(x)
    want = _plain_features(nnx.clone(model), x)
    for got, w in zip(feats, want, strict=True):
        assert got.dtype == w.dtype == jnp.bfloat16 and got.shape == w.shape
        np.testing.assert_allclose(
            got.astype(jnp.float32), w.astype(jnp.float32),
            rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("n, h, small_input", [
    (8, 32, True), (16, 16, True), (1, 7, True), (2, 3, True),
    (8, 64, False), (16, 32, False), (1, 12, False), (2, 6, False)],
    ids=["n8", "n16", "n1-h7", "n2-h3", "imagenet-n8", "imagenet-n16",
         "imagenet-n1-h12", "imagenet-n2-h6"])
def test_whole_images_where_the_rule_refuses(n, h, small_input):
    """From 8 images up, and where no strip count fits (N * G >= 8 with
    G dividing an odd or a small height, or, the ImageNet stem, leaving
    no multiple of 4): no ``strips``, no ``halo``, and the program
    ``features`` lowers to is the plain composition's, so the 128-image
    cells' step is what it was before the fold."""
    model = ResNet(Bottleneck, (1, 1, 1, 1), num_classes=3, width=8,
                   small_input=small_input, rngs=nnx.Rngs(0))
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

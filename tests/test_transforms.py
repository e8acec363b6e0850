"""Host-side transform tests."""

import numpy as np
import pytest

from tpu_syncbn.data import transforms as T


def test_random_crop_shape_and_determinism():
    x = np.arange(32 * 32 * 3, dtype=np.float32).reshape(32, 32, 3)
    t1 = T.RandomCrop(32, padding=4, seed=0)
    t2 = T.RandomCrop(32, padding=4, seed=0)  # same seed -> same crops
    a, b = t1(x), t2(x)
    assert a.shape == (32, 32, 3)
    np.testing.assert_array_equal(a, b)


def test_random_flip_probability():
    x = np.arange(12, dtype=np.float32).reshape(2, 2, 3)
    t = T.RandomHorizontalFlip(p=1.0)
    np.testing.assert_array_equal(t(x), x[:, ::-1])
    t0 = T.RandomHorizontalFlip(p=0.0)
    np.testing.assert_array_equal(t0(x), x)


def test_random_resized_crop_shape():
    x = np.random.RandomState(0).rand(64, 48, 3).astype(np.float32)
    out = T.RandomResizedCrop(32, seed=1)(x)
    assert out.shape == (32, 32, 3)


def test_center_crop_and_normalize_and_tofloat():
    x = (np.random.RandomState(0).rand(40, 40, 3) * 255).astype(np.uint8)
    pipe = T.Compose([
        T.ToFloat(),
        T.CenterCrop(32),
        T.Normalize(mean=(0.5, 0.5, 0.5), std=(0.25, 0.25, 0.25)),
    ])
    out = pipe(x)
    assert out.shape == (32, 32, 3)
    assert out.dtype == np.float32
    assert -2.1 <= out.min() and out.max() <= 2.1


def test_transform_dataset_integration():
    from tpu_syncbn import data as tdata

    base = tdata.SyntheticImageDataset(length=8, shape=(40, 40, 3))
    ds = tdata.TransformDataset(
        base, lambda s: (T.CenterCrop(32)(s[0]), s[1])
    )
    x, y = ds[0]
    assert x.shape == (32, 32, 3)


def test_syncbn_classmethod_spelling():
    """torch-parity spelling: nn.SyncBatchNorm.convert_sync_batchnorm(net)."""
    from flax import nnx

    from tpu_syncbn import nn as tnn

    class M(nnx.Module):
        def __init__(self):
            self.bn = tnn.BatchNorm2d(3)

    m = M()
    tnn.SyncBatchNorm.convert_sync_batchnorm(m)
    assert isinstance(m.bn, tnn.SyncBatchNorm)


def test_random_crop_zero_padding_default():
    x = np.ones((32, 32, 3), np.float32)
    t = T.RandomCrop(40, padding=4, seed=0)  # crop larger forces border use
    out = t(x)
    assert out.shape == (40, 40, 3)
    assert out.min() == 0.0  # zero-fill borders (torchvision default)


def test_crop_validation_errors():
    import pytest

    with pytest.raises(ValueError, match="larger than padded"):
        T.RandomCrop(64, padding=2, seed=0)(np.zeros((32, 32, 3), np.float32))
    with pytest.raises(ValueError, match="CenterCrop"):
        T.CenterCrop(32)(np.zeros((30, 30, 3), np.float32))


def test_shared_rng_injection():
    rng = np.random.RandomState(7)
    t = T.RandomHorizontalFlip(rng=rng)
    ref = np.random.RandomState(7)
    x = np.arange(12, dtype=np.float32).reshape(2, 2, 3)
    out = t(x)
    flipped = ref.rand() < 0.5
    np.testing.assert_array_equal(out, x[:, ::-1] if flipped else x)


def test_threaded_loader_with_random_transforms_no_crash():
    from tpu_syncbn import data as tdata

    aug = T.Compose([T.RandomCrop(32, padding=4, seed=0),
                     T.RandomHorizontalFlip(seed=1)])
    base = tdata.SyntheticImageDataset(length=64, shape=(32, 32, 3))
    ds = tdata.TransformDataset(base, lambda s: (aug(s[0]), s[1]))
    dl = tdata.DataLoader(ds, batch_size=8, num_workers=8, drop_last=True)
    batches = list(dl)
    assert len(batches) == 8
    assert all(b[0].shape == (8, 32, 32, 3) for b in batches)


# -- ToFloat and Normalize: made in place, today's bits -----------------------

MEAN, STD = (0.485, 0.456, 0.406), (0.229, 0.224, 0.225)


def to_float_formula(x):
    """``ToFloat`` as a formula: a float32 copy of uint8 over 255."""
    if x.dtype == np.uint8:
        return x.astype(np.float32) / 255.0
    return np.asarray(x, np.float32)


def normalize_formula(x, mean, std):
    """``Normalize`` as a formula, with fresh arrays at every step."""
    return ((np.asarray(x, np.float32) - np.asarray(mean, np.float32))
            / np.asarray(std, np.float32))


def every_value(dtype, shape):
    """Every uint8 value over ``shape``, in ``dtype``; the floats scaled
    off the integers and signed, so the inputs are not all whole."""
    x = (np.arange(int(np.prod(shape))) % 256).reshape(shape)
    if dtype == np.uint8:
        return x.astype(np.uint8)
    return (x * 1.37 - 100.0).astype(dtype)


def assert_same_bits(got, want):
    assert got.dtype == want.dtype == np.float32
    assert got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("dtype", [np.uint8, np.float32, np.float64])
@pytest.mark.parametrize("shape", [(16, 16, 3), (4, 8, 8, 3), (256, 3)],
                         ids=["image", "batch", "rows"])
@pytest.mark.parametrize("stats", [(MEAN, STD), (0.5, 0.25)],
                         ids=["per_channel", "scalar"])
@pytest.mark.parametrize("pipeline", ["fused", "to_float", "normalize"])
def test_to_float_and_normalize_give_the_formulas_bits(
        dtype, shape, stats, pipeline):
    """The pair in a ``Compose`` runs as one step, each alone as itself;
    all three give the formulas' bits, and the caller's array is left as
    it was. The batch shape is the resident cells' call."""
    mean, std = stats
    x = every_value(dtype, shape)
    before = x.copy()
    if pipeline == "fused":
        got = T.Compose([T.ToFloat(), T.Normalize(mean, std)])(x)
        want = normalize_formula(to_float_formula(x), mean, std)
    elif pipeline == "to_float":
        got, want = T.ToFloat()(x), to_float_formula(x)
    else:
        got, want = T.Normalize(mean, std)(x), normalize_formula(x, mean, std)
    assert_same_bits(got, want)
    np.testing.assert_array_equal(x, before)
    assert x.dtype == before.dtype


def test_every_uint8_value_gives_the_formulas_bits():
    x = np.arange(256, dtype=np.uint8).repeat(3).reshape(1, 256, 3)
    got = T.Compose([T.ToFloat(), T.Normalize(MEAN, STD)])(x)
    assert_same_bits(got, normalize_formula(to_float_formula(x), MEAN, STD))
    assert len(np.unique(got[..., 0])) == 256


@pytest.mark.parametrize("between", [
    T.CenterCrop(8), T.RandomHorizontalFlip(p=1.0)], ids=["crop", "flip"])
def test_a_transform_between_the_two_keeps_them_apart(between):
    x = every_value(np.uint8, (12, 12, 3))
    before = x.copy()
    got = T.Compose([T.ToFloat(), between, T.Normalize(MEAN, STD)])(x)
    want = normalize_formula(between(to_float_formula(x)), MEAN, STD)
    assert_same_bits(got, want)
    np.testing.assert_array_equal(x, before)


@pytest.mark.parametrize("x", [
    np.ones((8, 3), np.float32)[:, ::-1],            # not contiguous
    np.asfortranarray(every_value(np.float32, (6, 5, 3))),
    every_value(np.float32, (3,)),                  # one pixel
], ids=["strided", "fortran", "pixel"])
def test_normalize_in_place_on_any_layout(x):
    before = x.copy()
    got = T.Normalize(MEAN, STD)(x)
    assert_same_bits(np.ascontiguousarray(got),
                     np.ascontiguousarray(normalize_formula(x, MEAN, STD)))
    np.testing.assert_array_equal(x, before)


def test_normalize_whose_statistics_widen_the_input():
    """A mean that broadcasts the input to a larger shape cannot be
    written over it: a new array of the broadcast shape, as before."""
    x = every_value(np.float32, (5, 3))
    mean = np.full((2, 1, 3), 0.5, np.float32)
    got = T.Normalize(mean, STD)(x)
    assert got.shape == (2, 5, 3)
    assert_same_bits(got, normalize_formula(x, mean, STD))


def test_normalize_follows_the_row_shape_and_the_statistics_it_is_given():
    """The statistics tiled along a row are kept for the last row shape:
    images of another width, or a mean set anew, are normalized by what
    they are given."""
    norm = T.Normalize(MEAN, STD)
    for shape in [(4, 6, 3), (4, 7, 3), (2, 4, 6, 3), (5, 6, 3)]:
        x = every_value(np.float32, shape)
        assert_same_bits(norm(x), normalize_formula(x, MEAN, STD))
    norm.mean = np.asarray((0.1, 0.2, 0.3), np.float32)
    x = every_value(np.float32, (4, 6, 3))
    assert_same_bits(norm(x), normalize_formula(x, norm.mean, STD))

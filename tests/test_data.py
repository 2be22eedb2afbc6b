"""Data pipelines: determinism, resumability, sharding (stateless contract)."""

import numpy as np

from repro.data import mnist


def test_render_digits_range_and_determinism():
    labels = np.arange(10).astype(np.int32)
    a = mnist.render_digits(labels, seed=3)
    b = mnist.render_digits(labels, seed=3)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (10, 28, 28)
    assert a.min() >= 0.0 and a.max() <= 1.0
    # different classes render differently
    assert not np.allclose(a[0], a[1])


def test_digit_classes_are_separable():
    """Nearest-centroid on clean glyphs classifies jittered renders well —
    the procedural dataset is learnable, not noise."""
    protos = mnist.render_digits(np.arange(10), seed=0, jitter=False)
    protos = protos.reshape(10, -1)
    rng = np.random.default_rng(5)
    labels = rng.integers(0, 10, 128).astype(np.int32)
    imgs = mnist.render_digits(labels, seed=11).reshape(128, -1)
    d = ((imgs[:, None] - protos[None]) ** 2).sum(-1)
    acc = (d.argmin(1) == labels).mean()
    assert acc > 0.6  # raw-pixel NN under affine jitter; chance is 0.1


def test_mnist_batches_resumable_and_sharded():
    full = list(mnist.batches("train", 8, 6, seed=1))
    resumed = list(mnist.batches("train", 8, 6, seed=1, start_step=3))
    assert [s for s, _, _ in resumed] == [3, 4, 5]
    for (s1, x1, y1), (s2, x2, y2) in zip(full[3:], resumed):
        np.testing.assert_array_equal(x1, x2)
        np.testing.assert_array_equal(y1, y2)
    # different shards draw different data at the same step
    _, xa, _ = next(iter(mnist.batches("train", 8, 1, seed=1,
                                       shard_index=0, num_shards=2)))
    _, xb, _ = next(iter(mnist.batches("train", 8, 1, seed=1,
                                       shard_index=1, num_shards=2)))
    assert not np.array_equal(xa, xb)


def test_load_or_generate_contract():
    x, y = mnist.load_or_generate("test", 32, seed=0)
    assert x.shape == (32, 784) and y.shape == (32,)
    x2, y2 = mnist.load_or_generate("test", 32, seed=0)
    np.testing.assert_array_equal(x, x2)
    xt, _ = mnist.load_or_generate("train", 32, seed=0)
    assert not np.array_equal(x, xt)  # splits differ

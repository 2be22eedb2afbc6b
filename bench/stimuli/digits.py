"""Poisson-coded procedural MNIST digits (copied from the program's
``data/mnist.py`` renderer and ``core/coding.py`` encoder, so the
benchmark's inputs cannot move with the program)."""

import numpy as np

from bench import seeds

GLYPHS = {
    0: [[(0.5, 0.12), (0.76, 0.3), (0.76, 0.7), (0.5, 0.88),
         (0.24, 0.7), (0.24, 0.3), (0.5, 0.12)]],
    1: [[(0.35, 0.3), (0.55, 0.12), (0.55, 0.88)],
        [(0.35, 0.88), (0.72, 0.88)]],
    2: [[(0.26, 0.3), (0.4, 0.14), (0.64, 0.14), (0.74, 0.32),
         (0.62, 0.52), (0.3, 0.74), (0.26, 0.86)],
        [(0.26, 0.86), (0.76, 0.86)]],
    3: [[(0.28, 0.18), (0.6, 0.14), (0.72, 0.3), (0.55, 0.47)],
        [(0.42, 0.47), (0.72, 0.52), (0.72, 0.72), (0.55, 0.88),
         (0.28, 0.82)]],
    4: [[(0.62, 0.88), (0.62, 0.12), (0.26, 0.62), (0.78, 0.62)]],
    5: [[(0.72, 0.14), (0.3, 0.14), (0.28, 0.48), (0.6, 0.44),
         (0.74, 0.6), (0.68, 0.82), (0.3, 0.86)]],
    6: [[(0.66, 0.14), (0.38, 0.36), (0.28, 0.62), (0.4, 0.84),
         (0.64, 0.84), (0.72, 0.64), (0.58, 0.5), (0.32, 0.56)]],
    7: [[(0.26, 0.14), (0.76, 0.14), (0.48, 0.88)],
        [(0.36, 0.5), (0.66, 0.5)]],
    8: [[(0.5, 0.14), (0.7, 0.26), (0.62, 0.46), (0.5, 0.5),
         (0.38, 0.46), (0.3, 0.26), (0.5, 0.14)],
        [(0.5, 0.5), (0.72, 0.62), (0.64, 0.84), (0.5, 0.88),
         (0.36, 0.84), (0.28, 0.62), (0.5, 0.5)]],
    9: [[(0.68, 0.44), (0.42, 0.5), (0.28, 0.36), (0.36, 0.16),
         (0.6, 0.12), (0.72, 0.3), (0.68, 0.44), (0.62, 0.88)]],
}


def _segment_distance(px, py, ax, ay, bx, by):
    abx, aby = bx - ax, by - ay
    apx, apy = px - ax, py - ay
    t = np.clip((apx * abx + apy * aby) / (abx * abx + aby * aby + 1e-12),
                0.0, 1.0)
    return np.sqrt((px - (ax + t * abx)) ** 2 + (py - (ay + t * aby)) ** 2)


def render(labels, rng, size: int = 28) -> np.ndarray:
    """(B,) labels -> (B, size*size) float32 images in [0, 1], with random
    rotation, scale, shift, stroke width and pixel noise."""
    B = len(labels)
    ys, xs = np.mgrid[0:size, 0:size]
    xs, ys = (xs + 0.5) / size, (ys + 0.5) / size
    theta = rng.uniform(-0.22, 0.22, B)
    scale = rng.uniform(0.85, 1.12, B)
    dx, dy = rng.uniform(-0.1, 0.1, B), rng.uniform(-0.1, 0.1, B)
    width = rng.uniform(0.035, 0.055, B)
    out = np.zeros((B, size, size), np.float32)
    for i, lab in enumerate(labels):
        c, s = np.cos(theta[i]), np.sin(theta[i])
        gx = ((xs - 0.5 - dx[i]) * c + (ys - 0.5 - dy[i]) * s) / scale[i] + 0.5
        gy = (-(xs - 0.5 - dx[i]) * s + (ys - 0.5 - dy[i]) * c) / scale[i] + 0.5
        dist = np.full_like(gx, 1e9)
        for stroke in GLYPHS[int(lab)]:
            for (ax, ay), (bx, by) in zip(stroke[:-1], stroke[1:]):
                dist = np.minimum(dist,
                                  _segment_distance(gx, gy, ax, ay, bx, by))
        img = np.exp(-0.5 * (dist / width[i]) ** 2)
        out[i] = np.clip(img + rng.normal(0, 0.02, img.shape), 0.0, 1.0)
    return out.reshape(B, -1)


def pool(seed: int, n: int, steps: int, n_inputs: int) -> np.ndarray:
    """(n, steps, n_inputs) int32 Bernoulli-per-step spikes of ``n``
    rendered digits, encoded on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    if n_inputs != 28 * 28:
        raise ValueError(f"digit stimuli have 784 inputs, not {n_inputs}")
    r = seeds.rng(seed, "digits")
    images = render(r.integers(0, 10, n), r)

    @jax.jit
    def encode(key, img):
        u = jax.random.uniform(key, (img.shape[0], steps, img.shape[1]))
        return (u < img[:, None, :]).astype(jnp.int32)

    key = jax.random.key(seeds.derive(seed, "poisson"))
    return np.asarray(jax.device_get(encode(key, jnp.asarray(images))))

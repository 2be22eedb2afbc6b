"""Synthetic cochlear rasters at the shapes of the Spiking Heidelberg
Digits (Cramer et al., arXiv:1910.07407): 700 channels, one step a 14 ms
bin. The recordings are not in the repository; until they are, each
utterance is drawn from the seed.

The model: an utterance holds ``BANDS`` bands of neighbouring channels
(formant-like). Band ``b`` has a Gaussian profile over the channels whose
centre drifts linearly from a random start channel to a random end
channel over the pool's steps, a width ``sigma`` and a peak firing
probability. Channel ``c`` spikes at step ``t`` with probability
``BACKGROUND + sum_b peak_b exp(-((c - centre_b(t)) / sigma_b)**2 / 2)``
(at most 1), independently per (channel, step). ``DENSITY`` is the share
of (channel, step) pairs that spike, in expectation over utterances.
"""

import numpy as np

from bench import seeds

CHANNELS = 700
BANDS = (3, 6)            # bands per utterance, drawn from [3, 6)
SIGMA = (6.0, 16.0)       # band width in channels, uniform
PEAK = (0.2, 0.6)         # band peak spike probability, uniform
BACKGROUND = 0.005        # spike probability of every channel and step
DENSITY = 0.068           # expected share of (channel, step) pairs spiking


def pool(seed: int, n: int, steps: int, n_inputs: int) -> np.ndarray:
    """(n, steps, 700) int32 Bernoulli spikes of ``n`` utterances, drawn
    on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    if n_inputs != CHANNELS:
        raise ValueError(f"SHD stimuli have {CHANNELS} inputs, not {n_inputs}")
    r = seeds.rng(seed, "shd")
    hi = BANDS[1] - 1
    present = np.arange(hi)[None, :] < r.integers(*BANDS, n)[:, None]
    start, end = (r.uniform(0, CHANNELS, (n, hi)) for _ in range(2))
    sigma = r.uniform(*SIGMA, (n, hi))
    peak = r.uniform(*PEAK, (n, hi)) * present

    @jax.jit
    def draw(key, start, end, sigma, peak):
        t = jnp.linspace(0.0, 1.0, steps)[None, :, None]        # (1, T, 1)
        centre = start[:, None, :] + (end - start)[:, None, :] * t
        ch = jnp.arange(CHANNELS, dtype=jnp.float32)
        z = (ch[None, None, None, :] - centre[..., None]) / sigma[:, None,
                                                                  :, None]
        p = BACKGROUND + jnp.sum(peak[:, None, :, None]
                                 * jnp.exp(-0.5 * z * z), axis=2)
        u = jax.random.uniform(key, p.shape)
        return (u < jnp.minimum(p, 1.0)).astype(jnp.int32)

    key = jax.random.key(seeds.derive(seed, "shd-spikes"))
    args = (jnp.asarray(a, jnp.float32) for a in (start, end, sigma, peak))
    return np.asarray(jax.device_get(draw(key, *args)))

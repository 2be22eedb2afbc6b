"""Independent streams of randomness from one ``--seed``.

``--seed`` may exceed 32 bits; each purpose gets its own 31-bit integer
from ``numpy.random.SeedSequence``, so the weights, the stimuli and the
traffic of one seed never share a stream.
"""

import zlib

import numpy as np


def derive(seed: int, purpose: str) -> int:
    """A 31-bit seed for ``purpose``, fixed by ``seed``."""
    ss = np.random.SeedSequence([int(seed), zlib.crc32(purpose.encode())])
    return int(ss.generate_state(1)[0] >> 1)


def rng(seed: int, purpose: str) -> np.random.Generator:
    return np.random.default_rng(derive(seed, purpose))

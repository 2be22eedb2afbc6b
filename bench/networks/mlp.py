"""A feed-forward spiking MLP with random weights (SNAP-V Table IV nets).

Neurons are numbered layer by layer after the inputs; layer ``i``'s
neurons are the sources of layer ``i + 1``. Weights are
``normal * std_scale / sqrt(fan_in)``, clipped to ``+-weight_clip``, made
on the device in one jitted call from the seed.
"""

import numpy as np

from bench import seeds
from bench.reference import Network


def build(spec: dict, neuron: dict, seed: int) -> Network:
    import jax

    sizes = [int(s) for s in spec["layer_sizes"]]
    pairs = list(zip(sizes[:-1], sizes[1:]))

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(pairs))
        return [jax.random.normal(k, (a, b)) * (spec["std_scale"] / np.sqrt(a))
                for k, (a, b) in zip(keys, pairs)]

    layers = jax.device_get(draw(jax.random.key(seeds.derive(seed, "weights"))))
    n_in, n_neurons = sizes[0], sum(sizes[1:])
    w = np.zeros((n_in + n_neurons, n_neurons), np.float32)
    src, dst = 0, 0
    for layer, (a, b) in zip(layers, pairs):
        w[src:src + a, dst:dst + b] = np.clip(
            layer, -spec["weight_clip"], spec["weight_clip"])
        src, dst = n_in + dst, dst + b
    return Network(weights=w, n_inputs=n_in, n_neurons=n_neurons,
                   output_slice=(n_neurons - sizes[-1], n_neurons),
                   neuron=neuron)

"""One recurrently connected hidden layer of spiking neurons and a spiking
readout: the construction of the recurrent SNN baselines of the Spiking
Heidelberg Digits (Cramer et al., arXiv:1910.07407).

Neurons are numbered hidden first (``0..hidden-1``), then the outputs.
Every input drives every hidden neuron, every hidden neuron drives every
hidden neuron (itself included) and every output; the outputs drive
nothing. Weights are ``normal * scale / sqrt(fan_in)`` with the scale of
their projection, clipped to ``+-weight_clip``, made on the device in one
jitted call from the seed.
"""

import numpy as np

from bench import seeds
from bench.reference import Network


def build(spec: dict, neuron: dict, seed: int) -> Network:
    import jax

    n_in, n_hid, n_out = spec["inputs"], spec["hidden"], spec["outputs"]
    shapes = {"input": (n_in, n_hid), "recurrent": (n_hid, n_hid),
              "readout": (n_hid, n_out)}

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(shapes))
        return {name: jax.random.normal(k, (a, b))
                * (spec[f"{name}_scale"] / np.sqrt(a))
                for k, (name, (a, b)) in zip(keys, shapes.items())}

    w = {name: np.clip(x, -spec["weight_clip"], spec["weight_clip"])
         for name, x in jax.device_get(
             draw(jax.random.key(seeds.derive(seed, "weights")))).items()}
    n_neurons = n_hid + n_out
    weights = np.zeros((n_in + n_neurons, n_neurons), np.float32)
    weights[:n_in, :n_hid] = w["input"]
    weights[n_in:n_in + n_hid, :n_hid] = w["recurrent"]
    weights[n_in:n_in + n_hid, n_hid:] = w["readout"]
    return Network(weights=weights, n_inputs=n_in, n_neurons=n_neurons,
                   output_slice=(n_hid, n_neurons), neuron=neuron)

"""The leaky integrate-and-fire neuron of the Cerebra-H array, as a
configuration's ``neuron`` section states it::

    {"kind": "lif", "decay_rate": 0.1, "threshold": 1.0, "reset": "zero"}

``Reference`` is its plain reference, written from that description alone:
it imports nothing of the program and takes nothing the program made. It
quantizes the float weights the benchmark generated, applies the decay,
threshold and reset the section states, and steps every stream in NumPy.
Sums run in float64 over 0/1 sources, which is exact while
``|sum| < 2**53``; the membrane add wraps at 32 bits as the hardware's
adders do.

``precision="bf16"`` is the control: the same network with every weight
rounded to bfloat16 before the accumulate, the single-pass MXU shortcut
that a faster kernel would be tempted to take. It must fail the
comparison.

``program_params`` translates the section into the program's deployment.
"""

from __future__ import annotations

import numpy as np

from bench.reference import quantize

PRECISIONS = ("exact", "bf16")


def hardware_decay(rate: float, supported) -> float:
    """The supported decay rate nearest to ``rate`` (first on a tie)."""
    return float(min(supported, key=lambda r: abs(r - rate)))


def _wrap32(x):
    return ((x + (1 << 31)) % (1 << 32)) - (1 << 31)


def _decay(v, rate: float):
    """Arithmetic-shift decay of int64-held int32 potentials."""
    if rate == 0.125:
        return v - (v >> 3)
    if rate == 0.25:
        return v - (v >> 2)
    if rate == 0.5:
        return v - (v >> 1)
    if rate == 0.75:
        return v >> 2
    raise ValueError(f"no shift decay for rate {rate}")


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class Reference:
    """Steps streams of one network from the power-on state (V = 0, no
    prior spikes)."""

    def __init__(self, net, config: dict, precision: str = "exact"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        fx = config["fixed_point"]
        scale = 1 << fx["frac_bits"]
        wq = quantize(net.weights, fx["int_bits"], fx["frac_bits"])
        self.w = (wq.astype(np.float64) if precision == "exact"
                  else _bf16(wq))
        self.n_inputs = net.n_inputs
        self.n_neurons = net.n_neurons
        self.rate = hardware_decay(net.neuron["decay_rate"],
                                   config["hardware"]["decay_rates"])
        self.threshold = int(round(net.neuron["threshold"] * scale))
        self.reset = net.neuron["reset"]

    def run(self, ext: np.ndarray):
        """(B, T, n_inputs) 0/1 -> ((B, T, n_neurons) uint8 spikes,
        (B, n_neurons) int64 membrane potentials after step T)."""
        B, T, _ = ext.shape
        v = np.zeros((B, self.n_neurons), np.int64)
        prev = np.zeros((B, self.n_neurons), np.float64)
        out = np.zeros((B, T, self.n_neurons), np.uint8)
        src = np.zeros((B, self.n_inputs + self.n_neurons), np.float64)
        for t in range(T):
            src[:, :self.n_inputs] = ext[:, t]
            src[:, self.n_inputs:] = prev
            syn = np.rint(src @ self.w).astype(np.int64)
            v = _wrap32(_decay(v, self.rate) + syn)
            spikes = v >= self.threshold
            if self.reset == "zero":
                v = np.where(spikes, 0, v)
            elif self.reset == "subtract":
                v = _wrap32(v - spikes * self.threshold)
            out[:, t] = spikes
            prev = spikes.astype(np.float64)
        return out, v


def program_params(neuron: dict, fmt):
    """The program's ``LIFParams`` for this section (imports the program)."""
    from repro.core.lif import LIFParams

    return LIFParams(decay_rate=neuron["decay_rate"],
                     threshold=neuron["threshold"],
                     reset_mode=neuron["reset"], fmt=fmt)

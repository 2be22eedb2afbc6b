"""The current-based leaky integrate-and-fire neuron: two states per
neuron, a synaptic current ``i`` and a membrane potential ``v``, each with
a leak of its own. A configuration's ``neuron`` section states it::

    {"kind": "cuba", "decay_rate": 0.5, "syn_decay_rate": 0.75,
     "threshold": 1.0, "reset": "zero"}

One step, all Q16.16 with 32-bit wrapping adds, where ``acc`` is the sum
of the weights of the step's sources (the external spikes of this step
and the network's spikes of the last)::

    i = decay(i, syn_decay_rate) + acc
    u = decay(v, decay_rate) + i
    spike = u >= threshold;  v = reset(u)

``Reference`` is its plain reference, written from that description
alone: it imports nothing of the program, and follows ``lif.py`` (int64
held int32 that wraps, float64 sums over 0/1 sources, exact while
``|sum| < 2**53``; the decays snapped to the hardware's shift rates).

Controls that must fail the comparison: ``precision="bf16"``, every
weight rounded to bfloat16; ``precision="no_current"``, the same network
with the current removed (``u = decay(v) + acc``, a plain LIF), which is
what a kernel that dropped the second state would compute.

``program_params`` translates the section into the program's deployment.
"""

from __future__ import annotations

import pathlib

import numpy as np

from bench import load
from bench.reference import quantize

PRECISIONS = ("exact", "bf16", "no_current")

_lif = load.module(pathlib.Path(__file__).resolve().parents[2],
                   "neurons", "lif")


class Reference:
    """Steps streams of one network from the power-on state (``v = i =
    0``, no prior spikes)."""

    def __init__(self, net, config: dict, precision: str = "exact"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        fx = config["fixed_point"]
        scale = 1 << fx["frac_bits"]
        wq = quantize(net.weights, fx["int_bits"], fx["frac_bits"])
        self.w = (_lif._bf16(wq) if precision == "bf16"
                  else wq.astype(np.float64))
        self.current = precision != "no_current"
        self.n_inputs = net.n_inputs
        self.n_neurons = net.n_neurons
        rates = config["hardware"]["decay_rates"]
        self.rate = _lif.hardware_decay(net.neuron["decay_rate"], rates)
        self.syn_rate = _lif.hardware_decay(net.neuron["syn_decay_rate"],
                                            rates)
        self.threshold = int(round(net.neuron["threshold"] * scale))
        self.reset = net.neuron["reset"]

    def run(self, ext: np.ndarray):
        """(B, T, n_inputs) 0/1 -> ((B, T, n_neurons) uint8 spikes,
        (B, n_neurons) int64 membrane potentials after step T)."""
        B, T, _ = ext.shape
        v = np.zeros((B, self.n_neurons), np.int64)
        i = np.zeros((B, self.n_neurons), np.int64)
        prev = np.zeros((B, self.n_neurons), np.float64)
        out = np.zeros((B, T, self.n_neurons), np.uint8)
        src = np.zeros((B, self.n_inputs + self.n_neurons), np.float64)
        for t in range(T):
            src[:, :self.n_inputs] = ext[:, t]
            src[:, self.n_inputs:] = prev
            acc = np.rint(src @ self.w).astype(np.int64)
            if self.current:
                i = _lif._wrap32(_lif._decay(i, self.syn_rate) + acc)
                acc = i
            v = _lif._wrap32(_lif._decay(v, self.rate) + acc)
            spikes = v >= self.threshold
            if self.reset == "zero":
                v = np.where(spikes, 0, v)
            elif self.reset == "subtract":
                v = _lif._wrap32(v - spikes * self.threshold)
            out[:, t] = spikes
            prev = spikes.astype(np.float64)
        return out, v


def program_params(neuron: dict, fmt):
    """The program's ``LIFParams`` for this section (imports the program)."""
    from repro.core.lif import LIFParams

    return LIFParams(decay_rate=neuron["decay_rate"],
                     threshold=neuron["threshold"],
                     reset_mode=neuron["reset"], fmt=fmt,
                     syn_decay_rate=neuron["syn_decay_rate"])

"""The network a configuration states, its plain reference, and the
comparison that decides ``correct``.

The reference of a network is found by its configuration's neuron kind:
``neurons/<kind>.py`` holds the kind's NumPy reference (``Reference``),
which imports nothing of the program, and its translation into the
program's deployment (``program_params``). A configuration lists under
``checks`` what the comparison holds the served streams to: ``spikes``
(every raster bit) and ``potentials`` (every membrane potential after the
stream's last step).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import load

CHECKS = ("spikes", "potentials")


@dataclasses.dataclass(frozen=True)
class Network:
    """A network as the benchmark generated it: float weights over
    ``n_inputs`` external sources then ``n_neurons`` neurons, of the
    configuration's ``neuron`` model."""

    weights: np.ndarray           # (n_inputs + n_neurons, n_neurons) float32
    n_inputs: int
    n_neurons: int
    output_slice: tuple[int, int]
    neuron: dict                  # the configuration's ``neuron`` section

    @property
    def n_synapses(self) -> int:
        return int(np.count_nonzero(self.weights))


def quantize(weights, int_bits: int, frac_bits: int) -> np.ndarray:
    """Signed fixed point, round to nearest even, saturating (int64)."""
    r = np.round(np.asarray(weights, np.float64) * (1 << frac_bits))
    lo, hi = -(1 << (int_bits + frac_bits)), (1 << (int_bits + frac_bits)) - 1
    return np.clip(r, lo, hi).astype(np.int64)


def neuron_module(root, net: Network):
    """``neurons/<kind>.py`` of the network's neuron model."""
    return load.module(root, "neurons", net.neuron["kind"])


def model(root, net: Network, config: dict, precision: str = "exact"):
    """The plain reference of ``net`` in ``precision`` (``"exact"``, or a
    lower one the neuron kind offers as the control)."""
    return neuron_module(root, net).Reference(net, config, precision)


def answers(ref, checks, block: int = 64) -> list:
    """The reference's ``(raster (T, n_neurons), potentials (n_neurons,))``
    for every check's inputs, checks of equal length run as one batch,
    ``block`` at a time."""
    out = [None] * len(checks)
    by_len: dict[int, list[int]] = {}
    for i, c in enumerate(checks):
        by_len.setdefault(c.ext.shape[0], []).append(i)
    for idx in by_len.values():
        for j in range(0, len(idx), block):
            part = idx[j:j + block]
            spikes, v = ref.run(np.stack([checks[i].ext for i in part]))
            for k, i in enumerate(part):
                out[i] = (spikes[k], v[k])
    return out


@dataclasses.dataclass
class Check:
    """One answer to check: the external spikes a stream was sent, the
    physical raster it got back (None if it never came), and, where the
    configuration checks them, the stream's physical membrane potentials
    after its last step."""

    ext: np.ndarray                 # (T, n_inputs)
    served: np.ndarray | None       # (T', n_phys)
    potentials: np.ndarray | None = None   # (n_phys,)


def mismatches(ref, checks, compare) -> dict:
    """The numbers compared: for each of ``compare`` (a configuration's
    ``checks``), the bits or potentials where the served streams differ
    from ``ref``, and the streams that never answered.

    A served stream holds the model's neurons at physical slots
    ``0..n_neurons-1`` (the configuration deploys from cluster 0) and
    nothing elsewhere: a spike or a nonzero potential outside the model
    counts as a mismatch, so does every step of a raster that is short or
    long, and every neuron of an answer that carries no potentials.
    """
    unknown = set(compare) - set(CHECKS)
    if unknown:
        raise ValueError(f"unknown checks {sorted(unknown)}; known {CHECKS}")
    answered = [c for c in checks if c.served is not None]
    spikes = potentials = 0
    N = ref.n_neurons
    for c, (want, v) in zip(answered, answers(ref, answered)):
        got = np.asarray(c.served) != 0
        T = want.shape[0]
        n = min(T, got.shape[0])
        spikes += int(np.count_nonzero(got[:n, :N] != want[:n]))
        spikes += int(np.count_nonzero(got[:n, N:]))
        spikes += abs(T - got.shape[0]) * N
        if c.potentials is None:
            potentials += N
            continue
        pot = np.asarray(c.potentials, np.int64)
        potentials += int(np.count_nonzero(pot[:N] != v))
        potentials += int(np.count_nonzero(pot[N:]))
    numbers = {"spikes": spikes, "potentials": potentials}
    out = {f"mismatched_{k}": numbers[k] for k in compare}
    out["unanswered"] = len(checks) - len(answered)
    return out

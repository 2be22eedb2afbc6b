"""The plain reference: the fixed-point LIF network a configuration states,
written from its description alone, and the comparison that decides
``correct``.

It imports nothing of the program and takes nothing the program made: it
quantizes the float weights the benchmark generated, applies the decay,
threshold and reset the configuration states, and steps every stream in
NumPy. Sums run in float64 over 0/1 sources, which is exact while
``|sum| < 2**53``; the membrane add wraps at 32 bits as the hardware's
adders do.

``precision="bf16"`` is the control: the same network with every weight
rounded to bfloat16 before the accumulate, the single-pass MXU shortcut
that a faster kernel would be tempted to take. It must fail the
comparison.
"""

from __future__ import annotations

import dataclasses

import numpy as np

PRECISIONS = ("exact", "bf16")


@dataclasses.dataclass(frozen=True)
class Network:
    """A network as the benchmark generated it: float weights over
    ``n_inputs`` external sources then ``n_neurons`` neurons."""

    weights: np.ndarray           # (n_inputs + n_neurons, n_neurons) float32
    n_inputs: int
    n_neurons: int
    output_slice: tuple[int, int]
    decay_rate: float             # as the source states it
    threshold: float
    reset: str                    # "zero" | "subtract" | "hold"

    @property
    def n_synapses(self) -> int:
        return int(np.count_nonzero(self.weights))


def quantize(weights, int_bits: int, frac_bits: int) -> np.ndarray:
    """Signed fixed point, round to nearest even, saturating (int64)."""
    r = np.round(np.asarray(weights, np.float64) * (1 << frac_bits))
    lo, hi = -(1 << (int_bits + frac_bits)), (1 << (int_bits + frac_bits)) - 1
    return np.clip(r, lo, hi).astype(np.int64)


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

    def __init__(self, net: Network, config: dict, precision: str = "exact"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        fx = config["fixed_point"]
        scale = 1 << fx["frac_bits"]
        wq = quantize(net.weights, fx["int_bits"], fx["frac_bits"])
        self.w = (wq.astype(np.float64) if precision == "exact"
                  else _bf16(wq))
        self.n_inputs = net.n_inputs
        self.n_neurons = net.n_neurons
        self.rate = hardware_decay(net.decay_rate,
                                   config["hardware"]["decay_rates"])
        self.threshold = int(round(net.threshold * scale))
        self.reset = net.reset

    def run(self, ext: np.ndarray) -> np.ndarray:
        """(B, T, n_inputs) 0/1 -> (B, T, n_neurons) uint8 spikes."""
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
        return out

    def answers(self, checks, block: int = 64) -> list:
        """The reference's raster (T, n_neurons) for every check's inputs,
        checks of equal length run as one batch, ``block`` at a time."""
        out = [None] * len(checks)
        by_len: dict[int, list[int]] = {}
        for i, c in enumerate(checks):
            by_len.setdefault(c.ext.shape[0], []).append(i)
        for idx in by_len.values():
            for j in range(0, len(idx), block):
                part = idx[j:j + block]
                spikes = self.run(np.stack([checks[i].ext for i in part]))
                for k, i in enumerate(part):
                    out[i] = spikes[k]
        return out


@dataclasses.dataclass
class Check:
    """One answer to check: the external spikes a stream was sent and the
    physical raster it got back (None if it never came)."""

    ext: np.ndarray                 # (T, n_inputs)
    served: np.ndarray | None       # (T', n_phys)


def mismatches(ref: Reference, checks) -> dict:
    """Spike bits where the served rasters differ from ``ref``.

    A served raster holds the model's neurons at physical slots
    ``0..n_neurons-1`` (the configuration deploys from cluster 0) and
    nothing elsewhere: a spike outside the model counts as a mismatch, and
    so does every step of a raster that is short or long.
    """
    answered = [c for c in checks if c.served is not None]
    bad, N = 0, ref.n_neurons
    for c, want in zip(answered, ref.answers(answered)):
        got = np.asarray(c.served) != 0
        T = want.shape[0]
        n = min(T, got.shape[0])
        bad += int(np.count_nonzero(got[:n, :N] != want[:n]))
        bad += int(np.count_nonzero(got[:n, N:]))
        bad += abs(T - got.shape[0]) * N
    return {"mismatched_spikes": bad,
            "unanswered": len(checks) - len(answered)}

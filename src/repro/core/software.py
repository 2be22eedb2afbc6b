"""Float software-reference inference — the paper's snnTorch baseline role.

Runs a logical :class:`~repro.core.network.SNNetwork` in float32 with the
exact trained decay (not snapped to hardware rates) and unquantized
weights. The accuracy-deviation experiments (paper Table IV) compare this
against the bit-exact Cerebra-H hardware model on identical spike trains.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lif import cuba_step_float, lif_step_float
from repro.core.network import SNNetwork

__all__ = ["run_software"]


def run_software(net: SNNetwork, ext_spikes):
    """Float32 inference. ext_spikes: (T, B, n_inputs) in {0,1}.

    Current-based neurons (``params.syn_decay_rate`` set) step
    :func:`~repro.core.lif.cuba_step_float` with the exact leaks.

    Returns {'spikes': (T,B,N) f32, 'output_counts': (B, n_out) f32}.
    """
    W = jnp.asarray(net.weights)  # (n_in + N, N) float32
    ext_spikes = jnp.asarray(ext_spikes, jnp.float32)
    B = ext_spikes.shape[1]
    N = net.n_neurons
    neuron = cuba_step_float if net.params.has_current else lif_step_float

    def step(carry, x_t):
        state, prev = carry
        sources = jnp.concatenate([x_t, prev], axis=-1)  # (B, n_in + N)
        syn = sources @ W
        state, spikes = neuron(state, syn, net.params)
        return (state, spikes), spikes

    state = {"v": jnp.zeros((B, N))}
    if net.params.has_current:
        state["i"] = jnp.zeros((B, N))
    carry = (state, jnp.zeros((B, N)))
    _, spikes = jax.lax.scan(step, carry, ext_spikes)
    lo, hi = net.output_slice
    return {
        "spikes": spikes,
        "output_counts": jnp.sum(spikes[:, :, lo:hi], axis=0),
    }

"""The least work a round of the served step needs, from the rasters the
benchmark sent and received, and the least time the chip could do it in.

Per round (one chunk of every served stream):

- operations: 2 per synaptic operation, and a synaptic operation is one
  source event times that source's real fan-out (its nonzero weights);
- bytes: the fan-out rows of the distinct sources that spiked in the
  round, in any stream, 4 B a weight, plus each served stream's membrane
  potentials read and written once (4 B a neuron).

These count what the spikes require and nothing of how a kernel, gate, K
or weight layout implements it, so an exact implementation cannot beat
them: the share of the roofline they give stays under 100%.
"""

import numpy as np

from bench.reference import quantize

WORD = 4


def fan_out(net, config: dict, n_rec: int):
    """Real fan-out of each external source (n_in,) and of each of the
    first ``n_rec`` physical neurons (zero beyond the network)."""
    fx = config["fixed_point"]
    fan = np.count_nonzero(
        quantize(net.weights, fx["int_bits"], fx["frac_bits"]), axis=1)
    rec = np.zeros(n_rec, np.int64)
    rec[:net.n_neurons] = fan[net.n_inputs:]
    return fan[:net.n_inputs].astype(np.int64), rec


def least_time_s(net, config: dict, round_work, peaks: dict) -> dict:
    """Summed per-round max(ops / int8 peak, bytes / HBM bandwidth)."""
    ext_ev, rec_ev, streams = round_work
    fan_ext, fan_rec = fan_out(net, config, rec_ev.shape[1])
    sops = ext_ev @ fan_ext + rec_ev @ fan_rec
    rows = (ext_ev > 0) @ fan_ext + (rec_ev > 0) @ fan_rec
    nbytes = WORD * rows + 2 * WORD * net.n_neurons * streams
    t_ops = 2 * sops / peaks["int8_ops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": float(np.maximum(t_ops, t_bytes).sum()),
            "ops": int(2 * sops.sum()), "bytes": int(nbytes.sum()),
            "bound": "bytes" if t_bytes.sum() >= t_ops.sum() else "ops"}

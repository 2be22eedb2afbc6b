"""The current-based fused timestep kernel's share of its roofline, in
%: the least time the window's work needs over the summed device time of
the kernel's ops.

The least work is ``bench/work.py``'s per round (two operations per
synaptic operation; the fan-out rows of the distinct sources that spiked
and every served stream's membrane potentials read and written), plus
every served stream's synaptic currents read and written once a round
(4 B a neuron each way), the state a current-based neuron adds.
"""

import numpy as np

from bench import work

# the Mosaic custom call as the device trace names it
PATTERN = r"^%spike_timestep_fused_syn(\.\d+)? = "


def least_time_s(net, config: dict, round_work, peaks: dict) -> float:
    """Summed per-round max(ops / int8 peak, bytes / HBM bandwidth)."""
    ext_ev, rec_ev, streams = round_work
    fan_ext, fan_rec = work.fan_out(net, config, rec_ev.shape[1])
    sops = ext_ev @ fan_ext + rec_ev @ fan_rec
    rows = (ext_ev > 0) @ fan_ext + (rec_ev > 0) @ fan_rec
    states = 2  # membrane potential and synaptic current
    nbytes = (work.WORD * rows
              + states * 2 * work.WORD * net.n_neurons * streams)
    t_ops = 2 * sops / peaks["int8_ops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return float(np.maximum(t_ops, t_bytes).sum())


def read(obs):
    if obs.work is None:
        return None
    kernel_s = obs.trace.op_time_ns(PATTERN, obs.lo, obs.hi) / 1e9
    if kernel_s <= 0:
        return None
    return 100.0 * least_time_s(obs.net, obs.cell.config, obs.work,
                                obs.peaks) / kernel_s

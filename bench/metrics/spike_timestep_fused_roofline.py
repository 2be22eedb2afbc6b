"""The fused timestep kernel's share of its roofline, in %: the least
time the window's work needs (``bench/work.py``) over the summed device
time of the kernel's ops."""

from bench import work

# the Mosaic custom call as the device trace names it
PATTERN = r"^%spike_timestep_fused(\.\d+)? = "


def read(obs):
    if obs.work is None:
        return None
    kernel_s = obs.trace.op_time_ns(PATTERN, obs.lo, obs.hi) / 1e9
    if kernel_s <= 0:
        return None
    least = work.least_time_s(obs.net, obs.cell.config, obs.work, obs.peaks)
    return 100.0 * least["seconds"] / kernel_s

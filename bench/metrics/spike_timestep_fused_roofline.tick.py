"""The fused timestep kernel's share of its roofline under the control
fleet, in %: the reader of ``spike_timestep_fused_roofline`` over the
fleet driver's per-chunk work."""

from bench import load


def read(obs):
    return load.module(obs.root, "metrics",
                       "spike_timestep_fused_roofline").read(obs)
